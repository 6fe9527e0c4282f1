"""BENCHMARK.json holds together: every name the harness looks up by has its
file, every cell reports set-up, another end-to-end metric and a per-layer
metric, and names and units keep to their character sets."""
import json
import re

import pytest

from tiny import BENCH, REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return json.loads((REPO / "BENCHMARK.json").read_text())


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks/chip"]
    assert 1 <= bench["run_seconds"] <= 51


def test_every_lookup_has_its_file(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/chip/")
        cfg = json.loads((REPO / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
    names = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in names and w["chips"] == 1
        assert (BENCH / "mixes" / f"{w['traffic']}.json").exists()
        assert len(w["why"]) <= 200
    for m in bench["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").exists()


def test_names_units_and_bounds(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    for x in bench["configs"] + bench["workloads"] + metrics:
        assert NAME.match(x["name"]), x["name"]
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e


def test_every_cell_reports_enough(bench):
    def of(key, cell):
        return [m for m in bench[key]
                if cell in m.get("workloads", [cell])]
    for w in bench["workloads"]:
        e2e = {m["name"] for m in of("end_to_end", w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = of("per_layer", w["name"])
        assert layer and all(m["moves"] in e2e for m in layer)
