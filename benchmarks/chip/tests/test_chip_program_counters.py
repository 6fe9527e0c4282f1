"""The readers of the engine's wall-clock counters (`PagedStats`): a finite
value in every served cell, nothing in the sweep, and nothing where the
program lacks the counter or a denominator is 0."""
import json
import math
from types import SimpleNamespace

import pytest

import tiny

import spec  # noqa: E402  (tiny puts the harness on the path)

# name -> (counters it reads, denominators)
NEEDS = {"first_token_hold_ms.chat": (("first_token_hold_s",), ("prefills",)),
         "queue_wait_ms.chat": (("queue_wait_s",), ("admitted",)),
         "prefill_wall_ms_per_ktok.chat": (("prefill_wall_s",),
                                           ("prefill_tokens",)),
         "host_ms_per_chunk.chat": (("run_wall_s", "device_wait_s"),
                                    ("chunks",)),
         "host_ms_per_chunk.backlog": (("run_wall_s", "device_wait_s"),
                                       ("chunks",)),
         "page_wait_share.backlog": (("page_waits",), ("chunks",))}
NAMES = tuple(NEEDS)
ENTRIES = [m for m in json.loads((tiny.REPO / "BENCHMARK.json").read_text())
           ["per_layer"] if m["name"] in NAMES]


def _read(name, stats):
    path = tiny.BENCH / "metrics" / f"{name}.py"
    mod = spec.load("metric_" + name.replace(".", "_"), path)
    return mod.read(SimpleNamespace(counts={"stats": stats}))


def test_entries_are_program_counters_with_their_cells():
    assert sorted(m["name"] for m in ENTRIES) == sorted(NAMES)
    for m in ENTRIES:
        assert m["source"] == "program_counter" and m["workloads"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.checkout(tmp_path_factory.mktemp("counters"),
                         extra_metrics=ENTRIES)


@pytest.mark.parametrize("cell", ["tiny-backlog", "tiny-poisson"])
def test_served_cells_read_every_counter(root, monkeypatch, cell):
    out = tiny.run_cell(root, cell, seed=2**31 + 99, trace=1,
                        monkeypatch=monkeypatch)
    assert out["correct"] is True
    for name in NAMES:
        v = out["metrics"][name]["value"]
        assert math.isfinite(v) and v >= 0.0, (name, v)


def test_sweep_reads_none(root, monkeypatch):
    out = tiny.run_cell(root, "tiny-sweep", trace=1, monkeypatch=monkeypatch)
    assert out["correct"] is True
    assert not set(NAMES) & set(out["metrics"])


@pytest.mark.parametrize("name", NAMES)
def test_none_without_the_counter_or_a_denominator(name):
    counter, denominators = NEEDS[name]
    full = {"run_wall_s": 2.0, "device_wait_s": 1.0, "queue_wait_s": 1.0,
            "prefill_wall_s": 1.0, "prefill_tokens": 512,
            "first_token_hold_s": 1.0, "page_waits": 1, "prefills": 2,
            "admitted": 2, "chunks": 2}
    assert math.isfinite(_read(name, full))
    for field in counter + denominators:    # the parent's program lacks it
        assert _read(name, {k: v for k, v in full.items()
                            if k != field}) is None, field
    for field in denominators:
        assert _read(name, dict(full, **{field: 0})) is None, field
