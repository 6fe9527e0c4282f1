"""The harness end to end on the CPU at tiny sizes, with the look for a chip
skipped, and the rule that new cells, mixes, configurations and metrics
need only new files and entries."""
import json
import shutil

import numpy as np
import pytest

import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.checkout(tmp_path_factory.mktemp("checkout"))


@pytest.mark.parametrize("cell,metric", [
    ("tiny-backlog", "tokens_per_s"),
    ("tiny-poisson", "ttft_p90_s"),
    ("tiny-sweep", "candidates_per_s"),
])
def test_cell_runs_and_is_correct(root, monkeypatch, cell, metric):
    out = tiny.run_cell(root, cell, seed=2**31 + 12345,
                        monkeypatch=monkeypatch)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) >= {metric, "setup_s"}
    assert out["metrics"][metric]["value"] > 0
    assert list(out)[-1] == "checks"
    assert out["device"]["count"] == 1


def test_traced_run_reports_per_layer_metrics(root, monkeypatch):
    out = tiny.run_cell(root, "tiny-backlog", trace=1,
                        monkeypatch=monkeypatch)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"slot_occupancy.backlog", "mfu.backlog"}
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_same_seed_same_inputs(root):
    import traffic
    mix = json.loads((root / "benchmarks/chip/mixes/tiny-poisson.json")
                     .read_text())
    a = traffic.requests(mix, 99, 300)
    b = traffic.requests(mix, 99, 300)
    c = traffic.requests(mix, 98, 300)
    assert len(a) == 200
    assert all((x.prompt == y.prompt).all() and x.due_s == y.due_s
               for x, y in zip(a, b))
    # another seed: the same lengths and gaps, in another order
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in c)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in c]
    assert max(x.due_s for x in a) == pytest.approx(max(x.due_s for x in c))


def test_bursts_classes_and_prefix_from_data():
    """MMPP arrivals, two length classes and shared prefixes: the same work
    for every seed, and every prompt length among those set-up warms."""
    import traffic
    mix = json.loads((tiny.HERE / "data/extra/tiny-burst.json").read_text())
    a, c = traffic.requests(mix, 5, 300), traffic.requests(mix, 6, 300)
    assert len(a) == traffic.count(mix) > 0
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in c)
    warmed = set(traffic.grid_lengths(mix))
    assert {len(x.prompt) for x in a} <= warmed
    assert {len(x.prompt) for x in a} & {48, 64} and min(warmed) == 48
    heads = {x.prompt[:32].tobytes() for x in a}
    assert len(heads) == 2
    # bursts: gaps spread far more than a Poisson process's (CV 1)
    due = np.array([x.due_s for x in a])
    gaps = np.diff(due)
    assert gaps.std() / gaps.mean() > 1.3


@pytest.mark.parametrize("mix,files,engine", [
    # MMPP bursts, length classes and shared prefixes
    ("tiny-burst", [], {}),
    # a new open-loop arrival process, `arrivals/periodic.py`
    ("tiny-periodic", [("periodic.py", "arrivals")], {}),
])
def test_new_mix_config_and_metric_need_only_files(tmp_path, monkeypatch,
                                                   mix, files, engine):
    """A later change adds a mix file, a configuration file, a metric reader,
    an arrival process where it needs one, and entries in BENCHMARK.json, and
    edits no file of the harness."""
    metric = {"name": "prompt_tokens_per_call.any", "unit": "tokens",
              "better": "higher", "source": "program_counter",
              "layer": "prefill", "moves": "ttft_p90_s"}
    root = tiny.checkout(tmp_path, extra_metrics=[metric])
    bench_dir = root / "benchmarks/chip"
    extra = tiny.HERE / "data" / "extra"
    shutil.copy(extra / f"{mix}.json", bench_dir / "mixes")
    shutil.copy(extra / "prompt_tokens_per_call.any.py", bench_dir / "metrics")
    for name, where in files:
        assert not (bench_dir / where / name).exists()
        shutil.copy(extra / name, bench_dir / where)
    cfg = json.loads((bench_dir / "configs/tiny.json").read_text())
    cfg["model"]["name"] = "tiny-mha"
    cfg["model"]["num_kv_heads"] = cfg["model"]["num_heads"]
    cfg["engine"].update(engine)
    (bench_dir / "configs/tiny-mha.json").write_text(json.dumps(cfg))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-mha", "source": "test",
                             "file": "benchmarks/chip/configs/tiny-mha.json",
                             "reduced": [], "why": "test"})
    cell = f"tiny-mha-{mix}"
    bench["workloads"].append({"name": cell, "config": "tiny-mha",
                               "traffic": mix, "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("ttft_p90_s", "prompt_tokens_per_call.any"):
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    log = []
    out = tiny.run_cell(root, cell, monkeypatch=monkeypatch, log=log)
    assert out["correct"] is True and "ttft_p90_s" in out["metrics"]
    assert "compiles in window: 0" in log
    out = tiny.run_cell(root, cell, trace=1, monkeypatch=monkeypatch)
    assert out["metrics"]["prompt_tokens_per_call.any"]["value"] > 0


def test_new_driver_needs_only_a_file(tmp_path, monkeypatch):
    """A mix names its driver, `drivers/<name>.py`, which a later change can
    add as a file of its own."""
    root = tiny.checkout(tmp_path)
    bench_dir = root / "benchmarks/chip"
    src = (bench_dir / "drivers/sweep.py").read_text()
    (bench_dir / "drivers/sweep_twice.py").write_text(
        src.replace("outs.append(call())", "outs.append(call())\n"
                    "            outs.append(call())"))
    mix = json.loads((bench_dir / "mixes/tiny-sweep.json").read_text())
    (bench_dir / "mixes/tiny-sweep-twice.json").write_text(
        json.dumps(dict(mix, driver="sweep_twice")))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny-twice", "config": "tiny",
                               "traffic": "tiny-sweep-twice", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "candidates_per_s":
            m["workloads"].append("tiny-twice")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    out = tiny.run_cell(root, "tiny-twice", monkeypatch=monkeypatch)
    assert out["correct"] is True
    assert out["attempted"] % 48 == 0     # 24 candidates, sweeps in pairs


def test_no_result_without_a_chip(root, capsys):
    import run
    rc = run.main(["--workload", "tiny-backlog", "--seed", "1",
                   "--seconds", "1"], root=root)
    assert rc != 0
    assert capsys.readouterr().out == ""
