"""A scratch checkout with tiny cells, for running the harness on the CPU.

`checkout(tmp)` copies the benchmark directory into `tmp`, links the
program's `src`, adds the tiny configuration and mixes from `data/` as new
files, and writes a BENCHMARK.json whose cells use them: the same steps a
later change takes to add a configuration, a mix or a metric.
`run_cell` then drives run.py's `main` there with the look for a chip
skipped.
"""
from __future__ import annotations

import io
import json
import shutil
import sys
from contextlib import redirect_stderr, redirect_stdout
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
REPO = BENCH.parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(REPO / "src"))

CELLS = {
    "tiny-backlog": ("tiny", "tiny-backlog"),
    "tiny-poisson": ("tiny", "tiny-poisson"),
    "tiny-sweep": ("tiny", "tiny-sweep"),
}


def checkout(tmp: Path, extra_metrics=()) -> Path:
    dst = tmp / "benchmarks" / "chip"
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns(
        "__pycache__", ".jax_cache", "tests"))
    (tmp / "src").symlink_to(REPO / "src")
    shutil.copy(HERE / "data" / "tiny.json", dst / "configs" / "tiny.json")
    for mix in ("tiny-backlog", "tiny-poisson", "tiny-sweep"):
        shutil.copy(HERE / "data" / f"{mix}.json", dst / "mixes")
    peaks = json.loads((dst / "peaks.json").read_text())
    peaks["cpu"] = dict(peaks["TPU v5 lite"], source="test stand-in")
    (dst / "peaks.json").write_text(json.dumps(peaks))
    names = list(CELLS)
    bench = {
        "command": ["python3", "benchmarks/chip/run.py"],
        "paths": ["benchmarks/chip"], "run_seconds": 1,
        "configs": [{"name": "tiny", "source": "test",
                     "file": "benchmarks/chip/configs/tiny.json",
                     "reduced": [], "why": "test"}],
        "workloads": [{"name": n, "config": c, "traffic": t, "chips": 1,
                       "why": "test"} for n, (c, t) in CELLS.items()],
        "end_to_end": [
            {"name": "tokens_per_s", "unit": "tokens/s", "better": "higher",
             "bound": 0.05, "source": "host_clock",
             "workloads": ["tiny-backlog"]},
            {"name": "ttft_p90_s", "unit": "s", "better": "lower",
             "bound": 0.1, "source": "host_clock",
             "workloads": ["tiny-poisson"]},
            {"name": "tpot_p90_ms", "unit": "ms", "better": "lower",
             "bound": 0.1, "source": "host_clock",
             "workloads": ["tiny-poisson"]},
            {"name": "candidates_per_s", "unit": "candidates/s",
             "better": "higher", "bound": 0.05, "source": "host_clock",
             "workloads": ["tiny-sweep"]},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
             "source": "host_clock"}],
        "per_layer": [
            {"name": "slot_occupancy.backlog", "unit": "%",
             "better": "higher", "source": "program_counter",
             "layer": "admission and page ledger", "moves": "tokens_per_s",
             "workloads": ["tiny-backlog"]},
            {"name": "mfu.backlog", "unit": "%", "better": "higher",
             "source": "host_clock", "layer": "decode chunk",
             "moves": "tokens_per_s", "workloads": ["tiny-backlog"]},
            {"name": "device_idle_share.chat", "unit": "%", "better": "lower",
             "source": "device_trace", "layer": "device",
             "moves": "ttft_p90_s", "workloads": ["tiny-poisson"]},
            {"name": "device_idle_share.sweep", "unit": "%",
             "better": "lower", "source": "device_trace", "layer": "device",
             "moves": "candidates_per_s", "workloads": ["tiny-sweep"]}]
        + [dict(m, workloads=list(names)) for m in extra_metrics],
    }
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return tmp


def run_cell(root: Path, cell: str, seed: int = 7, seconds: float = 1.0,
             trace: int = 0, monkeypatch=None, log: list = None) -> dict:
    """run.py's main in `root`, with the TPU check skipped; returns the
    parsed last line of standard output, and appends the lines of standard
    error to `log` where given."""
    import jax
    import run
    monkeypatch.setattr(run, "require_devices",
                        lambda n: jax.devices()[:n])
    buf, err = io.StringIO(), io.StringIO()
    with redirect_stdout(buf), redirect_stderr(err):
        rc = run.main(["--workload", cell, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      root=root)
    if log is not None:
        log.extend(err.getvalue().splitlines())
    assert rc == 0, err.getvalue()[-2000:]
    return json.loads(buf.getvalue().strip().splitlines()[-1])
