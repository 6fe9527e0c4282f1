"""Runs one benchmark cell once, on the machine it is started on.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <window> --trace <0|1>

from the root of a checkout that holds BENCHMARK.json. The cell's entry
there names its configuration (`configs/<config>.json`) and traffic mix
(`mixes/<traffic>.json`); the mix's `driver` names the file in `drivers/`
that runs it (`serve` for served traffic, `sweep` for a Stage-II sweep).
Set-up builds and warms every program the window will run; the window then
runs for `--seconds`, and afterwards the outputs of the timed path are
compared with the plain reference in `reference/`.

With `--trace 0` the result carries the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics, read by `metrics/<name>.py` from a
profiler trace of the window. The last line of standard output is one JSON
object; the numbers compared for `correct` are the last lines of standard
error and the last key of that object. `correct` holds when each of them is
at most its limit.

Exits non-zero and prints no result unless JAX finds at least the cell's
number of TPU chips.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import spec as spec_mod  # noqa: E402

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


class NoChip(RuntimeError):
    pass


def require_devices(n: int):
    """The TPU devices the cell asks for, or NoChip."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise NoChip(f"needs {n} TPU chip(s); JAX found {len(devs)} "
                     f"{devs[0].platform} device(s)")
    return devs[:n]


def setup_compile_cache(here: Path) -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        here / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class Ctx:
    """What a driver gets: the cell's parts, the clock, annotations, the
    window (with the profiler around it when tracing) and the compile
    count."""

    def __init__(self, args, spec, cell, cfg, mix, devices):
        self.args, self.spec, self.cell = args, spec, cell
        self.cfg, self.mix, self.devices = cfg, mix, devices
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.tracing = bool(args.trace)
        self.compiles = 0
        self.trace_path = None
        self._tmp = None
        import jax.monitoring as mon

        def on_event(name, *a, **k):
            if name in COMPILE_EVENTS:
                self.compiles += 1
        mon.register_event_duration_secs_listener(on_event)

    @staticmethod
    def annotate(name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def window(self):
        """Profiler on (when tracing), then the `bench.window` annotation.
        Yields a namespace whose `compiles` the caller reads at the close."""
        import jax
        if self.tracing:
            self._tmp = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self._tmp, profiler_options=opts)
        w = SimpleNamespace(compiles=0, setup_s=time.perf_counter() - T0)
        c0 = self.compiles
        try:
            with self.annotate("bench.window"):
                yield w
        finally:
            w.compiles = self.compiles - c0
            if self.tracing:
                jax.profiler.stop_trace()
                found = glob.glob(os.path.join(self._tmp, "**",
                                               "*.xplane.pb"), recursive=True)
                self.trace_path = found[0] if found else None

    def memory_peak(self) -> int:
        """`peak_bytes_in_use` of the fullest chip."""
        return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in self.devices))

    def cleanup(self):
        if self._tmp:
            shutil.rmtree(self._tmp, ignore_errors=True)


def driver(mix: dict, here: Path):
    """The module `drivers/<driver>.py` that the mix names, in the
    benchmark's directory `here`."""
    name = mix["driver"]
    path = here / "drivers" / f"{name}.py"
    if not path.exists():
        raise ValueError(f"no driver {name!r} in {path.parent}")
    return spec_mod.load(f"driver_{name}", path)


def judge(checks: dict) -> bool:
    """`correct`: every compared number at most its limit."""
    return all(v <= lim for v, lim in checks.values())


def per_layer(spec, cell, ctx, res, device_kind: str):
    import reduce
    t = reduce.load(ctx.trace_path)
    lo, hi = reduce.window(t)
    rec = SimpleNamespace(
        model=ctx.cfg.get("model"), peak=spec.peak(device_kind),
        counts=res["counts"], window_s=(hi - lo) / 1e9,
        op_s=lambda p: reduce.time_s(t.ops, p, lo, hi) / t.n_devices,
        program_s=lambda name, holding=None, lacking=None: reduce.programs_s(
            t, name, lo, hi, holding, lacking) / t.n_devices,
        busy_s=reduce.busy_s(t.ops, lo, hi, t.n_devices))
    metrics = {}
    units = {m["name"]: m["unit"] for m in spec.per_layer(cell)}
    for name, read in spec.readers(cell).items():
        v = read(rec)
        if v is not None:
            metrics[name] = {"value": float(v), "unit": units[name]}
    extra = {"busy_s": rec.busy_s, "window_s": rec.window_s}
    breakdown = {"device_ops": reduce.top(t.ops, lo, hi),
                 "idle_gaps": reduce.idle_gaps(t, lo, hi)}
    return metrics, extra, breakdown


def open_cell(workload: str, root: Path = None):
    """(spec, cell, config, mix, devices) of a cell in the checkout at
    `root`, with the compile cache placed; raises NoChip."""
    root = root or HERE.parents[1]
    here = root / HERE.relative_to(HERE.parents[1])
    sys.path.insert(0, str(root / "src"))
    spec = spec_mod.Spec(root, here)
    cell = spec.cell(workload)
    print(f"compile cache: {setup_compile_cache(here)}", file=sys.stderr)
    return spec, cell, spec.config(cell), spec.mix(cell), \
        require_devices(cell["chips"])


def main(argv=None, root: Path = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec, cell, cfg, mix, devices = open_cell(args.workload, root)
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}",
          file=sys.stderr)

    ctx = Ctx(args, spec, cell, cfg, mix, devices)
    try:
        res = driver(mix, spec.here).run(ctx)
        print(f"compiles in window: {res['compiles']}", file=sys.stderr)
        out = {"correct": judge(res["checks"]), "attempted": res["attempted"],
               "failed": res["failed"]}
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devices),
                  "memory_peak_bytes": res["memory_peak_bytes"]}
        if ctx.tracing:
            metrics, extra, breakdown = per_layer(spec, cell, ctx, res,
                                                  dev.device_kind)
            device.update(extra)
            out["metrics"], out["breakdown"] = metrics, breakdown
        else:
            e2e = {m["name"]: m["unit"] for m in spec.end_to_end(cell)}
            out["metrics"] = {k: {"value": float(v), "unit": e2e[k]}
                              for k, v in res["metrics"].items() if k in e2e}
        out["device"] = device
    finally:
        ctx.cleanup()
    checks = {k: {"value": v, "limit": lim}
              for k, (v, lim) in res["checks"].items()}
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    out["checks"] = checks
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
