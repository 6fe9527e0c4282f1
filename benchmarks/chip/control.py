"""Reads a cell's compared numbers and its control's, over many seeds in one
process, on the machine it is started on.

    python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \
        --seeds 11 12 13 ...

For each seed it runs the cell's timed path at the cell's own size and load
for a short window, then prints one JSON line with two verdicts, each
reached by the same rule as a benchmark run's `correct` (`run.judge`):
`program`, on the program's readings (e.g. `logit_gap`), and `control`, on
the control's readings of the same numbers: the reference put in the
program's place at the precision below the configuration's (float8 for a
bfloat16 model, bfloat16 for the float32 Stage-II kernels). The control
has to come out not correct on every seed. The limits in the configuration
and mix files are set between the two readings. The benchmark's own runs
never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402


def main(argv=None, root: Path = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    try:
        spec, cell, cfg, mix, devices = run.open_cell(args.workload, root)
    except run.NoChip as e:
        print(f"control.py: {e}", file=sys.stderr)
        return 2
    drv = run.driver(mix, spec.here)
    failed = 0
    for seed in args.seeds:
        a = argparse.Namespace(seed=seed, seconds=args.seconds, trace=0)
        ctx = run.Ctx(a, spec, cell, cfg, mix, devices)
        res = drv.run(ctx, control=True)
        line = {"seed": seed}
        for side, checks in (("program", res["checks"]),
                             ("control", res["control_checks"])):
            line[side] = {"correct": run.judge(checks),
                          **{k: v for k, (v, _) in checks.items()}}
        failed += line["control"]["correct"]
        print(json.dumps(line), flush=True)
    if failed:
        print(f"control.py: the control came out correct on {failed} "
              "seed(s)", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
