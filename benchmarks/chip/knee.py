"""Finds the highest Poisson rate a served cell sustains: runs the cell's
mix at each given rate in one process and prints, per rate, the requests in
flight when the window opens and closes, the tail of time to first token
and the tokens per second. Above the knee the requests in flight grow
through the window.

    python3 benchmarks/chip/knee.py --workload <cell> --seconds <s> \
        --rates 1.5 2 2.5 3
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--preroll", type=float, help="seconds of arrivals "
                    "before the window (default: the mix's)")
    args = ap.parse_args(argv)
    try:
        spec, cell, cfg, mix, devices = run.open_cell(args.workload)
    except run.NoChip as e:
        print(f"knee.py: {e}", file=sys.stderr)
        return 2
    for rate in args.rates:
        pre = mix["preroll_s"] if args.preroll is None else args.preroll
        m = dict(mix, arrivals=dict(mix["arrivals"], rate_per_s=rate),
                 preroll_s=pre, schedule_s=pre + args.seconds + 60)
        a = argparse.Namespace(seed=args.seed, seconds=args.seconds, trace=0)
        res = run.driver(m, spec.here).run(run.Ctx(a, spec, cell, cfg, m, devices))
        c = res["counts"]
        print(json.dumps({"rate_per_s": rate, "live_open": c["live_open"],
                          "live_close": c["live_close"],
                          "attempted": res["attempted"],
                          **{k: v for k, v in res["metrics"].items()
                             if k != "setup_s"}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
