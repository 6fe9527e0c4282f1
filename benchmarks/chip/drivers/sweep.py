"""Driver for a Stage-II sweep: the candidate engine over an occupancy trace.

Set-up builds the trace (`traffic.occupancy_trace`, at the configuration's
KV bytes per token) and the mix's candidate grid, and warms the engine with
one evaluation. The window calls `evaluate_candidates(..., backend="auto")`
over the whole grid back to back. Afterwards every call's energies and
transition counts are compared with the float64 reference in
`reference/stage2.py`: the widest relative energy error, and the number of
candidates whose counts differ, which must be 0 on every candidate that is
not a tie (`stage2.ties`: a bank boundary that float32 may round either
way).
"""
from __future__ import annotations

import sys
import time

import numpy as np

import traffic
import work
from reference import stage2

MIB = 2 ** 20


def grid(mix: dict, occ: np.ndarray):
    """(capacity, banks, alpha, policy, min_gate_multiple) tuples, C-major;
    capacities are multiples of the trace's peak rounded up to a MiB."""
    g = mix["grid"]
    c_min = -(-int(occ.max()) // MIB)
    return [(int(round(f * c_min)) * MIB, b, a, p, 1.0)
            for f in g["capacity_multiples"] for b in g["banks"]
            for a in g["alphas"] for p in g["policies"]]


def run(ctx, control: bool = False):
    from repro.core.candidates import Candidate, evaluate_candidates

    mix = ctx.mix
    with ctx.annotate("bench.build"):
        dur, occ, reads, writes = traffic.occupancy_trace(
            mix["trace"], mix["base_seed"], ctx.seed,
            work.kv_bytes_per_token(ctx.cfg["model"]))
        cands = grid(mix, occ)
        objs = [Candidate(c, b, a, p, g) for c, b, a, p, g in cands]
        # accesses of 64 B
        row = work.kv_bytes_per_token(ctx.cfg["model"])
        kw = dict(n_reads=reads * row // 64, n_writes=writes * row // 64)

    def call():
        with ctx.annotate("bench.stage2"):
            r = evaluate_candidates(dur, occ, objs, backend="auto", **kw)
        return r.e_total.copy(), r.n_off.copy(), r.n_drowsy.copy()

    with ctx.annotate("bench.warm"):
        call()
    outs = []
    with ctx.window() as w:
        t_open = time.perf_counter()
        while time.perf_counter() - t_open < ctx.seconds:
            outs.append(call())
        t_close = time.perf_counter()
    peak = ctx.memory_peak()
    window_s = t_close - t_open
    n_cand = len(objs) * len(outs)
    print(f"window {window_s:.3f} s: {len(outs)} sweeps of {len(objs)} "
          f"candidates over {len(dur)} segments", file=sys.stderr)

    ref = stage2.evaluate(dur, occ, cands, **kw)
    tie = stage2.ties(occ, cands)
    lim = mix["limits"]

    def judged(outs):
        """(widest energy error, candidates off a tie whose counts differ,
        tie candidates whose counts differ) over every call's answers."""
        err = max(float(np.max(np.abs(e - ref["e_total"]) / ref["e_total"]))
                  for e, _, _ in outs)
        bad = [(n_off != ref["n_off"]) | (n_dr != ref["n_drowsy"])
               for _, n_off, n_dr in outs]
        return (err, sum(int(np.sum(b & ~tie)) for b in bad),
                sum(int(np.sum(b & tie)) for b in bad))

    err, differ, tie_differ = judged(outs)
    if differ:
        off = sorted({int(i) for _, n_off, n_dr in outs
                      for i in np.flatnonzero(((n_off != ref["n_off"])
                                               | (n_dr != ref["n_drowsy"]))
                                              & ~tie)})
        for i in off[:5]:
            print(f"counts differ off a tie: candidate {i} {cands[i]}: "
                  f"n_off {outs[0][1][i]} / {ref['n_off'][i]}, n_drowsy "
                  f"{outs[0][2][i]} / {ref['n_drowsy'][i]} (first call / "
                  "float64)", file=sys.stderr)
    print(f"tie candidates: {int(tie.sum())} of {len(cands)}; their "
          f"counts differ from float64 in {tie_differ} of "
          f"{int(tie.sum()) * len(outs)} answers", file=sys.stderr)
    out = {"attempted": n_cand, "failed": 0,
           "metrics": {"setup_s": w.setup_s,
                       "candidates_per_s": n_cand / window_s},
           "memory_peak_bytes": int(peak), "compiles": w.compiles,
           "counts": {"candidates": n_cand, "sweeps": len(outs),
                      "segments": len(dur), "window_s": window_s},
           "checks": {"energy_rel_err": (err, lim["energy_rel_err"]),
                      "counts_differ": (differ, 0)}}
    if control:
        # the reference in bfloat16 in the program's place: durations and
        # KiB occupancies rounded to bfloat16
        c = stage2.evaluate(bf16(dur), bf16(occ / 1024) * 1024, cands, **kw)
        c_err, c_differ, _ = judged([(c["e_total"], c["n_off"],
                                      c["n_drowsy"])])
        out["control_checks"] = {
            "energy_rel_err": (c_err, lim["energy_rel_err"]),
            "counts_differ": (c_differ, 0)}
    return out


def bf16(x):
    import ml_dtypes
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float64)
