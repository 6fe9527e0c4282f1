"""Plain float64 Stage-II evaluation: per candidate, per bank, idle runs.

The same semantics as the program's candidate engine, written the obvious
way and importing nothing of it: bank b of a candidate is needed in a
segment when ceil(occupancy / usable) >= b (usable = alpha * C / B, at most
B banks); every maximal run of segments in which it is not needed is an
idle run, long when it lasts at least the break-even time. The SRAM
constants are a copy of the program's CACTI surrogate (45 nm fit to the
paper's Table II).

`ties` flags the candidates whose bank activity the program may round the
other way: a float32 ratio occupancy / usable that lies within a few ulps of
a whole number of banks can take either ceiling.
"""
from __future__ import annotations

import math

import numpy as np

MIB = float(2 ** 20)
LEAK_W_PER_MIB = 0.682
AREA_MM2_PER_MIB = 16.78
AREA_BANK_MM2_PER_SQRT_MIB = 5.4
E_ACC_BASE_NJ, E_ACC_SQRT_NJ, E_ACC_ROUTE_NJ = 1.2, 0.35, 0.15
E_SW_NJ_PER_KIB = 0.4
DROWSY_LEAK_FRACTION = 0.25
DROWSY_SWITCH_FRACTION = 0.02


def sram(capacity: int, banks: int):
    """(leakage W per bank, read J, write J, switch J, break-even s)."""
    bank_bytes = capacity // banks
    mib = max(bank_bytes / MIB, 1e-9)
    leak = LEAK_W_PER_MIB * mib + AREA_BANK_MM2_PER_SQRT_MIB * math.sqrt(
        mib) * (LEAK_W_PER_MIB / AREA_MM2_PER_MIB)
    e_r = (E_ACC_BASE_NJ + E_ACC_SQRT_NJ * math.sqrt(mib)
           + E_ACC_ROUTE_NJ * math.log2(max(banks, 1))) * 1e-9
    e_sw = E_SW_NJ_PER_KIB * (bank_bytes / 1024) * 1e-9
    return leak, e_r, 1.1 * e_r, e_sw, e_sw / max(leak, 1e-12)


def idle_runs(durations, occupancy, usable: float, banks: int,
              threshold: float):
    """[active bank-seconds, #long runs, long seconds, #short, short s]."""
    d = np.asarray(durations, np.float64)
    act = np.minimum(np.ceil(np.asarray(occupancy, np.float64) / usable),
                     banks)
    out = np.zeros(5)
    out[0] = float((act * d).sum())
    # plateaus of equal activity: runs only begin or end between them
    cut = np.flatnonzero(np.diff(act)) + 1
    starts = np.concatenate([[0], cut])
    level = act[starts]
    dur = np.add.reduceat(d, starts)
    edge = np.concatenate([[0.0], np.cumsum(dur)])
    for b in range(1, banks + 1):
        idle = np.concatenate([[0], (level < b).astype(np.int8), [0]])
        step = np.diff(idle)
        lo, hi = np.flatnonzero(step == 1), np.flatnonzero(step == -1)
        run = edge[hi] - edge[lo]
        long = run >= threshold
        out[1] += long.sum()
        out[2] += run[long].sum()
        out[3] += (~long).sum()
        out[4] += run[~long].sum()
    return out


def evaluate(durations, occupancy, cands, n_reads: int, n_writes: int):
    """Per candidate (capacity, banks, alpha, policy, min_gate_multiple):
    dict of float64 arrays e_total, n_off, n_drowsy."""
    total = float(np.sum(np.asarray(durations, np.float64)))
    e_total, n_off, n_drowsy = [], [], []
    for cap, banks, alpha, policy, mult in cands:
        leak, e_r, e_w, e_sw, be = sram(cap, banks)
        e = n_reads * e_r + n_writes * e_w
        if policy == "none":
            e_total.append(e + leak * banks * total)
            n_off.append(0)
            n_drowsy.append(0)
            continue
        act_s, n_long, long_s, n_short, short_s = idle_runs(
            durations, occupancy, alpha * (cap / banks), banks, mult * be)
        if policy == "gate":
            e += leak * (banks * total - long_s) + n_long * e_sw
            n_drowsy.append(0)
        else:
            e += (leak * act_s + leak * DROWSY_LEAK_FRACTION * short_s
                  + n_long * e_sw + n_short * e_sw * DROWSY_SWITCH_FRACTION)
            n_drowsy.append(n_short)
        e_total.append(e)
        n_off.append(n_long)
    return {"e_total": np.asarray(e_total), "n_off": np.asarray(n_off),
            "n_drowsy": np.asarray(n_drowsy)}


# float32 ratios carry about 2**-23 of relative error from the rounding of
# usable and of the division (more where the device's division is not
# correctly rounded); a ratio this close to a whole number is a tie
TIE_REL = 2.0 ** -20


def ties(occupancy, cands) -> np.ndarray:
    """Per candidate: True where some segment's occupancy / usable lies
    within TIE_REL of a whole number k of banks, 1 <= k < banks (at k = 0
    and at k >= banks either ceiling gives the same activity)."""
    levels = np.unique(np.asarray(occupancy, np.float64))
    out = np.zeros(len(cands), bool)
    for i, (cap, banks, alpha, policy, _) in enumerate(cands):
        x = levels / (alpha * (cap / banks))
        k = np.rint(x)
        near = np.abs(x - k) <= TIE_REL * np.maximum(x, 1.0)
        out[i] = bool(np.any(near & (k >= 1) & (k < banks)))
    return out
