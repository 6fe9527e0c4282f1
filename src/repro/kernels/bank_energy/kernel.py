"""TRAPTI Stage-II trace analytics as a Pallas TPU kernel.

This is the paper's Eq. (1)/(4)/(5) inner loop — bank activity, active
bank-seconds (the leakage integral) and bank on/off transition counts — over
(trace segments x candidate configurations). Offline DSE sweeps evaluate
thousands of (C, B, alpha) candidates against million-segment traces, so the
kernel blocks the segment arrays into VMEM tiles; the TPU grid is sequential
per core, which makes cross-tile carries (previous segment's bank activity,
for transition counting) and output accumulation safe.

Under contiguous packing, banks fill lowest-first, so the number of on/off
toggles between consecutive segments is exactly |B_act(k) - B_act(k-1)| —
transition counting needs no per-bank state.

Two kernels share the (n_candidates, n_segment_blocks) grid layout, segment
blocks innermost:

  * `bank_energy_kernel`     — the cheap lower-bound stats (bank-seconds +
    toggle count); carries only the previous segment's activity.
  * `exact_bank_stats_kernel` — exact per-bank idle-run extraction for the
    batched Stage-II evaluator: per tile it rebuilds each bank's on/off
    series (bmax x block_s), finds run ends at rises of the series via an
    in-tile prefix-max of exceed end-times, and classifies each run against
    the candidate's break-even threshold. Cross-tile state (per-bank last
    required time, previous on/off value, elapsed time) lives in VMEM
    scratch, which is safe because the TPU grid is sequential per core.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _lane_shift(x: jax.Array, shift: int, fill) -> jax.Array:
    """x shifted `shift` lanes toward higher indices, `fill` shifted in.
    A lane rotate plus a mask: unaligned lane concatenation does not lower
    on the TPU."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    return jnp.where(lane >= shift, pltpu.roll(x, shift, x.ndim - 1), fill)


def _lane(x: jax.Array, i: int) -> jax.Array:
    """(R, n) -> (R, 1): lane `i` (negative counts from the end), as a
    masked lane reduction."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.sum(jnp.where(lane == i % x.shape[1], x, 0.0), axis=1,
                   keepdims=True)


def _total(x: jax.Array) -> jax.Array:
    """(R, n) -> (1, 1) sum, kept 2-D for the vector unit."""
    return jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0, keepdims=True)


def _row(vals) -> jax.Array:
    """Pack (1, 1) values into one (1, len(vals)) row: one vector store."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, len(vals)), 1)
    row = jnp.zeros((1, len(vals)), jnp.float32)
    for j, v in enumerate(vals):
        row = jnp.where(lane == j, v, row)
    return row


def _bank_kernel(dur_ref, occ_ref, usable_ref, nb_ref, out_ref, prev_sc, *,
                 num_seg_blocks: int):
    s = pl.program_id(1)

    dur = dur_ref[0]                          # (1, BS)
    occ = occ_ref[0]                          # (1, BS)
    usable = usable_ref[0]                    # (1, 1)
    nbanks = nb_ref[0]                        # (1, 1)

    act = jnp.clip(jnp.ceil(occ / usable), 0.0, nbanks)   # (1, BS)

    @pl.when(s == 0)
    def _first():
        # the first segment has no predecessor: count no toggle into it
        prev_sc[...] = _lane(act, 0)
        out_ref[...] = jnp.zeros_like(out_ref)

    bank_seconds = _total(act * dur)
    shifted = _lane_shift(act, 1, prev_sc[...])
    transitions = _total(jnp.abs(act - shifted))
    prev_sc[...] = _lane(act, -1)

    out_ref[0] += _row((bank_seconds, transitions))


def _scan_lanes(x: jax.Array, combine) -> jax.Array:
    """Inclusive prefix scan along the last axis via log-doubling shifts
    (Mosaic lowers no cumsum/cummax). 0.0 must be `combine`'s identity on
    x: a sum, or a max over x >= 0."""
    shift = 1
    while shift < x.shape[-1]:
        x = combine(x, _lane_shift(x, shift, 0.0))
        shift *= 2
    return x


def _exact_kernel(dur_ref, occ_ref, us_ref, nb_ref, th_ref, out_ref,
                  last_exc_t, prev_exc, tbase, *, bmax: int,
                  num_seg_blocks: int):
    s = pl.program_id(1)

    dur = dur_ref[0]                          # (1, BS)
    occ = occ_ref[0]                          # (1, BS)
    usable = us_ref[0]                        # (1, 1)
    nbanks = nb_ref[0]                        # (1, 1)
    threshold = th_ref[0]                     # (1, 1)

    act = jnp.clip(jnp.ceil(occ / usable), 0.0, nbanks)       # (1, BS)
    bank = jax.lax.broadcasted_iota(jnp.int32, (bmax, 1), 0).astype(
        jnp.float32)
    exceed = act > bank                                       # (bmax, BS)
    bankmask = bank < nbanks                                  # (bmax, 1)

    @pl.when(s == 0)
    def _first():
        out_ref[...] = jnp.zeros_like(out_ref)
        last_exc_t[...] = jnp.zeros_like(last_exc_t)
        # pre-trace state counts as ON so segment 0 never closes a run
        prev_exc[...] = jnp.ones_like(prev_exc)
        tbase[...] = jnp.zeros_like(tbase)

    t0 = tbase[...]                                           # (1, 1)
    cumend = t0 + _scan_lanes(dur, jnp.add)                   # (1, BS)
    cumstart = cumend - dur

    carry_t = last_exc_t[...]                                 # (bmax, 1)
    last_in = _scan_lanes(jnp.where(exceed, cumend, 0.0), jnp.maximum)
    run_start = jnp.maximum(_lane_shift(last_in, 1, carry_t), carry_t)
    exc_f = exceed.astype(jnp.float32)
    prev = _lane_shift(exc_f, 1, prev_exc[...]) > 0.5
    is_rise = exceed & ~prev
    run_dur = cumstart - run_start
    long = run_dur >= threshold
    rise_long = is_rise & long & bankmask
    rise_short = is_rise & ~long & bankmask

    zero = jnp.zeros_like(run_dur)
    out_ref[0] += _row((
        _total(act * dur),
        _total(rise_long.astype(jnp.float32)),
        _total(jnp.where(rise_long, run_dur, zero)),
        _total(rise_short.astype(jnp.float32)),
        _total(jnp.where(rise_short, run_dur, zero))))

    # the prefix-max is monotone, so its last lane is its lane max
    new_last = jnp.maximum(carry_t, jnp.max(last_in, axis=1, keepdims=True))
    t_end = t0 + jnp.sum(dur, axis=1, keepdims=True)          # (1, 1)
    last_exc = _lane(exc_f, -1)                              # (bmax, 1)
    last_exc_t[...] = new_last
    prev_exc[...] = last_exc
    tbase[...] = t_end

    @pl.when(s == num_seg_blocks - 1)
    def _flush():
        # close the still-open idle run of every bank idle at trace end
        tail_dur = t_end - new_last                           # (bmax, 1)
        tail_idle = (last_exc < 0.5) & bankmask
        tail_long = tail_idle & (tail_dur >= threshold)
        tail_short = tail_idle & ~tail_long
        zero1 = jnp.zeros_like(tail_dur)
        out_ref[0] += _row((
            jnp.zeros((1, 1), jnp.float32),
            _total(tail_long.astype(jnp.float32)),
            _total(jnp.where(tail_long, tail_dur, zero1)),
            _total(tail_short.astype(jnp.float32)),
            _total(jnp.where(tail_short, tail_dur, zero1))))


def exact_bank_stats_kernel(durations: jax.Array, occupancy: jax.Array,
                            usable: jax.Array, nbanks: jax.Array,
                            threshold: jax.Array, *, bmax: int,
                            block_s: int = 2048,
                            interpret: bool = False) -> jax.Array:
    """durations/occupancy: (S,) f32, S % block_s == 0 (pad durations with 0
    and occupancy with its last value — padding adds no time and no rises);
    usable/nbanks/threshold: (C,) f32; bmax: static max bank count.

    Returns (C, 5): [active bank-seconds, idle runs >= threshold, their
    seconds, idle runs < threshold, their seconds] — the exact Eq. (2)-(5)
    observables, same contract as `exact_bank_stats_np`.
    """
    S = durations.shape[0]
    C = usable.shape[0]
    block_s = min(block_s, S)
    assert S % block_s == 0, (S, block_s)
    nsb = S // block_s
    bmax_p = max(8, -(-bmax // 8) * 8)       # pad sublanes; masked via nbanks

    dur2 = durations.reshape(nsb, 1, block_s).astype(jnp.float32)
    occ2 = occupancy.reshape(nsb, 1, block_s).astype(jnp.float32)
    us2 = usable.reshape(C, 1, 1).astype(jnp.float32)
    nb2 = nbanks.reshape(C, 1, 1).astype(jnp.float32)
    th2 = threshold.reshape(C, 1, 1).astype(jnp.float32)

    kern = functools.partial(_exact_kernel, bmax=bmax_p, num_seg_blocks=nsb)
    return pl.pallas_call(
        kern,
        grid=(C, nsb),
        in_specs=[
            pl.BlockSpec((1, 1, block_s), lambda c, s: (s, 0, 0)),
            pl.BlockSpec((1, 1, block_s), lambda c, s: (s, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda c, s: (c, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda c, s: (c, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda c, s: (c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 5), lambda c, s: (c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((C, 1, 5), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bmax_p, 1), jnp.float32),     # last exceed end-time
            pltpu.VMEM((bmax_p, 1), jnp.float32),     # previous on/off (0/1)
            pltpu.VMEM((1, 1), jnp.float32),          # elapsed time
        ],
        interpret=interpret,
    )(dur2, occ2, us2, nb2, th2).reshape(C, 5)


def bank_energy_kernel(durations: jax.Array, occupancy: jax.Array,
                       usable: jax.Array, nbanks: jax.Array, *,
                       block_s: int = 2048,
                       interpret: bool = False) -> jax.Array:
    """durations/occupancy: (S,) f32 (S % block_s == 0 — pad durations with 0
    and occupancy with its last value); usable/nbanks: (C,) f32.

    Returns (C, 2): [:, 0] = integral of B_act dt, [:, 1] = on/off toggles.
    """
    S = durations.shape[0]
    C = usable.shape[0]
    block_s = min(block_s, S)
    assert S % block_s == 0, (S, block_s)
    nsb = S // block_s

    dur2 = durations.reshape(nsb, 1, block_s).astype(jnp.float32)
    occ2 = occupancy.reshape(nsb, 1, block_s).astype(jnp.float32)
    us2 = usable.reshape(C, 1, 1).astype(jnp.float32)
    nb2 = nbanks.reshape(C, 1, 1).astype(jnp.float32)

    kern = functools.partial(_bank_kernel, num_seg_blocks=nsb)
    return pl.pallas_call(
        kern,
        grid=(C, nsb),
        in_specs=[
            pl.BlockSpec((1, 1, block_s), lambda c, s: (s, 0, 0)),
            pl.BlockSpec((1, 1, block_s), lambda c, s: (s, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda c, s: (c, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda c, s: (c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 2), lambda c, s: (c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((C, 1, 2), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, 1), jnp.float32)],
        interpret=interpret,
    )(dur2, occ2, us2, nb2).reshape(C, 2)
