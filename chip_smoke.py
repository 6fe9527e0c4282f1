"""One-chip smoke run of both halves of the system at published widths.

1. Serves DeepSeek-R1-Distill-Qwen-1.5B (all 28 layers, published widths,
   random bf16 weights from a seed) through `PagedContinuousBatcher` with the
   Pallas paged-attention kernel, and checks that every request gets exactly
   its token budget.
2. Checks the Pallas paged-attention kernels against their references on the
   page pool and page table that serve left behind: bf16 pages, and an int8
   copy of them.
3. Prices the serve's page-occupancy trace with TRAPTI Stage II on the chip
   (`backend="auto"`, the Pallas bank-energy kernels) and checks the result
   against the float64 numpy backend.

It is a smoke run, not a benchmark: its times include compilation and are
printed only as a sign of life. It exits non-zero unless JAX reports a TPU,
and on any failed check. Run it from the root of a checkout on a machine
with one TPU chip:

    python chip_smoke.py

The last line of standard output is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ARCH = "dsr1d-qwen-1.5b"
SEED = 0

# Pool sized from a v5e compile of the decode chunk: 3.09 GB of bf16 weights
# and a 3.76 GB page pool as arguments, plus 4.16 GB of temporaries (about
# one more pool), leave ~5 GB of the 16 GB free.
NUM_SLOTS = 16
PAGE_SIZE = 16
NUM_PAGES = 8192                  # 131k tokens at 28 KiB of bf16 KV each
MAX_PAGES_PER_SLOT = 80           # 1280 tokens: longest prompt + its decode
CHUNK_STEPS = 16

NUM_REQUESTS = 16
PROMPT_TOKENS = (128, 1000)       # inclusive range, drawn from SEED
NEW_TOKENS = 32

# Pallas vs reference paged attention: max |diff| over max |reference|, on
# one layer of the live pool. Both sides read the same pages (bf16, or int8
# with f32 row scales) and accumulate in f32; the reference runs at highest
# matmul precision, while the kernel's MXU passes may round f32 operands
# (the softmax weights, dequantized int8 rows) to bf16, 2**-9 relative. So
# 1e-2 leaves 5x room, and a wrong page, row or mask still moves the output
# by O(1).
ATTN_TOL = 1e-2
# Stage II: the f32 Pallas kernels vs float64 numpy, relative error of each
# candidate's energy. Durations accumulate in f32 (2**-24 relative per add
# over a few thousand segments) and occupancy is KiB-exact in f32, so 1e-4 is
# far above rounding; a bank or idle run counted wrongly shows in the event
# counts, which must match exactly.
STAGE2_RTOL = 1e-4
STAGE2_BANKS = (1, 2, 4, 8, 16, 32)
STAGE2_POLICIES = ("none", "gate", "drowsy")


class SmokeFailure(RuntimeError):
    pass


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def serve(cfg):
    """Phase 1: build the model and serve NUM_REQUESTS requests."""
    from repro.models import build_model
    from repro.models.common import cast_params
    from repro.serve import PagedContinuousBatcher, Request
    from repro.serve.paged import pages_for

    model = build_model(cfg, compute_dtype=jnp.bfloat16, remat="none")
    t = time.perf_counter()
    params = jax.jit(lambda k: cast_params(model.init(k), jnp.bfloat16))(
        jax.random.PRNGKey(SEED))
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"weights: {n_params} bf16 parameters, {cfg.num_layers} layers, "
          f"made in {time.perf_counter() - t:.2f} s (compile included)")

    cb = PagedContinuousBatcher(
        model, params, num_slots=NUM_SLOTS, page_size=PAGE_SIZE,
        num_pages=NUM_PAGES, max_pages_per_slot=MAX_PAGES_PER_SLOT,
        chunk_steps=CHUNK_STEPS, attn_backend="pallas")

    rng = np.random.default_rng(SEED)
    lens = rng.integers(PROMPT_TOKENS[0], PROMPT_TOKENS[1] + 1, NUM_REQUESTS)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]

    # compile the batcher's own programs with the arguments it will pass,
    # so the times below are compile times and the chunk can be inspected
    z = jnp.zeros((NUM_SLOTS,), jnp.int32)
    t = time.perf_counter()
    chunk = cb._loop.lower(cb.params, cb._cache, z[:, None], z, z).compile()
    t_chunk = time.perf_counter() - t
    require("tpu_custom_call" in chunk.as_text(),
            "the decode chunk program holds no Pallas kernel")
    mem = chunk.memory_analysis()
    print(f"compile decode chunk ({CHUNK_STEPS} steps): {t_chunk:.2f} s; "
          f"tpu_custom_call present; arguments "
          f"{mem.argument_size_in_bytes} B, temp {mem.temp_size_in_bytes} B")
    longest = prompts[int(np.argmax(lens))]
    t = time.perf_counter()
    cb._prefill.lower(cb.params, {"tokens": jnp.asarray(longest[None])},
                      pages_for(len(longest), PAGE_SIZE) * PAGE_SIZE
                      ).compile()
    print(f"compile prefill ({len(longest)} tokens): "
          f"{time.perf_counter() - t:.2f} s")

    for rid, p in enumerate(prompts):
        cb.submit(Request(rid=rid, tokens=p, max_new_tokens=NEW_TOKENS))
    t = time.perf_counter()
    done = cb.run()
    wall = time.perf_counter() - t

    require(len(done) == NUM_REQUESTS,
            f"served {len(done)} of {NUM_REQUESTS} requests")
    for r in done:
        out = np.asarray(r.output)
        require(len(out) == NEW_TOKENS,
                f"request {r.rid} got {len(out)} tokens, not {NEW_TOKENS}")
        require(bool(((out >= 0) & (out < cfg.vocab_size)).all()),
                f"request {r.rid} emitted a token outside the vocabulary")
    n_tok = sum(len(r.output) for r in done)
    print(f"smoke serve (not a benchmark): {len(done)} requests, prompts "
          f"{int(lens.min())}-{int(lens.max())} tokens, {n_tok} new tokens "
          f"in {wall:.2f} s wall, {cb.stats.chunks} decode chunks "
          f"(prefill compiles included)")
    return cb


def check_attention(cb, cfg) -> None:
    """Phase 2: Pallas vs reference paged attention on the live pool."""
    from repro.kernels import quant
    from repro.kernels.paged_gqa_decode import (
        paged_gqa_decode, paged_gqa_decode_quant,
        paged_gqa_decode_quant_mirror_ref, paged_gqa_decode_ref)

    cache = cb._cache
    pos = np.asarray(cache["pos"])
    table = np.asarray(cache["page_table"])
    live = np.nonzero((pos > 0) & (table[:, 0] > 0))[0]
    require(len(live) > 0, "the serve left no live page-table row")
    pt = jnp.asarray(table[live])
    lengths = jnp.asarray(pos[live])
    kp = cache["slots"][0]["kp"][0]               # layer 0 (N, K, ps, d)
    vp = cache["slots"][0]["vp"][0]
    q = jax.random.normal(jax.random.PRNGKey(SEED + 1),
                          (len(live), cfg.num_heads, cfg.head_dim))
    q = q.astype(jnp.bfloat16).astype(jnp.float32)

    def rel_err(out, ref):
        out = np.asarray(out, np.float64)
        ref = np.asarray(ref, np.float64)
        require(bool(np.isfinite(out).all()), "non-finite kernel output")
        return float(np.abs(out - ref).max() / np.abs(ref).max())

    out = paged_gqa_decode(q, kp, vp, pt, lengths, backend="pallas")
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(paged_gqa_decode_ref)(q, kp, vp, pt, lengths)
    err = rel_err(out, ref)
    print(f"paged attention, bf16 pages, {len(live)} slots, lengths "
          f"{int(lengths.min())}-{int(lengths.max())}: pallas vs ref "
          f"max rel err {err:.3e} (tol {ATTN_TOL})")
    require(err <= ATTN_TOL, "bf16 paged attention disagrees with reference")

    qk, ks = quant.quantize_page_rows(kp)
    qv, vs = quant.quantize_page_rows(vp)
    out = paged_gqa_decode_quant(q, qk, qv, ks, vs, pt, lengths,
                                 backend="pallas")
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(paged_gqa_decode_quant_mirror_ref)(
            q, qk, qv, ks, vs, pt, lengths)
    err = rel_err(out, ref)
    print(f"paged attention, int8 pages: pallas vs mirror ref max rel err "
          f"{err:.3e} (tol {ATTN_TOL})")
    require(err <= ATTN_TOL, "int8 paged attention disagrees with reference")


def check_stage2(cb) -> None:
    """Phase 3: Stage II over the serve's trace, Pallas vs float64 numpy."""
    from repro.core.candidates import (evaluate_candidates,
                                       lower_bound_energies, make_grid)
    from repro.core.explorer import MIB, min_capacity_mib
    from repro.kernels.bank_energy import resolve_backend

    require(resolve_backend("auto") == "pallas",
            "Stage II backend 'auto' does not resolve to Pallas")
    bundle = cb.occupancy_bundle()
    trace = bundle.traces["kv"]
    dur, occ = trace.occupancy_series(bundle.total_time, use="needed")
    lo = min_capacity_mib(trace.peak_needed())
    cands = make_grid([c * MIB for c in (lo, 2 * lo, 4 * lo)], STAGE2_BANKS,
                      policies=STAGE2_POLICIES)
    kw = dict(n_reads=bundle.access.n_reads("kv"),
              n_writes=bundle.access.n_writes("kv"))

    t = time.perf_counter()
    dev = evaluate_candidates(dur, occ, cands, backend="auto", **kw)
    lb_dev = lower_bound_energies(dur, occ, cands, backend="auto", **kw)
    t_dev = time.perf_counter() - t
    ref = evaluate_candidates(dur, occ, cands, backend="numpy", **kw)
    lb_ref = lower_bound_energies(dur, occ, cands, backend="numpy", **kw)

    require(np.array_equal(dev.n_off, ref.n_off)
            and np.array_equal(dev.n_drowsy, ref.n_drowsy),
            "Stage II transition counts differ from the numpy backend")
    err = float(np.max(np.abs(dev.e_total - ref.e_total)
                       / np.abs(ref.e_total)))
    err_lb = float(np.max(np.abs(lb_dev - lb_ref) / np.abs(lb_ref)))
    best, e_best = ref.best()
    print(f"Stage II: {len(cands)} candidates (C {lo}/{2 * lo}/{4 * lo} MiB "
          f"x B {STAGE2_BANKS[0]}-{STAGE2_BANKS[-1]} x {STAGE2_POLICIES}) "
          f"over {len(dur)} trace segments in {t_dev:.2f} s (compile "
          f"included); pallas vs numpy max rel err: energy {err:.3e}, "
          f"lower bound {err_lb:.3e} (tol {STAGE2_RTOL}); best "
          f"C={best.capacity // MIB} MiB B={best.banks} {best.policy} "
          f"{e_best:.6e} J")
    require(err <= STAGE2_RTOL and err_lb <= STAGE2_RTOL,
            "Stage II energies disagree with the numpy backend")


def main() -> int:
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.configs import resolve_arch
    from repro.launch.compile_cache import setup_compile_cache

    print(f"compile cache: {setup_compile_cache()}")
    print(f"device: {dev.device_kind}, count {len(devices)}")
    cfg = resolve_arch(ARCH)
    cb = serve(cfg)
    check_attention(cb, cfg)
    check_stage2(cb)
    peak = dev.memory_stats()["peak_bytes_in_use"]
    print(f"peak_bytes_in_use: {peak}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
