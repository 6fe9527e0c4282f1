"""Paged GQA decode-attention Pallas TPU kernels.

Decode attention against a *paged* KV cache: K/V rows live in a global page
pool (N pages x page_size tokens, laid out `(N, K, page_size, d)`), and each
sequence names its pages through an int32 page-table row. The page table
and the per-sequence lengths are scalar-prefetched
(`PrefetchScalarGridSpec`), so the kernels read page ids from SMEM and
never touch the rest of the pool. A fp32 online softmax carries partial
(m, l, acc) statistics across the blocks of one sequence, which handles
ragged per-sequence lengths including a partially-filled last page.

`paged_gqa_decode_kernel` (bf16 / fp8 pages) walks the page table in
compute blocks of `pages_per_block` consecutive pages of one sequence,
across all KV heads at once: grid `(B, ceil(P / ppb))`. One page of every
KV head is one contiguous `(K, page_size, d)` slab of the pool, so each
live page is a single manual DMA into a double-buffered VMEM block, and
the copy of the next live block (the next slot's first block included)
starts before the current block's math. A block at or past a sequence's
length starts no copy and runs no body; a partial last block copies only
the pages that hold live rows.

`paged_gqa_decode_quant_kernel` (int8 pages) keeps the one-page grid
`(B, K, P)` with BlockSpec indirection: kv heads are a grid dimension, and
every page-table slot is a grid step whose math `pl.when` skips past the
sequence's length.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30

LANES = 128                  # a vreg row; head_dim pads to it in VMEM
BLOCK_ROWS = 128             # rows per head a block aims for
BLOCK_F32_BYTES = 1 << 20    # cap on one block's K widened to f32


def pages_per_block(num_kv_heads: int, page_size: int, head_dim: int,
                    pages_per_seq: int) -> int:
    """Pages of one sequence that one compute block of
    `paged_gqa_decode_kernel` covers, from the shapes alone.

    Start at `BLOCK_ROWS` rows per head (128 / page_size pages), so that a
    block's score row fills the 128 lanes of a vreg; halve while one
    block's K, widened to f32 with head_dim padded to 128 lanes, exceeds
    `BLOCK_F32_BYTES` (1 MiB): a block keeps six arrays of that size or
    less live in VMEM (K and V, double-buffered in the pool's dtype, and
    their f32 copies), well inside the default scoped VMEM; and never cover
    more pages than the table holds.

    dsr1d-qwen-1.5b (K 2, page 16, d 128, P 160): 8 pages, 128 rows;
    2 x 128 x 128 x 4 B = 128 KiB of f32 K (64 KiB of bf16); 20 blocks.
    GPT-2 XL (K 25, page 16, d 64 padded to 128, P 64): 8 pages would be
    25 x 128 x 128 x 4 B = 1.56 MiB, so 4 pages, 64 rows, 800 KiB of f32 K
    (400 KiB of bf16 in VMEM); 16 blocks.
    """
    d_pad = -(-head_dim // LANES) * LANES
    ppb = max(1, BLOCK_ROWS // page_size)
    while ppb > 1 and num_kv_heads * ppb * page_size * d_pad * 4 \
            > BLOCK_F32_BYTES:
        ppb //= 2
    return min(ppb, pages_per_seq)


def _paged_decode_kernel(len_ref, pt_ref, q_ref, k_hbm, v_hbm, o_ref,
                         k_buf, v_buf, sems, state, m_sc, l_sc, acc_sc, *,
                         scale: float, page_size: int, ppb: int,
                         num_pages: int, num_blocks: int, batch: int):
    """One grid step = block `i` (pages [i*ppb, (i+1)*ppb)) of slot `b`.

    `state` (SMEM) carries across grid steps: [0] the buffer the current
    block's pages land in, [1] 1 until the first live block has run (that
    block starts its own copy; every later live block's copy was started
    by the live block before it)."""
    b, i = pl.program_id(0), pl.program_id(1)
    rows = ppb * page_size
    cap = num_pages * page_size

    def length(bb):
        return jnp.minimum(len_ref[bb], cap)

    def page_copies(bb, ii, buf):
        """The DMAs of block (bb, ii) into buffer `buf`: one per live page
        for K and for V. The table index is clamped for the pages a block
        past the table's end would name (never live, so never copied)."""
        live = jnp.maximum(length(bb) - ii * rows, 0)
        n = jnp.minimum((live + page_size - 1) // page_size, ppb)
        out = []
        for j in range(ppb):
            page = pt_ref[bb, jnp.minimum(ii * ppb + j, num_pages - 1)]
            dst = pl.ds(j * page_size, page_size)
            out.append((j < n, [
                pltpu.make_async_copy(k_hbm.at[page], k_buf.at[buf, :, dst],
                                      sems.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[page], v_buf.at[buf, :, dst],
                                      sems.at[1, buf])]))
        return out

    def start(bb, ii, buf):
        for live, copies in page_copies(bb, ii, buf):
            @pl.when(live)
            def _():
                for c in copies:
                    c.start()

    def wait(bb, ii, buf):
        for live, copies in page_copies(bb, ii, buf):
            @pl.when(live)
            def _():
                for c in copies:
                    c.wait()

    def next_block():
        """(slot, block) of the next live block in grid order; slot ==
        batch when there is none."""
        def next_slot():
            return jax.lax.while_loop(
                lambda c: (c < batch) & (length(jnp.minimum(c, batch - 1))
                                         == 0),
                lambda c: c + 1, b + 1)
        return jax.lax.cond((i + 1) * rows < length(b),
                            lambda: (b, i + 1), lambda: (next_slot(), 0))

    @pl.when((b == 0) & (i == 0))
    def _call_start():
        state[0] = 0
        state[1] = 1
        # rows a partial block does not copy still enter p @ v (times 0):
        # keep them finite
        v_buf[...] = jnp.zeros_like(v_buf)

    @pl.when(i == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    t_start = i * rows

    @pl.when(t_start < length(b))
    def _body():
        buf = state[0]

        @pl.when(state[1] == 1)
        def _first():
            start(b, i, buf)
            state[1] = 0

        nb, ni = next_block()

        @pl.when(nb < batch)
        def _prefetch():
            start(nb, ni, 1 - buf)
            state[0] = 1 - buf

        wait(b, i, buf)
        q = q_ref[0].astype(jnp.float32) * scale           # (K, group, d)
        k = k_buf[buf].astype(jnp.float32)                 # (K, rows, d)
        v = v_buf[buf].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
        tpos = t_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(tpos < length(b), s, NEG_INF)        # (K, group, rows)
        m_prev = m_sc[...]                                 # (K, group, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=2, keepdims=True)
        acc_sc[...] = acc_sc[...] * corr + jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when(i == num_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0] = (acc_sc[...] / denom).astype(o_ref.dtype)


def _paged_decode_quant_kernel(len_ref, pt_ref, q_ref, k_ref, v_ref,
                               ks_ref, vs_ref, o_ref, m_sc, l_sc, acc_sc, *,
                               scale: float, page_size: int, num_pages: int):
    """int8 variant: K/V blocks arrive as int8 plus a per-row float32 scale
    block gathered through the same page-table indirection, and are
    dequantized in-register right before the split-K online-softmax update.
    The same accumulator math as `_paged_decode_kernel`, one page and one
    kv head per grid step."""
    b = pl.program_id(0)
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    length = len_ref[b]
    t_start = it * page_size

    @pl.when(t_start < length)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (group, d)
        # in-register dequant: int8 payload * per-row scale
        k = k_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0, 0][:, None]
        v = v_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0, 0][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        tpos = t_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(tpos < length, s, NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=1)
        acc_sc[...] = acc_sc[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when(it == num_pages - 1)
    def _finalize():
        denom = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0, 0] = (acc_sc[...] / denom[:, None]).astype(o_ref.dtype)


def paged_gqa_decode_quant_kernel(q: jax.Array, k_pages: jax.Array,
                                  v_pages: jax.Array, k_scale: jax.Array,
                                  v_scale: jax.Array, page_table: jax.Array,
                                  lengths: jax.Array, *,
                                  interpret: bool = False) -> jax.Array:
    """q: (B, H, d); k_pages, v_pages: (N, K, ps, d) int8; k_scale, v_scale:
    (N, K, ps) float32 per-row scales; page_table: (B, P) int32;
    lengths: (B,) int32. Returns (B, H, d) in q.dtype."""
    B, H, d = q.shape
    N, K, ps, _ = k_pages.shape
    P = page_table.shape[1]
    assert H % K == 0
    group = H // K
    scale = 1.0 / math.sqrt(d)

    qg = q.reshape(B, K, group, d)
    kern = functools.partial(_paged_decode_quant_kernel, scale=scale,
                             page_size=ps, num_pages=P)

    def q_map(b, kh, it, lens, pt):
        return (b, kh, 0, 0)

    def kv_map(b, kh, it, lens, pt):
        return (pt[b, it], kh, 0, 0)

    def sc_map(b, kh, it, lens, pt):
        # per-page scales ride the same prefetched page-table indirection
        return (pt[b, it], kh, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, K, P),
        in_specs=[
            pl.BlockSpec((1, 1, group, d), q_map),
            pl.BlockSpec((1, 1, ps, d), kv_map),
            pl.BlockSpec((1, 1, ps, d), kv_map),
            pl.BlockSpec((1, 1, 1, ps), sc_map),
            pl.BlockSpec((1, 1, 1, ps), sc_map),
        ],
        out_specs=pl.BlockSpec((1, 1, group, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((group,), jnp.float32),
            pltpu.VMEM((group,), jnp.float32),
            pltpu.VMEM((group, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, group, d), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), page_table.astype(jnp.int32),
      # scales as (N, K, 1, ps): a (1, ps) block then equals the array's
      # last two dims, as the TPU's (8, 128) tiling rule requires
      qg, k_pages, v_pages, k_scale.reshape(N, K, 1, ps),
      v_scale.reshape(N, K, 1, ps))
    return out.reshape(B, H, d)


def paged_gqa_decode_kernel(q: jax.Array, k_pages: jax.Array,
                            v_pages: jax.Array, page_table: jax.Array,
                            lengths: jax.Array, *,
                            interpret: bool = False) -> jax.Array:
    """q: (B, H, d); k_pages, v_pages: (N, K, ps, d); page_table: (B, P)
    int32; lengths: (B,) int32. Returns (B, H, d).

    Block size from `pages_per_block(K, ps, d, P)`; the pools stay in HBM
    (`pl.ANY`) and the kernel copies the live pages itself. A head_dim
    that is not a multiple of 128 lanes is zero-padded to one, pools
    included: the TPU compiler refuses a DMA of a page slice whose last
    dim is narrower than its 128-lane tile."""
    B, H, d = q.shape
    N, K, ps, _ = k_pages.shape
    P = page_table.shape[1]
    assert H % K == 0
    group = H // K
    ppb = pages_per_block(K, ps, d, P)
    nblk = -(-P // ppb)
    scale = 1.0 / math.sqrt(d)
    d_pad = -(-d // LANES) * LANES
    if d_pad != d:
        lanes = [(0, 0)] * 3 + [(0, d_pad - d)]
        q = jnp.pad(q, lanes[1:])
        k_pages = jnp.pad(k_pages, lanes)
        v_pages = jnp.pad(v_pages, lanes)

    qg = q.reshape(B, K, group, d_pad)
    kern = functools.partial(_paged_decode_kernel, scale=scale,
                             page_size=ps, ppb=ppb, num_pages=P,
                             num_blocks=nblk, batch=B)

    def q_map(b, i, lens, pt):
        return (b, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nblk),
        in_specs=[
            pl.BlockSpec((1, K, group, d_pad), q_map),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, K, group, d_pad), q_map),
        scratch_shapes=[
            pltpu.VMEM((2, K, ppb * ps, d_pad), k_pages.dtype),
            pltpu.VMEM((2, K, ppb * ps, d_pad), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((K, group, 1), jnp.float32),
            pltpu.VMEM((K, group, 1), jnp.float32),
            pltpu.VMEM((K, group, d_pad), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, group, d_pad), q.dtype),
        # the DMA state carries from one slot to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), page_table.astype(jnp.int32),
      qg, k_pages, v_pages)
    return out[..., :d].reshape(B, H, d)
