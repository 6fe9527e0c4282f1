"""Paged GQA decode-attention Pallas TPU kernel.

Decode attention against a *paged* KV cache: K/V rows live in a global page
pool (N pages x page_size tokens), and each sequence names its pages through
an int32 page-table row. The page table and the per-sequence lengths are
scalar-prefetched (`PrefetchScalarGridSpec`), so the BlockSpec index_map
itself performs the indirection — the kernel streams exactly the pages a
sequence owns, one HBM->VMEM copy per (kv head, page), and never touches the
rest of the pool. Split-K style fp32 online softmax accumulates partial
(m, l, acc) statistics across the page grid dimension, which natively
handles ragged per-sequence lengths including a partially-filled last page.

Grid (B, K, P): kv heads are the parallel dimension (all q heads of a GQA
group ride along in VMEM and reuse the same K/V page — the paper's GQA
bytes/“slot” observation expressed as a BlockSpec), pages are the innermost
sequential dimension so the accumulator scratch carries across them.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _paged_decode_kernel(len_ref, pt_ref, q_ref, k_ref, v_ref, o_ref,
                         m_sc, l_sc, acc_sc, *, scale: float, page_size: int,
                         num_pages: int):
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
        k = k_ref[0, 0].astype(jnp.float32)                # (ps, d)
        v = v_ref[0, 0].astype(jnp.float32)
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


def _paged_decode_quant_kernel(len_ref, pt_ref, q_ref, k_ref, v_ref,
                               ks_ref, vs_ref, o_ref, m_sc, l_sc, acc_sc, *,
                               scale: float, page_size: int, num_pages: int):
    """int8 variant: K/V blocks arrive as int8 plus a per-row float32 scale
    block gathered through the same page-table indirection, and are
    dequantized in-register right before the split-K online-softmax update.
    Identical control flow and accumulator math to `_paged_decode_kernel`."""
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
    int32; lengths: (B,) int32. Returns (B, H, d)."""
    B, H, d = q.shape
    N, K, ps, _ = k_pages.shape
    P = page_table.shape[1]
    assert H % K == 0
    group = H // K
    scale = 1.0 / math.sqrt(d)

    qg = q.reshape(B, K, group, d)
    kern = functools.partial(_paged_decode_kernel, scale=scale, page_size=ps,
                             num_pages=P)

    def q_map(b, kh, it, lens, pt):
        return (b, kh, 0, 0)

    def kv_map(b, kh, it, lens, pt):
        # the page-table indirection: block row = the page this sequence
        # maps at table slot `it` (unused slots hold the null page 0 and are
        # masked out by `lengths` inside the kernel body)
        return (pt[b, it], kh, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, K, P),
        in_specs=[
            pl.BlockSpec((1, 1, group, d), q_map),
            pl.BlockSpec((1, 1, ps, d), kv_map),
            pl.BlockSpec((1, 1, ps, d), kv_map),
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
      qg, k_pages, v_pages)
    return out.reshape(B, H, d)
