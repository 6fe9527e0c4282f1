"""Paged GQA speculative-verification Pallas TPU kernel.

Target-model verification of a speculative window: each slot carries
V = spec_k + 1 query rows (the pending token plus the k drafted candidates),
all attending against the same page-table-indirected pool the decode kernel
streams. Row v sits at absolute position base_lens[b] + v, so its causal
horizon is base_lens[b] + v + 1 — the causal mask widens by one row per
query row of the speculative window. All V rows of a (kv head, page) block
share one HBM->VMEM page copy, which is the point: scoring k + 1 candidates
costs one pass over the resident pages instead of k + 1 sequential decode
calls.

Grid (B, K, P), the one-page grid of `paged_gqa_decode_quant`: kv heads
parallel, pages innermost sequential so the fp32 split-K online-softmax
scratch carries across them. The only differences are the fatter query
block (V * group rows instead of group) and the per-row causal bound
derived from the row's spec index (row // group).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _paged_verify_kernel(len_ref, pt_ref, q_ref, k_ref, v_ref, o_ref,
                         m_sc, l_sc, acc_sc, *, scale: float, page_size: int,
                         num_pages: int, group: int, num_q: int):
    b = pl.program_id(0)
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    base = len_ref[b]
    t_start = it * page_size

    # the widest row (spec index num_q - 1) reaches base + num_q tokens
    @pl.when(t_start < base + num_q)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale    # (num_q * group, d)
        k = k_ref[0, 0].astype(jnp.float32)            # (ps, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        tpos = t_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # per-row causal horizon: query row r belongs to spec index
        # r // group and may attend tokens [0, base + r // group + 1)
        row_v = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
        s = jnp.where(tpos < base + row_v + 1, s, NEG_INF)
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


def paged_gqa_verify_kernel(q: jax.Array, k_pages: jax.Array,
                            v_pages: jax.Array, page_table: jax.Array,
                            base_lens: jax.Array, *,
                            interpret: bool = False) -> jax.Array:
    """q: (B, V, H, d) speculative-window queries; k_pages, v_pages:
    (N, K, ps, d); page_table: (B, P) int32; base_lens: (B,) int32 context
    lengths *before* the window (row v attends base_lens + v + 1 tokens).
    Returns (B, V, H, d) in q.dtype."""
    B, V, H, d = q.shape
    N, K, ps, _ = k_pages.shape
    P = page_table.shape[1]
    assert H % K == 0
    group = H // K
    scale = 1.0 / math.sqrt(d)

    # rows of one kv head block are laid out spec-major: row v * group + g
    # is query head g of spec index v, so the kernel recovers the spec
    # index as row // group
    qg = (q.reshape(B, V, K, group, d).transpose(0, 2, 1, 3, 4)
          .reshape(B, K, V * group, d))
    kern = functools.partial(_paged_verify_kernel, scale=scale, page_size=ps,
                             num_pages=P, group=group, num_q=V)

    def q_map(b, kh, it, lens, pt):
        return (b, kh, 0, 0)

    def kv_map(b, kh, it, lens, pt):
        return (pt[b, it], kh, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, K, P),
        in_specs=[
            pl.BlockSpec((1, 1, V * group, d), q_map),
            pl.BlockSpec((1, 1, ps, d), kv_map),
            pl.BlockSpec((1, 1, ps, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, V * group, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((V * group,), jnp.float32),
            pltpu.VMEM((V * group,), jnp.float32),
            pltpu.VMEM((V * group, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, V * group, d), q.dtype),
        interpret=interpret,
    )(base_lens.astype(jnp.int32), page_table.astype(jnp.int32),
      qg, k_pages, v_pages)
    return (out.reshape(B, K, V, group, d).transpose(0, 2, 1, 3, 4)
            .reshape(B, V, H, d))
