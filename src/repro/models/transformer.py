"""Decoder-only LM covering dense / GQA / MQA / MoE / SSM / hybrid / VLM
families, with three entry points:

    loss(params, batch)                    — training objective
    prefill(params, batch, cache_len)      — full-sequence forward + cache fill
    decode_step(params, cache, tokens)     — one token against the cache

The layer stack is grouped by the config's block pattern and scanned with
`lax.scan` over pattern repetitions (stacked params), keeping HLO size and
compile time O(pattern) instead of O(depth) — essential for 62-/88-layer
archs in the dry-run. A non-divisible tail (e.g. recurrentgemma 26 = 3×8 + 2)
is applied unstacked.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import attention as attn
from repro.models import ffn as ffn_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.kernels import quant
from repro.models.common import (PTpl, abstract_params, apply_norm, apply_rope,
                                 cross_entropy, embed_template, embed_tokens,
                                 init_params, lm_logits, norm_template,
                                 stack_tpl)
from repro.models.meshctx import constrain


# ---------------------------------------------------------------------------
# Block templates
# ---------------------------------------------------------------------------

def block_template(cfg, kind: str) -> dict:
    if kind in ("full", "local", "chunked"):
        t = {"norm1": norm_template(cfg), "attn": attn.attn_template(cfg),
             "norm2": norm_template(cfg)}
        t["ffn"] = (moe_mod.moe_template(cfg) if cfg.moe is not None
                    else ffn_mod.ffn_template(cfg))
        return t
    if kind == "rglru":
        return {"norm1": norm_template(cfg), "rec": rglru_mod.rglru_template(cfg),
                "norm2": norm_template(cfg), "ffn": ffn_mod.ffn_template(cfg)}
    if kind == "ssm":
        return {"norm1": norm_template(cfg), "ssm": ssm_mod.ssm_template(cfg)}
    raise ValueError(kind)


def lm_template(cfg) -> dict:
    pat = cfg.block_pattern
    n_rep = cfg.num_layers // len(pat)
    tail_kinds = cfg.layer_kinds()[n_rep * len(pat):]
    t: Dict[str, Any] = {"embed": embed_template(cfg)}
    t["blocks"] = [stack_tpl(block_template(cfg, k), n_rep) for k in pat]
    t["tail"] = [block_template(cfg, k) for k in tail_kinds]
    t["final_norm"] = norm_template(cfg)
    if cfg.frontend is not None:
        # stub modality projector: precomputed frontend embeddings -> d_model
        t["projector"] = {
            "w": PTpl((cfg.d_model, cfg.d_model), ("embed", "mlp")),
            "b": PTpl((cfg.d_model,), ("embed",), "zeros"),
        }
    return t


# ---------------------------------------------------------------------------
# Block application — full-sequence mode
# ---------------------------------------------------------------------------

def _attend_full_seq(cfg, kind: str, p: dict, x: jax.Array,
                     positions: jax.Array, kv_block: int,
                     unroll: bool = False):
    """Self-attention over a full sequence; returns (out, (k, v)).

    Sequence-parallel attention (Perf iteration A1/B1): query rows shard over
    the "model" axis while the (GQA-small) K/V replicate across it — all
    score/softmax/AV math is then local to each chip, eliminating the
    per-kv-block all-reduce flood that plain head-misaligned TP produces
    (qwen2's 28 heads don't divide a 16-way axis; 4096-row sequences do).
    This is the TP reading of the paper's GQA observation: shared K/V is
    small enough to replicate.
    """
    q, k, v = attn.project_qkv(cfg, p, x, x)
    if cfg.pos_emb == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if kind == "full":
        bspec = ("pod", "data")
        q = constrain(q, P(bspec, "model", None, None))
        k = constrain(k, P(bspec, None, None, None))
        v = constrain(v, P(bspec, None, None, None))
        o = attn.blocked_attention(q, k, v, causal=True, kv_block=kv_block,
                                   unroll=unroll)
        o = constrain(o, P(bspec, "model", None, None))
    elif kind == "local":
        o = attn.local_attention(q, k, v, cfg.local_window)
    else:
        o = attn.chunked_attention(q, k, v, cfg.local_window)
    o = o.reshape(*x.shape[:2], cfg.q_dim)
    return o @ p["wo"].astype(x.dtype), (k, v)


def apply_block(cfg, kind: str, p: dict, x: jax.Array, positions: jax.Array,
                kv_block: int, unroll: bool = False):
    """Returns (x_out, aux_loss, kv_or_None)."""
    aux = jnp.zeros((), jnp.float32)
    kv = None
    if kind in ("full", "local", "chunked"):
        h, kv = _attend_full_seq(cfg, kind, p["attn"],
                                 apply_norm(cfg, p["norm1"], x), positions,
                                 kv_block, unroll)
        x = x + h
        y = apply_norm(cfg, p["norm2"], x)
        if cfg.moe is not None:
            f, aux = moe_mod.apply_moe(cfg, p["ffn"], y)
        else:
            f = ffn_mod.apply_ffn(cfg, p["ffn"], y)
        x = x + f
    elif kind == "rglru":
        x = x + rglru_mod.apply_rglru(cfg, p["rec"],
                                      apply_norm(cfg, p["norm1"], x))
        x = x + ffn_mod.apply_ffn(cfg, p["ffn"],
                                  apply_norm(cfg, p["norm2"], x))
    elif kind == "ssm":
        x = x + ssm_mod.apply_ssm(cfg, p["ssm"],
                                  apply_norm(cfg, p["norm1"], x))
    else:
        raise ValueError(kind)
    # Perf iteration B5: for pure full-attention stacks, keep the residual
    # stream sequence-sharded over "model" (Megatron-SP style) — norms, FFN
    # rows and attention all operate on local sequence shards, so per-layer
    # collectives shrink to (B, S/tp, D)-sized partial reductions.
    if cfg.block_pattern == ("full",):
        x = constrain(x, P(("pod", "data"), "model", None))
    else:
        x = constrain(x, P(("pod", "data"), None, None))
    return x, aux, kv


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------

def _slot_cache_len(cfg, kind: str, cache_len: int) -> int:
    if kind in ("local", "chunked"):
        return min(cfg.local_window, cache_len)
    return cache_len


def init_cache(cfg, batch: int, cache_len: int, dtype=jnp.bfloat16) -> dict:
    """Decode cache pytree: one entry per pattern slot (stacked n_rep) plus
    unstacked tail entries and a scalar position."""
    pat = cfg.block_pattern
    n_rep = cfg.num_layers // len(pat)
    tail_kinds = cfg.layer_kinds()[n_rep * len(pat):]

    def slot(kind, stack: Optional[int]):
        def maybe_stack(a):
            return a if stack is None else jnp.broadcast_to(a, (stack,) + a.shape)
        if kind in ("full", "local", "chunked"):
            T = _slot_cache_len(cfg, kind, cache_len)
            z = jnp.zeros((batch, T, cfg.num_kv_heads, cfg.head_dim), dtype)
            return {"k": maybe_stack(z), "v": maybe_stack(z)}
        if kind == "rglru":
            c = rglru_mod.init_rglru_cache(cfg, batch, dtype)
        else:
            c = ssm_mod.init_ssm_cache(cfg, batch, dtype)
        return jax.tree.map(maybe_stack, c)

    return {
        "slots": [slot(k, n_rep) for k in pat],
        "tail": [slot(k, None) for k in tail_kinds],
        "pos": jnp.zeros((), jnp.int32),
    }


def cache_specs(cfg, batch: int, cache_len: int, mesh,
                dtype=jnp.bfloat16):
    """PartitionSpec pytree for the decode cache.

    Policy: shard the batch dim over "data" (and "pod" when present and
    divisible); for KV tensors additionally shard kv_heads over "model" when
    divisible, else head_dim, else the sequence dim (context parallelism for
    long_500k's batch=1). Recurrent states shard their width over "model".
    """
    shapes = jax.eval_shape(lambda: init_cache(cfg, batch, cache_len, dtype))

    def ax_ok(name, d):
        return name in mesh.shape and d % mesh.shape[name] == 0

    def spec_of(leaf):
        shp = leaf.shape
        spec = [None] * len(shp)
        # find the batch dim: first dim equal to `batch` (after optional stack)
        dims = list(range(len(shp)))
        bi = None
        for i in dims:
            if shp[i] == batch and (i == 0 or shp[0] != batch):
                bi = i
                break
        if shp and shp[0] == batch:
            bi = 0
        if bi is not None:
            if ax_ok("data", shp[bi]):
                spec[bi] = "data"
        if len(shp) >= 2 and leaf.dtype != jnp.int32:
            # KV caches: (..., B, T, K, h). Prefer kv-heads over "model"; when
            # they don't divide (GQA with few kv heads), shard the SEQUENCE
            # dim instead — decode attention then computes partial softmax
            # sums locally and all-reduces only (B,K,G)-sized statistics,
            # instead of all-gathering the whole cache (Perf iteration C1).
            if len(shp) >= 4 and shp[-2] == cfg.num_kv_heads \
                    and shp[-1] == cfg.head_dim:
                if ax_ok("model", shp[-2]):
                    spec[-2] = "model"
                elif ax_ok("model", shp[-3]):
                    spec[-3] = "model"     # sequence (context parallel)
                elif ax_ok("model", shp[-1]):
                    spec[-1] = "model"
                elif spec[bi] != "data" and ax_ok("data", shp[-3]):
                    spec[-3] = "data"      # shard seq when batch can't shard
            else:
                # recurrent states: shard trailing width over model
                if ax_ok("model", shp[-1]):
                    spec[-1] = "model"
        return P(*spec)

    return jax.tree.map(spec_of, shapes)


# ---------------------------------------------------------------------------
# Paged decode caches
# ---------------------------------------------------------------------------
#
# Serving allocates KV memory at *page* granularity instead of dense max_len
# slabs: K/V rows live in global per-layer page pools of shape
# (num_pages, K, page_size, head_dim), every slot owns an ordered row of an
# int32 page table (num_slots, max_pages) plus a true per-slot position, and
# attention gathers through the table (kernels/paged_gqa_decode). Page 0 is
# reserved as the null page: retired/inactive slots point their whole table
# at it, so their masked lanes write and read harmless garbage.

PAGED_NULL_PAGE = 0


def _pool_cast(x: jax.Array, dtype) -> jax.Array:
    """Cast KV rows to a pool dtype. fp8 pools store E4M3 bit codes in
    uint8 (see `quant.FP8_STORAGE_DTYPE`), and the cast saturates because
    E4M3 overflows to NaN, not inf."""
    if jnp.dtype(dtype) == quant.FP8_STORAGE_DTYPE:
        return quant.to_fp8_codes(x)
    if jnp.dtype(dtype) == jnp.dtype(quant.FP8_DTYPE):
        return quant.to_fp8(x)
    return x.astype(dtype)


def init_paged_cache(cfg, num_slots: int, num_pages: int, page_size: int,
                     max_pages_per_slot: int, dtype=jnp.bfloat16,
                     kv_dtype: Optional[str] = None) -> dict:
    """Paged decode state: per-layer page pools shared by all slots, one page
    table + true position per slot. Recurrent (SSM / RG-LRU) blocks keep
    their fixed-size per-slot state dense, batched over slots — only
    attention KV grows with context, so only it is paged. Sliding-window /
    chunked layers are bounded by construction and not supported here.

    `kv_dtype` selects the page storage format (see `kernels.quant`):
    None/"native" keeps pages in `dtype`; "int8" stores int8 payload pools
    plus per-row float32 scale pools ("ks"/"vs"); "fp8" stores scale-free
    E4M3 pools. Recurrent state always stays in `dtype`."""
    pat = cfg.block_pattern
    if any(k in ("local", "chunked") for k in pat):
        raise NotImplementedError(
            "paged decode supports full-attention (+ssm/rglru) stacks; "
            "window-bounded layers gain nothing from paging")
    spec = quant.kv_dtype_spec(kv_dtype or "native", native=dtype)
    n_rep = cfg.num_layers // len(pat)
    tail_kinds = cfg.layer_kinds()[n_rep * len(pat):]

    def slot(kind, stack: Optional[int]):
        def maybe_stack(a):
            return a if stack is None else jnp.broadcast_to(a, (stack,) + a.shape)
        if kind == "full":
            z = jnp.zeros((num_pages, cfg.num_kv_heads, page_size,
                           cfg.head_dim), spec.pool_dtype)
            e = {"kp": maybe_stack(z), "vp": maybe_stack(z)}
            if spec.has_scales:
                zs = jnp.zeros((num_pages, cfg.num_kv_heads, page_size),
                               jnp.float32)
                e["ks"] = maybe_stack(zs)
                e["vs"] = maybe_stack(zs)
            return e
        if kind == "rglru":
            c = rglru_mod.init_rglru_cache(cfg, num_slots, dtype)
        else:
            c = ssm_mod.init_ssm_cache(cfg, num_slots, dtype)
        return jax.tree.map(maybe_stack, c)

    return {
        "slots": [slot(k, n_rep) for k in pat],
        "tail": [slot(k, None) for k in tail_kinds],
        "pos": jnp.zeros((num_slots,), jnp.int32),
        "page_table": jnp.full((num_slots, max_pages_per_slot),
                               PAGED_NULL_PAGE, jnp.int32),
        "active": jnp.zeros((num_slots,), bool),
    }


def write_prefill_to_pages(cfg, paged: dict, dense: dict, slot,
                           page_ids: jax.Array) -> dict:
    """Admission: map a batch=1 dense prefill cache into slot `slot` of a
    paged cache — the prompt's KV rows are scattered into the slot's
    freshly-allocated pages and the page-table row is rewritten; nothing is
    re-prefilled. The dense cache_len must equal len(page_ids) * page_size."""
    pat = cfg.block_pattern
    n_rep = cfg.num_layers // len(pat)
    tail_kinds = cfg.layer_kinds()[n_rep * len(pat):]
    npg = page_ids.shape[0]

    def one(kind, entry, d_entry, stacked: bool):
        if kind == "full":
            kp = entry["kp"]
            ps = kp.shape[-2]

            def to_pages(x):
                # (..., 1, npg*ps, K, d) -> (..., npg, K, ps, d)
                if stacked:
                    n, T, K, d = x.shape[0], x.shape[2], x.shape[3], x.shape[4]
                    return x.reshape(n, npg, ps, K, d).transpose(0, 1, 3, 2, 4)
                T, K, d = x.shape[1], x.shape[2], x.shape[3]
                return x.reshape(npg, ps, K, d).transpose(0, 2, 1, 3)

            def scatter(pool, x):
                return (pool.at[:, page_ids].set(x) if stacked
                        else pool.at[page_ids].set(x))

            if "ks" in entry:                    # int8: quantize per row
                def put_q(pool, spool, dense_kv):
                    q8, s = quant.quantize_page_rows(to_pages(
                        dense_kv.astype(jnp.float32)))
                    return scatter(pool, q8), scatter(spool, s)

                kp_n, ks_n = put_q(kp, entry["ks"], d_entry["k"])
                vp_n, vs_n = put_q(entry["vp"], entry["vs"], d_entry["v"])
                return {"kp": kp_n, "vp": vp_n, "ks": ks_n, "vs": vs_n}

            def put(pool, dense_kv):
                return scatter(pool, to_pages(_pool_cast(dense_kv,
                                                         pool.dtype)))

            return {"kp": put(kp, d_entry["k"]), "vp": put(entry["vp"],
                                                           d_entry["v"])}
        # recurrent state: write the single prefilled sequence into slot row
        if stacked:
            return jax.tree.map(
                lambda s, d: s.at[:, slot].set(d[:, 0].astype(s.dtype)),
                entry, d_entry)
        return jax.tree.map(
            lambda s, d: s.at[slot].set(d[0].astype(s.dtype)),
            entry, d_entry)

    out = dict(paged)
    out["slots"] = [one(k, e, de, True) for k, e, de in
                    zip(pat, paged["slots"], dense["slots"])]
    out["tail"] = [one(k, e, de, False) for k, e, de in
                   zip(tail_kinds, paged["tail"], dense["tail"])]
    row = jnp.full((paged["page_table"].shape[1],), PAGED_NULL_PAGE,
                   jnp.int32).at[:npg].set(page_ids.astype(jnp.int32))
    out["page_table"] = paged["page_table"].at[slot].set(row)
    out["pos"] = paged["pos"].at[slot].set(dense["pos"].astype(jnp.int32))
    out["active"] = paged["active"].at[slot].set(True)
    return out


# Retirement needs no device call: the decode loop flips `active` in-scan,
# the batcher zeroes its host page-table mirror (pushed before each chunk),
# and re-admission overwrites pos/active/table — pool pages are only
# reachable through tables, so they never need clearing.


# ---------------------------------------------------------------------------
# Prefix sharing: gather / COW-write / page-copy helpers + suffix prefill
# ---------------------------------------------------------------------------
#
# Prefix reuse needs no kernel change — shared pages are reached through the
# same page-table indirection as private ones. The device-side verbs are:
#   gather_prefix_pages          pages -> dense (1, m, K, h) prefix KV
#   prefix_tail_rows             last j rows of a gathered prefix (the
#                                partially-matched page's valid rows)
#   write_shared_prefill_to_pages  head + suffix KV -> fresh pages, table row
#                                = shared pages ++ fresh pages
#   copy_pages                   COW split: clone one page across all layers
# and DecoderLM.prefill_shared runs the *suffix-only* forward against the
# gathered prefix KV (the compute half of "skipping prefill for the matched
# run"). All of it is restricted to pure full-attention stacks: recurrent
# blocks carry position-mixed state that cannot be sliced at a prefix
# boundary.

def _require_pure_full(cfg, what: str) -> None:
    if any(k != "full" for k in cfg.layer_kinds()):
        raise NotImplementedError(
            f"{what} requires a pure full-attention stack; "
            f"{cfg.name} mixes {set(cfg.layer_kinds())}")


def gather_prefix_pages(cfg, paged: dict, page_ids: jax.Array,
                        n_rows: int) -> dict:
    """Collect the first `n_rows` KV rows stored in `page_ids` (table order)
    as a dense prefix pytree {"slots": [{"k","v"}...], "tail": [...]} with
    leaves (n_rep, 1, n_rows, K, h) / (1, n_rows, K, h). Rows come back
    exactly as stored (post-RoPE, pool dtype); int8 pools dequantize with
    their per-row scales and fp8 pools decode their E4M3 codes, both
    returning float32 rows."""
    _require_pure_full(cfg, "gather_prefix_pages")

    def take(pool, spool, stacked: bool):
        fp8 = pool.dtype == quant.FP8_STORAGE_DTYPE
        if stacked:
            x = pool[:, page_ids]                      # (n, npg, K, ps, h)
            if spool is not None:
                x = quant.dequantize_page_rows(x, spool[:, page_ids])
            elif fp8:
                x = quant.from_fp8(x)
            n, npg, K, ps, h = x.shape
            x = x.transpose(0, 1, 3, 2, 4).reshape(n, npg * ps, K, h)
            return x[:, None, :n_rows]                 # (n, 1, rows, K, h)
        x = pool[page_ids]                             # (npg, K, ps, h)
        if spool is not None:
            x = quant.dequantize_page_rows(x, spool[page_ids])
        elif fp8:
            x = quant.from_fp8(x)
        npg, K, ps, h = x.shape
        x = x.transpose(0, 2, 1, 3).reshape(npg * ps, K, h)
        return x[None, :n_rows]                        # (1, rows, K, h)

    return {
        "slots": [{"k": take(e["kp"], e.get("ks"), True),
                   "v": take(e["vp"], e.get("vs"), True)}
                  for e in paged["slots"]],
        "tail": [{"k": take(e["kp"], e.get("ks"), False),
                  "v": take(e["vp"], e.get("vs"), False)}
                 for e in paged["tail"]],
    }


def prefix_tail_rows(prefix: dict, j: int) -> dict:
    """Last `j` rows of a gathered prefix — the valid head of the boundary
    page a COW admission rewrites into its private copy (j == 0 -> empty)."""
    def cut(a, stacked: bool):
        return a[:, :, a.shape[2] - j:] if stacked else a[:, a.shape[1] - j:]
    return {
        "slots": [{"k": cut(e["k"], True), "v": cut(e["v"], True)}
                  for e in prefix["slots"]],
        "tail": [{"k": cut(e["k"], False), "v": cut(e["v"], False)}
                 for e in prefix["tail"]],
    }


def write_shared_prefill_to_pages(cfg, paged: dict, suffix: dict, head: dict,
                                  slot, shared_ids: jax.Array,
                                  fresh_ids: jax.Array) -> dict:
    """Prefix-hit admission: map `shared_ids` read-only into the slot's
    table, then write `head` (j rows re-owned from the partially-matched
    page) followed by `suffix` (the freshly computed suffix KV) page-aligned
    into `fresh_ids`. Sets pos = |shared|*ps + j + |suffix| and activates
    the slot. With empty `shared_ids`/`head` this degenerates to a plain
    paged admission of a full prefill. int8 pools requantize the written
    rows per row — idempotent for `head` rows that were dequantized from
    the donor's pages, so shared pages stay quantized and bit-stable."""
    _require_pure_full(cfg, "write_shared_prefill_to_pages")
    n_shared = shared_ids.shape[0]
    npg_f = fresh_ids.shape[0]

    def put(pool, spool, head_x, suf_x, stacked: bool):
        ps = pool.shape[-2]
        quantized = spool is not None
        cast = ((lambda a: a.astype(jnp.float32)) if quantized
                else (lambda a: _pool_cast(a, pool.dtype)))
        if stacked:
            rows = jnp.concatenate([cast(head_x[:, 0]), cast(suf_x[:, 0])],
                                   axis=1)
            n, r, K, h = rows.shape
            rows = jnp.pad(rows, ((0, 0), (0, npg_f * ps - r),
                                  (0, 0), (0, 0)))
            x = rows.reshape(n, npg_f, ps, K, h).transpose(0, 1, 3, 2, 4)
            if quantized:
                q8, s = quant.quantize_page_rows(x)
                return (pool.at[:, fresh_ids].set(q8),
                        spool.at[:, fresh_ids].set(s))
            return pool.at[:, fresh_ids].set(x), None
        rows = jnp.concatenate([cast(head_x[0]), cast(suf_x[0])], axis=0)
        r, K, h = rows.shape
        rows = jnp.pad(rows, ((0, npg_f * ps - r), (0, 0), (0, 0)))
        x = rows.reshape(npg_f, ps, K, h).transpose(0, 2, 1, 3)
        if quantized:
            q8, s = quant.quantize_page_rows(x)
            return pool.at[fresh_ids].set(q8), spool.at[fresh_ids].set(s)
        return pool.at[fresh_ids].set(x), None

    def entry_out(e, hd, sf, stacked: bool):
        kp, ks = put(e["kp"], e.get("ks"), hd["k"], sf["k"], stacked)
        vp, vs = put(e["vp"], e.get("vs"), hd["v"], sf["v"], stacked)
        ne = {"kp": kp, "vp": vp}
        if ks is not None:
            ne["ks"], ne["vs"] = ks, vs
        return ne

    ps = paged["slots"][0]["kp"].shape[-2] if paged["slots"] \
        else paged["tail"][0]["kp"].shape[-2]
    j = (head["slots"][0]["k"].shape[2] if head["slots"]
         else head["tail"][0]["k"].shape[1])
    s_suf = (suffix["slots"][0]["k"].shape[2] if suffix["slots"]
             else suffix["tail"][0]["k"].shape[1])

    out = dict(paged)
    out["slots"] = [
        entry_out(e, hd, sf, True)
        for e, hd, sf in zip(paged["slots"], head["slots"], suffix["slots"])]
    out["tail"] = [
        entry_out(e, hd, sf, False)
        for e, hd, sf in zip(paged["tail"], head["tail"], suffix["tail"])]
    row = jnp.full((paged["page_table"].shape[1],), PAGED_NULL_PAGE,
                   jnp.int32)
    row = row.at[:n_shared].set(shared_ids.astype(jnp.int32))
    row = row.at[n_shared:n_shared + npg_f].set(fresh_ids.astype(jnp.int32))
    out["page_table"] = paged["page_table"].at[slot].set(row)
    out["pos"] = paged["pos"].at[slot].set(
        jnp.int32(n_shared * ps + j + s_suf))
    out["active"] = paged["active"].at[slot].set(True)
    return out


def copy_pages(cfg, paged: dict, src: jax.Array, dst: jax.Array) -> dict:
    """COW split: duplicate page `src` into `dst` across every layer pool
    (one jitted call, scalars traced — compiles once per pool geometry).
    Quantized pools copy payload and per-row scale pools alike, so COW
    clones stay quantized bit-for-bit."""
    _require_pure_full(cfg, "copy_pages")
    out = dict(paged)
    out["slots"] = [{key: a.at[:, dst].set(a[:, src]) for key, a in e.items()}
                    for e in paged["slots"]]
    out["tail"] = [{key: a.at[dst].set(a[src]) for key, a in e.items()}
                   for e in paged["tail"]]
    return out


# ---------------------------------------------------------------------------
# Block application — decode mode
# ---------------------------------------------------------------------------

def apply_block_decode(cfg, kind: str, p: dict, x: jax.Array, cache: dict,
                       pos: jax.Array):
    """x: (B,1,D). Returns (x_out, new_cache)."""
    if kind in ("full", "local", "chunked"):
        y = apply_norm(cfg, p["norm1"], x)
        q, k, v = attn.project_qkv(cfg, p["attn"], y, y)
        if cfg.pos_emb == "rope":
            posv = pos[None] if pos.ndim == 0 else pos
            q = apply_rope(q, jnp.broadcast_to(posv, (x.shape[0], 1)),
                           cfg.rope_theta)
            k = apply_rope(k, jnp.broadcast_to(posv, (x.shape[0], 1)),
                           cfg.rope_theta)
        T = cache["k"].shape[1]
        if kind == "full":
            widx = pos
        else:
            widx = pos % jnp.int32(T)
        ck, cv = attn.cache_write(cache["k"], cache["v"], k, v, widx)
        valid = attn.decode_valid_mask(kind, T, pos, cfg.local_window)
        o = attn.decode_attention(q, ck, cv, valid)
        o = o.reshape(x.shape[0], 1, cfg.q_dim) @ p["attn"]["wo"].astype(x.dtype)
        x = x + o
        y2 = apply_norm(cfg, p["norm2"], x)
        if cfg.moe is not None:
            f, _ = moe_mod.apply_moe(cfg, p["ffn"], y2)
        else:
            f = ffn_mod.apply_ffn(cfg, p["ffn"], y2)
        x = x + f
        return x, {"k": ck, "v": cv}
    if kind == "rglru":
        h, new_c = rglru_mod.apply_rglru_decode(
            cfg, p["rec"], apply_norm(cfg, p["norm1"], x), cache)
        x = x + h
        x = x + ffn_mod.apply_ffn(cfg, p["ffn"], apply_norm(cfg, p["norm2"], x))
        return x, new_c
    if kind == "ssm":
        h, new_c = ssm_mod.apply_ssm_decode(
            cfg, p["ssm"], apply_norm(cfg, p["norm1"], x), cache)
        return x + h, new_c
    raise ValueError(kind)


def apply_block_decode_paged(cfg, kind: str, p: dict, x: jax.Array,
                             cache: dict, pos: jax.Array,
                             lengths: jax.Array, page_table: jax.Array,
                             attn_backend: str = "auto"):
    """Paged decode block. x: (B,1,D); pos: (B,) true per-slot positions;
    lengths: (B,) rows each slot attends over (pos + 1, 0 where inactive);
    page_table: (B, P). Returns (x_out, new_cache).

    Full-attention blocks write the new K/V row through the page table
    (inactive slots resolve to the null page) and attend with the paged GQA
    kernel at exact per-slot lengths — no max-length mask. Recurrent blocks
    are position-independent and reuse the dense decode path."""
    if kind == "full":
        from repro.kernels.paged_gqa_decode import (paged_gqa_decode,
                                                    paged_gqa_decode_quant)
        y = apply_norm(cfg, p["norm1"], x)
        q, k, v = attn.project_qkv(cfg, p["attn"], y, y)
        if cfg.pos_emb == "rope":
            q = apply_rope(q, pos[:, None], cfg.rope_theta)
            k = apply_rope(k, pos[:, None], cfg.rope_theta)
        kp, vp = cache["kp"], cache["vp"]
        ps = kp.shape[-2]
        P = page_table.shape[1]
        B = x.shape[0]
        pidx = page_table[jnp.arange(B), jnp.clip(pos // ps, 0, P - 1)]
        off = pos % ps
        if "ks" in cache:
            # int8 pages: quantize the appended row per (slot, kv head) and
            # attend with the fused in-register-dequant kernel. Per-row
            # scales make the append local — rows already in the page keep
            # their codes and scales.
            ks, vs = cache["ks"], cache["vs"]
            qk, sk = quant.quantize_page_rows(k[:, 0].astype(jnp.float32))
            qv, sv = quant.quantize_page_rows(v[:, 0].astype(jnp.float32))
            kp = kp.at[pidx, :, off].set(qk)
            vp = vp.at[pidx, :, off].set(qv)
            ks = ks.at[pidx, :, off].set(sk)
            vs = vs.at[pidx, :, off].set(sv)
            o = paged_gqa_decode_quant(q[:, 0], kp, vp, ks, vs, page_table,
                                       lengths, backend=attn_backend)
            new_entry = {"kp": kp, "vp": vp, "ks": ks, "vs": vs}
        else:
            kp = kp.at[pidx, :, off].set(_pool_cast(k[:, 0], kp.dtype))
            vp = vp.at[pidx, :, off].set(_pool_cast(v[:, 0], vp.dtype))
            o = paged_gqa_decode(q[:, 0], kp, vp, page_table, lengths,
                                 backend=attn_backend)
            new_entry = {"kp": kp, "vp": vp}
        o = o.reshape(B, 1, cfg.q_dim) @ p["attn"]["wo"].astype(x.dtype)
        x = x + o
        y2 = apply_norm(cfg, p["norm2"], x)
        if cfg.moe is not None:
            f, _ = moe_mod.apply_moe(cfg, p["ffn"], y2)
        else:
            f = ffn_mod.apply_ffn(cfg, p["ffn"], y2)
        x = x + f
        return x, new_entry
    return apply_block_decode(cfg, kind, p, x, cache, pos)


def apply_block_verify_paged(cfg, p: dict, x: jax.Array, cache: dict,
                             pos: jax.Array, page_table: jax.Array,
                             attn_backend: str = "auto"):
    """Speculative-verification block. x: (B, V, D) — the V = spec_k + 1
    window rows per slot; pos: (B,) true per-slot context lengths *before*
    the window; page_table: (B, P). Returns (x_out, new_cache).

    Writes all V K/V rows through the page table at positions
    pos .. pos + V - 1 (inactive slots resolve to the null page), then
    scores every window row in one `paged_gqa_verify` call — one pass over
    the resident pages instead of V sequential decode calls. Rows past the
    eventually-accepted count are garbage the next round overwrites before
    reading; `pos` itself is owned by the caller."""
    from repro.kernels.paged_gqa_verify import paged_gqa_verify
    if "ks" in cache:
        raise NotImplementedError(
            "speculative verification does not support int8 KV pages: "
            "per-row scales of rolled-back rows would need requant-stable "
            "rewrites; use native/fp16/bf16/fp8 kv_dtype")
    B, V = x.shape[:2]
    y = apply_norm(cfg, p["norm1"], x)
    q, k, v = attn.project_qkv(cfg, p["attn"], y, y)
    positions = pos[:, None] + jnp.arange(V, dtype=jnp.int32)[None, :]
    if cfg.pos_emb == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    kp, vp = cache["kp"], cache["vp"]
    ps = kp.shape[-2]
    P = page_table.shape[1]
    pidx = page_table[jnp.arange(B)[:, None],
                      jnp.clip(positions // ps, 0, P - 1)]      # (B, V)
    off = positions % ps
    kp = kp.at[pidx, :, off].set(_pool_cast(k, kp.dtype))
    vp = vp.at[pidx, :, off].set(_pool_cast(v, vp.dtype))
    o = paged_gqa_verify(q, kp, vp, page_table, pos, backend=attn_backend)
    o = o.reshape(B, V, cfg.q_dim) @ p["attn"]["wo"].astype(x.dtype)
    x = x + o
    y2 = apply_norm(cfg, p["norm2"], x)
    if cfg.moe is not None:
        f, _ = moe_mod.apply_moe(cfg, p["ffn"], y2)
    else:
        f = ffn_mod.apply_ffn(cfg, p["ffn"], y2)
    x = x + f
    return x, {"kp": kp, "vp": vp}


def _apply_block_shared_prefill(cfg, p: dict, x: jax.Array,
                                positions: jax.Array, pk: jax.Array,
                                pv: jax.Array, kv_block: int,
                                unroll: bool = False,
                                pad_to: Optional[int] = None):
    """Full-attention block over suffix rows against a cached prefix.

    x: (1, S_suf, D) suffix activations; positions: (1, S_suf) absolute
    positions; pk/pv: (1, m, K, h) prefix KV exactly as stored (post-RoPE).
    Attention runs over concat(prefix, suffix) keys with `q_offset = m`.

    `pad_to` fixes the attention width: keys/values are zero-padded (and
    causally masked) to that many positions and contracted as one block.
    With a fixed width, row i's online-softmax reduction tree depends only
    on tokens <= i — so the KV a request computes is **bit-identical**
    whether its prefix rows came from its own prefill or a donor with a
    different continuation. That invariance is what makes prefix-cache
    hits exact; without `pad_to` the reduction width (and hence float
    rounding) varies with total sequence length. Returns (x_out, (k, v))
    with suffix-only KV."""
    m = pk.shape[1]
    y = apply_norm(cfg, p["norm1"], x)
    q, k, v = attn.project_qkv(cfg, p["attn"], y, y)
    if cfg.pos_emb == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    kk = jnp.concatenate([pk.astype(k.dtype), k], axis=1)
    vv = jnp.concatenate([pv.astype(v.dtype), v], axis=1)
    if pad_to is not None:
        T = kk.shape[1]
        assert pad_to >= T, (pad_to, T)
        pad = ((0, 0), (0, pad_to - T), (0, 0), (0, 0))
        kk, vv = jnp.pad(kk, pad), jnp.pad(vv, pad)
        kv_block = pad_to
    o = attn.blocked_attention(q, kk, vv, causal=True, q_offset=m,
                               kv_block=kv_block, unroll=unroll)
    o = o.reshape(*x.shape[:2], cfg.q_dim)
    x = x + o @ p["attn"]["wo"].astype(x.dtype)
    y2 = apply_norm(cfg, p["norm2"], x)
    if cfg.moe is not None:
        f, _ = moe_mod.apply_moe(cfg, p["ffn"], y2)
    else:
        f = ffn_mod.apply_ffn(cfg, p["ffn"], y2)
    return x + f, (k, v)


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

@dataclass
class DecoderLM:
    cfg: Any
    compute_dtype: Any = jnp.bfloat16
    remat: str = "full"            # none | full | dots
    kv_block: int = 1024
    # unroll lax.scan loops (layer stack + attention kv blocks). The dry-run
    # sets this so compiled.cost_analysis() counts every iteration's
    # FLOPs/bytes/collectives — HLO cost analysis visits loop bodies once.
    unroll: bool = False

    # ------------------------------------------------------------- params
    def template(self) -> dict:
        return lm_template(self.cfg)

    def init(self, rng: jax.Array) -> dict:
        return init_params(self.template(), rng)

    def abstract(self, dtype_override: Optional[str] = None):
        return abstract_params(self.template(), dtype_override)

    # ------------------------------------------------------------ helpers
    def _maybe_remat(self, fn):
        if self.remat == "none":
            return fn
        if self.remat == "dots":
            pol = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
            return jax.checkpoint(fn, policy=pol)
        return jax.checkpoint(fn)

    def _stack_forward(self, params: dict, x: jax.Array,
                       positions: jax.Array):
        """Scan over pattern groups + unstacked tail. Returns (x, aux)."""
        cfg = self.cfg
        pat = cfg.block_pattern
        n_rep = cfg.num_layers // len(pat)
        kvb = self.kv_block

        def group(x, slot_params):
            aux = jnp.zeros((), jnp.float32)
            for i, kind in enumerate(pat):
                x, a, _ = apply_block(cfg, kind, slot_params[i], x, positions,
                                      kvb, self.unroll)
                aux = aux + a
            return x, aux

        group = self._maybe_remat(group)

        def body(carry, slot_params):
            x, aux = carry
            x, a = group(x, slot_params)
            return (x, aux + a), None

        if n_rep > 0:
            (x, aux), _ = jax.lax.scan(
                body, (x, jnp.zeros((), jnp.float32)), tuple(params["blocks"]),
                unroll=n_rep if self.unroll else 1)
        else:
            aux = jnp.zeros((), jnp.float32)
        tail_kinds = cfg.layer_kinds()[n_rep * len(pat):]
        for tp, kind in zip(params["tail"], tail_kinds):
            x, a, _ = apply_block(cfg, kind, tp, x, positions, kvb,
                                  self.unroll)
            aux = aux + a
        return x, aux

    def _embed_inputs(self, params: dict, batch: dict) -> Tuple[jax.Array, jax.Array]:
        """tokens (+ optional prefix embeds) -> (x (B,S,D), positions (B,S))."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S_tok = tokens.shape
        pos_tok = jnp.broadcast_to(jnp.arange(S_tok), (B, S_tok))
        n_pfx = 0
        if cfg.frontend is not None and "prefix_embeds" in batch:
            n_pfx = batch["prefix_embeds"].shape[1]
            pos_tok = pos_tok + n_pfx
        x = embed_tokens(cfg, params["embed"], tokens, pos_tok,
                         self.compute_dtype)
        if n_pfx:
            pr = batch["prefix_embeds"].astype(self.compute_dtype)
            pr = pr @ params["projector"]["w"].astype(self.compute_dtype) \
                + params["projector"]["b"].astype(self.compute_dtype)
            x = jnp.concatenate([pr, x], axis=1)
            positions = jnp.concatenate(
                [jnp.broadcast_to(jnp.arange(n_pfx), (B, n_pfx)), pos_tok],
                axis=1)
        else:
            positions = pos_tok
        return x, positions

    # -------------------------------------------------------------- loss
    def loss(self, params: dict, batch: dict) -> jax.Array:
        cfg = self.cfg
        x, positions = self._embed_inputs(params, batch)
        x = constrain(x, P(("pod", "data"), None, None))
        x, aux = self._stack_forward(params, x, positions[0])
        x = apply_norm(cfg, params["final_norm"], x)
        n_pfx = x.shape[1] - batch["tokens"].shape[1]
        if n_pfx:
            x = x[:, n_pfx:, :]
        logits = lm_logits(cfg, params["embed"], x[:, :-1, :])
        labels = batch.get("labels", batch["tokens"])[:, 1:]
        return cross_entropy(logits, labels) + aux

    # ------------------------------------------------------------ prefill
    def prefill(self, params: dict, batch: dict, cache_len: int):
        """Returns (last-position logits, filled cache)."""
        cfg = self.cfg
        x, positions = self._embed_inputs(params, batch)
        S = x.shape[1]
        B = x.shape[0]
        pat = cfg.block_pattern
        n_rep = cfg.num_layers // len(pat)
        kvb = self.kv_block
        cache = init_cache(cfg, B, cache_len, self.compute_dtype)

        # full-sequence forward, capturing per-layer kv / states
        def run_block(x, kind, p, slot_cache):
            if kind in ("full", "local", "chunked"):
                x, _, (k, v) = apply_block(cfg, kind, p, x, positions[0], kvb,
                                           self.unroll)
                T = slot_cache["k"].shape[1]
                if kind == "full" or S <= T:
                    k_w = k[:, :T]
                    v_w = v[:, :T]
                    ck = jax.lax.dynamic_update_slice_in_dim(
                        slot_cache["k"], k_w.astype(slot_cache["k"].dtype), 0, 1)
                    cv = jax.lax.dynamic_update_slice_in_dim(
                        slot_cache["v"], v_w.astype(slot_cache["v"].dtype), 0, 1)
                else:
                    # ring: last T positions at slots (S-T+i) % T
                    kw = k[:, -T:].astype(slot_cache["k"].dtype)
                    vw = v[:, -T:].astype(slot_cache["v"].dtype)
                    idx = (S - T + jnp.arange(T)) % T
                    ck = slot_cache["k"].at[:, idx].set(kw)
                    cv = slot_cache["v"].at[:, idx].set(vw)
                return x, {"k": ck, "v": cv}
            if kind == "rglru":
                y = apply_norm(cfg, p["norm1"], x)
                h_out, final = _rglru_prefill(cfg, p["rec"], y)
                x = x + h_out
                x = x + ffn_mod.apply_ffn(cfg, p["ffn"],
                                          apply_norm(cfg, p["norm2"], x))
                new_c = {"h": final["h"],
                         "conv": final["conv"].astype(slot_cache["conv"].dtype)}
                return x, new_c
            # ssm
            y = apply_norm(cfg, p["norm1"], x)
            h_out, final = _ssm_prefill(cfg, p["ssm"], y)
            x = x + h_out
            new_c = {"state": final["state"],
                     "conv_x": final["conv_x"].astype(slot_cache["conv_x"].dtype),
                     "conv_B": final["conv_B"].astype(slot_cache["conv_B"].dtype),
                     "conv_C": final["conv_C"].astype(slot_cache["conv_C"].dtype)}
            return x, new_c

        def body(x, xs):
            slot_params, slot_caches = xs
            new_caches = []
            for i, kind in enumerate(pat):
                x, nc = run_block(x, kind, slot_params[i], slot_caches[i])
                new_caches.append(nc)
            return x, tuple(new_caches)

        if n_rep > 0:
            x, new_slots = jax.lax.scan(
                body, x, (tuple(params["blocks"]), tuple(cache["slots"])),
                unroll=n_rep if self.unroll else 1)
            cache["slots"] = list(new_slots)
        tail_kinds = cfg.layer_kinds()[n_rep * len(pat):]
        new_tail = []
        for tp, kind, tc in zip(params["tail"], tail_kinds, cache["tail"]):
            x, nc = run_block(x, kind, tp, tc)
            new_tail.append(nc)
        cache["tail"] = new_tail
        cache["pos"] = jnp.asarray(S, jnp.int32)

        x = apply_norm(cfg, params["final_norm"], x)
        logits = lm_logits(cfg, params["embed"], x[:, -1:, :])
        return logits, cache

    # ------------------------------------------------- prefix-hit prefill
    def prefill_shared(self, params: dict, batch: dict, prefix: dict,
                       pad_to: Optional[int] = None):
        """Suffix-only prefill against a cached prompt prefix.

        batch["tokens"]: (1, S_suf) — the prompt tokens *after* the matched
        prefix; `prefix`: the pytree from `gather_prefix_pages` (per-layer
        post-RoPE KV of the matched m tokens). Embeds/ropes the suffix at
        absolute positions [m, m + S_suf) and attends over the concatenated
        keys, so only the suffix's compute is paid — the prefill skip of a
        prefix-cache hit. `pad_to` fixes the attention width for donor-
        independent bit-exactness (see `_apply_block_shared_prefill`); the
        paged batcher passes its slot capacity. Returns (last-position
        logits, suffix KV pytree with leaves (n_rep, 1, S_suf, K, h) ready
        for `write_shared_prefill_to_pages`). Pure full-attention stacks
        only; with an empty prefix (m == 0) this is a full prefill minus
        the dense cache padding.
        """
        cfg = self.cfg
        _require_pure_full(cfg, "prefill_shared")
        if "prefix_embeds" in batch:
            raise NotImplementedError("prefix caching is token-keyed; "
                                      "frontend prefix embeds unsupported")
        tokens = batch["tokens"]
        B, S = tokens.shape
        m = (prefix["slots"][0]["k"].shape[2] if prefix["slots"]
             else prefix["tail"][0]["k"].shape[1])
        positions = jnp.broadcast_to(m + jnp.arange(S), (B, S))
        x = embed_tokens(cfg, params["embed"], tokens, positions,
                         self.compute_dtype)
        pat = cfg.block_pattern
        n_rep = cfg.num_layers // len(pat)
        kvb = self.kv_block

        def body(x, xs):
            slot_params, slot_prefix = xs
            kvs = []
            for i in range(len(pat)):
                x, (k, v) = _apply_block_shared_prefill(
                    cfg, slot_params[i], x, positions, slot_prefix[i]["k"],
                    slot_prefix[i]["v"], kvb, self.unroll, pad_to)
                kvs.append({"k": k, "v": v})
            return x, tuple(kvs)

        if n_rep > 0:
            x, suf_slots = jax.lax.scan(
                body, x, (tuple(params["blocks"]), tuple(prefix["slots"])),
                unroll=n_rep if self.unroll else 1)
            suf_slots = list(suf_slots)
        else:
            suf_slots = []
        suf_tail = []
        for tp, pfx in zip(params["tail"], prefix["tail"]):
            x, (k, v) = _apply_block_shared_prefill(
                cfg, tp, x, positions, pfx["k"], pfx["v"], kvb, self.unroll,
                pad_to)
            suf_tail.append({"k": k, "v": v})

        x = apply_norm(cfg, params["final_norm"], x)
        logits = lm_logits(cfg, params["embed"], x[:, -1:, :])
        return logits, {"slots": suf_slots, "tail": suf_tail}

    # -------------------------------------------------------- decode step
    def decode_step(self, params: dict, cache: dict, tokens: jax.Array):
        """tokens: (B, 1) -> (logits (B,1,V), new cache)."""
        cfg = self.cfg
        pos = cache["pos"]
        B = tokens.shape[0]
        x = embed_tokens(cfg, params["embed"], tokens,
                         jnp.broadcast_to(pos, (B, 1)), self.compute_dtype)
        pat = cfg.block_pattern
        n_rep = cfg.num_layers // len(pat)

        def body(x, xs):
            slot_params, slot_caches = xs
            new_caches = []
            for i, kind in enumerate(pat):
                x, nc = apply_block_decode(cfg, kind, slot_params[i], x,
                                           slot_caches[i], pos)
                new_caches.append(nc)
            return x, tuple(new_caches)

        new_cache = dict(cache)
        if n_rep > 0:
            x, new_slots = jax.lax.scan(
                body, x, (tuple(params["blocks"]), tuple(cache["slots"])),
                unroll=n_rep if self.unroll else 1)
            new_cache["slots"] = list(new_slots)
        tail_kinds = cfg.layer_kinds()[n_rep * len(pat):]
        new_tail = []
        for tp, kind, tc in zip(params["tail"], tail_kinds, cache["tail"]):
            x, nc = apply_block_decode(cfg, kind, tp, x, tc, pos)
            new_tail.append(nc)
        new_cache["tail"] = new_tail
        new_cache["pos"] = pos + 1

        x = apply_norm(cfg, params["final_norm"], x)
        logits = lm_logits(cfg, params["embed"], x)
        return logits, new_cache

    # ------------------------------------------------- paged decode step
    def decode_step_paged(self, params: dict, cache: dict, tokens: jax.Array,
                          attn_backend: str = "auto"):
        """tokens: (num_slots, 1) against an `init_paged_cache` state.

        Per-slot positions are exact: each slot embeds/ropes at its own
        `pos`, writes its K/V row through its page-table row, and attends
        over exactly `pos + 1` tokens. Inactive slots run masked (null page)
        and attend over no rows, so the paged kernel copies nothing for
        them; their `pos` does not advance."""
        cfg = self.cfg
        pos = cache["pos"]
        page_table = cache["page_table"]
        active = cache["active"]
        lengths = jnp.where(active, pos + 1, 0)
        x = embed_tokens(cfg, params["embed"], tokens, pos[:, None],
                         self.compute_dtype)
        pat = cfg.block_pattern
        n_rep = cfg.num_layers // len(pat)

        def body(x, xs):
            slot_params, slot_caches = xs
            new_caches = []
            for i, kind in enumerate(pat):
                x, nc = apply_block_decode_paged(
                    cfg, kind, slot_params[i], x, slot_caches[i], pos,
                    lengths, page_table, attn_backend)
                new_caches.append(nc)
            return x, tuple(new_caches)

        new_cache = dict(cache)
        if n_rep > 0:
            x, new_slots = jax.lax.scan(
                body, x, (tuple(params["blocks"]), tuple(cache["slots"])),
                unroll=n_rep if self.unroll else 1)
            new_cache["slots"] = list(new_slots)
        tail_kinds = cfg.layer_kinds()[n_rep * len(pat):]
        new_tail = []
        for tp, kind, tc in zip(params["tail"], tail_kinds, cache["tail"]):
            x, nc = apply_block_decode_paged(cfg, kind, tp, x, tc, pos,
                                             lengths, page_table,
                                             attn_backend)
            new_tail.append(nc)
        new_cache["tail"] = new_tail
        new_cache["pos"] = pos + active.astype(jnp.int32)

        x = apply_norm(cfg, params["final_norm"], x)
        logits = lm_logits(cfg, params["embed"], x)
        return logits, new_cache

    # --------------------------------------------- speculative verify step
    def verify_step_paged(self, params: dict, cache: dict, tokens: jax.Array,
                          attn_backend: str = "auto"):
        """tokens: (num_slots, V) — the pending token followed by the
        k = V - 1 drafted candidates — against an `init_paged_cache` state.

        Writes all V K/V rows at positions pos .. pos + V - 1 through the
        page table and scores the whole window in one batched
        `paged_gqa_verify` call; logits[:, v] conditions on tokens[:, :v+1],
        so argmax(logits[:, v]) is the target's greedy continuation after
        consuming candidate v. `pos` is NOT advanced — the speculative
        decode loop owns accept/rollback and moves `pos` by the accepted
        count, which is what makes a rejected suffix roll back for free
        (its rows become garbage past `pos` that the next round overwrites
        before reading). Pure full-attention stacks only: recurrent state
        cannot un-consume a rejected token."""
        cfg = self.cfg
        _require_pure_full(cfg, "verify_step_paged")
        pos = cache["pos"]
        page_table = cache["page_table"]
        B, V = tokens.shape
        positions = pos[:, None] + jnp.arange(V, dtype=jnp.int32)[None, :]
        x = embed_tokens(cfg, params["embed"], tokens, positions,
                         self.compute_dtype)
        pat = cfg.block_pattern
        n_rep = cfg.num_layers // len(pat)

        def body(x, xs):
            slot_params, slot_caches = xs
            new_caches = []
            for i in range(len(pat)):
                x, nc = apply_block_verify_paged(
                    cfg, slot_params[i], x, slot_caches[i], pos, page_table,
                    attn_backend)
                new_caches.append(nc)
            return x, tuple(new_caches)

        new_cache = dict(cache)
        if n_rep > 0:
            x, new_slots = jax.lax.scan(
                body, x, (tuple(params["blocks"]), tuple(cache["slots"])),
                unroll=n_rep if self.unroll else 1)
            new_cache["slots"] = list(new_slots)
        new_tail = []
        for tp, tc in zip(params["tail"], cache["tail"]):
            x, nc = apply_block_verify_paged(cfg, tp, x, tc, pos, page_table,
                                             attn_backend)
            new_tail.append(nc)
        new_cache["tail"] = new_tail

        x = apply_norm(cfg, params["final_norm"], x)
        logits = lm_logits(cfg, params["embed"], x)
        return logits, new_cache


def self_spec_draft(model: "DecoderLM", params: dict,
                    skip: int = 2) -> Tuple["DecoderLM", dict]:
    """Self-speculation draft: the target restricted to every `skip`-th
    layer, sharing the target's weights (the stacked block params of the
    single pattern slot are sliced along the repetition axis; embedding,
    final norm and LM head are reused as-is). `skip=1` returns a model
    whose greedy drafts always match the target — a 100%-acceptance oracle
    the bit-identity tests lean on. Single-group block patterns only."""
    cfg = model.cfg
    if len(cfg.block_pattern) != 1:
        raise NotImplementedError(
            "self-speculation slices the stacked params of one pattern "
            f"slot; {cfg.name} has pattern {cfg.block_pattern}")
    if skip < 1:
        raise ValueError(f"skip must be >= 1, got {skip}")
    keep = list(range(0, cfg.num_layers, skip))
    dcfg = dataclasses.replace(cfg, num_layers=len(keep),
                               name=f"{cfg.name}-selfspec{skip}")
    idx = jnp.asarray(keep)
    dparams = dict(params)
    dparams["blocks"] = [jax.tree.map(lambda a: a[idx], params["blocks"][0])]
    dparams["tail"] = []
    draft = DecoderLM(dcfg, compute_dtype=model.compute_dtype,
                      remat=model.remat, kv_block=model.kv_block,
                      unroll=model.unroll)
    return draft, dparams


# ---------------------------------------------------------------------------
# Prefill variants of the recurrent blocks that also return final state
# ---------------------------------------------------------------------------

def _rglru_prefill(cfg, p, x):
    dt_ = x.dtype
    f32 = jnp.float32
    br = jax.nn.gelu(x @ p["w_branch"].astype(dt_))
    u_lin = x @ p["w_rec"].astype(dt_)
    u = rglru_mod._conv_causal(u_lin, p["conv"].astype(dt_))
    uf = u.astype(f32)
    r = jax.nn.sigmoid(uf @ p["w_a"].astype(f32))
    i = jax.nn.sigmoid(uf @ p["w_i"].astype(f32))
    a_log = -rglru_mod._C * jax.nn.softplus(p["lam"].astype(f32)) * r
    gated = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * a_log), 1e-9)) * (i * uf)
    h, h_last = rglru_mod.rglru_scan(gated, a_log)
    out = (h.astype(dt_) * br) @ p["wo"].astype(dt_)
    cw = cfg.rglru.conv_width
    conv_buf = u_lin[:, -(cw - 1):, :]
    return out, {"h": h_last, "conv": conv_buf}


def _ssm_prefill(cfg, p, x):
    s = cfg.ssm
    b, S, D = x.shape
    di = s.d_inner(D)
    H = s.num_heads(D)
    Pd = s.head_dim
    dt_ = x.dtype
    z = x @ p["wz"].astype(dt_)
    x_lin = x @ p["wx"].astype(dt_)
    B_lin = x @ p["wB"].astype(dt_)
    C_lin = x @ p["wC"].astype(dt_)
    xin = ssm_mod._causal_conv(x_lin, p["conv_x"].astype(dt_))
    Bt = ssm_mod._causal_conv(B_lin, p["conv_B"].astype(dt_))
    Ct = ssm_mod._causal_conv(C_lin, p["conv_C"].astype(dt_))
    dt = jax.nn.softplus((x @ p["wdt"].astype(dt_)).astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    xh = xin.reshape(b, S, H, Pd)
    y, final_state = ssm_mod.ssd_chunked(xh, dt, A, Bt, Ct, s.chunk_size)
    y = y + xh.astype(jnp.float32) * p["D"].astype(jnp.float32)[None, None, :, None]
    out = ssm_mod._gated_out(p, y.reshape(b, S, di), z, dt_)
    cw = s.conv_width
    final = {"state": final_state,
             "conv_x": x_lin[:, -(cw - 1):, :],
             "conv_B": B_lin[:, -(cw - 1):, :],
             "conv_C": C_lin[:, -(cw - 1):, :]}
    return out, final
