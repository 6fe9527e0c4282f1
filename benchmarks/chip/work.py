"""Operations and bytes that the work needs, from shapes and live lengths.

Kept with the benchmark so that every PR counts work the same way. Byte
counts assume bfloat16 (2 B) weights, activations and KV pages.
"""
from __future__ import annotations

BF16 = 2


def kv_bytes_per_token(m: dict) -> int:
    """K and V rows of one token over all layers."""
    return m["num_layers"] * 2 * m["num_kv_heads"] * m["head_dim"] * BF16


def matmul_params(m: dict) -> int:
    """Parameters that take part in a matmul per token, LM head included
    (the embedding lookup is a gather and is not counted)."""
    D, F = m["d_model"], m["d_ff"]
    Q = m["num_heads"] * m["head_dim"]
    KV = m["num_kv_heads"] * m["head_dim"]
    ffn = (3 if m["ffn_kind"] == "swiglu" else 2) * D * F
    return m["num_layers"] * (2 * D * Q + 2 * D * KV + ffn) \
        + m["vocab_size"] * D


def attn_flops_per_row(m: dict) -> int:
    """QK^T and PV for one query against one context row, all layers."""
    return 4 * m["num_layers"] * m["num_heads"] * m["head_dim"]


def model_flops(m: dict, tokens: int, context_rows: int) -> float:
    """Model FLOPs of `tokens` decoded tokens that together attended over
    `context_rows` rows: 2 x matmul parameters per token plus attention."""
    return 2.0 * matmul_params(m) * tokens \
        + attn_flops_per_row(m) * context_rows


def paged_attn_work(m: dict, slot_steps: int, context_rows: int):
    """(flops, bytes) that paged decode attention needs for `slot_steps`
    live (slot, step) pairs attending over `context_rows` live rows in all:
    each live row's K and V once per layer, plus q and the output."""
    L, H, K, hd = (m["num_layers"], m["num_heads"], m["num_kv_heads"],
                   m["head_dim"])
    flops = attn_flops_per_row(m) * context_rows
    nbytes = (L * 2 * K * hd * BF16 * context_rows
              + L * 2 * H * hd * BF16 * slot_steps)
    return float(flops), float(nbytes)


def decode_rows(prompt_len: int, outputs_before: int, gained: int) -> int:
    """Context rows that `gained` decode steps of one request attended over.

    After admission a request holds its prompt's rows and one output token
    (from the prefill). Decode step j (1-based) of a chunk that starts with
    n outputs writes row prompt_len + n - 1 + j and attends over that many.
    """
    base = prompt_len + outputs_before - 1
    return gained * base + gained * (gained + 1) // 2


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict) -> float:
    """Least time the chip could take over the measured time, in %."""
    least = max(flops / peak["bf16_flops_per_s"],
                nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds


def stage2_work(n_segments: int, n_candidates: int, max_banks: int):
    """(ops, bytes) of one exact Stage-II kernel pass: each candidate reads
    the (duration, occupancy) float32 series once and tests every bank
    level in every segment."""
    return (float(n_segments) * n_candidates * max_banks,
            float(n_segments) * n_candidates * 8)
