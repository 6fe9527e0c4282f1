"""Plain float32 forward of a pure full-attention decoder: no kernels, no
cache, no batching, every matmul at `highest` precision.

Covers the two block styles the configurations use:
  * Qwen2: RMSNorm (stored as scale - 1), RoPE (rotate-half), biased Q/K/V,
    grouped K/V heads, SwiGLU;
  * GPT-2: LayerNorm, learned positions, unbiased attention projections (as
    the program's block has them), tanh-GELU MLP with biases.
It reads the tree that `weights.tree_shapes` describes and imports nothing
of the program.

`cast` rounds every matmul operand; the identity gives the reference, and
`fp8` gives the control: the same arithmetic in float8 (E4M3, one scale per
tensor), the precision below the bfloat16 the configurations serve in.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG = -1e30


def ident(x):
    return x


def fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _norm(m, p, x):
    if m["norm"] == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * p["scale"] + p["bias"]
    var = (x * x).mean(-1, keepdims=True)
    return x * jax.lax.rsqrt(var + 1e-6) * (1.0 + p["scale"])


def _rope(x, theta):
    S, _, d = x.shape
    half = d // 2
    freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _mm(cast, a, b):
    return cast(a) @ cast(b)


def _block(m, cast, x, p):
    S = x.shape[0]
    H, K, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    a = p["attn"]
    y = _norm(m, p["norm1"], x)
    q = _mm(cast, y, a["wq"])
    k = _mm(cast, y, a["wk"])
    v = _mm(cast, y, a["wv"])
    if "bq" in a:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q, k, v = (q.reshape(S, H, hd), k.reshape(S, K, hd),
               v.reshape(S, K, hd))
    if m["pos_emb"] == "rope":
        q = _rope(q, m.get("rope_theta", 10000.0))
        k = _rope(k, m.get("rope_theta", 10000.0))
    g = H // K
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("shd,thd->hst", cast(q), cast(k)) / jnp.sqrt(float(hd))
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal[None], s, NEG)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hst,thd->shd", cast(w), cast(v)).reshape(S, H * hd)
    x = x + _mm(cast, o, a["wo"])
    y = _norm(m, p["norm2"], x)
    f = p["ffn"]
    if m["ffn_kind"] == "swiglu":
        h = jax.nn.silu(_mm(cast, y, f["w_gate"])) * _mm(cast, y, f["w_up"])
        return x + _mm(cast, h, f["w_down"])
    h = jax.nn.gelu(_mm(cast, y, f["w_up"]) + f["b_up"], approximate=True)
    return x + _mm(cast, h, f["w_down"]) + f["b_down"]


def hidden(m, params, tokens, cast=ident):
    """Final-norm hidden states (S, D) of one sequence, causal, in float32."""
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    e = params["embed"]
    x = e["tok"][tokens].astype(jnp.float32)
    if m["pos_emb"] == "learned":
        x = x + e["pos"][: tokens.shape[0]].astype(jnp.float32)

    def body(x, p):
        return _block(m, cast, x, f32(p)), None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(body, x, params["blocks"][0])
        return _norm(m, f32(params["final_norm"]), x)


def logits(m, params, h, cast=ident):
    """(S, vocab_size) logits of hidden states, without the padded rows."""
    V = m["vocab_size"]
    e = params["embed"]
    with jax.default_matmul_precision("highest"):
        if m.get("tie_embeddings", False):
            w = e["tok"][:V].astype(jnp.float32)
            return _mm(cast, h, w.T)
        return _mm(cast, h, e["head"][:, :V].astype(jnp.float32))


def gaps(m, params, tokens, pick=None):
    """For each position t, the gap by which the reference logit of token
    `pick[t]` lies below the reference's best, where `pick` defaults to the
    next token of `tokens` (the served one). Returns (S,) float32."""
    h = hidden(m, params, tokens)
    lg = logits(m, params, h)
    if pick is None:
        pick = jnp.concatenate([tokens[1:], tokens[:1]])
    return lg.max(-1) - jnp.take_along_axis(lg, pick[:, None], -1)[:, 0]


def control_gaps(m, params, tokens):
    """The control: the token that the float8 forward puts first at each
    position, and the reference gap of that token."""
    pick = jnp.argmax(logits(m, params, hidden(m, params, tokens, fp8), fp8),
                      -1)
    return gaps(m, params, tokens, pick)
