"""Random weights from the seed, in the program's parameter tree.

The parameter tree is the benchmark's interface with the program's models:
`tree_shapes` writes it down from a configuration file, the harness checks
it against `jax.eval_shape(model.init, key)` before it serves, and the plain
reference reads the same tree. Weights are made here, not by the program's
`init`, so the reference can make the same ones again from the seed without
taking anything the program made.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number, also one above 32 bits."""
    k = jax.random.PRNGKey(int(seed) & 0x7FFFFFFF)
    return jax.random.fold_in(k, (int(seed) >> 31) & 0x7FFFFFFF)


def padded_vocab(m: dict) -> int:
    mult = m.get("pad_vocab_multiple", 128)
    return -(-m["vocab_size"] // mult) * mult


def tree_shapes(m: dict) -> dict:
    """Shapes of a pure full-attention decoder's parameters, keyed as the
    program keys them (layers stacked on a leading axis)."""
    D, L, F = m["d_model"], m["num_layers"], m["d_ff"]
    Q = m["num_heads"] * m["head_dim"]
    KV = m["num_kv_heads"] * m["head_dim"]
    V = padded_vocab(m)

    def norm(*lead):
        n = {"scale": lead + (D,)}
        if m["norm"] == "layernorm":
            n["bias"] = lead + (D,)
        return n

    embed = {"tok": (V, D)}
    if m["pos_emb"] == "learned":
        embed["pos"] = (min(m["max_seq_len"], 32768), D)
    if not m.get("tie_embeddings", False):
        embed["head"] = (D, V)
    attn = {"wq": (L, D, Q), "wk": (L, D, KV), "wv": (L, D, KV),
            "wo": (L, Q, D)}
    if m.get("attn_bias", False):
        attn.update(bq=(L, Q), bk=(L, KV), bv=(L, KV))
    if m["ffn_kind"] == "swiglu":
        ffn = {"w_gate": (L, D, F), "w_up": (L, D, F), "w_down": (L, F, D)}
    elif m["ffn_kind"] == "gelu_mlp":
        ffn = {"w_up": (L, D, F), "b_up": (L, F), "w_down": (L, F, D),
               "b_down": (L, D)}
    else:
        raise ValueError(f"ffn_kind {m['ffn_kind']!r} has no reference")
    block = {"norm1": norm(L), "attn": attn, "norm2": norm(L), "ffn": ffn}
    return {"embed": embed, "blocks": [block], "tail": [],
            "final_norm": norm()}


def _leaf(key, path: str, name: str, shape, m: dict):
    """Matrices ~ N(0, 1/fan_in); embeddings ~ N(0, 0.02^2); norm scales
    and biases get a random part too, so a program that drops one differs
    from the reference. RMSNorm scales are stored as (scale - 1), as the
    program stores them."""
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    n = jax.random.normal(k, shape, jnp.float32)
    if name in ("tok", "pos", "head"):
        return 0.02 * n
    if name == "scale":
        return (1.0 if m["norm"] == "layernorm" else 0.0) + 0.1 * n
    if name.startswith("b"):
        return 0.1 * n
    return n / math.sqrt(shape[-2])


def make(m: dict, seed: int, dtype=jnp.bfloat16):
    """The whole tree on the device, in one jitted call, in `dtype`."""
    shapes = tree_shapes(m)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))

    def build(key):
        leaves = [_leaf(key, jax.tree_util.keystr(p), p[-1].key, s, m)
                  .astype(dtype) for p, s in flat]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(seed_key(seed))


def check_tree(params, abstract) -> None:
    """Raise unless `params` has the program's tree and shapes."""
    a = jax.tree_util.tree_structure(abstract)
    b = jax.tree_util.tree_structure(params)
    if a != b:
        raise ValueError(f"parameter tree differs from the program's:\n"
                         f"program {a}\nbenchmark {b}")
    for x, y in zip(jax.tree.leaves(abstract), jax.tree.leaves(params)):
        if tuple(x.shape) != tuple(y.shape):
            raise ValueError(f"leaf shape {y.shape} != program's {x.shape}")
