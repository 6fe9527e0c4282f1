"""Compile the main path's Pallas kernels for a TPU v5e chip at real widths.

Nothing runs: XLA's TPU compiler, which is installed with JAX, compiles for a
described v5e chip and refuses what the chip would refuse (block shapes that
break the (8, 128) tiling rule, primitives Mosaic cannot lower). Every test
asserts that the compiled program holds the Pallas kernel
(`tpu_custom_call`). The topology is described inside a fixture, never at
import, so each test worker collects the same tests and only the worker
given this file loads the TPU library.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            t = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler can be loaded here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a program compiled for a described chip is written to the
        # persistent cache but cannot be read back without one: keep the
        # cache off so later compiles stay silent
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield t
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


# (H, K, head_dim): dsr1d-qwen-1.5b GQA, and GPT-2 XL MHA at head_dim 64
DECODE_WIDTHS = {"dsr1d": (12, 2, 128), "gpt2xl": (25, 25, 64)}
B, PS, N_PAGES, P = 16, 16, 4096, 256
# widths, then (slots, pages per slot, pool pages): the rehearsal sizes,
# and the engines of the benchmark's backlog cells
DECODE_CASES = {"dsr1d": ("dsr1d", B, P, N_PAGES),
                "gpt2xl": ("gpt2xl", B, P, N_PAGES),
                "dsr1d-cell": ("dsr1d", 64, 160, 10240),
                "gpt2xl-cell": ("gpt2xl", 16, 64, 384)}


@pytest.mark.parametrize("case", sorted(DECODE_CASES))
def test_paged_decode_compiles(one_chip, case):
    from repro.kernels.paged_gqa_decode import paged_gqa_decode
    widths, b, p, n = DECODE_CASES[case]
    H, K, d = DECODE_WIDTHS[widths]
    s = functools.partial(_spec, one_chip)
    pool = s((n, K, PS, d), jnp.bfloat16)
    _assert_kernel(paged_gqa_decode.lower(
        s((b, H, d), jnp.bfloat16), pool, pool, s((b, p), jnp.int32),
        s((b,), jnp.int32), backend="pallas").compile())


def test_paged_decode_quant_compiles(one_chip):
    from repro.kernels.paged_gqa_decode import paged_gqa_decode_quant
    H, K, d = DECODE_WIDTHS["dsr1d"]
    s = functools.partial(_spec, one_chip)
    pool = s((N_PAGES, K, PS, d), jnp.int8)
    scales = s((N_PAGES, K, PS), jnp.float32)
    _assert_kernel(paged_gqa_decode_quant.lower(
        s((B, H, d), jnp.bfloat16), pool, pool, scales, scales,
        s((B, P), jnp.int32), s((B,), jnp.int32), backend="pallas").compile())


def test_paged_verify_compiles(one_chip):
    from repro.kernels.paged_gqa_verify import paged_gqa_verify
    H, K, d = DECODE_WIDTHS["dsr1d"]
    s = functools.partial(_spec, one_chip)
    pool = s((N_PAGES, K, PS, d), jnp.bfloat16)
    _assert_kernel(paged_gqa_verify.lower(
        s((B, 5, H, d), jnp.bfloat16), pool, pool, s((B, P), jnp.int32),
        s((B,), jnp.int32), backend="pallas").compile())


# Stage II: a 64k-segment trace against 96 candidates of up to 32 banks
N_SEG, N_CAND, BMAX = 65536, 96, 32


def test_exact_bank_stats_compiles(one_chip):
    from repro.kernels.bank_energy.ops import _exact_bank_stats_jit
    seg = _spec(one_chip, (N_SEG,), jnp.float32)
    cand = _spec(one_chip, (N_CAND,), jnp.float32)
    _assert_kernel(_exact_bank_stats_jit.lower(
        seg, seg, cand, cand, cand, bmax=BMAX, backend="pallas",
        block_s=2048).compile())


def test_bank_activity_stats_compiles(one_chip):
    from repro.kernels.bank_energy.ops import _bank_activity_stats_jit
    seg = _spec(one_chip, (N_SEG,), jnp.float32)
    cand = _spec(one_chip, (N_CAND,), jnp.float32)
    _assert_kernel(_bank_activity_stats_jit.lower(
        seg, seg, cand, cand, backend="pallas", block_s=2048).compile())


def test_decode_chunk_compiles_at_full_width_and_fits(one_chip):
    """The served program: 16 greedy steps of all 28 dsr1d layers over the
    page pool `chip_smoke.py` serves from (16 slots, 8192 pages of 16
    tokens, bf16 weights). Its arguments plus temporaries must fit one
    chip's HBM with room to spare for the prefill and the host's arrays."""
    from repro.configs import resolve_arch
    from repro.models import build_model
    from repro.models.common import cast_params
    from repro.models.transformer import init_paged_cache
    from repro.serve.paged import _decode_loop

    slots, pages, pages_per_slot, steps = 16, 8192, 80, 16
    cfg = resolve_arch("dsr1d-qwen-1.5b")
    model = build_model(cfg, compute_dtype=jnp.bfloat16, remat="none")

    def on_chip(tree):
        return jax.tree.map(
            lambda a: _spec(one_chip, a.shape, a.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda k: cast_params(model.init(k), jnp.bfloat16),
        jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(lambda: init_paged_cache(
        cfg, slots, pages, PS, pages_per_slot, dtype=jnp.bfloat16)))
    vec = _spec(one_chip, (slots,), jnp.int32)
    loop = jax.jit(functools.partial(_decode_loop, model, steps, "pallas",
                                     False), donate_argnums=(1,))
    compiled = loop.lower(params, cache, _spec(one_chip, (slots, 1),
                                               jnp.int32), vec, vec).compile()
    _assert_kernel(compiled)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 0.8 * V5E_HBM_BYTES
