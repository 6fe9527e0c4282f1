"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.bank_energy import (bank_activity_stats, candidate_grid,
                                       exact_bank_stats, exact_bank_stats_np)
from repro.kernels.flash_attention import flash_attention, flash_attention_ref
from repro.kernels.gqa_decode import gqa_decode, gqa_decode_ref
from repro.kernels.int8_matmul import (int8_matmul, int8_matmul_ref,
                                       quantize_cols, quantize_rows)
from repro.kernels.paged_gqa_decode import (gather_pages, paged_gqa_decode,
                                            paged_gqa_decode_ref)
from repro.kernels.paged_gqa_verify import (paged_gqa_verify,
                                            paged_gqa_verify_ref)


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


# --- flash attention ---------------------------------------------------------

@pytest.mark.parametrize("B,H,K,S,T,d", [
    (1, 2, 2, 128, 128, 64),       # MHA
    (2, 4, 1, 128, 256, 64),       # MQA
    (1, 8, 2, 256, 128, 128),      # GQA group 4
    (2, 6, 3, 384, 384, 32),       # non-pow2 heads, small head_dim
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, H, K, S, T, d, dtype, causal):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(ks[0], (B, H, S, d), dtype)
    k = _rand(ks[1], (B, K, T, d), dtype)
    v = _rand(ks[2], (B, K, T, d), dtype)
    out = flash_attention(q, k, v, causal=causal, backend="interpret",
                          block_q=128, block_k=128)
    ref = flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_flash_attention_block_shape_independence():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand(ks[0], (1, 2, 256, 64), jnp.float32)
    k = _rand(ks[1], (1, 2, 256, 64), jnp.float32)
    v = _rand(ks[2], (1, 2, 256, 64), jnp.float32)
    o1 = flash_attention(q, k, v, backend="interpret", block_q=64, block_k=128)
    o2 = flash_attention(q, k, v, backend="interpret", block_q=128, block_k=64)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)


# --- gqa decode ---------------------------------------------------------------

@pytest.mark.parametrize("B,H,K,T,d", [
    (1, 4, 4, 256, 64),
    (2, 8, 2, 512, 64),
    (4, 16, 1, 256, 128),
    (2, 12, 3, 768, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gqa_decode_sweep(B, H, K, T, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = _rand(ks[0], (B, H, d), dtype)
    k = _rand(ks[1], (B, K, T, d), dtype)
    v = _rand(ks[2], (B, K, T, d), dtype)
    lengths = jax.random.randint(ks[3], (B,), 1, T + 1, jnp.int32)
    out = gqa_decode(q, k, v, lengths, backend="interpret")
    ref = gqa_decode_ref(q, k, v, lengths)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_gqa_decode_respects_length_mask():
    """Entries beyond `lengths` must not affect the output."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    B, H, K, T, d = 1, 4, 2, 256, 64
    q = _rand(ks[0], (B, H, d), jnp.float32)
    k = _rand(ks[1], (B, K, T, d), jnp.float32)
    v = _rand(ks[2], (B, K, T, d), jnp.float32)
    lengths = jnp.array([100], jnp.int32)
    o1 = gqa_decode(q, k, v, lengths, backend="interpret")
    k2 = k.at[:, :, 150:].set(99.0)
    v2 = v.at[:, :, 150:].set(-99.0)
    o2 = gqa_decode(q, k2, v2, lengths, backend="interpret")
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-6)


# --- paged gqa decode ---------------------------------------------------------

def _paged_case(seed, B, K, d, ps, P, N, max_len=None, lengths=None):
    """Random pool + ragged shuffled page tables; every slot gets a distinct
    length (first one is a full-page multiple, rest arbitrary — so both a
    partially-filled and an exactly-full last page are exercised), unless
    `lengths` gives them. Table entries past a slot's pages name the null
    page 0, which no slot owns."""
    rng = np.random.default_rng(seed)
    pool_k = jnp.asarray(rng.normal(size=(N, K, ps, d)), jnp.float32)
    pool_v = jnp.asarray(rng.normal(size=(N, K, ps, d)), jnp.float32)
    cap = max_len or P * ps
    if lengths is None:
        lengths = rng.integers(1, cap + 1, B)
        lengths[0] = min(ps * max(1, int(lengths[0]) // ps), cap)
    lengths = np.asarray(lengths)
    pt = np.zeros((B, P), np.int64)
    pool_ids = list(range(1, N))
    rng.shuffle(pool_ids)
    for b in range(B):
        npg = -(-int(lengths[b]) // ps)
        pt[b, :npg] = [pool_ids.pop() for _ in range(npg)]
    return pool_k, pool_v, jnp.asarray(pt, jnp.int32), jnp.asarray(
        lengths, jnp.int32)


# Rows with `lengths` pin the block walk of the kernel: `pages_per_block`
# gives 8 pages of 16 rows (128 rows) per block for dsr1d-qwen-1.5b's
# 12 / 2 heads of 128 and 4 pages (64 rows) for GPT-2 XL's 25 heads of 64;
# a P that is no multiple of it leaves a last block that runs past the
# table. Length 0 is a slot the engine left inactive.
PAGED_ROWS = [
    ((2, 4, 4, 32, 8, 4, 12, None), "2-4-4-32-8-4-12"),       # MHA
    ((3, 8, 2, 64, 16, 3, 16, None), "3-8-2-64-16-3-16"),     # GQA group 4
    ((2, 8, 1, 64, 8, 6, 16, None), "2-8-1-64-8-6-16"),       # MQA
    ((2, 12, 3, 32, 8, 4, 12, None), "2-12-3-32-8-4-12"),     # non-pow2 heads
    # dsr1d-shaped, P 20 = 2.5 blocks: on a block boundary, one row past
    # it, one row, and a slot at full capacity (the clamped last block)
    ((4, 12, 2, 128, 16, 20, 48, (128, 129, 1, 320)), "dsr1d-boundaries"),
    # dsr1d-shaped: inside the partial last block, two rows, inactive
    ((3, 12, 2, 128, 16, 20, 48, (300, 2, 0)), "dsr1d-tail-inactive"),
    # GPT-2-XL-shaped (group 1, head_dim 64), P 10 = 2.5 blocks
    ((5, 25, 25, 64, 16, 10, 40, (64, 65, 1, 160, 0)), "gpt2xl-shaped"),
]


@pytest.mark.parametrize("B,H,K,d,ps,P,N,lens", [r for r, _ in PAGED_ROWS],
                         ids=[i for _, i in PAGED_ROWS])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_gqa_decode_sweep(B, H, K, d, ps, P, N, lens, dtype):
    pool_k, pool_v, pt, lengths = _paged_case(10 + B, B, K, d, ps, P, N,
                                              lengths=lens)
    if lens is not None:
        # garbage in the null page that every table tail names
        pool_k = pool_k.at[0].set(99.0)
        pool_v = pool_v.at[0].set(-99.0)
    q = _rand(jax.random.PRNGKey(B), (B, H, d), dtype)
    pool_k, pool_v = pool_k.astype(dtype), pool_v.astype(dtype)
    out = paged_gqa_decode(q, pool_k, pool_v, pt, lengths,
                           backend="interpret")
    ref = paged_gqa_decode_ref(q, pool_k, pool_v, pt, lengths)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_paged_ref_matches_dense_oracle():
    """Gathering the pages densely and running the dense GQA decode oracle
    must agree exactly with the paged reference."""
    B, H, K, d, ps, P, N = 3, 8, 2, 32, 8, 5, 24
    pool_k, pool_v, pt, lengths = _paged_case(3, B, K, d, ps, P, N)
    q = _rand(jax.random.PRNGKey(7), (B, H, d), jnp.float32)
    ref = paged_gqa_decode_ref(q, pool_k, pool_v, pt, lengths)
    dense = gqa_decode_ref(q, gather_pages(pool_k, pt),
                           gather_pages(pool_v, pt), lengths)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(dense))


def test_paged_gqa_decode_respects_length_and_table():
    """Pool pages a slot does not own — and the tail of its partially-filled
    last page — must not affect its output."""
    B, H, K, d, ps, P, N = 2, 4, 2, 32, 8, 4, 16
    pool_k, pool_v, pt, lengths = _paged_case(4, B, K, d, ps, P, N,
                                              max_len=P * ps - 3)
    q = _rand(jax.random.PRNGKey(9), (B, H, d), jnp.float32)
    o1 = paged_gqa_decode(q, pool_k, pool_v, pt, lengths,
                          backend="interpret")
    owned = np.unique(np.asarray(pt))
    foreign = [p for p in range(N) if p not in owned]
    pk = pool_k.at[jnp.asarray(foreign)].set(99.0)
    pv = pool_v.at[jnp.asarray(foreign)].set(-99.0)
    # also poison the invalid tail of each slot's last page
    for b in range(B):
        L = int(lengths[b])
        last = int(np.asarray(pt)[b, (L - 1) // ps])
        if L % ps:
            pk = pk.at[last, :, L % ps:].set(77.0)
            pv = pv.at[last, :, L % ps:].set(-77.0)
    o2 = paged_gqa_decode(q, pk, pv, pt, lengths, backend="interpret")
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-6)


# --- paged gqa verify ----------------------------------------------------------

def _verify_case(seed, B, K, d, ps, P, N, V):
    """Random pool + ragged base lengths for a V-row speculative window;
    every slot's page table covers base + V rows (the window rows are
    written before verification). First slot's base is a page multiple so
    both an exactly-full and a partially-filled last page are exercised."""
    rng = np.random.default_rng(seed)
    pool_k = jnp.asarray(rng.normal(size=(N, K, ps, d)), jnp.float32)
    pool_v = jnp.asarray(rng.normal(size=(N, K, ps, d)), jnp.float32)
    cap = P * ps - V
    base = rng.integers(1, cap + 1, B)
    base[0] = min(ps * max(1, int(base[0]) // ps), cap)   # page multiple
    pt = np.zeros((B, P), np.int64)
    pool_ids = list(range(1, N))
    rng.shuffle(pool_ids)
    for b in range(B):
        npg = -(-(int(base[b]) + V) // ps)
        pt[b, :npg] = [pool_ids.pop() for _ in range(npg)]
    return pool_k, pool_v, jnp.asarray(pt, jnp.int32), jnp.asarray(
        base, jnp.int32)


@pytest.mark.parametrize("B,H,K,d,ps,P,N,V", [
    (2, 4, 4, 32, 8, 4, 12, 3),    # MHA
    (3, 8, 2, 64, 16, 3, 16, 4),   # GQA group 4
    (2, 8, 1, 64, 8, 6, 16, 2),    # MQA
    (2, 12, 3, 32, 8, 4, 12, 5),   # non-pow2 heads
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_gqa_verify_sweep(B, H, K, d, ps, P, N, V, dtype):
    pool_k, pool_v, pt, base = _verify_case(20 + B + V, B, K, d, ps, P, N, V)
    q = _rand(jax.random.PRNGKey(B + V), (B, V, H, d), dtype)
    pool_k, pool_v = pool_k.astype(dtype), pool_v.astype(dtype)
    out = paged_gqa_verify(q, pool_k, pool_v, pt, base, backend="interpret")
    ref = paged_gqa_verify_ref(q, pool_k, pool_v, pt, base)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-6
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_paged_gqa_verify_rows_match_decode():
    """Row v of the fused verify kernel must equal the decode kernel run at
    that row's causal length base + v + 1 — verification is exactly V fused
    decode calls sharing one pass over the pages."""
    B, H, K, d, ps, P, N, V = 2, 8, 2, 32, 8, 4, 16, 3
    pool_k, pool_v, pt, base = _verify_case(31, B, K, d, ps, P, N, V)
    q = _rand(jax.random.PRNGKey(17), (B, V, H, d), jnp.float32)
    out = paged_gqa_verify(q, pool_k, pool_v, pt, base, backend="interpret")
    for v in range(V):
        row = paged_gqa_decode(q[:, v], pool_k, pool_v, pt, base + v + 1,
                               backend="interpret")
        np.testing.assert_allclose(np.asarray(out[:, v]), np.asarray(row),
                                   atol=1e-6, rtol=1e-6)


def test_paged_gqa_verify_respects_window_and_table():
    """Pool pages a slot does not own — and tokens at or past the widest
    row's horizon base + V, including the partially-filled last page —
    must not affect any window row."""
    B, H, K, d, ps, P, N, V = 2, 4, 2, 32, 8, 4, 16, 3
    pool_k, pool_v, pt, base = _verify_case(43, B, K, d, ps, P, N, V)
    q = _rand(jax.random.PRNGKey(23), (B, V, H, d), jnp.float32)
    o1 = paged_gqa_verify(q, pool_k, pool_v, pt, base, backend="interpret")
    owned = np.unique(np.asarray(pt))
    foreign = [p for p in range(N) if p not in owned]
    pk = pool_k.at[jnp.asarray(foreign)].set(99.0)
    pv = pool_v.at[jnp.asarray(foreign)].set(-99.0)
    # poison everything past each slot's widest horizon base + V
    for b in range(B):
        L = int(base[b]) + V
        last = int(np.asarray(pt)[b, (L - 1) // ps])
        if L % ps:
            pk = pk.at[last, :, L % ps:].set(77.0)
            pv = pv.at[last, :, L % ps:].set(-77.0)
    o2 = paged_gqa_verify(q, pk, pv, pt, base, backend="interpret")
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-6)


# --- int8 matmul ---------------------------------------------------------------

@pytest.mark.parametrize("M,K,N", [
    (128, 128, 128), (256, 384, 128), (128, 512, 384), (384, 256, 256),
])
def test_int8_matmul_sweep(M, K, N):
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    x = jax.random.normal(ks[0], (M, K)) * 3.0
    w = jax.random.normal(ks[1], (K, N))
    xq, sx = quantize_rows(x)
    wq, sw = quantize_cols(w)
    out = int8_matmul(xq, wq, sx, sw, backend="interpret")
    ref = int8_matmul_ref(xq, wq, sx, sw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-5)
    # end-to-end quantization error vs fp32 stays small
    full = np.asarray(x @ w)
    rel = np.abs(np.asarray(out) - full).max() / np.abs(full).max()
    assert rel < 0.03


def test_int8_matmul_exact_integers():
    """Integer inputs with unit scales must be exact."""
    rng = np.random.default_rng(0)
    xq = jnp.asarray(rng.integers(-127, 128, (128, 256)), jnp.int8)
    wq = jnp.asarray(rng.integers(-127, 128, (256, 128)), jnp.int8)
    sx = jnp.ones((128, 1), jnp.float32)
    sw = jnp.ones((1, 128), jnp.float32)
    out = int8_matmul(xq, wq, sx, sw, backend="interpret")
    ref = np.asarray(xq, np.int64) @ np.asarray(wq, np.int64)
    np.testing.assert_array_equal(np.asarray(out, np.int64), ref)


# --- bank energy ----------------------------------------------------------------

@pytest.mark.parametrize("nseg", [17, 256, 1000, 4096])
def test_bank_energy_padding_and_grid(nseg):
    rng = np.random.default_rng(5)
    d = rng.random(nseg).astype(np.float32) * 1e-3
    occ = (rng.random(nseg) * 128 * 2**20).astype(np.float32)
    us, nb, meta = candidate_grid(
        [c * 2**20 for c in (48, 64, 128)], [1, 4, 16], 0.9)
    out_i = np.asarray(bank_activity_stats(d, occ, us, nb,
                                           backend="interpret", block_s=256))
    out_r = np.asarray(bank_activity_stats(d, occ, us, nb, backend="ref"))
    np.testing.assert_allclose(out_i, out_r, rtol=1e-5, atol=1e-4)


def test_bank_energy_float32_range_regression():
    """128 MiB capacity: byte-valued occupancy near 10^8 sits beyond f32's
    exact-integer range, so the old f32 default misread bank boundaries
    (act off by one on a few-byte offset). The auto backend must now be
    exact on CPU (float64 numpy)."""
    from repro.core.banking import bank_activity
    mib = 2**20
    cap, banks, alpha = 128 * mib, 5, 0.9
    usable = alpha * (cap / banks)              # non-power-of-two divisor
    occ = np.floor(np.array([k * usable + off for off in (-3.0, 3.0)
                             for k in range(1, 6)]))
    d = np.ones_like(occ)
    act = bank_activity(occ.astype(np.int64), alpha, cap, banks)
    out = np.asarray(bank_activity_stats(
        d, occ, np.array([usable]), np.array([float(banks)])))
    assert out[0, 0] == pytest.approx(float((act * d).sum()), abs=1e-9)
    assert out[0, 1] == pytest.approx(
        float(np.abs(np.diff(act.astype(np.float64))).sum()), abs=1e-9)


# --- exact idle-run stats (batched Stage-II engine) ----------------------------

def _exact_inputs(nseg, seed=6):
    rng = np.random.default_rng(seed)
    d = rng.random(nseg) * 1e-3 + 1e-6
    occ = (rng.integers(0, 130 * 2**20, nseg) // 1024 * 1024).astype(
        np.float64)
    us, nb, _ = candidate_grid(
        [c * 2**20 for c in (48, 64, 128)], [1, 4, 16, 32], 0.9)
    th = np.tile([1e-4, 5e-4, 1e-3, 2e-3], 3)
    return d, occ, us, nb, th


@pytest.mark.parametrize("nseg", [1, 17, 256, 1000])
def test_exact_bank_stats_kernel_vs_numpy(nseg):
    """Pallas exact-stats kernel (interpret mode, cross-tile carries) vs the
    float64 reference."""
    d, occ, us, nb, th = _exact_inputs(nseg)
    ref = exact_bank_stats_np(d, occ, us, nb, th)
    out = np.asarray(exact_bank_stats(d, occ, us, nb, th,
                                      backend="interpret", block_s=64))
    np.testing.assert_allclose(out, ref, rtol=5e-3, atol=5e-3)


def test_exact_bank_stats_block_shape_independence():
    d, occ, us, nb, th = _exact_inputs(300, seed=7)
    o1 = np.asarray(exact_bank_stats(d, occ, us, nb, th,
                                     backend="interpret", block_s=32))
    o2 = np.asarray(exact_bank_stats(d, occ, us, nb, th,
                                     backend="interpret", block_s=128))
    np.testing.assert_allclose(o1, o2, rtol=1e-5, atol=1e-5)


def test_exact_bank_stats_jnp_vs_numpy():
    d, occ, us, nb, th = _exact_inputs(500, seed=8)
    ref = exact_bank_stats_np(d, occ, us, nb, th)
    out = np.asarray(exact_bank_stats(d, occ, us, nb, th, backend="ref"))
    np.testing.assert_allclose(out, ref, rtol=5e-3, atol=5e-3)
