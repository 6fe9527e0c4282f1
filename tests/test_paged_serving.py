"""Paged KV-cache serving path: exact ragged-slot decode, device-resident
chunk loop (no per-step recompilation), page-granular Stage-I traces."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.models import build_model
from repro.models.transformer import init_paged_cache
from repro.serve import (BatchedServer, PagedContinuousBatcher, Request,
                         ServeConfig)
from repro.serve import engine as engine_mod
from repro.serve import paged as paged_mod


@pytest.fixture(scope="module")
def small():
    cfg = reduced(get_arch("tinyllama-1.1b"), layers=2)
    m = build_model(cfg, compute_dtype=jnp.float32, remat="none")
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


def _batcher(m, params, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("num_pages", 32)
    kw.setdefault("max_pages_per_slot", 8)
    kw.setdefault("chunk_steps", 4)
    kw.setdefault("attn_backend", "ref")
    return PagedContinuousBatcher(m, params, **kw)


# ---------------------------------------------------------------------------
# Ragged-slot exactness (the regression the dense batcher's docstring hack
# used to paper over): a mixed-length batch through the shared paged cache
# must reproduce isolated single-sequence greedy decode token-for-token.
# ---------------------------------------------------------------------------

def test_mixed_length_batch_matches_single_sequence_decode(small):
    cfg, m, params = small
    rng = np.random.default_rng(0)
    prompts = [np.arange(10) % cfg.vocab_size,
               rng.integers(0, cfg.vocab_size, 23),
               rng.integers(0, cfg.vocab_size, 5),
               rng.integers(0, cfg.vocab_size, 17),
               rng.integers(0, cfg.vocab_size, 31)]
    new = [6, 9, 4, 12, 7]
    srv = BatchedServer(m, params, ServeConfig(max_len=64))
    refs = [np.asarray(srv.generate(
        {"tokens": jnp.asarray(p[None, :], jnp.int32)},
        max_new_tokens=n)["tokens"][0]) for p, n in zip(prompts, new)]

    cb = _batcher(m, params)
    for i, (p, n) in enumerate(zip(prompts, new)):
        cb.submit(Request(rid=i, tokens=p, max_new_tokens=n))
    done = cb.run()
    assert len(done) == 5
    assert cb.stats.peak_active_slots == 2        # overlapping lifetimes
    for r in done:
        np.testing.assert_array_equal(np.asarray(r.output), refs[r.rid])


def test_eos_frees_slot_and_pages_early(small):
    cfg, m, params = small
    prompt = np.arange(8) % cfg.vocab_size
    probe = _batcher(m, params)
    probe.submit(Request(rid=0, tokens=prompt, max_new_tokens=3))
    eos = probe.run()[0].output[1]
    cb = _batcher(m, params, num_slots=1)
    cb.submit(Request(rid=1, tokens=prompt, max_new_tokens=10, eos_id=eos))
    done = cb.run()
    assert len(done[0].output) <= 3
    assert cb.ledger.allocator.n_allocated == 0
    assert cb.stats.pages_freed == cb.stats.pages_allocated > 0


def test_moe_arch_through_paged_batcher():
    cfg = reduced(get_arch("olmoe-1b-7b"), layers=2)
    m = build_model(cfg, compute_dtype=jnp.float32, remat="none")
    params = m.init(jax.random.PRNGKey(1))
    cb = _batcher(m, params)
    cb.submit(Request(rid=0, tokens=np.arange(9) % cfg.vocab_size,
                      max_new_tokens=5))
    done = cb.run()
    assert len(done) == 1 and len(done[0].output) == 5


def test_window_bounded_archs_rejected():
    cfg = reduced(get_arch("recurrentgemma-2b"))
    with pytest.raises(NotImplementedError):
        init_paged_cache(cfg, 2, 8, 8, 4)


# ---------------------------------------------------------------------------
# Compile discipline: the chunk loop and the BatchedServer scan compile once
# ---------------------------------------------------------------------------

def test_chunk_loop_compiles_once_across_chunks_and_admissions(small):
    cfg, m, params = small
    cb = _batcher(m, params)
    rng = np.random.default_rng(2)
    for i in range(6):
        cb.submit(Request(rid=i, tokens=rng.integers(0, cfg.vocab_size, 6 + i),
                          max_new_tokens=5 + i % 4))
    n0 = paged_mod.loop_compile_count()
    done = cb.run()
    assert len(done) == 6
    assert cb.stats.chunks > 2                  # several host round-trips...
    assert paged_mod.loop_compile_count() - n0 == 1   # ...one compilation


def test_generate_loop_compiles_once_across_calls(small):
    cfg, m, params = small
    srv = BatchedServer(m, params, ServeConfig(max_len=64, max_new_tokens=8))
    batch = {"tokens": jnp.asarray(
        (np.arange(20) % cfg.vocab_size).reshape(2, 10), jnp.int32)}
    srv.generate(batch)
    n0 = engine_mod.loop_compile_count()
    o1 = srv.generate(batch)
    o2 = srv.generate(batch)
    assert engine_mod.loop_compile_count() == n0   # no per-call re-trace
    np.testing.assert_array_equal(o1["tokens"], o2["tokens"])


# ---------------------------------------------------------------------------
# Page-granular Stage-I artifact
# ---------------------------------------------------------------------------

def test_trace_is_page_granular_and_feeds_stage2(small):
    cfg, m, params = small
    cb = _batcher(m, params)
    rng = np.random.default_rng(3)
    for i in range(5):
        cb.submit(Request(rid=i, tokens=rng.integers(0, cfg.vocab_size, 5 + 3 * i),
                          max_new_tokens=4 + i))
    cb.run()
    bundle = cb.occupancy_bundle()
    tr = bundle.traces["kv"]
    t, n, o = tr.as_arrays()
    # every level is an integer number of pages; drained at the end
    assert (np.asarray(n) % cb.page_bytes == 0).all()
    assert int(n[-1]) == 0
    assert sum(tr.ev_dneeded) == 0
    assert tr.peak_needed() == cb.stats.peak_pages * cb.page_bytes
    assert tr.peak_total() <= tr.capacity
    # Stage-II consumes the bundle unchanged
    from repro.core.explorer import sweep
    tbl = sweep(bundle, mem_name="kv", capacities_mib=[16], banks=[1, 4])
    assert len(tbl.rows) == 2
    assert tbl.best().result.e_total > 0


def test_admission_time_retirement_does_not_poison_next_chunk(small):
    """A request satisfied by its prefill token (max_new_tokens=1) retires
    host-side before any chunk runs; its slot's device state must not leak
    into the neighbouring slot's decode (the liveness mask is pushed from
    the host before every chunk)."""
    cfg, m, params = small
    p1 = np.arange(10) % cfg.vocab_size
    p2 = (np.arange(14) * 3) % cfg.vocab_size
    srv = BatchedServer(m, params, ServeConfig(max_len=64))
    ref = np.asarray(srv.generate(
        {"tokens": jnp.asarray(p2[None, :], jnp.int32)},
        max_new_tokens=7)["tokens"][0])
    cb = _batcher(m, params)
    cb.submit(Request(rid=0, tokens=p1, max_new_tokens=1))
    cb.submit(Request(rid=1, tokens=p2, max_new_tokens=7))
    done = cb.run()
    assert len(done) == 2
    assert len(next(r for r in done if r.rid == 0).output) == 1
    np.testing.assert_array_equal(
        np.asarray(next(r for r in done if r.rid == 1).output), ref)
    assert cb.ledger.allocator.n_allocated == 0


def test_admission_blocks_until_pages_available(small):
    """FCFS backpressure: a pool too small for two concurrent requests must
    serialize them rather than fail mid-stream."""
    cfg, m, params = small
    cb = _batcher(m, params, num_slots=2, num_pages=7, max_pages_per_slot=6,
                  page_size=8)
    # each request worst-cases at 5 pages (33 tokens prompt + 7 new)
    for i in range(2):
        cb.submit(Request(rid=i, tokens=np.arange(33) % cfg.vocab_size,
                          max_new_tokens=8))
    done = cb.run()
    assert len(done) == 2
    assert cb.stats.peak_active_slots == 1
    assert cb.ledger.allocator.n_allocated == 0


@pytest.mark.parametrize("kv_dtype", ["native", "fp8", "int8"])
def test_attn_block_counters_match_kernel_lengths(small, monkeypatch,
                                                  kv_dtype):
    """`attn_blocks_live` counts the paged kernel's compute blocks that hold
    a live row, summed over decode steps, and `attn_blocks_total` the
    blocks of its grid. Both must equal a brute-force count: from the
    lengths the kernel is handed at every step (recorded as it runs) and
    from each request's prompt and output lengths. The mix retires a slot
    mid-chunk, admits into a freed slot and crosses a block boundary. int8
    pools go through the one-page kernel and count nothing."""
    import repro.kernels.paged_gqa_decode as pgd
    cfg, m, params = small
    seen = []
    inner = pgd.paged_gqa_decode

    def recording(q, kp, vp, pt, lengths, **kw):
        jax.debug.callback(lambda x: seen.append(np.asarray(x)), lengths)
        return inner(q, kp, vp, pt, lengths, **kw)

    monkeypatch.setattr(pgd, "paged_gqa_decode", recording)
    ps, P, slots, steps = 8, 24, 3, 4
    ppb = pgd.pages_per_block(cfg.num_kv_heads, ps, cfg.head_dim, P)
    rows, nblk = ppb * ps, -(-P // ppb)
    assert nblk == 2 and P % ppb
    cb = _batcher(m, params, num_slots=slots, page_size=ps, num_pages=80,
                  max_pages_per_slot=P, chunk_steps=steps, kv_dtype=kv_dtype)
    rng = np.random.default_rng(3)
    lens = [(120, 11), (126, 6), (60, 9), (40, 7)]
    for i, (S, n) in enumerate(lens):
        cb.submit(Request(rid=i, tokens=rng.integers(0, cfg.vocab_size, S),
                          max_new_tokens=n))
    done = cb.run()
    assert len(done) == len(lens)
    st = cb.stats
    if kv_dtype == "int8":
        assert not seen and st.chunks > 0
        assert st.attn_blocks_live == st.attn_blocks_total == 0
        return

    def blocks(L):
        return -(-np.asarray(L) // rows)

    # a request of prompt S and n tokens decodes n - 1 steps over S + 1 ..
    # S + n - 1 rows (its first token comes from prefill)
    per_request = sum(int(blocks(np.arange(len(r.tokens) + 1,
                                           len(r.tokens) + len(r.output))
                                 ).sum()) for r in done)
    layers = cfg.num_layers
    assert len(seen) == st.chunks * steps * layers
    from_kernel = sum(int(blocks(L).sum()) for L in seen)
    assert from_kernel % layers == 0
    assert st.attn_blocks_live == per_request == from_kernel // layers
    assert st.attn_blocks_total == st.chunks * steps * slots * nblk
    assert st.decode_steps == sum(len(r.output) - 1 for r in done)
