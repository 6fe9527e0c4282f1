"""Wall-clock spans and counters of the paged engine, on the profiler's clock:
the `PagedStats` wall counters and their identities, the first-token stamp,
the page-wait count, and the `serve.*` / `stage2.*` spans a profiler trace
records on the host line of the thread that ran them."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.core.candidates import Candidate, evaluate_candidates
from repro.models import build_model
from repro.obs import Telemetry, host_span
from repro.serve import PagedContinuousBatcher, Request
from repro.serve.paged import PagedStats, loop_compile_count

SERVE_SPANS = ("serve.run", "serve.admit", "serve.prefill",
               "serve.chunk.prep", "serve.chunk.wait", "serve.chunk.emit")
MODES = {"plain": {}, "chunked": {"prefill_chunk_tokens": 8},
         "prefix": {"prefix_cache": True}}


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


def _requests(cfg, lens=(10, 23, 5, 17), new=(6, 9, 4, 7)):
    rng = np.random.default_rng(0)
    out = []
    for i, (n, k) in enumerate(zip(lens, new)):
        toks = rng.integers(0, cfg.vocab_size, n)
        toks[0] = i            # distinct first tokens: no prefix is shared
        out.append(Request(rid=i, tokens=toks, max_new_tokens=k))
    return out


def _serve(cb, reqs, max_chunks=1):
    for r in reqs:
        cb.submit(r)
    done = []
    while len(done) < len(reqs):
        done += cb.run(max_chunks=max_chunks)
    return done


@pytest.fixture(scope="module", params=list(MODES))
def served(request, small):
    cfg, m, params = small
    reqs = _requests(cfg)
    cb = _batcher(m, params, **MODES[request.param])
    return cb, _serve(cb, reqs), reqs


def test_wall_counters_hold_their_identities(served):
    cb, done, reqs = served
    st = cb.stats
    assert len(done) == len(reqs)
    assert 0.0 < st.device_wait_s <= st.run_wall_s
    assert st.prefill_tokens == sum(len(r.tokens) for r in reqs)
    assert 0.0 < st.prefill_wall_s < st.run_wall_s
    assert st.queue_wait_s > 0.0
    assert 0.0 <= st.first_token_hold_s <= st.prefills * st.run_wall_s
    assert st.page_waits == 0 and st.prefills == st.admitted == len(reqs)


def test_first_token_stamp_lies_between_submit_and_finish(served):
    _, done, _ = served
    for r in done:
        assert 0.0 < r.submitted_wall_s <= r.first_token_wall_s \
            <= r.finished_wall_s


def test_first_token_hold_is_measured_to_the_run_return(small):
    """One run() that admits, decodes and retires everything holds every
    first token until it returns: the hold equals the summed gaps from each
    stamp to that return."""
    cfg, m, params = small
    reqs = _requests(cfg)
    cb = _batcher(m, params, num_slots=4)
    for r in reqs:
        cb.submit(r)
    done = cb.run()
    t_return = max(r.finished_wall_s for r in done)
    held = sum(t_return - r.first_token_wall_s for r in done)
    assert held <= cb.stats.first_token_hold_s
    assert cb.stats.first_token_hold_s - held < 0.25 * cb.stats.run_wall_s


@pytest.mark.parametrize("num_pages,waits", [(7, True), (32, False)])
def test_page_waits_count_admissions_stopped_on_pages(small, num_pages,
                                                      waits):
    """20 prompt + 12 new tokens need 4 pages of 8 at worst: a 6-page pool
    holds one such worst case for two slots, so the second request waits
    with a slot free; a 31-page pool never makes it wait."""
    cfg, m, params = small
    reqs = _requests(cfg, lens=(20, 20, 20), new=(12, 12, 12))
    tel = Telemetry()
    cb = _batcher(m, params, num_pages=num_pages, max_pages_per_slot=4,
                  telemetry=tel)
    done = _serve(cb, reqs)
    assert len(done) == 3
    assert (cb.stats.page_waits > 0) == waits
    assert cb.stats.page_waits == tel.counter(
        "serve.paged.backpressure_waits").value


def _trace_events(path):
    """{line_id: [(name, start_ns, end_ns, stats)]} of every host line."""
    from jax.profiler import ProfileData
    f = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)[0]
    lines = {}
    for plane in ProfileData.from_file(f).planes:
        if not plane.name.startswith("/host"):
            continue
        for k, line in enumerate(plane.lines):
            lines[(plane.name, k)] = [
                (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns),
                 dict(e.stats) if e.name == "serve.prefill" else {})
                for e in line.events]
    return lines


def _line_with(lines, name):
    for evs in lines.values():
        if any(e[0] == name for e in evs):
            return evs
    raise AssertionError(f"no host line holds {name}")


def test_serve_spans_land_bare_and_nested_on_the_host_line(small, tmp_path):
    cfg, m, params = small
    cb = _batcher(m, params)
    reqs = _requests(cfg)
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("test.outer"):
            _serve(cb, reqs)
    evs = _line_with(_trace_events(str(tmp_path)), "test.outer")
    (_, o0, o1, _), = [e for e in evs if e[0] == "test.outer"]
    names = {e[0] for e in evs}
    for span in SERVE_SPANS:
        assert span in names, span
    serve = [e for e in evs if e[0].startswith("serve.")]
    assert {e[0] for e in serve} == set(SERVE_SPANS)   # names stay bare
    assert all(o0 <= a <= b <= o1 for _, a, b, _ in serve)
    runs = [(a, b) for n, a, b, _ in serve if n == "serve.run"]
    for n, a, b, _ in serve:
        assert any(r0 <= a <= b <= r1 for r0, r1 in runs), n
    prefills = [st for n, _, _, st in serve if n == "serve.prefill"]
    assert sorted(int(st["rid"]) for st in prefills) == [r.rid for r in reqs]
    assert sorted(int(st["tokens"]) for st in prefills) == sorted(
        len(r.tokens) for r in reqs)


def test_tokens_and_compiles_same_with_profiler_on_and_off(small, tmp_path):
    cfg, m, params = small
    outs, compiles = [], []
    for profile in (False, True):
        cb = _batcher(m, params)
        c0 = loop_compile_count()
        if profile:
            with jax.profiler.trace(str(tmp_path)):
                done = _serve(cb, _requests(cfg))
        else:
            done = _serve(cb, _requests(cfg))
        compiles.append(loop_compile_count() - c0)
        outs.append({r.rid: list(r.output) for r in done})
    assert outs[0] == outs[1]
    assert compiles[0] == compiles[1] == 1


def test_stage2_spans_land_on_the_host_line(tmp_path):
    rng = np.random.default_rng(0)
    d = rng.random(64) * 1e-3 + 1e-6
    occ = rng.integers(0, 64 << 20, 64).astype(np.int64)
    cands = [Candidate(64 << 20, b, 0.9, p) for b in (1, 8)
             for p in ("none", "gate")]
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("test.outer"):
            evaluate_candidates(d, occ, cands, n_reads=3, n_writes=4,
                                backend="numpy")
    evs = _line_with(_trace_events(str(tmp_path)), "test.outer")
    names = [e[0] for e in evs if e[0].startswith("stage2.")]
    assert names == ["stage2.prepare", "stage2.kernel", "stage2.energies"]


def test_host_span_adds_elapsed_seconds_even_when_the_body_raises():
    st = PagedStats()
    with host_span("test.span", st, "run_wall_s", rid=1):
        pass
    first = st.run_wall_s
    assert first > 0.0
    with pytest.raises(ValueError):
        with host_span("test.span", st, "run_wall_s"):
            raise ValueError("body")
    assert st.run_wall_s > first
    with host_span("test.span"):         # no stats: annotation only
        pass
