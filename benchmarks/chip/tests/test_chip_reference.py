"""The plain references read what the program computes: the decoder forward
reads the program's parameter tree (both block styles, at a reduced size),
and the float64 Stage-II evaluation gives the program's float64 answers."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tiny import BENCH
import traffic
import weights
from reference import decoder, stage2

SMALL = dict(num_layers=2, d_model=64, head_dim=16, d_ff=96, vocab_size=300)


def small(name):
    m = json.loads((BENCH / "configs" / f"{name}.json").read_text())["model"]
    m.update(SMALL)
    m["num_heads"], m["num_kv_heads"] = (4, 2) if m["pos_emb"] == "rope" \
        else (4, 4)
    if m["pos_emb"] == "learned":
        m["max_seq_len"] = 64
    return m


@pytest.mark.parametrize("name", ["dsr1d-qwen-1.5b", "gpt2-xl"])
def test_forward_reads_the_program_tree(name):
    from repro.configs.base import ArchConfig
    from repro.models import build_model
    m = small(name)
    model = build_model(ArchConfig(**m), compute_dtype=jnp.float32,
                        remat="none", kv_block=16)
    p = weights.make(m, 5, jnp.float32)
    weights.check_tree(p, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 300, 48),
                       jnp.int32)
    with jax.default_matmul_precision("highest"):
        lg, _ = model.prefill(p, {"tokens": toks[None]}, cache_len=48)
    ref = decoder.logits(m, p, decoder.hidden(m, p, toks))
    np.testing.assert_allclose(np.asarray(lg[0, -1, :300]),
                               np.asarray(ref[-1]), atol=1e-5)


def test_served_tokens_have_no_gap_in_float32():
    m = small("dsr1d-qwen-1.5b")
    p = weights.make(m, 5, jnp.float32)
    seq = [3, 1, 4]
    for _ in range(5):
        s = jnp.asarray(seq, jnp.int32)
        seq.append(int(jnp.argmax(decoder.logits(
            m, p, decoder.hidden(m, p, s))[-1])))
    # each generated token is the argmax after its prefix: its gap is 0
    g = decoder.gaps(m, p, jnp.asarray(seq, jnp.int32))
    assert float(g[2:-1].max()) == 0.0 and float(g[:2].max()) > 0.0


def test_tree_check_refuses_a_different_tree():
    m = small("gpt2-xl")
    p = weights.make(m, 5, jnp.float32)
    m2 = dict(m, attn_bias=True)
    with pytest.raises(ValueError):
        weights.check_tree(p, weights.make(m2, 5, jnp.float32))


def test_stage2_reference_matches_the_program_float64_path():
    from repro.core.candidates import Candidate, evaluate_candidates
    t = {"slots": 4, "rate_per_s": 8.0, "step_s": 0.02, "horizon_s": 30.0,
         "prompt": {"median": 32, "sigma": 0.5, "min": 16, "max": 64},
         "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 16}}
    dur, occ, r, w = traffic.occupancy_trace(t, 0, 9, 307_200)
    # uneven durations, so that some idle runs fall below break-even
    dur = dur * np.random.default_rng(1).uniform(1e-4, 1.0, len(dur))
    peak = int(occ.max())
    cands = [(c, b, a, p, g) for c in (peak, 2 * peak) for b in (1, 3, 8)
             for a in (1.0, 0.6) for p in ("none", "gate", "drowsy")
             for g in (1.0, 3e4)]
    got = evaluate_candidates(dur, occ, [Candidate(*c) for c in cands],
                              n_reads=r, n_writes=w, backend="numpy")
    ref = stage2.evaluate(dur, occ, cands, n_reads=r, n_writes=w)
    np.testing.assert_allclose(got.e_total, ref["e_total"], rtol=1e-12)
    np.testing.assert_array_equal(got.n_off, ref["n_off"])
    np.testing.assert_array_equal(got.n_drowsy, ref["n_drowsy"])
    assert ref["n_drowsy"].max() > 0 and ref["n_off"].max() > 0
