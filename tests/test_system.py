"""End-to-end behaviour tests: the TRAPTI two-stage flow on arbitrary archs,
train -> serve round trip, and the serving engine."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, get_arch, reduced
from repro.core.explorer import min_capacity_mib, sweep
from repro.core.workload import build_graph
from repro.sim.accelerator import baseline_accelerator
from repro.sim.engine import find_min_sram, simulate


def test_trapti_two_stage_end_to_end():
    """Stage I (size -> trace) then Stage II (banking) on the paper workload."""
    cfg = get_arch("dsr1d-qwen-1.5b")
    g = build_graph(cfg, M=2048, subops=4)
    mib, sim = find_min_sram(g, baseline_accelerator(128), lo_mib=16,
                             hi_mib=128, step_mib=16)
    assert sim.writebacks == 0
    table = sweep(sim, capacities_mib=[mib, 128])
    best = table.best()
    assert best.banks > 1
    assert best.result.e_total < table.rows[0].result.e_total


@pytest.mark.parametrize("name", ASSIGNED_ARCHS)
def test_trapti_applies_to_every_assigned_arch(name):
    """The paper's technique is workload-agnostic: every assigned arch lowers
    to a graph, simulates, and yields a banking recommendation."""
    cfg = reduced(get_arch(name))
    g = build_graph(cfg, M=256, subops=4)
    assert g.total_macs() > 0
    sim = simulate(g, baseline_accelerator(64))
    assert sim.total_time > 0
    tr = sim.traces["sram"]
    assert tr.peak_needed() > 0
    table = sweep(sim, capacities_mib=[16], banks=(1, 4, 8))
    assert len(table.rows) == 3
    assert table.best().result.e_total <= table.rows[0].result.e_total


def test_gqa_vs_mha_banking_advantage():
    """Paper claim C5: the GQA workload benefits more from banking+PG."""
    gpt = simulate(build_graph(get_arch("gpt2-xl"), M=2048, subops=4),
                   baseline_accelerator(160))
    ds = simulate(build_graph(get_arch("dsr1d-qwen-1.5b"), M=2048, subops=4),
                  baseline_accelerator(128))
    t_gpt = sweep(gpt, capacities_mib=[128])
    t_ds = sweep(ds, capacities_mib=[128])
    best_gpt = min(r.delta_e_pct for r in t_gpt.rows)
    best_ds = min(r.delta_e_pct for r in t_ds.rows)
    assert best_ds < best_gpt - 10.0     # ours: ~ -70% vs -49%


def test_train_then_serve_round_trip(tmp_path):
    from repro.data import DataConfig, SyntheticTokens
    from repro.models import build_model
    from repro.optim import AdamW, constant
    from repro.serve import BatchedServer, ServeConfig
    from repro.train import LoopConfig, TrainLoop

    cfg = reduced(get_arch("dsr1d-qwen-1.5b"), layers=2)
    m = build_model(cfg, compute_dtype=jnp.float32, remat="none")
    opt = AdamW(lr=constant(2e-3))
    data = SyntheticTokens(DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                      global_batch=8, seed=11))
    loop = TrainLoop(m, opt, data, LoopConfig(
        total_steps=30, ckpt_every=30, ckpt_dir=str(tmp_path / "ck")))
    out = loop.run()
    losses = [h["loss"] for h in out["history"]]
    assert losses[-1] < losses[0]

    srv = BatchedServer(m, out["params"], ServeConfig(max_len=64,
                                                      max_new_tokens=6))
    prompts = {"tokens": jnp.asarray(
        np.arange(3 * 12).reshape(3, 12) % cfg.vocab_size, jnp.int32)}
    res = srv.generate(prompts)
    assert res["tokens"].shape == (3, 6)
    assert (res["tokens"] >= 0).all()
    assert (res["tokens"] < cfg.padded_vocab).all()
    # greedy decoding is deterministic
    res2 = srv.generate(prompts)
    np.testing.assert_array_equal(res["tokens"], res2["tokens"])


def test_serve_batch_entries_independent():
    """Row i's generation must not depend on other rows in the batch."""
    from repro.models import build_model
    from repro.serve import BatchedServer, ServeConfig
    cfg = reduced(get_arch("tinyllama-1.1b"), layers=2)
    m = build_model(cfg, compute_dtype=jnp.float32, remat="none")
    params = m.init(jax.random.PRNGKey(0))
    srv = BatchedServer(m, params, ServeConfig(max_len=32, max_new_tokens=4))
    p1 = np.arange(8)[None, :] % cfg.vocab_size
    p2 = (np.arange(8)[None, :] * 3 + 1) % cfg.vocab_size
    both = srv.generate({"tokens": jnp.asarray(
        np.concatenate([p1, p2]), jnp.int32)})
    solo = srv.generate({"tokens": jnp.asarray(p1, jnp.int32)})
    np.testing.assert_array_equal(both["tokens"][0], solo["tokens"][0])


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_dir_from_env_else_checkout(tmp_path, monkeypatch,
                                                  env_dir):
    """The persistent compile cache goes to $JAX_COMPILATION_CACHE_DIR when
    set, else to the fixed `<checkout>/.jax_cache`, which git ignores."""
    from pathlib import Path

    from repro.launch.compile_cache import setup_compile_cache
    root = Path(__file__).resolve().parents[1]
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        want = str(tmp_path)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(root / ".jax_cache")
        assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    before = jax.config.jax_compilation_cache_dir
    try:
        assert setup_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
