"""Sharding rules engine: divisibility fallback, mesh-free constraints."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_arch, reduced
from repro.launch.mesh import make_host_mesh
from repro.models.common import PTpl
from repro.models.meshctx import constrain, current_mesh, use_mesh
from repro.models.sharding import (SERVE_RULES, TRAIN_RULES, batch_spec,
                                   spec_for)


def test_spec_for_divisible_dims():
    mesh = make_host_mesh()
    # weight (D, F): embed -> data, mlp -> model (both divisible by 1)
    s = spec_for(("embed", "mlp"), (64, 128), mesh, TRAIN_RULES)
    assert s == P("data", "model")


def test_spec_for_indivisible_falls_back_to_replicate():
    mesh = make_host_mesh()
    # simulate a 16-way axis via a fake mesh-like object
    class FakeMesh:
        shape = {"data": 16, "model": 16}
    s = spec_for(("heads",), (28,), FakeMesh(), TRAIN_RULES)
    assert s == P(None)                      # 28 % 16 != 0 -> replicate
    s = spec_for(("qkv_out",), (3584,), FakeMesh(), TRAIN_RULES)
    assert s == P("model")                   # 3584 % 16 == 0


def test_spec_for_no_axis_reuse_within_tensor():
    class FakeMesh:
        shape = {"data": 4, "model": 4}
    # both dims want "model" (vocab then mlp); second must not reuse it
    s = spec_for(("vocab", "mlp"), (64, 64), FakeMesh(), TRAIN_RULES)
    assert s == P("model", None)


def test_batch_spec_prefers_pod_data_in_train():
    class FakeMesh:
        shape = {"pod": 2, "data": 16, "model": 16}
    assert batch_spec(FakeMesh(), 256, "train") == P(("pod", "data"))
    assert batch_spec(FakeMesh(), 2, "train") == P(None)   # 2 % 32 != 0


def test_constrain_is_noop_without_mesh():
    assert current_mesh() is None
    x = jnp.ones((4, 4))
    y = constrain(x, P("data", None))
    assert (y == x).all()


def test_constrain_drops_missing_axes_and_indivisible():
    mesh = make_host_mesh()
    with use_mesh(mesh):
        x = jnp.ones((4, 4))
        # "pod" doesn't exist on this mesh; must not raise
        y = constrain(x, P(("pod", "data"), None))
        assert (y == x).all()


def test_template_shardings_cover_full_tree():
    from repro.models import build_model
    from repro.models.sharding import template_shardings
    cfg = reduced(get_arch("qwen2-7b"))
    m = build_model(cfg, compute_dtype=jnp.float32)
    mesh = make_host_mesh()
    tpl = m.template()
    sh = template_shardings(tpl, mesh, TRAIN_RULES)
    n_tpl = len(jax.tree.leaves(tpl, is_leaf=lambda x: isinstance(x, PTpl)))
    n_sh = len(jax.tree.leaves(sh))
    assert n_tpl == n_sh


def test_cache_specs_structure_matches_cache():
    from repro.models.transformer import cache_specs, init_cache
    cfg = reduced(get_arch("recurrentgemma-2b"))
    mesh = make_host_mesh()
    cache = jax.eval_shape(lambda: init_cache(cfg, 4, 64))
    specs = cache_specs(cfg, 4, 64, mesh)
    assert jax.tree.structure(
        jax.tree.map(lambda _: 0, cache)) == jax.tree.structure(
        jax.tree.map(lambda _: 0, specs, is_leaf=lambda x: isinstance(x, P)))
