"""Tests for the §Perf hillclimb features: stochastic greedy, MoE
token-exchange numerics, sharding profiles."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core.functions import make_objective
from repro.core.greedy import greedy
from repro.data.synthetic import gen_images
from repro.models.moe import moe_apply
from repro.sharding import axes as AX


def test_stochastic_greedy_quality_and_evals():
    x = gen_images(1024, 64, classes=16, seed=3)
    obj = make_objective("facility")
    ids = jnp.arange(1024, dtype=jnp.int32)
    valid = jnp.ones(1024, bool)
    exact = greedy(obj, ids, jnp.asarray(x), valid, 32)
    sto = greedy(obj, ids, jnp.asarray(x), valid, 32, sample=128,
                 key=jax.random.PRNGKey(5))
    assert float(sto.value) >= 0.93 * float(exact.value)
    assert int(sto.evals) < int(exact.evals) / 4
    sel = np.asarray(sto.ids)[np.asarray(sto.valid)]
    assert len(set(sel.tolist())) == len(sel)      # no duplicates


def test_stochastic_greedy_deterministic_under_key():
    x = gen_images(256, 32, classes=8, seed=1)
    obj = make_objective("facility")
    ids = jnp.arange(256, dtype=jnp.int32)
    valid = jnp.ones(256, bool)
    a = greedy(obj, ids, jnp.asarray(x), valid, 8, sample=32,
               key=jax.random.PRNGKey(1))
    b = greedy(obj, ids, jnp.asarray(x), valid, 8, sample=32,
               key=jax.random.PRNGKey(1))
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))


def test_moe_token_exchange_same_numerics():
    """token_exchange only adds sharding constraints — on one device the
    outputs must be identical up to the bf16 accumulation dtype change."""
    cfg = registry.smoke_config("qwen3-moe-30b-a3b")
    from repro.models import transformer as T
    params, _ = T.init_params(jax.random.PRNGKey(0), cfg)
    p0 = jax.tree.map(lambda v: v[0], params["blocks"]["pos0"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model))
    base, _ = moe_apply(p0, x, cfg, cfg.moe)
    mcfg = dataclasses.replace(cfg.moe, token_exchange=True)
    var, _ = moe_apply(p0, x, cfg, mcfg)
    np.testing.assert_allclose(np.asarray(base), np.asarray(var),
                               atol=5e-2, rtol=5e-2)


def test_moe_token_exchange_grad_finite():
    cfg = registry.smoke_config("qwen3-moe-30b-a3b")
    mcfg = dataclasses.replace(cfg.moe, token_exchange=True)
    from repro.models import transformer as T
    params, _ = T.init_params(jax.random.PRNGKey(0), cfg)
    p0 = jax.tree.map(lambda v: v[0], params["blocks"]["pos0"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, cfg.d_model))

    def loss(p):
        out, _ = moe_apply(p, x, cfg, mcfg)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    g = jax.grad(loss)(p0)
    for leaf in jax.tree.leaves(g):
        assert bool(jnp.all(jnp.isfinite(leaf.astype(jnp.float32))))


def _abstract_mesh(sizes, names):
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(sizes), tuple(names))


def test_sharding_profiles_switch_and_restore():
    assert AX.current_profile() == "default"
    AX.use_profile("dp_only")
    try:
        assert AX.current_profile() == "dp_only"
        # dp_only: act_batch can take all three axes; params drop TP
        mesh = _abstract_mesh((2, 16, 16), ("pod", "data", "model"))
        spec = AX.resolve_spec(("act_batch",), (512,), mesh,
                               AX.current_act_rules())
        assert spec[0] == ("pod", "data", "model")
        pspec = AX.resolve_spec(("embed", "mlp"), (1024, 4096), mesh,
                                AX.current_param_rules())
        assert "model" not in str(pspec)
    finally:
        AX.use_profile("default")
    spec = AX.resolve_spec(("act_batch",), (512,),
                           _abstract_mesh((2, 16, 16),
                                          ("pod", "data", "model")),
                           AX.current_act_rules())
    assert spec[0] == ("pod", "data")


from jax.sharding import AbstractMesh  # noqa: E402  (test-local import)
