"""Pipeline parallelism on the virtual 8-device CPU mesh."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vtpu.models import ModelConfig, init_params
from vtpu.models.transformer import prefill
from vtpu.parallel.mesh import make_axis_mesh
from vtpu.parallel.pipeline import microbatch, pipeline_apply, pp_loss, pp_transformer_forward

# Heavyweight tier: compile-bound or sleep-bound; CI
# runs the slow tier separately so the unit tier stays under two minutes.
pytestmark = pytest.mark.slow

needs8 = pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")

CFG = ModelConfig(
    vocab=128, d_model=64, n_heads=4, n_layers=8, d_ff=128,
    max_seq=16, head_dim=16, dtype=jnp.float32, use_pallas=False,
)


def test_microbatch_shapes():
    x = jnp.zeros((8, 16, 4))
    assert microbatch(x, 4).shape == (4, 2, 16, 4)
    with pytest.raises(ValueError, match="not divisible"):
        microbatch(x, 3)


@needs8
def test_pipeline_apply_matches_sequential():
    """4-stage pipeline over stacked linear layers == sequential scan."""
    mesh = make_axis_mesh("pp", 4, devices=jax.devices()[:4])
    l, d = 8, 16
    w = jax.random.normal(jax.random.key(0), (l, d, d)) * 0.3
    xs = jax.random.normal(jax.random.key(1), (6, 2, d))  # 6 microbatches

    stage = lambda lp, x: jnp.tanh(x @ lp)  # noqa: E731
    got = jax.jit(lambda w, xs: pipeline_apply(w, xs, stage, mesh))(w, xs)

    want, _ = jax.lax.scan(lambda h, lp: (stage(lp, h), None), xs, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@needs8
def test_pp_transformer_matches_dense_prefill():
    mesh = make_axis_mesh("pp", 8)
    params = init_params(jax.random.key(0), CFG)
    tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, CFG.vocab)
    want, _ = prefill(params, CFG, tokens)
    got = jax.jit(lambda p, t: pp_transformer_forward(p, CFG, t, mesh))(params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


@needs8
def test_pipeline_rejects_bad_geometry():
    mesh = make_axis_mesh("pp", 8)
    params = init_params(jax.random.key(0), CFG)
    bad = dataclasses.replace(CFG, n_layers=6)
    with pytest.raises(ValueError, match="not divisible"):
        pp_transformer_forward(init_params(jax.random.key(0), bad), bad,
                               jnp.zeros((8, 16), jnp.int32), mesh)
    with pytest.raises(ValueError, match="microbatches"):
        pipeline_apply(params["layers"],
                       jnp.zeros((2, 1, 16, CFG.d_model)),  # 2 microbatches < 8 stages
                       lambda lp, x: x, mesh)


@needs8
def test_pp_train_step_reduces_loss():
    """Backprop through the pipeline schedule: one SGD step lowers the loss."""
    import optax

    mesh = make_axis_mesh("pp", 4, devices=jax.devices()[:4])
    params = init_params(jax.random.key(0), CFG)
    tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, CFG.vocab)
    opt = optax.sgd(5e-2)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state):
        loss, grads = jax.value_and_grad(lambda p: pp_loss(p, CFG, tokens, mesh))(params)
        updates, opt_state = opt.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    params, opt_state, loss0 = step(params, opt_state)
    for _ in range(4):
        params, opt_state, loss = step(params, opt_state)
    assert jnp.isfinite(loss)
    assert float(loss) < float(loss0)
