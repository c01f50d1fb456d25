"""Sequence-parallel long-context prefill: exact vs the dense path, causal,
trainable through the ring."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vtpu.models import ModelConfig, init_params
from vtpu.models.transformer import prefill
from vtpu.parallel.longctx import place_sp_tokens, sp_loss, sp_prefill
from vtpu.parallel.mesh import make_sp_mesh

# Heavyweight tier: compile-bound, tens of seconds
# each; CI runs them separately so the unit tier stays under two minutes.
pytestmark = pytest.mark.slow

CFG = ModelConfig(vocab=128, d_model=64, n_heads=2, n_layers=2, d_ff=128,
                  max_seq=64, head_dim=32, dtype=jnp.float32, use_pallas=False)

needs8 = pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.key(0), CFG)


def _tokens(seed, s=32):
    return jax.random.randint(jax.random.key(seed), (2, s), 0, CFG.vocab, jnp.int32)


@needs8
def test_sp_prefill_matches_dense(params):
    mesh = make_sp_mesh(8)
    tokens = _tokens(1)
    got = sp_prefill(params, CFG, place_sp_tokens(tokens, mesh), mesh)
    want, _ = prefill(params, CFG, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


@needs8
def test_sp_prefill_rejects_indivisible_seq(params):
    mesh = make_sp_mesh(8)
    with pytest.raises(ValueError, match="not divisible"):
        sp_prefill(params, CFG, _tokens(1, s=30), mesh)


@needs8
def test_sp_loss_trains_through_the_ring(params):
    """Gradients flow back through the ppermute schedule: one SGD step on the
    sp loss must match the dense-loss step (same math, different schedule)."""
    from vtpu.ops.loss import next_token_ce

    mesh = make_sp_mesh(8)
    tokens = _tokens(2)

    def dense_loss(p):
        logits, _ = prefill(p, CFG, tokens)
        return next_token_ce(logits, tokens)

    l_sp, g_sp = jax.value_and_grad(
        lambda p: sp_loss(p, CFG, place_sp_tokens(tokens, mesh), mesh))(params)
    l_d, g_d = jax.value_and_grad(dense_loss)(params)
    assert abs(float(l_sp) - float(l_d)) < 1e-4
    for a, b in zip(jax.tree.leaves(g_sp), jax.tree.leaves(g_d)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-4)
