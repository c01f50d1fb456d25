"""Selective-SSM model family: causality, scan/recurrent equivalence,
trainability (f32 CPU determinism)."""

import jax
import jax.numpy as jnp
import numpy as np

import pytest

from vtpu.models.ssm import (
    SSMConfig,
    init_ssm_params,
    init_ssm_state,
    ssm_decode_step,
    ssm_forward,
    ssm_loss,
)

# Heavyweight tier: compile-bound or sleep-bound; CI
# runs the slow tier separately so the unit tier stays under two minutes.
pytestmark = pytest.mark.slow

CFG = SSMConfig(vocab=64, d_model=32, n_layers=2, d_state=4, d_conv=3,
                expand=2, dtype=jnp.float32)


def _setup(seed=0, batch=2, seq=12):
    params = init_ssm_params(jax.random.key(seed), CFG)
    tokens = jax.random.randint(jax.random.key(seed + 1), (batch, seq), 0, CFG.vocab, jnp.int32)
    return params, tokens


def test_forward_shapes_finite():
    params, tokens = _setup()
    logits = ssm_forward(params, CFG, tokens)
    assert logits.shape == (2, 12, CFG.vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_causality():
    """Changing a future token must not change past logits."""
    params, tokens = _setup()
    base = ssm_forward(params, CFG, tokens)
    perturbed = tokens.at[:, 8].set((tokens[:, 8] + 1) % CFG.vocab)
    got = ssm_forward(params, CFG, perturbed)
    np.testing.assert_allclose(np.asarray(base[:, :8]), np.asarray(got[:, :8]),
                               rtol=1e-5, atol=1e-5)
    assert not np.allclose(np.asarray(base[:, 8:]), np.asarray(got[:, 8:]))


def test_recurrent_decode_matches_parallel_scan():
    """Feeding tokens one at a time through the O(1) stepper reproduces the
    associative-scan forward at every position."""
    params, tokens = _setup(batch=2, seq=10)
    want = ssm_forward(params, CFG, tokens)  # [B,S,V]
    state = init_ssm_state(CFG, batch=2)
    step = jax.jit(lambda s, t: ssm_decode_step(params, CFG, s, t))
    for pos in range(tokens.shape[1]):
        logits, state = step(state, tokens[:, pos])
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(want[:, pos]), rtol=2e-4, atol=2e-4,
        )


def test_trainable():
    params, tokens = _setup()
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: ssm_loss(p, CFG, tokens)))(params)
    assert jnp.isfinite(loss)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(grads))
    assert any(float(jnp.max(jnp.abs(g))) > 0 for g in jax.tree.leaves(grads))
