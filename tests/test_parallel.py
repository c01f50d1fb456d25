"""SPMD tests on the virtual 8-device CPU mesh (conftest sets XLA_FLAGS)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vtpu.models import ModelConfig, init_params
from vtpu.models.transformer import prefill
from vtpu.ops import causal_attention
from vtpu.parallel import make_mesh, mesh_shape_for, ring_attention, shard_params
from vtpu.parallel.mesh import make_sp_mesh
from vtpu.parallel.train import init_train_state, make_train_step, place_batch

# Heavyweight tier: compile-bound or sleep-bound; CI
# runs the slow tier separately so the unit tier stays under two minutes.
pytestmark = pytest.mark.slow

CFG = ModelConfig(
    vocab=128, d_model=64, n_heads=4, n_layers=2, d_ff=128,
    max_seq=32, head_dim=32, dtype=jnp.float32, use_pallas=False,
)

needs8 = pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")


def test_mesh_shape_factorization():
    assert mesh_shape_for(8) == (2, 4)
    assert mesh_shape_for(4) == (1, 4)
    assert mesh_shape_for(8, tp=2) == (4, 2)
    with pytest.raises(ValueError):
        mesh_shape_for(8, tp=3)


@needs8
def test_ring_attention_matches_reference():
    mesh = make_sp_mesh(8)
    k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
    shape = (2, 64, 2, 16)  # S=64 -> 8 chunks of 8
    q = jax.random.normal(k1, shape, jnp.float32)
    k = jax.random.normal(k2, shape, jnp.float32)
    v = jax.random.normal(k3, shape, jnp.float32)
    want = causal_attention(q, k, v)
    got = ring_attention(q, k, v, mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@needs8
def test_ulysses_attention_matches_reference():
    from vtpu.parallel.ulysses import ulysses_attention

    mesh = make_sp_mesh(8)
    k1, k2, k3 = jax.random.split(jax.random.key(1), 3)
    shape = (2, 64, 8, 16)  # H=8 divides the 8-way mesh; S=64 -> chunks of 8
    q = jax.random.normal(k1, shape, jnp.float32)
    k = jax.random.normal(k2, shape, jnp.float32)
    v = jax.random.normal(k3, shape, jnp.float32)
    want = causal_attention(q, k, v)
    got = ulysses_attention(q, k, v, mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@needs8
def test_ulysses_rejects_indivisible_heads():
    import pytest

    from vtpu.parallel.ulysses import ulysses_attention

    mesh = make_sp_mesh(8)
    q = jnp.zeros((1, 16, 6, 8))  # 6 heads over 8 devices
    with pytest.raises(ValueError, match="ring_attention instead"):
        ulysses_attention(q, q, q, mesh)


@needs8
def test_sharded_prefill_matches_single_device():
    mesh = make_mesh(8)  # dp=2, tp=4
    params = init_params(jax.random.key(0), CFG)
    tokens = jax.random.randint(jax.random.key(1), (4, 16), 0, CFG.vocab)
    want, _ = prefill(params, CFG, tokens)
    sharded = shard_params(params, mesh)
    got, _ = jax.jit(lambda p, t: prefill(p, CFG, t))(sharded, place_batch(tokens, mesh))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-3)


@needs8
def test_train_step_reduces_loss_on_mesh():
    mesh = make_mesh(8)
    state, opt = init_train_state(jax.random.key(0), CFG, mesh, lr=5e-3)
    step = make_train_step(CFG, opt)
    tokens = place_batch(
        jax.random.randint(jax.random.key(1), (4, 16), 0, CFG.vocab), mesh
    )
    state, loss0 = step(state, tokens)
    for _ in range(5):
        state, loss = step(state, tokens)
    assert float(loss) < float(loss0)


@needs8
def test_multislice_train_step():
    """2 slices x (2 dp x 2 tp): the full train step compiles and runs with
    batch sharded over ('slice','dp') — XLA's gradient reduction is then
    hierarchical (ICI within a slice, one DCN hop across)."""
    import jax.numpy as jnp

    from vtpu.models import ModelConfig
    from vtpu.parallel.mesh import make_multislice_mesh
    from vtpu.parallel.train import init_train_state, make_train_step, place_batch

    cfg = ModelConfig(vocab=128, d_model=64, n_heads=2, n_layers=2, d_ff=128,
                      max_seq=16, head_dim=32, dtype=jnp.float32, use_pallas=False)
    mesh = make_multislice_mesh(2, per_slice=4, tp=2)
    assert dict(mesh.shape) == {"slice": 2, "dp": 2, "tp": 2}
    state, opt = init_train_state(jax.random.key(0), cfg, mesh)
    step = make_train_step(cfg, opt)
    tokens = place_batch(
        jax.random.randint(jax.random.key(1), (8, 16), 0, cfg.vocab, jnp.int32), mesh
    )
    assert tokens.sharding.spec == jax.sharding.PartitionSpec(("slice", "dp"), None)
    state, loss = step(state, tokens)
    assert jnp.isfinite(loss)
    state, loss2 = step(state, tokens)
    assert jnp.isfinite(loss2) and float(loss2) < float(loss)  # it learns


def test_multislice_mesh_validation():
    from vtpu.parallel.mesh import make_multislice_mesh

    n = len(jax.devices())
    if n % 3:
        with pytest.raises(ValueError, match="do not split"):
            make_multislice_mesh(3)
    with pytest.raises(ValueError, match="have"):
        make_multislice_mesh(2, per_slice=n)  # 2n devices needed
