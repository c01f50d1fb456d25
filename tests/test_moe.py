"""MoE model family + expert parallelism on the virtual 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vtpu.models.moe import MoEConfig, init_moe_params, moe_forward, moe_loss, route
from vtpu.parallel.expert import ep_moe_forward, moe_param_shardings
from vtpu.parallel.mesh import make_axis_mesh, make_dp_ep_mesh

# Heavyweight tier: compile-bound or sleep-bound; CI
# runs the slow tier separately so the unit tier stays under two minutes.
pytestmark = pytest.mark.slow

needs8 = pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")

# capacity_factor = E/k -> capacity == token count -> no token ever dropped,
# so the dense and expert-parallel paths are numerically comparable.
CFG = MoEConfig(
    vocab=128, d_model=32, n_heads=2, n_layers=2, d_ff=64,
    n_experts=8, top_k=2, capacity_factor=4.0,
    max_seq=16, head_dim=16, dtype=jnp.float32,
)


def test_route_shapes_and_drop_semantics():
    cfg = MoEConfig(d_model=16, n_experts=4, top_k=2, capacity_factor=0.5)
    t = 32
    cap = cfg.capacity(t)  # deliberately tight -> drops happen
    x = jax.random.normal(jax.random.key(0), (t, cfg.d_model))
    w = jax.random.normal(jax.random.key(1), (cfg.d_model, cfg.n_experts))
    dispatch, combine, aux = route(w, x, cfg, cap)
    assert dispatch.shape == (t, cfg.n_experts, cap)
    # each (expert, slot) holds at most one token
    assert float(jnp.max(jnp.sum(dispatch, axis=0))) <= 1.0
    # per-token combined gate mass is <= 1 (dropped tokens contribute 0)
    assert float(jnp.max(jnp.sum(combine, axis=(1, 2)))) <= 1.0 + 1e-6
    assert jnp.isfinite(aux)


def test_route_no_drops_preserves_all_tokens():
    cfg = MoEConfig(d_model=16, n_experts=4, top_k=2, capacity_factor=2.0)
    t = 16
    cap = cfg.capacity(t)
    assert cap >= t * cfg.top_k // cfg.n_experts
    x = jax.random.normal(jax.random.key(2), (t, cfg.d_model))
    w = jax.random.normal(jax.random.key(3), (cfg.d_model, cfg.n_experts))
    cap = t  # guarantee zero drops
    dispatch, combine, _ = route(w, x, cfg, cap)
    # every token keeps its full (normalized) top-k gate mass
    np.testing.assert_allclose(np.asarray(jnp.sum(combine, axis=(1, 2))), 1.0, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(jnp.sum(dispatch, axis=(1, 2))), cfg.top_k, atol=1e-5
    )


def test_dense_moe_forward_finite():
    params = init_moe_params(jax.random.key(0), CFG)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, CFG.vocab)
    logits, aux = jax.jit(lambda p, t: moe_forward(p, CFG, t))(params, tokens)
    assert logits.shape == (2, 16, CFG.vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))
    assert float(aux) > 0.0


@needs8
def test_ep_forward_matches_dense():
    mesh = make_axis_mesh("ep", 8)
    params = init_moe_params(jax.random.key(0), CFG)
    tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, CFG.vocab)
    want, aux_want = moe_forward(params, CFG, tokens)
    got, aux_got = jax.jit(lambda p, t: ep_moe_forward(p, CFG, t, mesh))(params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)
    # aux is a balance statistic: EP computes it per-shard and pmeans, which is
    # a different (equally valid) estimator than the dense global one -- only
    # the model output must agree.
    assert jnp.isfinite(aux_got) and float(aux_got) > 0.0


@needs8
def test_moe_train_step_pjit_ep_sharded():
    """Annotation path: expert weights sharded over 'ep', XLA inserts the
    all-to-alls; one SGD step over a ('dp','ep') mesh reduces the loss."""
    import optax

    mesh = make_dp_ep_mesh(8)  # dp=2, ep=4
    params = init_moe_params(jax.random.key(0), CFG)
    specs = moe_param_shardings(mesh)
    params = jax.tree.map(jax.device_put, params, specs)
    tokens = jax.device_put(
        jax.random.randint(jax.random.key(1), (4, 16), 0, CFG.vocab),
        jax.NamedSharding(mesh, jax.sharding.PartitionSpec("dp", None)),
    )
    opt = optax.sgd(5e-2)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(lambda p: moe_loss(p, CFG, tokens))(params)
        updates, opt_state = opt.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    params, opt_state, loss0 = step(params, opt_state, tokens)
    for _ in range(5):
        params, opt_state, loss = step(params, opt_state, tokens)
    assert jnp.isfinite(loss)
    assert float(loss) < float(loss0)


def test_moe_prefill_right_padding_is_harmless():
    """ADVICE r2 (medium): under the training capacity formula a pad token's
    FIRST choice could exhaust an expert before a real token's SECOND choice
    claimed its slot, so a padded-bucket prefill diverged from the unpadded
    forward. Serving prefill now routes with capacity >= token count (like
    decode): real-token logits must be bit-comparable whatever the padding."""
    from vtpu.models.moe import moe_prefill

    # tight capacity factor so the training formula WOULD drop under load
    cfg = MoEConfig(
        vocab=128, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        n_experts=4, top_k=2, capacity_factor=0.5,
        max_seq=64, head_dim=16, dtype=jnp.float32,
    )
    params = init_moe_params(jax.random.key(0), cfg)
    true = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab, (1, 12)), jnp.int32)
    logits_true, cache_true = moe_prefill(params, cfg, true)
    padded = jnp.concatenate(
        [true, jnp.zeros((1, 20), jnp.int32)], axis=1)  # right-pad to 32
    logits_pad, cache_pad = moe_prefill(params, cfg, padded)
    np.testing.assert_allclose(
        np.asarray(logits_pad[:, :12]), np.asarray(logits_true), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(cache_pad["k"][:, :, :12]), np.asarray(cache_true["k"][:, :, :12]),
        rtol=2e-5, atol=2e-5)


def test_moe_prefill_true_len_masks_pads_and_bounds_capacity():
    """ADVICE r3 (low): capacity = full token count grows dispatch/combine to
    [T, E, T]. With true_len, pads are masked out of routing so capacity can
    follow the cf formula — pads claim no capacity slot, so they can never
    evict a real token. (Routing-imbalance overflow drops remain possible
    under the formula capacity, as in training; this prompt stays well
    within capacity at both bucket sizes, so outputs here are exact.)"""
    from vtpu.models.moe import moe_prefill

    cfg = MoEConfig(
        vocab=128, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        n_experts=4, top_k=2, capacity_factor=2.0,
        max_seq=64, head_dim=16, dtype=jnp.float32,
    )
    params = init_moe_params(jax.random.key(0), cfg)
    true = jnp.asarray(
        np.random.RandomState(1).randint(0, cfg.vocab, (1, 12)), jnp.int32)
    # same prompt in two bucket sizes; pads masked via true_len
    pad32 = jnp.concatenate([true, jnp.zeros((1, 20), jnp.int32)], axis=1)
    pad48 = jnp.concatenate([true, jnp.zeros((1, 36), jnp.int32)], axis=1)
    logits32, _ = moe_prefill(params, cfg, pad32, true_len=jnp.int32(12))
    logits48, _ = moe_prefill(params, cfg, pad48, true_len=jnp.int32(12))
    np.testing.assert_allclose(
        np.asarray(logits32[:, :12]), np.asarray(logits48[:, :12]),
        rtol=2e-5, atol=2e-5)
    # and the masked path matches the no-drop exact forward at cf ample
    # enough that the formula capacity can't drop a 12-token prompt
    logits_exact, _ = moe_prefill(params, cfg, true)
    np.testing.assert_allclose(
        np.asarray(logits32[:, :12]), np.asarray(logits_exact),
        rtol=2e-5, atol=2e-5)


def test_moe_prefill_int8_kv_cache():
    """kv_int8 flows through the MoE family's shared cache machinery: the
    prefill fill site quantizes, and the serving decode trunk reads the
    int8 window through the post-scale attention path."""
    import dataclasses

    from vtpu.models.moe import moe_decode_ffn, moe_prefill
    from vtpu.models.slots import batched_decode_step

    cfg = MoEConfig(
        vocab=128, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        n_experts=4, top_k=2, max_seq=64, head_dim=16, dtype=jnp.float32,
    )
    cfg_q = dataclasses.replace(cfg, kv_int8=True)
    params = init_moe_params(jax.random.key(0), cfg)
    tokens = jnp.asarray(
        np.random.RandomState(3).randint(0, cfg.vocab, (2, 12)), jnp.int32)

    logits_ex, cache_ex = moe_prefill(params, cfg, tokens)
    logits_q, cache_q = moe_prefill(params, cfg_q, tokens)
    assert cache_q["k"].dtype == jnp.int8 and "k_scale" in cache_q
    np.testing.assert_allclose(
        np.asarray(logits_q), np.asarray(logits_ex), rtol=1e-5, atol=1e-5)

    active = jnp.ones((2,), bool)
    tok = jnp.argmax(logits_ex[:, -1], axis=-1).astype(jnp.int32)
    step_ex, _ = batched_decode_step(
        params, cfg, cache_ex, tok, active, ffn_fn=moe_decode_ffn(cfg))
    step_q, _ = batched_decode_step(
        params, cfg_q, cache_q, tok, active, ffn_fn=moe_decode_ffn(cfg_q))
    np.testing.assert_allclose(
        np.asarray(step_q), np.asarray(step_ex), rtol=0.05, atol=0.05)
