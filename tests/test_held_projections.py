"""A serving adapter holds wq, wk, wv as its programs' products read them
(ISSUE 31): [L, H, Dh, d] where the published stacks are [L, d, H*Dh].

On the CPU, so what is held here is the arithmetic and the bookkeeping: the
one ``_qkv`` gives the same bits from either form, on one chip and over a
('tp',) mesh of two; the adapter holds each projection once, in a dict of
its own. That no serving program copies a stack into another layout is a
property of the v5e compiler's output: tests/test_tpu_compile.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from vtpu.models import ModelConfig, init_params
from vtpu.models.moe import MoEConfig, init_moe_params
from vtpu.models.transformer import PROJECTIONS, _qkv, hold_projections
from vtpu.ops import rope_angles
from vtpu.parallel.sharding import shard_moe_params, shard_params
from vtpu.serving.adapters import MoeSlotModel, TransformerSlotModel

DENSE = ModelConfig(
    vocab=64, d_model=96, n_heads=4, n_layers=3, d_ff=64, max_seq=32,
    head_dim=32, dtype=jnp.bfloat16, use_pallas=False)
MOE = MoEConfig(
    vocab=64, d_model=96, n_heads=4, n_layers=3, d_ff=32, n_experts=4,
    top_k=2, max_seq=32, head_dim=32, dtype=jnp.bfloat16)
FAMILIES = {
    "dense": (DENSE, init_params, TransformerSlotModel,
              lambda p, mesh: shard_params(p, mesh)),
    "moe": (MOE, init_moe_params, MoeSlotModel,
            lambda p, mesh: shard_moe_params(p, mesh, MOE.n_experts)),
}


def _mesh(tp: int):
    if not tp:
        return None
    if len(jax.devices()) < tp:
        pytest.skip(f"needs {tp} devices")
    return Mesh(np.array(jax.devices()[:tp]), ("tp",))


@pytest.mark.parametrize("tp", [0, 2], ids=["one-chip", "tp2"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_qkv_is_bit_equal_on_held_and_published_leaves(family, tp):
    """Every layer, a decode step's row and a chunk's rows: q, k and v from
    the adapter's held leaves equal the three-product form on the published
    ones bit for bit (bf16 operands, each output the same dot product)."""
    cfg, init, adapter, shard = FAMILIES[family]
    mesh = _mesh(tp)
    params = init(jax.random.key(0), cfg)
    model = adapter(params, cfg, mesh=mesh, kv_page=8)
    published = shard(params, mesh) if mesh is not None else params
    cos, sin = rope_angles(cfg.max_seq, cfg.head_dim)
    for b, s in ((4, 1), (1, 16)):
        x = jax.random.normal(jax.random.key(s), (b, s, cfg.d_model), cfg.dtype)
        positions = jnp.broadcast_to(jnp.arange(3, 3 + s), (b, s))

        @jax.jit
        def project(layers, layer):
            lp = jax.tree.map(lambda a: a[layer], layers)
            return _qkv(cfg, lp, x, cos, sin, positions)

        for layer in range(cfg.n_layers):
            want = project(published["layers"], layer)
            got = project(model.params["layers"], layer)
            for name, w, g in zip("qkv", want, got):
                assert g.shape == (b, s, cfg.n_heads, cfg.head_dim)
                np.testing.assert_array_equal(
                    np.asarray(g, np.float32), np.asarray(w, np.float32),
                    err_msg=f"{name} of layer {layer} at [{b}, {s}]")


@pytest.mark.parametrize("tp", [0, 2], ids=["one-chip", "tp2"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_adapter_holds_each_projection_once_in_its_own_dict(family, tp):
    cfg, init, adapter, _ = FAMILIES[family]
    mesh = _mesh(tp)
    params = init(jax.random.key(0), cfg)
    given = dict(params["layers"])
    model = adapter(params, cfg, mesh=mesh, kv_page=8)
    # the caller's dict: the same leaves, in the published form
    assert params["layers"].keys() == given.keys()
    for name, leaf in given.items():
        assert params["layers"][name] is leaf
    qd = cfg.n_heads * cfg.head_dim
    # the adapter's: every key once, the projections held, the bytes equal
    held = model.params["layers"]
    assert held is not params["layers"] and held.keys() == given.keys()
    assert model.params.keys() == params.keys()
    for name in PROJECTIONS:
        assert given[name].shape == (cfg.n_layers, cfg.d_model, qd)
        assert held[name].shape == (
            cfg.n_layers, cfg.n_heads, cfg.head_dim, cfg.d_model)
        np.testing.assert_array_equal(
            np.asarray(held[name], np.float32).reshape(cfg.n_layers, qd, -1),
            np.asarray(given[name], np.float32).swapaxes(1, 2))
        if mesh is not None:  # the head axis is the sharded one
            assert held[name].sharding.spec == P(None, "tp", None, None)
    assert (sum(x.nbytes for x in jax.tree.leaves(model.params))
            == sum(x.nbytes for x in jax.tree.leaves(params)))


def test_a_shape_stands_for_a_leaf_that_is_one():
    """The compile-only rehearsal (vbench/rehearse.py) builds the adapter
    over shapes placed on a described device: the held form of a shape is a
    shape on that device, and nothing runs."""
    device = jax.devices()[-1]
    on = jax.sharding.SingleDeviceSharding(device)
    shapes = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=on),
        jax.eval_shape(lambda: init_params(jax.random.key(0), DENSE)))
    held = hold_projections(shapes["layers"], DENSE)
    for name in PROJECTIONS:
        assert isinstance(held[name], jax.ShapeDtypeStruct)
        assert held[name].shape == (3, 4, 32, 96)
        assert held[name].dtype == jnp.bfloat16
        assert held[name].sharding.device_set == {device}
    assert held["wo"] is shapes["layers"]["wo"]
