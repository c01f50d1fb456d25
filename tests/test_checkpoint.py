"""Sharded checkpoint/resume: save from one mesh, restore onto another
(elastic recovery — the rescheduled-onto-a-different-topology story)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vtpu.models import ModelConfig
from vtpu.parallel.checkpoint import TrainCheckpointer
from vtpu.parallel.mesh import make_mesh
from vtpu.parallel.train import init_train_state, make_train_step, place_batch

# Heavyweight tier: compile-bound or sleep-bound; CI
# runs the slow tier separately so the unit tier stays under two minutes.
pytestmark = pytest.mark.slow

CFG = ModelConfig(
    vocab=128, d_model=64, n_heads=2, n_layers=2, d_ff=128,
    max_seq=32, head_dim=32, dtype=jnp.float32, use_pallas=False,
)

needs8 = pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")


def _tokens(seed, batch):
    return jax.random.randint(jax.random.key(seed), (batch, 16), 0, CFG.vocab, jnp.int32)


@needs8
def test_save_restore_roundtrip_same_mesh(tmp_path):
    mesh = make_mesh(8)
    state, opt = init_train_state(jax.random.key(0), CFG, mesh)
    step_fn = make_train_step(CFG, opt)
    state, _ = step_fn(state, place_batch(_tokens(1, 8), mesh))

    ckpt = TrainCheckpointer(str(tmp_path / "ckpt"))
    try:
        ckpt.save(1, state)
        assert ckpt.latest_step() == 1
        restored, step = ckpt.restore(CFG, mesh, opt)
        assert step == 1
        for a, b in zip(jax.tree.leaves(state["params"]), jax.tree.leaves(restored["params"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # training continues identically from the restored state
        s1, l1 = step_fn(state, place_batch(_tokens(2, 8), mesh))
        s2, l2 = step_fn(restored, place_batch(_tokens(2, 8), mesh))
        assert float(l1) == float(l2)
    finally:
        ckpt.close()


@needs8
def test_restore_onto_different_mesh_geometry(tmp_path):
    """dp4xtp2 checkpoint resumes on a dp2xtp4 mesh — orbax reshards, the
    step function re-jits, the numbers match."""
    mesh_a = make_mesh(8, tp=2)
    state, opt = init_train_state(jax.random.key(0), CFG, mesh_a)
    step_fn = make_train_step(CFG, opt)
    state, loss_a = step_fn(state, place_batch(_tokens(1, 8), mesh_a))

    ckpt = TrainCheckpointer(str(tmp_path / "ckpt"))
    try:
        ckpt.save(5, state)
        mesh_b = make_mesh(8, tp=4)
        restored, step = ckpt.restore(CFG, mesh_b, opt)
        assert step == 5
        # shardings live on the NEW mesh
        leaf = restored["params"]["layers"]["wq"]
        assert leaf.sharding.mesh.shape["dp"] == 2
        for a, b in zip(jax.tree.leaves(state["params"]), jax.tree.leaves(restored["params"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # and a step on the new mesh runs from the restored state
        _, loss_b = step_fn(restored, place_batch(_tokens(2, 8), mesh_b))
        assert jnp.isfinite(loss_b)
    finally:
        ckpt.close()


@needs8
def test_keep_n_retention_and_missing_step(tmp_path):
    mesh = make_mesh(8)
    state, opt = init_train_state(jax.random.key(0), CFG, mesh)
    ckpt = TrainCheckpointer(str(tmp_path / "ckpt"), keep=2)
    try:
        for s in (1, 2, 3):
            ckpt.save(s, state)
        assert ckpt.latest_step() == 3
        steps = ckpt.manager.all_steps()
        assert list(steps) == [2, 3]  # keep=2 pruned step 1
    finally:
        ckpt.close()
    empty = TrainCheckpointer(str(tmp_path / "none"))
    try:
        with pytest.raises(FileNotFoundError):
            empty.restore(CFG, mesh, opt)
    finally:
        empty.close()
