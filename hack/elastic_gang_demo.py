"""Elastic gang recovery, end to end: kill a slice worker, reschedule it,
repair its rank, resume training from the checkpoint on a different mesh.

Gang-rank repair and orbax elastic restore each had tests, but
no artifact showed the RECOVERY STORY they exist for. This demo ties them:

  Act 1 (control plane) - a 2-worker gang lands on one physical slice with
    ranks 0/1; worker 1's pod dies; the replacement pod must land back on the
    SAME slice, on a host distinct from the survivor, and be assigned rank 1
    (the only rank no live member holds) so its TPU_WORKER_ID matches the
    slot the job expects.
  Act 2 (data plane) - the same job's training state: dp4xtp2 mesh trains and
    checkpoints; the "rescheduled" worker restores the latest step onto a
    dp2xtp4 mesh (elastic: orbax reshards onto the new geometry) and training
    continues, loss matching an uninterrupted run at the same step.

Writes ELASTIC_r04.json. CPU-only (8 virtual devices), no TPU needed.
"""

from __future__ import annotations

import json
import os
import pathlib
import sys
import tempfile

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

os.environ["JAX_PLATFORMS"] = "cpu"
# strip any pre-existing device-count flag: the meshes below need exactly 8
flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
         if "xla_force_host_platform_device_count" not in f]
os.environ["XLA_FLAGS"] = " ".join(
    flags + ["--xla_force_host_platform_device_count=8"])

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def act1_control_plane(evidence: dict) -> None:
    from vtpu.device.types import SliceInfo
    from vtpu.scheduler.scheduler import Scheduler
    from vtpu.util import types as t
    from vtpu.util.k8sclient import annotations
    from tests.helpers import fake_cluster, register_tpu_backend, tpu_pod

    gang = {"pod-group.scheduling.sigs.k8s.io/name": "trainjob"}

    def worker(name):
        return tpu_pod(name, tpu=4, annotations={
            t.SLICE_WORKERS_ANNO: "2", **gang})

    from tests.helpers import v5e_devices

    client = fake_cluster({
        "a0": v5e_devices(4, prefix="a0"), "a1": v5e_devices(4, prefix="a1"),
        "b0": v5e_devices(4, prefix="b0"), "b1": v5e_devices(4, prefix="b1"),
    })
    for node, (sid, wid) in {"a0": ("s1", 0), "a1": ("s1", 1),
                             "b0": ("s2", 0), "b1": ("s2", 1)}.items():
        client.patch_node_annotations(node, {
            # 2 hosts x 4 v5e chips = an 8-chip 2x4 slice, matching the
            # v5e_devices(4) fleet above
            t.NODE_SLICE_ANNO: SliceInfo(sid, wid, 2, "v5e-8", "2x4").encode()})
    sched = Scheduler(client)
    register_tpu_backend(quota=sched.quota_manager)
    sched.start(register_interval=3600)
    nodes = ["a0", "a1", "b0", "b1"]
    try:
        p0 = client.put_pod(worker("w0"))
        r0 = sched.filter({"Pod": p0, "NodeNames": nodes})
        p1 = client.put_pod(worker("w1"))
        r1 = sched.filter({"Pod": p1, "NodeNames": nodes})
        host0, host1 = r0["NodeNames"][0], r1["NodeNames"][0]
        slice_of = {"a0": "s1", "a1": "s1", "b0": "s2", "b1": "s2"}
        assert slice_of[host0] == slice_of[host1] and host0 != host1
        rank0 = int(annotations(client.get_pod("default", "w0"))[t.GANG_RANK_ANNO])
        rank1 = int(annotations(client.get_pod("default", "w1"))[t.GANG_RANK_ANNO])
        assert sorted((rank0, rank1)) == [0, 1]
        evidence["initial_placement"] = {
            "w0": {"node": host0, "rank": rank0},
            "w1": {"node": host1, "rank": rank1},
            "slice": slice_of[host0],
        }

        # ---- worker w1 DIES (pod deleted; node survives)
        dead = client.get_pod("default", "w1")
        client.delete_pod("default", "w1")
        sched.on_del_pod(dead)

        # ---- the replacement must rejoin the SAME slice on the free host
        # with the dead worker's rank repaired back to it
        pr = client.put_pod(worker("w1-replacement"))
        rr = sched.filter({"Pod": pr, "NodeNames": nodes})
        new_host = rr["NodeNames"][0]
        assert slice_of[new_host] == slice_of[host0], "left the gang's slice"
        assert new_host != host0, "collided with the survivor's host"
        new_rank = int(annotations(
            client.get_pod("default", "w1-replacement"))[t.GANG_RANK_ANNO])
        assert new_rank == rank1, (
            f"repaired rank {new_rank} != dead worker's rank {rank1}")
        evidence["after_worker_death"] = {
            "w1_replacement": {"node": new_host, "rank": new_rank},
            "survivor_untouched": {"node": host0, "rank": rank0},
            "rank_repair": "replacement received the smallest rank no live "
                           "member holds -- the dead worker's slot",
        }
    finally:
        sched.stop()


def act2_data_plane(evidence: dict) -> None:
    from vtpu.models import ModelConfig
    from vtpu.parallel.checkpoint import TrainCheckpointer
    from vtpu.parallel.mesh import make_mesh
    from vtpu.parallel.train import init_train_state, make_train_step, place_batch

    cfg = ModelConfig(vocab=128, d_model=64, n_heads=2, n_layers=2, d_ff=128,
                      max_seq=32, head_dim=32, dtype=jnp.float32,
                      use_pallas=False)

    def tokens(seed):
        return jax.random.randint(
            jax.random.key(seed), (8, 16), 0, cfg.vocab, jnp.int32)

    with tempfile.TemporaryDirectory() as tmp:
        # the job trains on its original geometry, checkpointing as it goes
        mesh_a = make_mesh(8, tp=2)
        state, opt = init_train_state(jax.random.key(0), cfg, mesh_a)
        step_fn = make_train_step(cfg, opt)
        ckpt = TrainCheckpointer(os.path.join(tmp, "ckpt"))
        pre_losses = []
        try:
            # a fixed batch: loss must strictly improve across the failure
            batch = tokens(1)
            for step in range(1, 4):
                state, loss = step_fn(state, place_batch(batch, mesh_a))
                pre_losses.append(float(loss))
                ckpt.save(step, state)

            # a reference run that never fails: three more steps on mesh A
            ref_state = state
            ref_losses = []
            for step in range(4, 7):
                ref_state, loss = step_fn(
                    ref_state, place_batch(batch, mesh_a))
                ref_losses.append(float(loss))

            # ---- FAILURE: the job is rescheduled; the replacement worker
            # set comes up with a DIFFERENT mesh split (elastic restore)
            mesh_b = make_mesh(8, tp=4)
            restored, resumed_step = ckpt.restore(cfg, mesh_b, opt)
            assert resumed_step == 3
            resumed_losses = []
            for step in range(4, 7):
                restored, loss = step_fn(
                    restored, place_batch(batch, mesh_b))
                resumed_losses.append(float(loss))
        finally:
            ckpt.close()

        # same state, same batches: the resumed run tracks the uninterrupted
        # one (different mesh split -> different reduction order; tolerance)
        np.testing.assert_allclose(resumed_losses, ref_losses,
                                   rtol=2e-4, atol=2e-4)
        assert resumed_losses[-1] < pre_losses[0], "loss stopped improving"
        evidence["training"] = {
            "checkpoint_mesh": "dp4 x tp2",
            "restore_mesh": "dp2 x tp4 (elastic: orbax reshards)",
            "resumed_from_step": resumed_step,
            "pre_failure_losses": [round(x, 5) for x in pre_losses],
            "uninterrupted_losses": [round(x, 5) for x in ref_losses],
            "resumed_losses": [round(x, 5) for x in resumed_losses],
            "max_divergence": float(np.max(np.abs(
                np.asarray(resumed_losses) - np.asarray(ref_losses)))),
        }


def main() -> int:
    evidence: dict = {
        "harness": "hack/elastic_gang_demo.py",
        "story": "slice worker dies -> replacement rejoins the same slice "
                 "with its rank repaired -> training resumes from the last "
                 "checkpoint on a different mesh geometry",
    }
    ok = False
    try:
        act1_control_plane(evidence)
        act2_data_plane(evidence)
        ok = True
    except BaseException as exc:
        evidence["error"] = f"{type(exc).__name__}: {exc}"[:2000]
        raise
    finally:
        evidence["ok"] = ok
        (REPO / "ELASTIC_r04.json").write_text(
            json.dumps(evidence, indent=2) + "\n")
        print(json.dumps(evidence, indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
