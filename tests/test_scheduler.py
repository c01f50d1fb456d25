"""Scheduler Filter/Bind over a fake cluster — the reference's core test
strategy (scheduler_test.go, score_test.go): fabricate node annotations, run
the extender protocol, assert chosen node + patched annotations."""

import pytest

from vtpu.device.quota import QuotaManager
from vtpu.scheduler.scheduler import Scheduler
from vtpu.util import types as t
from vtpu.util.k8sclient import annotations

from tests.helpers import fake_cluster, register_tpu_backend, tpu_pod, v5e_devices


@pytest.fixture
def cluster():
    client = fake_cluster({
        "node-a": v5e_devices(8, prefix="a"),
        "node-b": v5e_devices(8, prefix="b"),
    })
    sched = Scheduler(client)
    register_tpu_backend(quota=sched.quota_manager)
    sched.start(register_interval=3600)
    yield client, sched
    sched.stop()


def _filter(sched, client, pod, nodes=("node-a", "node-b")):
    pod = client.put_pod(pod)
    return pod, sched.filter({"Pod": pod, "NodeNames": list(nodes)})


def test_filter_picks_node_and_patches_annotations(cluster):
    client, sched = cluster
    pod, result = _filter(sched, client, tpu_pod("p1", tpumem=4096))
    assert result["Error"] == ""
    assert len(result["NodeNames"]) == 1
    winner = result["NodeNames"][0]
    stored = client.get_pod("default", "p1")
    annos = annotations(stored)
    assert annos[t.ASSIGNED_NODE] == winner
    assert "vtpu.io/tpu-devices-to-allocate" in annos
    assert annos["vtpu.io/tpu-devices-to-allocate"].count(",") >= 3
    # usage is visible in the snapshot
    usage = sched.inspect_all_nodes_usage()[winner]["TPU"]
    assert sum(d.usedmem for d in usage) == 4096


def test_filter_binpack_consolidates(cluster):
    client, sched = cluster
    _, r1 = _filter(sched, client, tpu_pod("p1", tpumem=2048))
    _, r2 = _filter(sched, client, tpu_pod("p2", tpumem=2048))
    assert r1["NodeNames"] == r2["NodeNames"]  # same node
    # and same chip (device binpack)
    usage = sched.inspect_all_nodes_usage()[r1["NodeNames"][0]]["TPU"]
    shared = [d for d in usage if d.used == 2]
    assert len(shared) == 1


def test_filter_spread_policy_annotation(cluster):
    client, sched = cluster
    _, r1 = _filter(sched, client, tpu_pod("p1", tpumem=2048))
    pod2 = tpu_pod("p2", tpumem=2048,
                   annotations={t.NODE_SCHEDULER_POLICY_ANNO: t.NODE_POLICY_SPREAD})
    _, r2 = _filter(sched, client, pod2)
    assert r1["NodeNames"] != r2["NodeNames"]


def test_filter_no_fit_reports_reasons(cluster):
    client, sched = cluster
    pod, result = _filter(sched, client, tpu_pod("big", tpu=16))
    assert result["NodeNames"] == []
    assert set(result["FailedNodes"]) == {"node-a", "node-b"}
    assert client.events, "FilteringFailed event expected"
    assert client.events[-1]["reason"] == "FilteringFailed"


def test_filter_non_device_pod_errors(cluster):
    client, sched = cluster
    pod = client.put_pod({"metadata": {"name": "plain", "namespace": "default"},
                          "spec": {"containers": [{"name": "c", "resources": {}}]}})
    result = sched.filter({"Pod": pod, "NodeNames": ["node-a"]})
    assert "no schedulable device" in result["Error"]


def test_bind_locks_node_and_binds(cluster):
    client, sched = cluster
    pod, result = _filter(sched, client, tpu_pod("p1", tpumem=4096))
    winner = result["NodeNames"][0]
    bind_result = sched.bind({"PodName": "p1", "PodNamespace": "default", "Node": winner})
    assert bind_result["Error"] == ""
    assert client.bindings == [("default", "p1", winner)]
    annos = annotations(client.get_pod("default", "p1"))
    assert annos[t.BIND_PHASE] == t.BIND_PHASE_ALLOCATING
    # node lock held by p1
    assert "default,p1" in annotations(client.get_node(winner))[t.NODE_LOCK_ANNO]


def test_bind_contention_releases_and_reports(cluster):
    client, sched = cluster
    _, r1 = _filter(sched, client, tpu_pod("p1", tpumem=1024))
    winner = r1["NodeNames"][0]
    assert sched.bind({"PodName": "p1", "PodNamespace": "default", "Node": winner})["Error"] == ""
    # second pod tries to bind onto the locked node
    _, r2 = _filter(sched, client, tpu_pod("p2", tpumem=1024, annotations={
        t.USE_DEVICE_UUID_ANNO: f"{winner.split('-')[1]}-0"}))
    res = sched.bind({"PodName": "p2", "PodNamespace": "default", "Node": winner})
    assert "locked" in res["Error"]
    # p2's decision was rolled back
    annos = annotations(client.get_pod("default", "p2"))
    assert t.ASSIGNED_NODE not in annos
    assert not sched.pod_manager.has_pod(client.get_pod("default", "p2")["metadata"]["uid"])


def test_bind_pod_group_member_retries_contended_lock(cluster):
    """Gang members queue behind a contended node lock instead of failing
    (reference acquireNodeLocks scheduler.go:794-819)."""
    import threading
    import time as _time

    client, sched = cluster
    sched.node_lock_retry_timeout = 5.0
    _, r1 = _filter(sched, client, tpu_pod("g1", tpumem=1024))
    winner = r1["NodeNames"][0]
    assert sched.bind({"PodName": "g1", "PodNamespace": "default", "Node": winner})["Error"] == ""

    gang_pod = tpu_pod("g2", tpumem=1024,
                       annotations={"scheduling.k8s.io/group-name": "gang-x"})
    _, r2 = _filter(sched, client, gang_pod)

    def release_later():
        _time.sleep(1.0)
        from vtpu.util import nodelock
        nodelock.release_node_lock(client, winner, client.get_pod("default", "g1"))

    releaser = threading.Thread(target=release_later)
    releaser.start()
    res = sched.bind({"PodName": "g2", "PodNamespace": "default", "Node": winner})
    releaser.join()
    assert res["Error"] == ""
    assert ("default", "g2", winner) in client.bindings


def test_bind_pod_group_retry_times_out(cluster):
    client, sched = cluster
    sched.node_lock_retry_timeout = 0.8
    _, r1 = _filter(sched, client, tpu_pod("g1", tpumem=1024))
    winner = r1["NodeNames"][0]
    assert sched.bind({"PodName": "g1", "PodNamespace": "default", "Node": winner})["Error"] == ""
    gang_pod = tpu_pod("g2", tpumem=1024,
                       annotations={"scheduling.k8s.io/group-name": "gang-x"})
    _filter(sched, client, gang_pod)
    res = sched.bind({"PodName": "g2", "PodNamespace": "default", "Node": winner})
    assert "locked" in res["Error"]


def test_pod_delete_frees_usage(cluster):
    client, sched = cluster
    _, result = _filter(sched, client, tpu_pod("p1", tpumem=4096))
    winner = result["NodeNames"][0]
    client.delete_pod("default", "p1")
    usage = sched.inspect_all_nodes_usage()[winner]["TPU"]
    assert sum(d.usedmem for d in usage) == 0


def test_restart_replays_annotations():
    """Annotations are the database: a fresh Scheduler rebuilds usage from
    scheduled pods (reference onAddPod replay)."""
    client = fake_cluster({"node-a": v5e_devices(8, prefix="a")})
    sched = Scheduler(client)
    register_tpu_backend(quota=sched.quota_manager)
    sched.start(register_interval=3600)
    _filter(sched, client, tpu_pod("p1", tpumem=4096))
    sched.stop()

    sched2 = Scheduler(client)
    sched2.start(register_interval=3600)
    usage = sched2.inspect_all_nodes_usage()["node-a"]["TPU"]
    assert sum(d.usedmem for d in usage) == 4096
    sched2.stop()


def test_simulation_path_scores_without_patching(cluster):
    client, sched = cluster
    pod = client.put_pod(tpu_pod("sim", tpumem=1024))
    result = sched.filter({
        "Pod": pod,
        "Nodes": {"Items": [client.get_node("node-a"), client.get_node("node-b")]},
    })
    assert len(result["NodeNames"]) == 1
    assert t.ASSIGNED_NODE not in annotations(client.get_pod("default", "sim"))


def test_handshake_withdraws_dead_agent():
    import vtpu.device.codec as codec
    client = fake_cluster({"node-a": v5e_devices(8, prefix="a")})
    sched = Scheduler(client)
    backend = register_tpu_backend(quota=sched.quota_manager)
    sched.start(register_interval=3600)
    assert "node-a" in sched.inspect_all_nodes_usage()
    # a stale Requesting mark (dead plugin) withdraws the node's devices
    client.patch_node_annotations("node-a", {
        backend.handshake_annotation(): "Requesting_2020-01-01T00:00:00+0000"})
    sched.register_from_node_annotations()
    assert "node-a" not in sched.inspect_all_nodes_usage()
    sched.stop()


def test_filter_retry_does_not_double_count_quota(cluster):
    """Regression: re-Filter of a still-unbound pod supersedes the previous
    decision instead of stacking quota usage."""
    client, sched = cluster
    sched.quota_manager.add_quota({
        "metadata": {"name": "q", "namespace": "default"},
        "spec": {"hard": {"limits.google.com/tpumem": 100000}}})
    pod, _ = _filter(sched, client, tpu_pod("p1", tpumem=4096))
    pod = client.get_pod("default", "p1")
    sched.filter({"Pod": pod, "NodeNames": ["node-a", "node-b"]})  # retry
    used = sched.quota_manager.snapshot()["default"]["google.com/tpumem"]["used"]
    assert used == 4096
    client.delete_pod("default", "p1")
    used = sched.quota_manager.snapshot()["default"]["google.com/tpumem"]["used"]
    assert used == 0


def test_sidecar_before_device_container_keeps_slot_alignment(cluster):
    """Regression: a deviceless container BEFORE the device container still
    occupies annotation slot 0."""
    from vtpu.device import codec as codec_mod
    client, sched = cluster
    pod = tpu_pod("sidecar-first", tpumem=1024)
    pod["spec"]["containers"].insert(0, {"name": "sidecar", "resources": {}})
    pod, result = _filter(sched, client, pod)
    assert result["NodeNames"]
    anno = annotations(client.get_pod("default", "sidecar-first"))[
        "vtpu.io/tpu-devices-to-allocate"]
    slots = codec_mod.decode_pod_single_device(anno)
    assert len(slots) == 2
    assert slots[0] == [] and len(slots[1]) == 1


def test_scheduler_binary_fake_cluster_end_to_end():
    """The real `python -m vtpu.scheduler --fake-cluster` binary: flags parse,
    the HTTP extender serves /healthz + /filter + /metrics over a real socket,
    and SIGTERM exits cleanly."""
    import json
    import signal
    import socket
    import time
    import urllib.request

    from tests.helpers import BinaryUnderTest

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    bin_ = BinaryUnderTest("vtpu.scheduler", ["--fake-cluster", "2",
                                              "--port", str(port)])
    alive = bin_.alive
    try:

        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            alive()
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/healthz", timeout=5) as r:
                    if r.status == 200:
                        break
            except OSError:
                pass
            time.sleep(0.2)
        else:
            raise AssertionError("scheduler never served /healthz")

        pod = tpu_pod("bin-e2e", tpumem=2048)
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/filter",
            data=json.dumps({"Pod": pod, "NodeNames": ["tpu-node-0", "tpu-node-1"]}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=10) as r:
            result = json.loads(r.read())
        assert result["Error"] == "" and len(result["NodeNames"]) == 1, result

        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
            metrics = r.read().decode()
        assert "vtpu_scheduler_filter_seconds" in metrics

        bin_.terminate(signal.SIGTERM, timeout=15)
    finally:
        bin_.cleanup()


def test_filter_lock_free_during_decision_patch(cluster):
    """The decision-annotation PATCH (network I/O against
    a real apiserver) must not run inside the global filter lock. Block one
    pod's patch on an event and prove another pod's whole Filter completes
    while the first is still mid-patch."""
    import threading

    client, sched = cluster
    in_patch = threading.Event()
    release = threading.Event()
    real_patch = client.patch_pod_annotations

    def gated_patch(ns, name, annos):
        if name == "slow":
            in_patch.set()
            assert release.wait(10), "test gate never released"
        return real_patch(ns, name, annos)

    client.patch_pod_annotations = gated_patch
    slow = client.put_pod(tpu_pod("slow", tpumem=1024))
    t_slow = threading.Thread(
        target=sched.filter, args=({"Pod": slow, "NodeNames": ["node-a", "node-b"]},)
    )
    t_slow.start()
    assert in_patch.wait(10), "slow filter never reached its patch"
    try:
        # The slow pod holds NO lock while patching: this filter must finish.
        fast = client.put_pod(tpu_pod("fast", tpumem=1024))
        result = sched.filter({"Pod": fast, "NodeNames": ["node-a", "node-b"]})
        assert result["NodeNames"], result
    finally:
        release.set()
        t_slow.join(10)
    assert not t_slow.is_alive()
    # and the slow decision still landed once released
    assert annotations(client.get_pod("default", "slow"))[t.ASSIGNED_NODE]


def test_filter_patch_failure_rolls_back_reservation(cluster):
    """A failed decision patch must free the reserved devices (and not nuke a
    superseding re-Filter's newer reservation)."""
    client, sched = cluster
    real_patch = client.patch_pod_annotations
    calls = {"n": 0}

    def failing_patch(ns, name, annos):
        calls["n"] += 1
        from vtpu.util.k8sclient import ApiError
        raise ApiError("injected apiserver failure")

    client.patch_pod_annotations = failing_patch
    pod = client.put_pod(tpu_pod("p1", tpumem=4096))
    result = sched.filter({"Pod": pod, "NodeNames": ["node-a", "node-b"]})
    assert "patch failed" in result["Error"]
    assert calls["n"] == 1
    client.patch_pod_annotations = real_patch
    # reservation rolled back: nothing counted against any node
    for node_usage in sched.inspect_all_nodes_usage().values():
        for devs in node_usage.values():
            assert all(d.usedmem == 0 for d in devs)
    # and a clean retry succeeds end to end
    result = sched.filter({"Pod": pod, "NodeNames": ["node-a", "node-b"]})
    assert result["NodeNames"]


def test_filter_init_only_pod_schedules_and_reserves(cluster):
    """A device ask that lives ONLY in an init container must
    schedule (reference Resourcereqs walks init containers first,
    devices.go:611-663). The decision annotation gets one slot per container,
    init rows first, so kubelet's in-order Allocate pairing holds."""
    from vtpu.device import codec

    client, sched = cluster
    pod = tpu_pod("initonly", init_limits={"google.com/tpumem": "4096"})
    pod, result = _filter(sched, client, pod)
    assert result["Error"] == ""
    assert len(result["NodeNames"]) == 1
    annos = annotations(client.get_pod("default", "initonly"))
    slots = codec.decode_pod_single_device(annos["vtpu.io/tpu-devices-to-allocate"])
    assert len(slots) == 2  # [init0, main]
    assert slots[0] and slots[0][0].usedmem == 4096  # init row carries the ask
    assert slots[1] == []  # main row is empty
    usage = sched.inspect_all_nodes_usage()[result["NodeNames"][0]]["TPU"]
    assert sum(d.usedmem for d in usage) == 4096


def test_filter_init_larger_than_main_fits_both_rows(cluster):
    """Init ask larger than the main container's: both rows must fit
    (conservative cumulative fit, like the reference — kubelet may reuse the
    init container's devices, the scheduler doesn't assume it)."""
    from vtpu.device import codec

    client, sched = cluster
    pod = tpu_pod("initbig", tpu=1, init_limits={"google.com/tpu": "2"})
    pod, result = _filter(sched, client, pod)
    assert result["Error"] == ""
    annos = annotations(client.get_pod("default", "initbig"))
    slots = codec.decode_pod_single_device(annos["vtpu.io/tpu-devices-to-allocate"])
    assert [len(s) for s in slots] == [2, 1]  # init row first, then main
    usage = sched.inspect_all_nodes_usage()[result["NodeNames"][0]]["TPU"]
    assert sum(d.used for d in usage) == 3
