"""Device plugin: rm enumeration, gRPC surface over a unix socket, and the
full control-plane slice (scheduler Filter/Bind -> plugin Allocate), mirroring
the reference's plugin tests + e2e pod suite shape."""

import os
import threading

import grpc
import pytest

from vtpu.device import codec
from vtpu.plugin import envs
from vtpu.plugin.api import deviceplugin_pb2 as pb
from vtpu.plugin.api.grpc_api import DevicePluginStub
from vtpu.plugin.register import Registrar
from vtpu.plugin.rm import TpuResourceManager, discover_chips
from vtpu.plugin.server import PluginConfig, PluginServer, TpuDevicePlugin
from vtpu.scheduler.scheduler import Scheduler
from vtpu.util import types as t
from vtpu.util.k8sclient import FakeKubeClient, annotations

from tests.helpers import fake_cluster, register_tpu_backend, tpu_pod, v5e_devices


@pytest.fixture
def mock_chips(monkeypatch):
    monkeypatch.setenv("VTPU_MOCK_DEVICES", "8")
    monkeypatch.setenv("VTPU_MOCK_DEVMEM", "16384")
    return discover_chips(split_count=4, hostname="host1")


def test_discover_mock_chips(mock_chips):
    assert len(mock_chips) == 8
    assert mock_chips[0].uuid == "host1-tpu-0"
    assert mock_chips[0].devmem == 16384
    assert {c.numa for c in mock_chips} == {0, 1}
    assert mock_chips[7].ici.x == 3 and mock_chips[7].ici.y == 1


def test_rm_replicas_and_health(mock_chips):
    rm = TpuResourceManager(mock_chips, split_count=4)
    ids = rm.replica_ids()
    assert len(ids) == 32
    assert ids[0][0] == "host1-tpu-0::0"
    assert rm.chip_uuid_of("host1-tpu-0::3") == "host1-tpu-0"
    fired = []
    rm.on_health_change(lambda: fired.append(1))
    rm.set_health("host1-tpu-0", False)
    assert fired and not rm.replica_ids()[0][1]
    rm.set_health("host1-tpu-0", False)  # no change, no event
    assert len(fired) == 1


def test_registrar_publishes_annotations(mock_chips):
    client = FakeKubeClient()
    client.put_node({"metadata": {"name": "n1"}})
    rm = TpuResourceManager(mock_chips, split_count=4)
    Registrar(client, rm, "n1").register_once()
    annos = annotations(client.get_node("n1"))
    devices = codec.decode_node_devices(annos["vtpu.io/node-tpu-register"])
    assert len(devices) == 8 and devices[0].count == 4
    assert annos["vtpu.io/node-handshake-tpu"].startswith("Reported_")
    # TPU node labeled on register, label withdrawn when inventory empties
    # (reference e2e node suite test_node.go:57-91)
    assert client.get_node("n1")["metadata"]["labels"]["vtpu.io/tpu-node"] == "true"
    for chip in list(rm.chips):
        rm.set_health(chip.uuid, False)
    rm.chips.clear()
    Registrar(client, rm, "n1").register_once()
    assert "vtpu.io/tpu-node" not in client.get_node("n1")["metadata"].get("labels", {})


@pytest.fixture
def served_plugin(mock_chips, tmp_path):
    client = fake_cluster({"host1": v5e_devices(8, prefix="host1-tpu")})
    rm = TpuResourceManager(mock_chips, split_count=4)
    config = PluginConfig(node_name="host1", hook_path=str(tmp_path / "hook"))
    plugin = TpuDevicePlugin(rm, client, config)
    server = PluginServer(plugin, str(tmp_path / "vtpu.sock"))
    server.start()
    channel = grpc.insecure_channel(f"unix://{server.socket_path}")
    yield client, rm, DevicePluginStub(channel), config
    channel.close()
    server.stop(grace=0.1)


def test_grpc_list_and_watch_and_options(served_plugin):
    _, rm, stub, _ = served_plugin
    opts = stub.GetDevicePluginOptions(pb.Empty())
    assert opts.get_preferred_allocation_available
    stream = stub.ListAndWatch(pb.Empty())
    first = next(stream)
    assert len(first.devices) == 32
    assert first.devices[0].health == "Healthy"
    assert first.devices[0].topology.nodes[0].ID in (0, 1)
    # flip health -> pushed update
    rm.set_health("host1-tpu-2", False)
    second = next(stream)
    sick = [d for d in second.devices if d.ID.startswith("host1-tpu-2::")]
    assert all(d.health == "Unhealthy" for d in sick) and len(sick) == 4


def test_grpc_preferred_allocation_prefers_adjacent_chips(served_plugin):
    _, rm, stub, _ = served_plugin
    available = [rid for rid, _, _ in rm.replica_ids()]
    resp = stub.GetPreferredAllocation(pb.PreferredAllocationRequest(
        container_requests=[pb.ContainerPreferredAllocationRequest(
            available_deviceIDs=available, allocation_size=2)]))
    picked = list(resp.container_responses[0].deviceIDs)
    assert len(picked) == 2
    chips = {rm.chip_uuid_of(r) for r in picked}
    if len(chips) == 2:  # two chips: must be ICI neighbors
        a, b = (rm.chip_by_uuid(u) for u in chips)
        assert a.ici.distance(b.ici) == 1


def test_allocate_full_slice(served_plugin):
    """scheduler Filter+Bind then kubelet Allocate: the minimum end-to-end
    control-plane slice (SURVEY §7)."""
    client, rm, stub, config = served_plugin
    sched = Scheduler(client)
    register_tpu_backend(quota=sched.quota_manager)
    sched.start(register_interval=3600)

    pod = client.put_pod(tpu_pod("infer", tpumem=4096, tpucores=25,
                                 annotations={t.TASK_PRIORITY_ANNO: "1"}))
    result = sched.filter({"Pod": pod, "NodeNames": ["host1"]})
    assert result["NodeNames"] == ["host1"]
    assert sched.bind({"PodName": "infer", "PodNamespace": "default",
                       "Node": "host1"})["Error"] == ""

    resp = stub.Allocate(pb.AllocateRequest(
        container_requests=[pb.ContainerAllocateRequest(devicesIDs=["host1-tpu-0::0"])]))
    assert len(resp.container_responses) == 1
    ctr = resp.container_responses[0]
    env = dict(ctr.envs)
    assert env[envs.ENV_DEVICE_MEMORY_LIMIT.format(index=0)] == "4096m"
    assert env[envs.ENV_CORE_LIMIT] == "25"
    assert env[envs.ENV_TASK_PRIORITY] == "1"
    assert env[envs.ENV_VISIBLE_CHIPS] != ""
    # fractional share on a non-exclusive chip: attach queueing armed
    # (docs/multitenancy.md exclusive-attach fallback)
    assert env[envs.ENV_ATTACH_WAIT] == "120000"
    # no floor configured -> the knob is absent (local-runtime default)
    assert envs.ENV_CHARGE_FLOOR not in env
    mounts = {m.container_path: m.host_path for m in ctr.mounts}
    assert mounts["/etc/ld.so.preload"].endswith("ld.so.preload")
    assert "/usr/local/vtpu/libvtpu.so" in mounts
    # shared-region host dir was created
    region_host_dir = mounts[envs.CONTAINER_CACHE_DIR]
    assert os.path.isdir(region_host_dir)

    stored = client.get_pod("default", "infer")
    annos = annotations(stored)
    assert annos[t.BIND_PHASE] == t.BIND_PHASE_SUCCESS
    assert "vtpu.io/tpu-devices-to-allocate" not in annos  # consumed
    assert "vtpu.io/tpu-devices-allocated" in annos  # durable record
    # node lock released
    assert t.NODE_LOCK_ANNO not in annotations(client.get_node("host1"))
    sched.stop()


def test_allocate_mounts_license_hook_when_present(served_plugin):
    """Operator-provisioned license + validator in the hook dir surface as
    read-only container mounts (reference server.go:712-724)."""
    client, rm, stub, config = served_plugin
    sched = Scheduler(client)
    register_tpu_backend(quota=sched.quota_manager)
    sched.start(register_interval=3600)
    os.makedirs(config.hook_path, exist_ok=True)
    for fname in (envs.LICENSE_FILE, envs.VALIDATOR_BIN):
        with open(os.path.join(config.hook_path, fname), "w") as f:
            f.write("x")
    try:
        pod = client.put_pod(tpu_pod("lic", tpumem=1024))
        assert sched.filter({"Pod": pod, "NodeNames": ["host1"]})["NodeNames"]
        assert sched.bind({"PodName": "lic", "PodNamespace": "default",
                           "Node": "host1"})["Error"] == ""
        resp = stub.Allocate(pb.AllocateRequest(
            container_requests=[pb.ContainerAllocateRequest(
                devicesIDs=["host1-tpu-0::0"])]))
        mounts = {m.container_path: m for m in resp.container_responses[0].mounts}
        lic = mounts[envs.CONTAINER_LICENSE_PATH]
        assert lic.host_path.endswith(envs.LICENSE_FILE) and lic.read_only
        val = mounts[envs.CONTAINER_VALIDATOR_PATH]
        assert val.host_path.endswith(envs.VALIDATOR_BIN) and val.read_only
    finally:
        sched.stop()


def test_allocate_qos_policy_maps_to_core_policy(served_plugin):
    """QoS annotation drives libvtpu's core-utilization policy (reference
    metax qos.go: best-effort never throttles, fixed-share always does)."""
    client, rm, stub, config = served_plugin
    sched = Scheduler(client)
    register_tpu_backend(quota=sched.quota_manager)
    sched.start(register_interval=3600)

    config.qos_enabled = True
    pod = client.put_pod(tpu_pod("be", tpumem=1024,
                                 annotations={t.QOS_POLICY_ANNO: t.QOS_BEST_EFFORT}))
    assert sched.filter({"Pod": pod, "NodeNames": ["host1"]})["NodeNames"] == ["host1"]
    assert sched.bind({"PodName": "be", "PodNamespace": "default",
                       "Node": "host1"})["Error"] == ""
    resp = stub.Allocate(pb.AllocateRequest(
        container_requests=[pb.ContainerAllocateRequest(devicesIDs=["host1-tpu-0::0"])]))
    assert dict(resp.container_responses[0].envs)[envs.ENV_CORE_POLICY] == "disable"
    sched.stop()


def test_cdi_spec_and_qualified_devices(mock_chips, tmp_path):
    """CDI mode: spec file on disk + qualified names in Allocate (reference
    nvinternal/cdi/cdi.go)."""
    import json

    from vtpu.plugin import cdi
    from vtpu.plugin.server import PluginConfig, TpuDevicePlugin

    path = cdi.write_spec(cdi.generate_spec(mock_chips, "/usr/local/vtpu"),
                          str(tmp_path / "cdi"))
    spec = json.loads(open(path).read())
    assert spec["kind"] == "vtpu.io/tpu"
    assert len(spec["devices"]) == 8
    assert any(m["containerPath"] == "/usr/local/vtpu/libvtpu.so"
               for m in spec["containerEdits"]["mounts"])

    client = fake_cluster({"host1": v5e_devices(8, prefix="host1-tpu")})
    rm = TpuResourceManager(mock_chips, split_count=4)
    plugin = TpuDevicePlugin(rm, client, PluginConfig(
        node_name="host1", hook_path=str(tmp_path / "hook"), cdi_enabled=True))
    sched = Scheduler(client)
    register_tpu_backend(quota=sched.quota_manager)
    sched.start(register_interval=3600)
    pod = client.put_pod(tpu_pod("cdi-pod", tpumem=1024))
    assert sched.filter({"Pod": pod, "NodeNames": ["host1"]})["NodeNames"] == ["host1"]
    assert sched.bind({"PodName": "cdi-pod", "PodNamespace": "default",
                       "Node": "host1"})["Error"] == ""

    class _Req:
        container_requests = [type("C", (), {"devicesIDs": ["host1-tpu-0::0"]})()]

    resp, _done = plugin._allocate_pending(client.get_pod("default", "cdi-pod"), _Req())
    ctr = resp.container_responses[0]
    assert [d.name for d in ctr.cdi_devices] == ["vtpu.io/tpu=host1-tpu-0"]
    assert not ctr.devices  # no raw device paths in CDI mode
    assert all(m.container_path != "/usr/local/vtpu/libvtpu.so" for m in ctr.mounts)
    sched.stop()


def test_allocate_exclusive_repartitions_chip(served_plugin):
    """An exclusive ask pins the chip's operating mode via the dynamic
    repartition path (reference processMigConfigs during Allocate)."""
    client, rm, stub, config = served_plugin
    sched = Scheduler(client)
    register_tpu_backend(quota=sched.quota_manager)
    sched.start(register_interval=3600)
    pod = client.put_pod(tpu_pod("excl", tpu=1, tpucores=100))
    assert sched.filter({"Pod": pod, "NodeNames": ["host1"]})["NodeNames"] == ["host1"]
    assert sched.bind({"PodName": "excl", "PodNamespace": "default",
                       "Node": "host1"})["Error"] == ""
    resp = stub.Allocate(pb.AllocateRequest(
        container_requests=[pb.ContainerAllocateRequest(devicesIDs=["host1-tpu-0::0"])]))
    assert len(resp.container_responses) == 1
    allocated = [c for c in rm.chips if (c.mode or "") == "exclusive"]
    assert len(allocated) == 1  # the assigned chip was pinned exclusive
    # the apply lock was released (monitor resumes)
    from vtpu.plugin.partition import lock_dir_for, lock_held

    assert not lock_held(lock_dir_for(config.hook_path))
    # the host inventory was republished with the new geometry (the
    # monitor's host-level families read it)
    import json

    with open(os.path.join(config.hook_path, envs.HOST_CHIPS_FILE)) as f:
        inv = {c["uuid"]: c for c in json.load(f)}
    assert inv[allocated[0].uuid]["mode"] == "exclusive"
    sched.stop()


def test_allocate_without_pending_pod_fails(served_plugin):
    _, _, stub, _ = served_plugin
    with pytest.raises(grpc.RpcError) as exc:
        stub.Allocate(pb.AllocateRequest(
            container_requests=[pb.ContainerAllocateRequest(devicesIDs=["x"])]))
    assert exc.value.code() == grpc.StatusCode.FAILED_PRECONDITION


def test_allocate_multi_container_consumes_in_order(served_plugin):
    client, rm, stub, config = served_plugin
    sched = Scheduler(client)
    register_tpu_backend(quota=sched.quota_manager)
    sched.start(register_interval=3600)
    pod = tpu_pod("multi", tpumem=2048)
    pod["spec"]["containers"].append(
        {"name": "second", "resources": {"limits": {"google.com/tpumem": "1024"}}})
    pod = client.put_pod(pod)
    assert sched.filter({"Pod": pod, "NodeNames": ["host1"]})["NodeNames"] == ["host1"]
    assert sched.bind({"PodName": "multi", "PodNamespace": "default",
                       "Node": "host1"})["Error"] == ""
    resp = stub.Allocate(pb.AllocateRequest(container_requests=[
        pb.ContainerAllocateRequest(devicesIDs=["a"]),
        pb.ContainerAllocateRequest(devicesIDs=["b"]),
    ]))
    envs0 = dict(resp.container_responses[0].envs)
    envs1 = dict(resp.container_responses[1].envs)
    assert envs0[envs.ENV_DEVICE_MEMORY_LIMIT.format(index=0)] == "2048m"
    assert envs1[envs.ENV_DEVICE_MEMORY_LIMIT.format(index=0)] == "1024m"
    sched.stop()


def test_allocate_charge_floor_passthrough(mock_chips, tmp_path):
    """chargeFloorMs (chart) -> --charge-floor-ms (plugin) -> the Allocate env
    contract, so libvtpu deducts the declared transport floor from duty
    charges on proxied runtimes (docs/protocol.md)."""
    client = fake_cluster({"host1": v5e_devices(8, prefix="host1-tpu")})
    rm = TpuResourceManager(mock_chips, split_count=4)
    config = PluginConfig(node_name="host1", hook_path=str(tmp_path / "hook"),
                          charge_floor_ms=150)
    plugin = TpuDevicePlugin(rm, client, config)
    server = PluginServer(plugin, str(tmp_path / "vtpu.sock"))
    server.start()
    sched = Scheduler(client)
    register_tpu_backend(quota=sched.quota_manager)
    sched.start(register_interval=3600)
    try:
        pod = client.put_pod(tpu_pod("floored", tpumem=2048))
        assert sched.filter({"Pod": pod, "NodeNames": ["host1"]})["NodeNames"]
        assert sched.bind({"PodName": "floored", "PodNamespace": "default",
                           "Node": "host1"})["Error"] == ""
        with grpc.insecure_channel(f"unix://{server.socket_path}") as ch:
            resp = DevicePluginStub(ch).Allocate(pb.AllocateRequest(
                container_requests=[pb.ContainerAllocateRequest(
                    devicesIDs=["host1-tpu-0::0"])]))
        env = dict(resp.container_responses[0].envs)
        assert env[envs.ENV_CHARGE_FLOOR] == "150"
    finally:
        sched.stop()
        server.stop(grace=0.1)


def test_allocate_init_container_slot(served_plugin):
    """An init container's device ask allocates correctly —
    its decision slot is first (kubelet allocates init containers before app
    ones), and the container response is built for the INIT container's
    name (per-container shared-region dir)."""
    client, rm, stub, config = served_plugin
    sched = Scheduler(client)
    register_tpu_backend(quota=sched.quota_manager)
    sched.start(register_interval=3600)

    pod = client.put_pod(tpu_pod("initalloc", init_limits={"google.com/tpumem": "2048"}))
    result = sched.filter({"Pod": pod, "NodeNames": ["host1"]})
    assert result["NodeNames"] == ["host1"]
    assert sched.bind({"PodName": "initalloc", "PodNamespace": "default",
                       "Node": "host1"})["Error"] == ""

    resp = stub.Allocate(pb.AllocateRequest(
        container_requests=[pb.ContainerAllocateRequest(devicesIDs=["host1-tpu-0::0"])]))
    assert len(resp.container_responses) == 1
    ctr = resp.container_responses[0]
    env = dict(ctr.envs)
    assert env[envs.ENV_DEVICE_MEMORY_LIMIT.format(index=0)] == "2048m"
    # the response was built for the init container, not "main"
    mounts = {m.container_path: m.host_path for m in ctr.mounts}
    assert "init0" in mounts[envs.CONTAINER_CACHE_DIR]
    annos = annotations(client.get_pod("default", "initalloc"))
    assert "vtpu.io/tpu-devices-to-allocate" not in annos  # consumed
    sched.stop()


def test_allocate_two_calls_keep_container_pairing(served_plugin):
    """Init AND app container both request devices: kubelet issues one
    Allocate per container. Consumption must EMPTY used slots in place (not
    drop them) so the second call still maps its slot index to the right
    container's name/region dir."""
    client, rm, stub, config = served_plugin
    sched = Scheduler(client)
    register_tpu_backend(quota=sched.quota_manager)
    sched.start(register_interval=3600)

    pod = tpu_pod("twostep", tpumem=1024,
                  init_limits={"google.com/tpumem": "2048"})
    pod = client.put_pod(pod)
    result = sched.filter({"Pod": pod, "NodeNames": ["host1"]})
    assert result["NodeNames"] == ["host1"]
    assert sched.bind({"PodName": "twostep", "PodNamespace": "default",
                       "Node": "host1"})["Error"] == ""

    # call 1: the init container (kubelet allocates init containers first)
    r1 = stub.Allocate(pb.AllocateRequest(
        container_requests=[pb.ContainerAllocateRequest(devicesIDs=["host1-tpu-0::0"])]))
    m1 = {m.container_path: m.host_path for m in r1.container_responses[0].mounts}
    assert "init0" in m1[envs.CONTAINER_CACHE_DIR]
    e1 = dict(r1.container_responses[0].envs)
    assert e1[envs.ENV_DEVICE_MEMORY_LIMIT.format(index=0)] == "2048m"
    # mid-sequence: still allocating, node lock still HELD — releasing
    # between container calls would let another pod bind and steal
    # get_pending_pod (newest bind-time wins)
    annos = annotations(client.get_pod("default", "twostep"))
    assert annos[t.BIND_PHASE] == t.BIND_PHASE_ALLOCATING
    assert t.NODE_LOCK_ANNO in annotations(client.get_node("host1"))

    # call 2: the app container — must NOT inherit the init slot's identity
    r2 = stub.Allocate(pb.AllocateRequest(
        container_requests=[pb.ContainerAllocateRequest(devicesIDs=["host1-tpu-0::1"])]))
    m2 = {m.container_path: m.host_path for m in r2.container_responses[0].mounts}
    assert "main" in m2[envs.CONTAINER_CACHE_DIR]
    e2 = dict(r2.container_responses[0].envs)
    assert e2[envs.ENV_DEVICE_MEMORY_LIMIT.format(index=0)] == "1024m"

    annos = annotations(client.get_pod("default", "twostep"))
    assert "vtpu.io/tpu-devices-to-allocate" not in annos  # fully consumed
    assert annos[t.BIND_PHASE] == t.BIND_PHASE_SUCCESS
    assert t.NODE_LOCK_ANNO not in annotations(client.get_node("host1"))
    sched.stop()
