"""Concurrency regression tests.

Parity: reference pkg/scheduler/register_race_test.go:38-60 — a
health-flapping device racing register() against onDelNode must not corrupt
the node cache; Go runs these under -race, here we hammer the same
interleavings from threads and assert invariants (Python's allocator won't
segfault, but dict/list corruption and lost updates would surface as
assertion failures or exceptions)."""

from __future__ import annotations

import threading

import pytest

from vtpu.device import codec
from vtpu.scheduler.scheduler import Scheduler
from vtpu.util import types as t

from tests.helpers import REGISTER_ANNO, fake_cluster, register_tpu_backend, tpu_pod, v5e_devices

ROUNDS = 60


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


def test_register_vs_node_delete_race(cluster):
    """Flapping node registration racing node deletion (reference
    Test_register_NodeCacheConcurrency)."""
    client, sched = cluster
    errors: list[BaseException] = []

    def flap():
        try:
            for i in range(ROUNDS):
                # health-flap: re-register with devices, then with none
                client.patch_node_annotations(
                    "node-a", {REGISTER_ANNO: codec.encode_node_devices(
                        v5e_devices(8, prefix="a"))})
                sched.register_from_node_annotations()
                client.patch_node_annotations("node-a", {REGISTER_ANNO: None})
                sched.register_from_node_annotations()
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    def deleter():
        try:
            for i in range(ROUNDS):
                sched.on_del_node({"metadata": {"name": "node-a"}})
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=flap), threading.Thread(target=deleter)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errors, errors
    # cache still coherent: node-b unaffected, node-a either present or absent
    usage = sched.inspect_all_nodes_usage()
    assert "node-b" in usage and len(usage["node-b"]["TPU"]) == 8


def test_concurrent_filters_never_overcommit(cluster):
    """Parallel Filter calls on one scheduler must not place more than
    count=4 sharers on any chip (the in-memory bookkeeping race)."""
    client, sched = cluster
    errors: list[BaseException] = []

    def submit(i: int):
        try:
            pod = client.put_pod(tpu_pod(f"p{i}", tpumem=2048))
            sched.filter({"Pod": pod, "NodeNames": ["node-a", "node-b"]})
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=submit, args=(i,)) for i in range(24)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errors, errors
    for node, vendors in sched.inspect_all_nodes_usage().items():
        for dev in vendors["TPU"]:
            assert dev.used <= dev.count, f"{node}/{dev.id} overshared: {dev.used}"
            assert dev.usedmem <= dev.totalmem, f"{node}/{dev.id} HBM overcommitted"


def test_informer_replay_vs_filter_race(cluster):
    """Pod add/delete informer events racing Filter decisions keep the
    PodManager and QuotaManager consistent (reference onAddPod/onDelPod)."""
    client, sched = cluster
    stop = threading.Event()
    errors: list[BaseException] = []

    def churn():
        try:
            i = 0
            while not stop.is_set():
                pod = tpu_pod(f"churn{i}", tpumem=1024, ns="churn")
                pod = client.put_pod(pod)
                sched.filter({"Pod": pod, "NodeNames": ["node-a", "node-b"]})
                client.delete_pod("churn", f"churn{i}")
                i += 1
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    workers = [threading.Thread(target=churn) for _ in range(4)]
    for th in workers:
        th.start()
    import time

    time.sleep(2.0)
    stop.set()
    for th in workers:
        th.join()
    assert not errors, errors
    # every churn pod was deleted -> its usage must be fully released
    usage = sched.inspect_all_nodes_usage()
    for vendors in usage.values():
        for dev in vendors["TPU"]:
            assert dev.used == 0, f"leaked usage on {dev.id}: {dev.used}"


def test_concurrent_gang_filters_one_worker_per_host():
    """Multi-host gang invariant under concurrency: N workers filed from N
    threads must land on N DISTINCT hosts of one slice even when every
    Filter runs simultaneously (the filter lock serializes snapshot->record,
    and gang state is derived inside it)."""
    from vtpu.device.types import SliceInfo

    client = fake_cluster({f"h{i}": v5e_devices(4, prefix=f"h{i}") for i in range(4)})
    for i in range(4):
        client.patch_node_annotations(
            f"h{i}", {t.NODE_SLICE_ANNO: SliceInfo("fab", i, 4, "v5p-32", "").encode()}
        )
    sched = Scheduler(client)
    register_tpu_backend(quota=sched.quota_manager)
    sched.start(register_interval=3600)
    try:
        gang = {t.SLICE_WORKERS_ANNO: "4",
                "pod-group.scheduling.sigs.k8s.io/name": "racegang"}
        results: dict[str, list] = {}
        errors: list = []

        def file_worker(i: int) -> None:
            try:
                pod = client.put_pod(tpu_pod(f"w{i}", tpu=4, annotations=gang))
                r = sched.filter({"Pod": pod, "NodeNames": [f"h{j}" for j in range(4)]})
                results[f"w{i}"] = r["NodeNames"]
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        workers = [threading.Thread(target=file_worker, args=(i,)) for i in range(4)]
        for th in workers:
            th.start()
        for th in workers:
            th.join()
        assert not errors, errors
        placed = [r[0] for r in results.values() if r]
        assert len(placed) == 4 and len(set(placed)) == 4, results
        # gang-own ranks assigned under the same lock: exactly 0..3, no dupes
        ranks = sorted(
            int(client.get_pod("default", f"w{i}")["metadata"]["annotations"][
                t.GANG_RANK_ANNO])
            for i in range(4)
        )
        assert ranks == [0, 1, 2, 3], ranks
    finally:
        sched.stop()


# ---------------------------------------------------------------- churn fuzzer


def _fuzz_live_gangs(client) -> dict:
    """Live gang membership from the cluster's pods (what a rebooted
    scheduler would derive): {(ns, group): [(pod, node, rank, slice_id,
    mega_slice)]}. Only pods Filter actually placed count as live."""
    gangs: dict = {}
    for pod in client.list_pods():
        annos = pod.get("metadata", {}).get("annotations") or {}
        group = annos.get("pod-group.scheduling.sigs.k8s.io/name")
        node = annos.get(t.ASSIGNED_NODE)
        if not group or not node:
            continue
        key = (pod["metadata"].get("namespace", "default"), group)
        gangs.setdefault(key, []).append({
            "pod": pod["metadata"]["name"],
            "node": node,
            "rank": int(annos.get(t.GANG_RANK_ANNO, -1)),
            "mega": annos.get(t.MEGASCALE_SLICE_ID_ANNO),
            "workers": int(annos.get(t.SLICE_WORKERS_ANNO, 0)),
            "slices_wanted": int(annos.get(t.NUM_SLICES_ANNO, 1)),
        })
    return gangs


def _fuzz_check_invariants(client, sched, slice_of: dict,
                           corrupted: dict | None = None) -> None:
    """The properties churn must never break, derived from cluster truth:
    rank uniqueness, slice cohesion, bounded multislice spread, and no
    overcommitted / negative device usage. Gangs the fuzzer deliberately
    damaged (``corrupted``) keep their injected rank anomaly — the
    scheduler refuses them rather than rewriting live pods — so only their
    rank checks are relaxed; cohesion and usage invariants still hold."""
    corrupted = corrupted or {}
    for (ns, group), members in _fuzz_live_gangs(client).items():
        workers = members[0]["workers"]
        by_scope: dict = {}
        for m in members:
            if group not in corrupted:
                assert 0 <= m["rank"] < workers, (group, m)
            scope = m["mega"] if m["slices_wanted"] > 1 else "solo"
            by_scope.setdefault(scope, []).append(m)
        for scope, ms in by_scope.items():
            ranks = [m["rank"] for m in ms]
            if group not in corrupted:
                assert len(ranks) == len(set(ranks)), \
                    f"gang {group} scope {scope} duplicate ranks: {ms}"
            slices = {slice_of.get(m["node"]) for m in ms}
            assert len(slices) == 1 and None not in slices, \
                f"gang {group} scope {scope} spans slices {slices}: {ms}"
            hosts = [m["node"] for m in ms]
            assert len(hosts) == len(set(hosts)), \
                f"gang {group} scope {scope} doubled a host: {ms}"
        if members[0]["slices_wanted"] > 1:
            megas = {m["mega"] for m in members}
            assert len(megas) <= members[0]["slices_wanted"], \
                f"gang {group} uses {megas}"
    for node, vendors in sched.inspect_all_nodes_usage().items():
        for dev in vendors.get("TPU", []):
            assert 0 <= dev.used <= dev.count, f"{node}/{dev.id}: {dev.used}"
            assert 0 <= dev.usedmem <= dev.totalmem, f"{node}/{dev.id} HBM"


# ------------------------------------------- serving-engine failure races


def test_cancel_vs_disagg_claim_single_typed_terminal():
    """ISSUE 12 satellite: cancel/shed racing the disagg worker claim
    path. Client threads cancel requests at random points while the
    prefill worker claims, prefills and hands off — whatever interleaving
    wins, every request ends with EXACTLY ONE typed Terminal sentinel
    (finish() is idempotent across the worker and the loop) and a status
    from the legal set; the conftest leak_check fixture then audits that
    nothing any path held leaked."""
    import queue as _queue
    import time

    import jax
    import jax.numpy as jnp

    from vtpu.models import ModelConfig, init_params
    from vtpu.serving import (
        DisaggConfig, ServingConfig, ServingEngine, Status, Terminal)

    cfg = ModelConfig(
        vocab=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq=64, head_dim=16, dtype=jnp.float32, use_pallas=False)
    params = init_params(jax.random.key(0), cfg)
    eng = ServingEngine(params, cfg, ServingConfig(
        slots=2, prefill_buckets=(16,), max_new_tokens=4,
        prefill_chunk=16, kv_page=8,
        disagg=DisaggConfig(prefill_workers=2)))
    eng.start()
    try:
        import random

        rng = random.Random(5)
        reqs = []
        cancellers = []
        for i in range(16):
            prompt = [int(t) for t in jax.random.randint(
                jax.random.key(100 + i), (12,), 1, cfg.vocab, jnp.int32)]
            req = eng.submit(prompt, max_new_tokens=4)
            reqs.append(req)
            if rng.random() < 0.5:
                delay = rng.random() * 0.02
                th = threading.Thread(
                    target=lambda r=req, d=delay: (time.sleep(d),
                                                   r.cancel(), r.cancel()))
                th.start()
                cancellers.append(th)
        for th in cancellers:
            th.join()
        for req in reqs:
            list(req.stream())
    finally:
        eng.stop()
    for req in reqs:
        assert req.status in (Status.OK, Status.CANCELLED), req.status
        # exactly one sentinel ever reached the queue: stream() consumed
        # it, so anything left is a double-delivery bug
        leftovers = []
        while True:
            try:
                leftovers.append(req.out.get_nowait())
            except _queue.Empty:
                break
        assert not [x for x in leftovers if isinstance(x, Terminal)], \
            f"request {req.rid} received a second terminal: {leftovers}"


def test_fleet_drain_vs_submit_race():
    """ISSUE 14 satellite: drain() flips ``_draining`` on the CALLER's
    thread while submit()'s admission check runs on its own — a submit
    landing in the flip gap can enqueue onto a draining engine, and one
    landing just after sees the closed door raise. The fleet resolves
    both halves: raised submits re-route to a survivor, in-gap
    stragglers are migrated off by the drain loop (and by submit()'s own
    post-enqueue rescue, whichever runs first). Under a submit storm
    racing fleet.drain, every stream must end OK and token-equal, the
    drained source must read empty, and no request may hang or
    double-terminate."""
    import queue as _queue
    import time

    import jax
    import jax.numpy as jnp

    from vtpu.models import ModelConfig, init_params
    from vtpu.serving import (
        EngineFleet, FleetConfig, ServingConfig, ServingEngine, Status,
        Terminal)

    cfg = ModelConfig(
        vocab=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq=32, head_dim=16, dtype=jnp.float32, use_pallas=False)
    params = init_params(jax.random.key(0), cfg)
    serving = dict(slots=2, prefill_buckets=(8,), max_new_tokens=4,
                   kv_page=8, kv_swap=8)
    prompt = [int(t) for t in jax.random.randint(
        jax.random.key(7), (5,), 1, cfg.vocab, jnp.int32)]
    ref_eng = ServingEngine(params, cfg, ServingConfig(**serving))
    ref_eng.start()
    try:
        want = list(ref_eng.submit(prompt, max_new_tokens=4).stream())
    finally:
        ref_eng.stop()

    class PinA:
        """Prefer 'a' while it lives, so the storm targets the engine
        being drained (scoring filters draining engines, so the race is
        exactly the submit-vs-flip window)."""

        def score(self, name, signals):
            if signals.draining:
                return None
            return 1.0 if name == "a" else 0.0

    engines = {n: ServingEngine(params, cfg, ServingConfig(**serving))
               for n in ("a", "b")}
    fleet = EngineFleet(engines, FleetConfig(
        probe_interval_ms=5.0, miss_ms=2000.0, route_policy=PinA))
    fleet.start()
    reqs: list = []
    stop_storm = threading.Event()

    def storm():
        while not stop_storm.is_set():
            try:
                reqs.append(fleet.submit(prompt, max_new_tokens=4))
            except RuntimeError:
                # the whole fleet momentarily unroutable is not part of
                # this race (b never drains); surface it
                raise
            time.sleep(0.001)

    th = threading.Thread(target=storm)
    try:
        # seed a few sessions onto 'a' so the drain has live + waiting
        # work to evacuate while the storm lands in its gaps
        reqs.extend(fleet.submit(prompt, max_new_tokens=4)
                    for _ in range(3))
        th.start()
        time.sleep(0.02)  # storm in full flight
        report = fleet.drain("a", timeout=120.0)
        stop_storm.set()
        th.join(timeout=30)
        assert not th.is_alive()
        streams = [list(r.stream()) for r in reqs]
        sa = engines["a"].stats()
    finally:
        stop_storm.set()
        if th.is_alive():  # pragma: no cover - diagnostic path
            th.join(timeout=10)
        fleet.stop()
    assert reqs, "the storm must have submitted something"
    assert all(r.status == Status.OK for r in reqs), \
        [r.status for r in reqs]
    assert all(s == want for s in streams), "a straggler lost tokens"
    # the drained source ended empty: nothing active, parked, queued or
    # holding pool blocks — stragglers were re-routed, not stranded
    assert sa["active_slots"] == 0 and sa["parked_sessions"] == 0
    assert sa["queued"] == 0 and sa["admitting_slots"] == 0
    assert sa["kv_pool_free"] == sa["kv_pool_blocks"]
    assert report["faulted"] == 0
    # exactly one terminal per request ever reached a queue
    for req in reqs:
        leftovers = []
        while True:
            try:
                leftovers.append(req.out.get_nowait())
            except _queue.Empty:
                break
        assert not [x for x in leftovers if isinstance(x, Terminal)], \
            f"request {req.rid} received a second terminal"


@pytest.mark.parametrize("seed", [13])
def test_engine_chaos_seeded_lifecycle_races(seed):
    """Seeded chaos iteration of the races suite (ISSUE 12 satellite):
    a FaultPlan.seeded schedule fires across the pool/swap/dispatch seams
    while client threads submit, cancel, park and resume concurrently.
    The containment contract under test: the engine survives, every
    request reaches a typed terminal, and (via leak_check) the allocator
    free list, host swap pool and slot occupancy return to initial."""
    import random
    import time

    import jax
    import jax.numpy as jnp

    from vtpu.models import ModelConfig, init_params
    from vtpu.serving import FaultPlan, ServingConfig, ServingEngine

    cfg = ModelConfig(
        vocab=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq=64, head_dim=16, dtype=jnp.float32, use_pallas=False)
    params = init_params(jax.random.key(0), cfg)
    plan = FaultPlan.seeded(seed, rates={
        "alloc_exhaust": 0.10, "dispatch_exc": 0.05,
        "swap_d2h_loss": 0.25, "swap_h2d_loss": 0.25})
    eng = ServingEngine(params, cfg, ServingConfig(
        slots=2, prefill_buckets=(16,), max_new_tokens=8,
        prefill_chunk=16, kv_page=8, kv_pool_blocks=8, kv_swap=8,
        shed_queue_depth=6, faults=plan))
    eng.start()
    rng = random.Random(seed)
    errors: list[BaseException] = []

    def client(i: int):
        try:
            prompt = [int(t) for t in jax.random.randint(
                jax.random.key(200 + i), (8,), 1, cfg.vocab, jnp.int32)]
            req = eng.submit(prompt, max_new_tokens=8,
                             priority=rng.randrange(3),
                             deadline_ms=None if rng.random() < 0.8
                             else 2000.0)
            it = iter(req.stream())
            for tok in it:
                roll = rng.random()
                if roll < 0.10:
                    req.cancel()
                elif roll < 0.18:
                    eng.park(req)
                    time.sleep(0.01)
                    eng.resume(req)
            # drain to the terminal regardless of how the loop above exits
            list(it)
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(10)]
    try:
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
        assert not any(th.is_alive() for th in threads), "client wedged"
    finally:
        eng.stop()
    assert not errors, errors
    stats = eng.stats()
    assert stats["decode_ticks"] > 0
    # every injected fault was absorbed by a typed recovery path — the
    # engine never died (clients all drained) and the leak_check fixture
    # verifies the resource ledgers on teardown
    assert stats["faults_injected"] >= 1


@pytest.mark.slow
@pytest.mark.fuzz
@pytest.mark.parametrize("seed", [11, 23, 37, 53, 71])
def test_gang_multislice_churn_fuzzer(seed):
    """Randomized churn over the gang/multislice state machine: workers dying mid-stamp (deleted between Filter and any bind),
    slices deregistering and returning, DCN scores flapping, scheduler
    restarts replaying informer state — across hundreds of iterations the
    refusal paths in _constrain_to_gang_slice/_constrain_multislice may
    reject work but must never corrupt it: no duplicate ranks, no
    cross-slice gangs, no doubled hosts, no leaked or negative
    reservations, and full usage release once every pod is gone."""
    import random

    from vtpu.device.types import DcnScore, SliceInfo

    rng = random.Random(seed)
    n_slices, hosts_per = 250, 4  # 1,000-node fleet
    nodes: dict = {}
    slice_of: dict = {}
    for s in range(n_slices):
        for h in range(hosts_per):
            name = f"s{s}h{h}"
            nodes[name] = v5e_devices(4, prefix=name)
            slice_of[name] = f"sl{s}"
    client = fake_cluster(nodes)
    slice_anno = {}
    for s in range(n_slices):
        for h in range(hosts_per):
            slice_anno[f"s{s}h{h}"] = SliceInfo(
                f"sl{s}", h, hosts_per, "v5e-16", "").encode()
            client.patch_node_annotations(
                f"s{s}h{h}", {t.NODE_SLICE_ANNO: slice_anno[f"s{s}h{h}"]})
    sched = Scheduler(client)
    register_tpu_backend(quota=sched.quota_manager)
    sched.start(register_interval=3600)
    pod_seq = [0]
    gangs = [f"g{i}" for i in range(24)] + [f"ms{i}" for i in range(12)]
    deregistered: set = set()
    # groups the fuzzer has deliberately corrupted (stripped or duplicated
    # rank annotations): the scheduler must refuse/repair, never spread the
    # damage; the invariant checker relaxes rank checks for exactly these
    corrupted: dict[str, str] = {}

    def gang_members(group: str) -> list[dict]:
        out = []
        for pod in client.list_pods():
            annos = pod.get("metadata", {}).get("annotations") or {}
            if (annos.get("pod-group.scheduling.sigs.k8s.io/name") == group
                    and annos.get(t.ASSIGNED_NODE)):
                out.append(pod)
        return out

    def submit(group: str) -> bool:
        i = pod_seq[0] = pod_seq[0] + 1
        annos = {"pod-group.scheduling.sigs.k8s.io/name": group,
                 t.SLICE_WORKERS_ANNO: str(hosts_per)}
        if group.startswith("ms"):
            annos[t.SLICE_WORKERS_ANNO] = "2"
            annos[t.NUM_SLICES_ANNO] = "2"
        pod = client.put_pod(tpu_pod(f"{group}-p{i}", tpu=4, annotations=annos))
        # candidate bias: a pinned gang can only extend onto its own slice's
        # remaining hosts — pure uniform 24-of-1000 sampling would include
        # one with ~7% probability and gangs would never fill (measured),
        # leaving the full-gang refusal paths untested
        anchors = {
            slice_of[(p["metadata"]["annotations"] or {})[t.ASSIGNED_NODE]]
            for p in gang_members(group)
        }
        slice_hosts = [n for n in nodes if slice_of[n] in anchors]
        cand = sorted(set(rng.sample(sorted(nodes), 24)) | set(slice_hosts))
        r = sched.filter({"Pod": pod, "NodeNames": cand})
        if not r.get("NodeNames"):
            client.delete_pod("default", f"{group}-p{i}")  # unplaceable
            return False
        if rng.random() < 0.25:
            # died mid-stamp: ranked + assigned, deleted before running
            client.delete_pod("default", f"{group}-p{i}")
        return True

    try:
        for it in range(400):
            op = rng.random()
            if op < 0.55:
                submit(rng.choice(gangs))
            elif op < 0.70:
                placed = [p for p in client.list_pods()
                          if (p["metadata"].get("annotations") or {})
                          .get(t.ASSIGNED_NODE)]
                if placed:
                    victim = rng.choice(placed)
                    client.delete_pod(
                        victim["metadata"].get("namespace", "default"),
                        victim["metadata"]["name"])
            elif op < 0.80:
                s = rng.randrange(n_slices)
                if f"sl{s}" in deregistered:
                    deregistered.discard(f"sl{s}")
                    for h in range(hosts_per):
                        client.patch_node_annotations(
                            f"s{s}h{h}",
                            {t.NODE_SLICE_ANNO: slice_anno[f"s{s}h{h}"]})
                else:
                    deregistered.add(f"sl{s}")
                    for h in range(hosts_per):
                        client.patch_node_annotations(
                            f"s{s}h{h}", {t.NODE_SLICE_ANNO: None})
                sched.register_from_node_annotations()
            elif op < 0.85:
                name = rng.choice(sorted(nodes))
                flap = None if rng.random() < 0.4 else DcnScore(
                    peer=rng.choice(sorted(nodes)),
                    bw_mbps=rng.randrange(1, 10000),
                    rtt_us=rng.randrange(100, 50000)).encode()
                client.patch_node_annotations(name, {t.NODE_DCN_ANNO: flap})
                sched.register_from_node_annotations()
            elif op < 0.90:
                # corruption injection: crash-shaped annotation damage. The
                # scheduler's own refusal/repair branches
                # (_constrain_to_gang_slice duplicate-rank refuse + legacy
                # repair, scheduler.py:536-605) are the subject here.
                group = rng.choice(gangs)
                members = gang_members(group)
                if members and group not in corrupted:
                    victim = rng.choice(members)
                    ns_v = victim["metadata"].get("namespace", "default")
                    # a duplicate is only invalid within one rank scope:
                    # the whole gang for single-slice, a mega-slice for
                    # multislice (ranks legally repeat across slices)
                    scope_of = lambda m: (m["metadata"]["annotations"]  # noqa: E731
                                          .get(t.MEGASCALE_SLICE_ID_ANNO))
                    peers = [m for m in members if m is not victim
                             and scope_of(m) == scope_of(victim)]
                    if rng.random() < 0.5 or not peers:
                        kind = "strip"  # lost rank stamp (crash mid-assign)
                        client.patch_pod_annotations(
                            ns_v, victim["metadata"]["name"],
                            {t.GANG_RANK_ANNO: None})
                    else:
                        kind = "dup"  # two live workers share a rank scope
                        other = rng.choice(peers)
                        client.patch_pod_annotations(
                            ns_v, victim["metadata"]["name"],
                            {t.GANG_RANK_ANNO: other["metadata"][
                                "annotations"][t.GANG_RANK_ANNO]})
                    corrupted[group] = kind
                    placed = submit(group)
                    if kind == "dup":
                        # duplicate ranks are unrepairable: extension must
                        # be refused, and the damage must not spread
                        assert not placed, \
                            f"gang {group} extended over duplicate ranks"
                    else:
                        # stripped rank: the repair path stamps the live
                        # member's physical rank; whether or not the new
                        # pod also fit, the victim must be whole again
                        repaired = client.get_pod(
                            ns_v, victim["metadata"]["name"])
                        anno = (repaired["metadata"].get("annotations")
                                or {}).get(t.GANG_RANK_ANNO)
                        if anno is not None:
                            corrupted.pop(group, None)
            else:
                # crash-restart: a fresh scheduler must rebuild the same
                # truth from the cluster (informer replay + repair paths)
                sched.stop()
                sched = Scheduler(client)
                register_tpu_backend(quota=sched.quota_manager)
                sched.start(register_interval=3600)
            if it % 20 == 0:
                # un-flag corrupted gangs whose injected anomaly is GONE
                # (damaged pods deleted, gang legitimately regrown): leaving
                # the marker would permanently disable rank checking for
                # them and erode coverage as the run progresses
                for group in list(corrupted):
                    scopes: dict = {}
                    healthy = True
                    for m in gang_members(group):
                        annos_m = m["metadata"]["annotations"]
                        r = annos_m.get(t.GANG_RANK_ANNO)
                        if r is None:
                            healthy = False
                            break
                        scope = annos_m.get(t.MEGASCALE_SLICE_ID_ANNO)
                        if int(r) in scopes.setdefault(scope, set()):
                            healthy = False
                            break
                        scopes[scope].add(int(r))
                    if healthy:
                        corrupted.pop(group)
                # the STATIC physical topology: a slice whose registration
                # annotation flapped away still physically hosts its live
                # members (the scheduler merely refuses to extend gangs
                # there), so cross-slice cohesion is judged against the
                # fixed map, not the registration state
                _fuzz_check_invariants(client, sched, slice_of, corrupted)
        # teardown: delete everything -> zero leaked usage
        for pod in list(client.list_pods()):
            client.delete_pod(pod["metadata"].get("namespace", "default"),
                              pod["metadata"]["name"])
        for vendors in sched.inspect_all_nodes_usage().values():
            for dev in vendors.get("TPU", []):
                assert dev.used == 0 and dev.usedmem == 0, dev
    finally:
        sched.stop()
