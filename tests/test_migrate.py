"""Live session migration across engines (ISSUE 13 tentpole).

Fast tier. The contract under test, layered like the change:

- migrate() is LOSSLESS: a session moved mid-stream resumes on the
  destination at exactly its next token — the migrated stream is
  token-identical to a stay-put run, for resident payload transfers,
  host-tier-spilled sources, waiting-request requeues, and under a
  ('tp',) head-sharded mesh (the staging pair moves per-chip shards);
- ZERO COPIES beyond the one D2H/H2D each side already pays for swap:
  stats()["migration_copies"] == 0 on both engines, payload bytes
  counted on the migrate_{out,in}_bytes flow counters;
- crash recovery: a source dying after the metadata handshake
  (migrate_src_death) or a payload lost in transit (migrate_payload_loss)
  rebuilds the session on the destination from token history via the
  recompute-on-fault prefill path — token-equal; only a session that can
  neither transfer nor rebuild ends FAULTED (typed, never silent);
- races: cancel-racing-migrate releases every block on BOTH engines
  (the conftest leak_check fixture audits every engine a test builds —
  source and destination alike);
- drain(): admission closes, every live/parked/waiting session
  evacuates, and the source reads empty — pool free == capacity, no
  slots, nothing parked or queued.
"""

import dataclasses
import time

import jax
import jax.numpy as jnp
import pytest

from vtpu.models import ModelConfig, init_params
from vtpu.obs.trace import (
    MIGRATE_DST_SEQUENCE,
    MIGRATE_SRC_SEQUENCE,
    subsequence,
)
from vtpu.serving import (
    FaultPlan,
    FaultSpec,
    MigrationError,
    ServingConfig,
    ServingEngine,
    Status,
    migrate,
)

CFG = ModelConfig(
    vocab=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
    max_seq=32, head_dim=16, dtype=jnp.float32, use_pallas=False,
)
# an int8 pool: the payload carries the quantised planes and their scales,
# and the stream has to go on from them unchanged. Two layers, so that what
# the second reads of the first's cache reaches the logits: the one-layer
# model's stream survives a payload that lost a plane
LAYOUTS = {"exact": CFG,
           "int8": dataclasses.replace(CFG, kv_int8=True, n_layers=2)}
PAGE = 8
STEPS = 8
BASE = dict(slots=2, prefill_buckets=(8,), max_new_tokens=STEPS,
            kv_page=PAGE, prefill_chunk=8, kv_swap=8)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.key(0), CFG)


def _prompt(seed, n=5):
    return [int(t) for t in jax.random.randint(
        jax.random.key(seed), (n,), 1, CFG.vocab, jnp.int32)]


P1, P2, P3 = _prompt(1, 5), _prompt(2, 6), _prompt(3, 5)


def _reference(params, cfg=CFG):
    eng = ServingEngine(params, cfg, ServingConfig(**BASE))
    eng.start()
    try:
        return [list(eng.submit(p, max_new_tokens=STEPS).stream())
                for p in (P1, P2, P3)]
    finally:
        eng.stop()


@pytest.fixture(scope="module")
def refs(params):
    """Stay-put reference streams for P1/P2/P3 (one engine, no moves)."""
    return _reference(params)


def _wait_parked(eng, req, timeout=10.0):
    t0 = time.perf_counter()
    while req not in eng._parked:
        assert req.status is None, "request finished before the park"
        assert time.perf_counter() - t0 < timeout, "park never landed"
        time.sleep(0.002)


# ~10 ms a token on every source. The engine decodes whether or not the
# client reads: on a loaded machine an unthrottled stream of 8 tokens ends
# between a test's head reads and its park or its migrate(), and leaves
# nothing to move ("request finished before the park", a path "completed").
THROTTLE = FaultSpec("delayed_fetch", at=0, count=100000, arg=0.01)


def _pair(params, src_kw=None, dst_kw=None, cfg=CFG):
    src_kw = {"faults": FaultPlan([THROTTLE]), **(src_kw or {})}
    src = ServingEngine(params, cfg, ServingConfig(**{**BASE, **src_kw}))
    dst = ServingEngine(params, cfg, ServingConfig(**{**BASE, **(dst_kw or {})}))
    src.start()
    dst.start()
    return src, dst


def _pools_clean(*engines):
    for eng in engines:
        s = eng.stats()
        assert s["kv_pool_free"] == s["kv_pool_blocks"]
        assert s["parked_sessions"] == 0
        if s["swap_host_blocks"]:
            assert s["swap_host_free"] == s["swap_host_blocks"]


# ------------------------------------------------------------- happy path


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_migrate_mid_stream_token_equal(params, refs, layout):
    """The tentpole contract: a session migrated mid-stream resumes at
    exactly its next token (resident payload path — one D2H snapshot on
    the source, one staged H2D on the destination, a fused-row remap at
    resume), with the zero-extra-copy counter at 0 on both engines and
    the handshake visible in both traces. Over the exact pool and the
    int8 one."""
    cfg = LAYOUTS[layout]
    if cfg is not CFG:
        params = init_params(jax.random.key(0), cfg)
        refs = _reference(params, cfg)
    src, dst = _pair(params, cfg=cfg)
    try:
        r = src.submit(P1, max_new_tokens=STEPS)
        it = r.stream()
        got = [next(it), next(it)]
        rep = migrate(r, src, dst)
        got += list(it)
        assert got == refs[0]
        assert rep["path"] == "resident" and rep["bytes"] > 0
        ss, ds = src.stats(), dst.stats()
        assert ss["migrations_out"] == 1 and ds["migrations_in"] == 1
        assert ss["migrate_out_bytes"] == ds["migrate_in_bytes"] > 0
        assert ss["migration_copies"] == 0 and ds["migration_copies"] == 0
        # the source holds nothing of the session anymore; the stream
        # ended OK on the destination
        assert r.status == Status.OK
        assert ss["parked_sessions"] == 0
        assert ss["kv_pool_free"] == ss["kv_pool_blocks"]
        src_events = [e["event"] for e in src.trace.events()]
        dst_events = [e["event"] for e in dst.trace.events()]
        assert subsequence(MIGRATE_SRC_SEQUENCE, src_events)
        assert subsequence(MIGRATE_DST_SEQUENCE, dst_events)
    finally:
        src.stop()
        dst.stop()


def test_migrate_while_parked_reads_spilled_payload(params, refs):
    """A session already parked AND evicted to the source's host tier
    migrates without touching the device for its spilled pages (their
    D2H already happened at eviction): the payload is read from host
    memory, the source host pool frees, and the stream stays
    token-equal."""
    src, dst = _pair(params, src_kw=dict(kv_pool_blocks=2))
    try:
        r1 = src.submit(P1, max_new_tokens=STEPS)
        it1 = r1.stream()
        got1 = [next(it1)]
        src.park(r1)
        _wait_parked(src, r1)
        # pool of 2: admitting P2 evicts the parked session to the host
        # tier (the overcommit machinery, unchanged)
        r2 = src.submit(P2, max_new_tokens=STEPS)
        got2 = list(r2.stream())
        t0 = time.perf_counter()
        while src.stats()["evicted_blocks"] == 0:
            assert time.perf_counter() - t0 < 10, "eviction never happened"
            time.sleep(0.002)
        rep = migrate(r1, src, dst)
        got1 += list(it1)
        assert got1 == refs[0] and got2 == refs[1]
        assert rep["path"] == "resident"
        s = src.stats()
        assert s["swap_out_bytes"] > 0  # the eviction spilled...
        assert s["swap_host_free"] == s["swap_host_blocks"]  # ...and freed
        _pools_clean(src, dst)
    finally:
        src.stop()
        dst.stop()


def test_migrate_of_waiting_request_requeues(params, refs):
    """A request still in the source's waiting line migrates as metadata
    only (no pages exist yet) and re-queues through the destination's
    ordinary admission — stream token-equal to a direct submit."""
    src, dst = _pair(params, src_kw=dict(slots=1))
    try:
        r0 = src.submit(P1, max_new_tokens=STEPS)  # holds the only slot
        rw = src.submit(P3, max_new_tokens=STEPS)  # waits
        rep = migrate(rw, src, dst)
        assert rep["path"] == "requeue" and rep["bytes"] == 0
        assert list(rw.stream()) == refs[2]
        list(r0.stream())
        assert dst.stats()["migrations_in"] == 1
    finally:
        src.stop()
        dst.stop()


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs 2 virtual devices")
def test_migrate_tp2_head_shard_roundtrip():
    """Under a ('tp',) mesh the payload snapshot gathers each chip's head
    shard and the install lands pre-sharded (the swap staging discipline,
    pointed across engines): the migrated stream equals the stay-put tp
    run."""
    from vtpu.parallel.mesh import make_axis_mesh

    cfg = ModelConfig(
        vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_seq=32, head_dim=8, dtype=jnp.float32, use_pallas=False,
    )
    tp_params = init_params(jax.random.key(0), cfg)
    mesh = make_axis_mesh("tp", 2)
    p = [int(t) % cfg.vocab for t in _prompt(80, 5)]
    ref = ServingEngine(tp_params, cfg, ServingConfig(**BASE), mesh=mesh)
    ref.start()
    try:
        want = list(ref.submit(p, max_new_tokens=STEPS).stream())
    finally:
        ref.stop()
    src = ServingEngine(tp_params, cfg, ServingConfig(
        **BASE, faults=FaultPlan([THROTTLE])), mesh=mesh)
    dst = ServingEngine(tp_params, cfg, ServingConfig(**BASE), mesh=mesh)
    src.start()
    dst.start()
    try:
        r = src.submit(p, max_new_tokens=STEPS)
        it = r.stream()
        got = [next(it)]
        rep = migrate(r, src, dst)
        got += list(it)
        assert got == want
        assert rep["path"] == "resident"
        ss, ds = src.stats(), dst.stats()
        assert ds["tp"] == 2
        assert ss["migrate_out_bytes"] == ds["migrate_in_bytes"] > 0
        assert ss["migration_copies"] == 0 and ds["migration_copies"] == 0
        _pools_clean(src, dst)
    finally:
        src.stop()
        dst.stop()


# ---------------------------------------------------------- crash recovery


def test_migrate_src_death_rebuilds_from_history(params, refs):
    """The source dies after the metadata handshake (injected seam): the
    destination holds token history but no payload, installs the entry
    dropped, and the recompute-on-fault prefill path rebuilds the KV —
    the stream continues token-equal, no FAULTED terminal."""
    src = ServingEngine(params, CFG, ServingConfig(
        **BASE, faults=FaultPlan([FaultSpec("migrate_src_death", at=0),
                                  THROTTLE])))
    dst = ServingEngine(params, CFG, ServingConfig(**BASE))
    src.start()
    dst.start()
    try:
        r = src.submit(P1, max_new_tokens=STEPS)
        it = r.stream()
        got = [next(it), next(it)]
        rep = migrate(r, src, dst)
        got += list(it)
        assert got == refs[0]
        assert rep["path"] == "recompute" and rep["src_died"]
        assert rep["bytes"] == 0  # the payload never shipped
        ds = dst.stats()
        assert ds["migrate_recomputes"] == 1
        assert ds["fault_recomputes"] == 1  # the prefill rebuild ran
        assert ds["migrate_failures"] == 0 and r.status == Status.OK
        _pools_clean(src, dst)
    finally:
        src.stop()
        dst.stop()


def test_migrate_payload_loss_recomputes_or_faults(params, refs):
    """Payload lost in transit (injected at the destination install):
    a rebuildable session recomputes token-equal; a session the
    destination cannot rebuild (sequence past every prefill route) ends
    with a typed FAULTED terminal — never a silent close, and nothing
    leaks on either engine."""
    # (a) rebuildable: recompute fallback, token-equal
    src = ServingEngine(params, CFG, ServingConfig(
        **BASE, faults=FaultPlan([THROTTLE])))
    dst = ServingEngine(params, CFG, ServingConfig(
        **BASE, faults=FaultPlan([FaultSpec("migrate_payload_loss", at=0)])))
    src.start()
    dst.start()
    try:
        r = src.submit(P2, max_new_tokens=STEPS)
        it = r.stream()
        got = [next(it)]
        rep = migrate(r, src, dst)
        got += list(it)
        assert got == refs[1]
        assert rep["path"] == "recompute"
        assert dst.stats()["migrate_recomputes"] == 1
    finally:
        src.stop()
        dst.stop()
    # (b) unrebuildable: the destination has no chunked prefill and a
    # bucket smaller than the sequence — typed FAULTED, both pools clean
    src = ServingEngine(params, CFG, ServingConfig(
        **BASE, faults=FaultPlan([THROTTLE])))
    dst = ServingEngine(params, CFG, ServingConfig(
        slots=2, prefill_buckets=(8,), max_new_tokens=STEPS, kv_page=PAGE,
        kv_swap=0,
        faults=FaultPlan([FaultSpec("migrate_payload_loss", at=0)])))
    src.start()
    dst.start()
    try:
        r = src.submit(P1, max_new_tokens=STEPS)
        it = r.stream()
        tokens = [next(it) for _ in range(4)]  # seq grows past dst's bucket
        assert len(tokens) == 4
        rep = migrate(r, src, dst)
        assert rep["path"] == "faulted"
        # tokens delivered before the park settled are legitimate (the
        # park is lossless); the typed terminal then ends the stream
        # short of its budget, and nothing after it diverged
        got = tokens + list(it)
        assert got == refs[0][:len(got)] and len(got) < STEPS
        assert r.status == Status.FAULTED
        assert dst.stats()["migrate_failures"] == 1
        assert dst.stats()["faulted_requests"] == 1
        _pools_clean(src, dst)
    finally:
        src.stop()
        dst.stop()


# ------------------------------------------------------------------- races


def test_cancel_racing_migrate_releases_both_engines(params):
    """Cancel landing at any point of the transfer ends the stream with
    its typed terminal and releases every block on BOTH engines (the
    leak_check fixture audits source and destination at teardown; the
    explicit pool asserts here catch it in-test)."""
    src, dst = _pair(params)
    try:
        # (a) cancel before extraction: the source's parked sweep owns it
        r = src.submit(P1, max_new_tokens=STEPS)
        it = r.stream()
        next(it)
        src.park(r)
        _wait_parked(src, r)
        r.cancel()
        rep = migrate(r, src, dst)
        assert rep["path"] in ("cancelled", "gone", "completed")
        assert r.status == Status.CANCELLED
        list(it)  # tokens delivered pre-park drain; the terminal ends it
        # (b) cancel between extraction and install: the destination
        # refuses the install and the stream ends typed (the payload is
        # host bytes by then — nothing device-side to leak)
        r2 = src.submit(P2, max_new_tokens=STEPS)
        it2 = r2.stream()
        next(it2)
        src.park(r2)
        _wait_parked(src, r2)
        from vtpu.serving.migrate import _Ticket, _ask

        out = _ask(src, "migrate_out", _Ticket(r2), 30.0)
        assert out["status"] == "ok"
        r2.cancel()
        res = _ask(dst, "migrate_in",
                   _Ticket(r2, meta=out["meta"], payload=out["payload"]),
                   30.0)
        assert res["path"] == "cancelled"
        assert r2.status == Status.CANCELLED
        _pools_clean(src, dst)
        assert dst.stats()["migrations_in"] == 0
    finally:
        src.stop()
        dst.stop()


def test_migrate_validation_errors(params):
    """Incompatible pairs fail fast on the caller's thread with nothing
    transferred: kv_swap off, mismatched page geometry, self-migration,
    an unstarted destination."""
    eng = ServingEngine(params, CFG, ServingConfig(**BASE))
    eng.start()
    try:
        req = eng.submit(P1, max_new_tokens=STEPS)
        with pytest.raises(MigrationError, match="own engine"):
            migrate(req, eng, eng)
        no_swap = ServingEngine(params, CFG, ServingConfig(
            slots=2, prefill_buckets=(8,), max_new_tokens=STEPS,
            kv_page=PAGE, prefill_chunk=8))
        with pytest.raises(MigrationError, match="kv_swap"):
            migrate(req, eng, no_swap)
        no_swap.stop()
        other_page = ServingEngine(params, CFG, ServingConfig(
            **{**BASE, "kv_page": 4, "prefill_chunk": 8}))
        other_page.start()
        with pytest.raises(MigrationError, match="kv_page mismatch"):
            migrate(req, eng, other_page)
        other_page.stop()
        stopped = ServingEngine(params, CFG, ServingConfig(**BASE))
        with pytest.raises(MigrationError, match="not started"):
            migrate(req, eng, stopped)
        stopped.stop()
        list(req.stream())
    finally:
        eng.stop()


# ------------------------------------------------------------------- drain


def test_drain_evacuates_live_parked_and_waiting(params, refs):
    """ServingEngine.drain(dst): admission closes (submit raises), every
    session — live, parked, waiting — moves to the destination and
    completes there token-equal, and the source reads EMPTY: pool free ==
    capacity, no slots, nothing parked or queued. A session the caller
    abandoned retires with its typed CANCELLED terminal; drain never ends
    a live stream."""
    src, dst = _pair(params, src_kw=dict(slots=2),
                     dst_kw=dict(slots=4, max_new_tokens=STEPS))
    try:
        r1 = src.submit(P1, max_new_tokens=STEPS)
        it1 = r1.stream()
        g1 = [next(it1)]
        r2 = src.submit(P2, max_new_tokens=STEPS)
        it2 = r2.stream()
        g2 = [next(it2)]
        src.park(r1)
        _wait_parked(src, r1)
        r3 = src.submit(P3, max_new_tokens=STEPS)
        rc = src.submit(_prompt(99), max_new_tokens=STEPS)
        rc.cancel()  # explicitly abandoned: typed terminal, never moved
        report = src.drain(dst)
        with pytest.raises(RuntimeError, match="draining"):
            src.submit(P1)
        g1 += list(it1)
        g2 += list(it2)
        g3 = list(r3.stream())
        list(rc.stream())
        # streams that were still mid-flight completed on the destination
        # token-equal; ones that finished on the source during the drain
        # are counted, not moved — either way nothing diverged
        assert g1 == refs[0] and g2 == refs[1] and g3 == refs[2]
        assert rc.status == Status.CANCELLED
        assert report["migrated"] + report["completed"] >= 1
        s = src.stats()
        assert s["active_slots"] == 0 and s["parked_sessions"] == 0
        assert s["queued"] == 0 and s["admitting_slots"] == 0
        assert s["kv_pool_free"] == s["kv_pool_blocks"]
        assert s["swap_host_free"] == s["swap_host_blocks"]
        assert s["draining"] is True
        assert dst.stats()["draining"] is False
        # an evacuation pays what a migration pays and no copy more
        assert s["migration_copies"] == 0
        assert dst.stats()["migration_copies"] == 0
    finally:
        src.stop()
        dst.stop()


def test_drain_with_waiting_prefix_backed_request(params):
    """A prefix-backed request still WAITING cannot migrate (its prefix
    registration lives on the source), and drain must not livelock
    retrying it: it stays on the source until a slot frees (admission
    stays open to already-queued requests), admits, and then migrates
    fine — the prefix content rides the payload, whole-sequence
    private. A direct migrate() of the waiter fails fast with nothing
    transferred."""
    pre = list(range(1, 17))  # two full pages, no COW boundary
    ref = ServingEngine(params, CFG, ServingConfig(**BASE))
    ref.start()
    try:
        ref_pid = ref.register_prefix(pre)
        ref0 = list(ref.submit(P1, max_new_tokens=STEPS).stream())
        ref_p = list(ref.submit([7, 8], max_new_tokens=4,
                                prefix=ref_pid).stream())
    finally:
        ref.stop()
    src, dst = _pair(params, src_kw=dict(slots=1))
    try:
        pid = src.register_prefix(pre)
        r0 = src.submit(P1, max_new_tokens=STEPS)  # holds the only slot
        it0 = r0.stream()
        g0 = [next(it0)]
        rp = src.submit([7, 8], max_new_tokens=4, prefix=pid)
        with pytest.raises(MigrationError, match="prefix"):
            migrate(rp, src, dst)
        report = src.drain(dst)
        g0 += list(it0)
        gp = list(rp.stream())
        assert g0 == ref0 and gp == ref_p
        assert r0.status == Status.OK and rp.status == Status.OK
        assert report["faulted"] == 0
        src.unregister_prefix(pid)
        s = src.stats()
        assert s["active_slots"] == 0 and s["parked_sessions"] == 0
        assert s["queued"] == 0
        assert s["kv_pool_free"] == s["kv_pool_blocks"]
    finally:
        src.stop()
        dst.stop()
