"""Cross-host engine fleets over the fabric (ISSUE 18 tentpole).

Fast tier. The organizing claim under test: a fleet member whose engine
lives ACROSS A TRANSPORT is the same fleet member — one routing, drain,
rebalance and failover code path — and the transport's failure modes
map onto the existing supervision ladder without inventing new ones:

- a LINK death is not an ENGINE death: a partition ages the remote's
  beat and walks the same SUSPECT -> DEAD ladder a hung engine would,
  but a heal delivers a fresh pong and hysteresis restores HEALTHY with
  ``failovers == 0``, while the per-session seq + resend protocol
  replays whatever the blip swallowed — tokens are delayed, never
  doubled and never dropped;
- an ENGINE death behind a LIVE link (or a SIGKILLed host process) is
  the ISSUE-14 scenario verbatim: the beat goes stale, the ladder
  declares DEAD, and every stream rebuilds token-equal on a survivor
  from the CLIENT-side mirror ledger (the host's ledger cannot be read
  from a corpse);
- a payload whose checksum fails in transit downgrades the migration to
  the recompute path — never to wrong tokens;
- a protocol-version mismatch is refused TYPED at hello, never a hang.

The conftest ``leak_check`` audits every in-proc engine these tests
build — the loopback host-side engines included (the ``EngineHost``
ping path reaps its own corpses, the host-process analogue of the
fleet's ``_reap``)."""

import threading
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from vtpu.models import ModelConfig, init_params
from vtpu.serving import (
    EngineFleet,
    FaultPlan,
    FleetConfig,
    RoutePolicy,
    ServingConfig,
    ServingEngine,
    Status,
)
from vtpu.serving.fabric import (
    EngineHost,
    ProtocolError,
    connect_host,
    loopback_pair,
    spawn_host,
    tcp_connect,
)
from vtpu.serving.fabric.host import reap_corpse
from vtpu.serving.migrate import MigrationError, _ask, _Ticket, migrate
from vtpu.serving.shed import EngineSignals

MK = dict(vocab=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
          max_seq=32, head_dim=16, dtype=jnp.float32, use_pallas=False)
CFG = ModelConfig(**MK)
PAGE = 8
STEPS = 20
# TWO prefill buckets on purpose: a failed-over or payload-lost session
# rebuilds through the prefill path, and its sequence may have grown
# past the small bucket by the time the rebuild runs — route (8, 32)
# keeps recompute feasible for any point in a STEPS-long stream.
BASE = dict(slots=2, prefill_buckets=(8, 32), max_new_tokens=STEPS,
            kv_page=PAGE, kv_swap=8)
# ladder clocks: KILL declares a silent engine DEAD in ~2 s (test_fleet's
# wide-window rationale); HEAL shrinks the miss window to 500 ms (safe:
# an idle loop still beats every <= ~50 ms) and stretches dead_misses so
# a partitioned link has a ~1.5 s SUSPECT window to heal inside — the
# scenario is reconnect-restores-HEALTHY, not failover.
FC_KILL = dict(probe_interval_ms=5.0, miss_ms=2000.0,
               suspect_misses=2, dead_misses=4)
FC_HEAL = dict(probe_interval_ms=5.0, miss_ms=500.0,
               suspect_misses=2, dead_misses=300)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.key(0), CFG)


def _prompt(seed, n=5):
    return [int(t) for t in jax.random.randint(
        jax.random.key(seed), (n,), 1, CFG.vocab, jnp.int32)]


P1, P2, P3 = _prompt(1, 5), _prompt(2, 6), _prompt(3, 5)


@pytest.fixture(scope="module")
def refs(params):
    """Single-engine reference streams for P1/P2/P3 (greedy decode is
    deterministic, so per-prompt streams are placement-invariant)."""
    eng = ServingEngine(params, CFG, ServingConfig(**{**BASE, "slots": 3}))
    eng.start()
    try:
        return [list(eng.submit(p, max_new_tokens=STEPS).stream())
                for p in (P1, P2, P3)]
    finally:
        eng.stop()


class PinPolicy(RoutePolicy):
    """Route everything to one named engine; survivors rank by name."""

    def __init__(self, name):
        self.name = name

    def score(self, name, signals):
        if signals.draining:
            return None
        return 1.0 if name == self.name else 0.0


def _wait(pred, timeout=30.0, msg="condition"):
    t0 = time.perf_counter()
    while not pred():
        if time.perf_counter() - t0 > timeout:
            raise AssertionError(f"timed out waiting for {msg}")
        time.sleep(0.002)


@pytest.fixture()
def remote_member(request, params):
    """Factory: one started engine behind an in-proc loopback EngineHost,
    proxied as a RemoteEngine. Returns a namespace with the host-side
    engine, the host, the fault ``link``, the client and the proxy."""
    opened = []

    def build(cfg=CFG, prm=None, faults=None, eng_faults=None, host="h0",
              name="r0"):
        eng = ServingEngine(prm if prm is not None else params, cfg,
                            ServingConfig(**BASE, faults=eng_faults))
        eng.start()
        srv = EngineHost({name: eng})
        a, b, link = loopback_pair(faults=faults, delay_s=0.0)
        threading.Thread(target=srv.serve_channel, args=(b,),
                         daemon=True).start()
        client, engines = connect_host(a, host=host)
        t = SimpleNamespace(eng=eng, srv=srv, link=link, client=client,
                            rem=engines[name], host_chan=b)
        opened.append(t)
        return t

    yield build
    for t in opened:
        t.client.close()
        t.srv.stop()


def _member_fleet(params, t, fc, pin="r0"):
    """A 3-member fleet: the remote proxy plus two local engines."""
    engines = {"r0": t.rem,
               "e1": ServingEngine(params, CFG, ServingConfig(**BASE)),
               "e2": ServingEngine(params, CFG, ServingConfig(**BASE))}
    fleet = EngineFleet(engines, FleetConfig(
        **fc, route_policy=PinPolicy(pin)))
    return fleet, engines


# -------------------------------------------------------- token equality


@pytest.mark.parametrize("layout", ["exact", "int8"])
def test_loopback_fleet_token_equal(params, refs, remote_member, layout):
    """A fleet whose pinned member is REMOTE streams byte-identical
    tokens to the in-proc reference — for the exact and int8 pools (the
    wire carries ints; the layout lives host-side)."""
    if layout == "int8":
        cfg = ModelConfig(kv_int8=True, **MK)
        prm = init_params(jax.random.key(0), cfg)
        ref_eng = ServingEngine(prm, cfg, ServingConfig(**BASE))
        ref_eng.start()
        try:
            want = list(ref_eng.submit(P1, max_new_tokens=STEPS).stream())
        finally:
            ref_eng.stop()
    else:
        cfg, prm, want = CFG, params, refs[0]
    t = remote_member(cfg=cfg, prm=prm)
    engines = {"r0": t.rem,
               "e1": ServingEngine(prm, cfg, ServingConfig(**BASE))}
    fleet = EngineFleet(engines, FleetConfig(
        **FC_HEAL, route_policy=PinPolicy("r0")))
    fleet.start()
    try:
        _wait(lambda: t.rem._beat_ns != 0, 60, "remote warm-up beat")
        req = fleet.submit(P1, max_new_tokens=STEPS)
        toks = list(req.stream())
        assert toks == want
        assert req.status == Status.OK
        st = fleet.stats(include_engines=False)
        assert st["failovers"] == 0
        assert st["remote_engines"] == 1
        assert st["fabric_msgs_sent"] > 0 and st["fabric_msgs_recv"] > 0
        # the route hop is host-tagged with the member's host label
        j = fleet.trace.journeys()[req.jid]
        assert [h["kind"] for h in j["hops"]] == ["route"]
        assert j["hops"][0]["host"] == "h0"
        # dcnprobe seam: the heartbeat RTT surfaces on the proxy's signals
        assert t.rem.signals().fabric_rtt_ms is not None
    finally:
        fleet.stop()


# ----------------------------------------------- link death != engine death


def test_partition_suspect_heal_no_failover(params, refs, remote_member):
    """A partitioned link walks the remote into SUSPECT exactly like a
    hung engine; the heal's fresh pong restores HEALTHY with ZERO
    failovers — and a mid-stream partition is survived token-exact: the
    host keeps generating into its outbox, the client detects the seq
    gap on heal and the resend replays it, duplicates dropped by seq."""
    plan = FaultPlan()
    t = remote_member(eng_faults=plan)
    fleet, _ = _member_fleet(params, t, FC_HEAL)
    fleet.start()
    try:
        _wait(lambda: t.rem._beat_ns != 0, 60, "remote warm-up beat")

        def state():
            return fleet.stats(include_engines=False)["engine_states"]["r0"]

        # quiet partition: SUSPECT, then heal back to HEALTHY
        t.link.partition(True)
        _wait(lambda: state() == "SUSPECT", 15, "SUSPECT after partition")
        t.link.partition(False)
        _wait(lambda: state() == "HEALTHY", 15, "HEALTHY after heal")
        st = fleet.stats(include_engines=False)
        assert st["failovers"] == 0

        # mid-stream partition: wait until the HOST has demonstrably
        # produced tokens into the blackout (their sends were dropped),
        # so the heal MUST exercise the gap-detect + resend path. The
        # host is throttled (~10 ms a token): unthrottled, on a loaded
        # machine, it ends the whole stream inside the partition, and a
        # heal that finds no live stream has no gap to detect
        plan.arm("delayed_fetch", count=100000, arg=0.01)
        req = fleet.submit(P2, max_new_tokens=STEPS)
        it = iter(req.stream())
        got = [next(it)]
        def host_delivered():
            return sum(r.delivered for r in t.eng._slot_req
                       if r is not None)

        base = host_delivered()
        t.link.partition(True)
        _wait(lambda: host_delivered() >= base + 3, 20,
              "host-side tokens generated into the partition")
        t.link.partition(False)
        got += list(it)
        assert got == refs[1]
        assert req.status == Status.OK
        st = fleet.stats(include_engines=False)
        assert st["failovers"] == 0, "a link blip must never fail over"
        assert st["fabric_resends"] >= 1
    finally:
        t.link.partition(False)
        fleet.stop()


def test_dropped_link_ask_fails_typed_fast(params, remote_member):
    """The ticket-timeout bugfix, remote half: once the transport is
    KNOWN dead (a recv error, unlike a silent partition which only a
    timeout can catch), a lifecycle ask fails with a typed
    MigrationError immediately — never stranding the caller for the
    full ticket timeout."""
    t = remote_member()
    _wait(lambda: t.rem._beat_ns != 0, 60, "remote warm-up beat")
    req = t.rem.submit(P1, max_new_tokens=STEPS)
    first = req.out.get()
    assert first is not None
    # kill the transport under the session: the host side closes, the
    # client's receiver observes the error and marks the link broken
    t.host_chan.close()
    _wait(lambda: not t.client.link_ok, 10, "link marked broken")
    t0 = time.perf_counter()
    with pytest.raises(MigrationError, match="link|down|fabric"):
        t.rem.ask("migrate_out", _Ticket(req), timeout=60.0)
    assert time.perf_counter() - t0 < 10.0, \
        "a dead-link ask must fail typed fast, not ride its 60s timeout"
    req.cancel()  # host-side session was cancelled by the channel sweep


def test_ask_on_dead_local_engine_fails_typed_fast(params):
    """The ticket-timeout bugfix, local half: `_ask` on an engine whose
    loop thread died raises typed immediately (watched wait), instead of
    blocking out the full ticket timeout on a corpse."""
    plan = FaultPlan()
    eng = ServingEngine(params, CFG, ServingConfig(**BASE, faults=plan))
    eng.start()
    req = eng.submit(P1, max_new_tokens=STEPS)
    assert req.out.get() is not None
    plan.arm("engine_death")
    _wait(lambda: eng._died, 30, "engine death")
    t0 = time.perf_counter()
    with pytest.raises(MigrationError, match="serving loop is dead"):
        _ask(eng, "migrate_out", _Ticket(req), timeout=60.0)
    assert time.perf_counter() - t0 < 10.0
    # the host-process supervisor's corpse reap (fabric.host.reap_corpse)
    # restores the audit invariants leak_check asserts at teardown —
    # the same repair the fleet's _reap performs for a fleet member
    reap_corpse(eng)


def test_dead_engine_behind_live_link_fails_over(params, refs,
                                                 remote_member):
    """The other half of link-vs-engine death: the HOST-side engine dies
    (loop gone, no cleanup) while the transport stays healthy. The
    host-reported beat age goes stale, the ladder declares DEAD, and the
    stream finishes token-equal on a local survivor, rebuilt from the
    client-side mirror ledger."""
    plan = FaultPlan()
    t = remote_member(eng_faults=plan)
    fleet, _ = _member_fleet(params, t, FC_KILL)
    fleet.start()
    try:
        _wait(lambda: t.rem._beat_ns != 0, 60, "remote warm-up beat")
        req = fleet.submit(P3, max_new_tokens=STEPS)
        it = iter(req.stream())
        got = [next(it), next(it)]
        # kill the host-side loop at its next flush, crash semantics:
        # no terminals, no cleanup — exactly engine_death's contract
        plan.arm("engine_death")
        got += list(it)
        assert got == refs[2]
        assert req.status == Status.OK
        st = fleet.stats(include_engines=False)
        assert st["failovers"] == 1
        assert st["engine_states"]["r0"] == "DEAD"
        # the link itself never broke: the death was the engine's
        assert t.client.link_ok
        # journey: route hop on the remote host, failover hop local.
        # Conservation needs the journey CLOSED (the monitor's prune
        # pass stamps delivered) — wait for the close first.
        _wait(lambda: fleet.stats(
            include_engines=False)["journeys_ended"] >= 1, 10,
            "journey close")
        j = fleet.trace.journeys()[req.jid]
        assert [h["kind"] for h in j["hops"]] == ["route", "failover"]
        assert j["hops"][0]["host"] == "h0"
        assert j["hops"][1]["host"] == "local"
        assert j["conserved"] is True
    finally:
        fleet.stop()


def test_dead_local_engine_fails_over_onto_a_remote_survivor(
        params, refs, remote_member):
    """The rebuild crosses the wire the other way: the engine that dies is
    local and the only survivor is remote, so the session's history goes
    out as a ``migrate_in`` ask and its stream comes back through the
    proxy — token-equal, with the failover hop tagged by the survivor's
    host and the rebuild counted on the engine behind the link."""
    plan = FaultPlan()
    # throttled (~10 ms a token), as every kill here: the death has to
    # land while the stream runs
    plan.arm("delayed_fetch", count=100000, arg=0.01)
    t = remote_member(host="h1", name="r1")
    engines = {"a": ServingEngine(params, CFG, ServingConfig(
                   **BASE, faults=plan)),
               "r1": t.rem}
    fleet = EngineFleet(engines, FleetConfig(
        **FC_KILL, route_policy=PinPolicy("a")))
    fleet.start()
    try:
        _wait(lambda: t.rem._beat_ns != 0, 60, "remote warm-up beat")
        req = fleet.submit(P1, max_new_tokens=STEPS)
        it = iter(req.stream())
        got = [next(it), next(it)]
        plan.arm("engine_death")
        got += list(it)
        assert got == refs[0]
        assert req.status == Status.OK
        _wait(lambda: fleet.stats(
            include_engines=False)["journeys_ended"] >= 1, 10,
            "journey close")
        st = fleet.stats(include_engines=False)
        assert st["failovers"] == 1 and st["failover_sessions"] == 1
        assert st["failover_faulted"] == 0
        assert st["engine_states"]["a"] == "DEAD"
        j = fleet.trace.journeys()[req.jid]
        assert [h["kind"] for h in j["hops"]] == ["route", "failover"]
        assert [h["host"] for h in j["hops"]] == ["local", "h1"]
        assert j["conserved"] is True
        assert t.eng.stats()["migrations_in"] == 1
        _wait(lambda: t.eng.stats()["active_slots"] == 0, 15,
              "host-side slot reclaimed")
    finally:
        fleet.stop()


# ----------------------------------------------------- payload integrity


def test_payload_corruption_downgrades_to_recompute(params, refs,
                                                    remote_member):
    """A migration payload whose chunk CRC fails in transit is dropped at
    decode (payload_lost) and the destination rebuilds the session
    through the recompute path — token-equal, never wrong tokens. The
    clean run right after ships the pages and installs them resident."""
    plan = FaultPlan()
    t = remote_member(faults=plan)
    dst = ServingEngine(params, CFG, ServingConfig(**BASE))
    dst.start()
    _wait(lambda: t.rem._beat_ns != 0, 60, "remote warm-up beat")

    # corrupted payload -> recompute
    req = t.rem.submit(P2, max_new_tokens=STEPS)
    got = [req.out.get()]
    plan.arm("fabric_payload_corrupt", count=1)
    rep = migrate(req, t.rem, dst)
    got += list(req.stream())
    assert got == refs[1]
    assert rep["path"] == "recompute"
    assert t.client.fabric_stats()["checksum_faults"] >= 1

    # clean payload -> resident install, bytes counted honestly
    req2 = t.rem.submit(P3, max_new_tokens=STEPS)
    got2 = [req2.out.get()]
    rep2 = migrate(req2, t.rem, dst)
    got2 += list(req2.stream())
    assert got2 == refs[2]
    assert rep2["path"] in ("resident", "host")
    assert rep2["bytes"] > 0
    assert t.client.fabric_stats()["payload_bytes_recv"] >= rep2["bytes"]


# ------------------------------------------------------- wire hardening


def test_hello_version_mismatch_refused_typed(monkeypatch):
    """A protocol-version mismatch at hello is a TYPED refusal carrying
    both versions — the client raises ProtocolError, the host closes the
    channel; neither side hangs."""
    import vtpu.serving.fabric.remote as remote_mod

    srv = EngineHost({"r0": object()})  # never touched before the refuse
    a, b, _ = loopback_pair(delay_s=0.0)
    threading.Thread(target=srv.serve_channel, args=(b,),
                     daemon=True).start()
    monkeypatch.setattr(remote_mod, "PROTO_VERSION", 999)
    with pytest.raises(ProtocolError, match="refused"):
        connect_host(a, host="h0", timeout=10.0)
    srv.stop()


def test_engine_signals_round_trip():
    """EngineSignals crosses the wire as a dict: to_dict/from_dict
    round-trips every field; unknown keys (a newer peer) are dropped and
    missing ones take defaults — schema drift never breaks the fleet."""
    sig = EngineSignals(queue_depth=3, active_slots=2, pool_free=7,
                        pool_used_hwm=9, parked_sessions=1,
                        prefill_backlog=4, now_ns=123, pool_blocks=16,
                        draining=True, duty=0.5, fabric_rtt_ms=1.25,
                        fabric_gbps=8.0)
    assert EngineSignals.from_dict(sig.to_dict()) == sig
    d = sig.to_dict()
    d["from_the_future"] = {"x": 1}
    assert EngineSignals.from_dict(d) == sig
    sparse = EngineSignals.from_dict({"queue_depth": 5})
    assert sparse.queue_depth == 5
    assert sparse.fabric_rtt_ms is None and sparse.duty is None


def test_tcp_frame_straddling_poll_windows_never_desyncs():
    """The receive buffer keeps partially-read bytes across poll
    timeouts: a frame dripped onto the wire slower than the caller's
    poll window (large migrate-meta JSON on a congested link) arrives
    intact over several polls, and the NEXT frame still parses — the
    stream can never desync into reading mid-frame bytes as headers."""
    import socket

    from vtpu.serving.fabric.transport import TcpChannel
    from vtpu.serving.fabric.wire import FRAME_JSON, HDR, encode_msg

    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    cli = socket.create_connection(srv.getsockname())
    conn, _ = srv.accept()
    srv.close()
    chan = TcpChannel(conn)
    try:
        msg = {"kind": "meta", "blob": "x" * 4096}
        body = encode_msg(msg)
        frame = HDR.pack(len(body), FRAME_JSON) + body

        def drip():
            # ~25 pieces, each slower than the reader's 2ms poll window
            for i in range(0, len(frame), 173):
                cli.sendall(frame[i:i + 173])
                time.sleep(0.004)
            body2 = encode_msg({"kind": "after"})
            cli.sendall(HDR.pack(len(body2), FRAME_JSON) + body2)

        threading.Thread(target=drip, daemon=True).start()
        got = None
        for _ in range(2000):
            got, _ = chan.recv(timeout=0.002)
            if got is not None:
                break
        assert got == msg
        got2 = None
        for _ in range(2000):
            got2, _ = chan.recv(timeout=0.002)
            if got2 is not None:
                break
        assert got2 == {"kind": "after"}
    finally:
        chan.close()
        cli.close()


def test_cancel_swallowed_by_partition_retransmits_on_heal(params,
                                                           remote_member):
    """A cancel sent into a partition is silently lost (the send
    'succeeds' onto a dead link). Cancels re-send until the terminal
    arrives, so the heal replays it and the host stops decoding —
    instead of running the whole stream for a caller that cancelled
    long ago."""
    plan = FaultPlan()
    t = remote_member(eng_faults=plan)
    _wait(lambda: t.rem._beat_ns != 0, 60, "remote warm-up beat")
    # throttle the host's decode so the stream is still live through
    # the partition + heal window
    plan.arm("delayed_fetch", count=100000, arg=0.05)
    req = t.rem.submit(P1, max_new_tokens=STEPS)
    assert req.out.get() is not None
    t.link.partition(True)
    req.cancel()
    time.sleep(0.4)  # several cancel re-sends land in the partition
    t.link.partition(False)
    _wait(lambda: req.status == Status.CANCELLED, 15,
          "CANCELLED terminal after heal")
    _wait(lambda: t.eng.stats()["active_slots"] == 0, 15,
          "host-side slot reclaimed")


# ------------------------------------------------------------ TCP + kill


def test_tcp_sigkill_child_failover_token_equal(params, refs, monkeypatch):
    """The fabric's reason to exist: a REAL child process serving an
    engine over TCP is SIGKILLed mid-stream, and the stream finishes
    token-equal on a local survivor — rebuilt from the client-side
    mirror, with the survivors leak-clean (conftest audits them)."""
    import os
    import signal

    from vtpu.util.jaxcache import compile_cache_dir

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", compile_cache_dir())
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    spec = {"model": dict(vocab=64, d_model=32, n_heads=2, n_layers=1,
                          d_ff=64, max_seq=32, head_dim=16,
                          dtype="float32", use_pallas=False),
            "seed": 0,
            "engines": {"r0": dict(
                slots=2, prefill_buckets=[8, 32], max_new_tokens=STEPS,
                kv_page=PAGE, kv_swap=8,
                # throttle the child's decode (~10ms/token): the tiny
                # model would otherwise finish the whole stream into the
                # socket buffer before the SIGKILL lands — the kill must
                # be MID-stream for the failover to have work to do
                faults=[dict(seam="delayed_fetch", at=0, count=100000,
                             arg=0.01)])}}
    proc, port = spawn_host(spec)
    client = None
    fleet = None
    try:
        chan = tcp_connect("127.0.0.1", port)
        client, engines = connect_host(chan, host="h0", proc=proc)
        rem = engines["r0"]
        assert rem._page == PAGE and rem._block_bytes > 0
        locals_ = {
            "e1": ServingEngine(params, CFG, ServingConfig(**BASE)),
            "e2": ServingEngine(params, CFG, ServingConfig(**BASE))}
        fleet = EngineFleet({"r0": rem, **locals_}, FleetConfig(
            **FC_KILL, route_policy=PinPolicy("r0")))
        fleet.start()
        _wait(lambda: rem._beat_ns != 0, 180, "child engine warm-up")
        req = fleet.submit(P1, max_new_tokens=STEPS)
        it = iter(req.stream())
        got = [next(it), next(it), next(it)]
        os.kill(proc.pid, signal.SIGKILL)
        got += list(it)
        assert got == refs[0]
        assert req.status == Status.OK
        # the journey closes on the monitor's prune pass — wait for it
        # before reading the stitched blackout percentile
        _wait(lambda: fleet.stats(
            include_engines=False)["journeys_ended"] >= 1, 10,
            "journey close")
        st = fleet.stats(include_engines=False)
        assert st["failovers"] == 1 and st["failover_sessions"] == 1
        assert st["failover_faulted"] == 0
        assert st["engine_states"]["r0"] == "DEAD"
        assert st["failover_blackout_p99_ms"] is not None
        # the fabric's counters account for the traffic: messages both
        # ways, and more bytes in than out, since the tokens flow back
        assert st["remote_engines"] == 1
        assert st["fabric_msgs_sent"] > 0 and st["fabric_msgs_recv"] > 0
        assert st["fabric_bytes_recv"] > st["fabric_bytes_sent"] > 0
        # journey host tags survive the hop across processes
        j = fleet.trace.journeys()[req.jid]
        assert [h["kind"] for h in j["hops"]] == ["route", "failover"]
        assert j["hops"][0]["host"] == "h0"
        assert j["hops"][1]["host"] == "local"
        assert j["conserved"] is True
        # survivors hold nothing (leak_check re-audits at teardown)
        for n in ("e1", "e2"):
            assert fleet.engines[n].stats()["active_slots"] == 0
    finally:
        if fleet is not None:
            fleet.stop()
        if client is not None:
            client.close()
        if proc.poll() is None:
            proc.kill()
