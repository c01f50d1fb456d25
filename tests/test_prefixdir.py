"""Prefix gravity: the fleet-wide content-addressed prefix tier
(ISSUE 20 tentpole).

Fast tier. The organizing claim under test: the prefix cache is a FLEET
resource — a prefix registered on one engine is routable, replicable and
recoverable anywhere — and every movement of it is zero-copy at
admission time (``prefix_install_copies`` stays 0 fleet-wide; the only
transfers are the once-per-engine staged export/install). Layered:

- the directory: content pids, refcounts fed by the share()/release()
  listener discipline, the route-bonus arithmetic, and the hot/cold
  candidate policies — pure unit tests, no engine;
- routing: ``submit(prefix_tokens=...)`` steers to the resident engine
  over equal-pressure peers, ties break deterministically by name, a
  prefix that lives nowhere falls back to a token-equal full-prompt
  submit, and every prefix-aware submit lands as EXACTLY one directory
  hit or one miss (the accounting contract the bench gates on);
- movement: hot replication rebuilds on a second engine with zero
  staged copies, cold spill parks the payload in the shared host tier
  where ANY engine (a loopback-fabric remote included) installs it and
  streams token-equal;
- failover: a survivor holding the dead engine's prefix rebuilds the
  session AROUND it — sharing the registered blocks and recomputing
  only the private tail (``failover_prefix_reuses``).

The conftest ``leak_check`` audits every engine these tests build —
dead ones and loopback host-side ones included."""

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
from vtpu.serving.fabric import EngineHost, connect_host, loopback_pair
from vtpu.serving.prefixdir import (
    LOGITS_PLANE,
    PrefixDirectory,
    export_prefix,
    install_prefix,
    prefix_id,
)

CFG = ModelConfig(
    vocab=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
    max_seq=32, head_dim=16, dtype=jnp.float32, use_pallas=False,
)
PAGE = 8
STEPS = 8    # short streams for routing/movement tests
KSTEPS = 20  # long enough that an armed kill lands MID-stream
# chunked prefill (register_prefix needs it) + kv_swap (export/install
# staging lives there); max_new_tokens is the per-request CAP
BASE = dict(slots=2, prefill_buckets=(8,), max_new_tokens=KSTEPS,
            kv_page=PAGE, prefill_chunk=8, kv_swap=8)
# test_fleet's wide-window ladder rationale, plus a tiny queue-slot
# denominator: the route bonus is 0.25 * plen * ms_per_token /
# queue_slot_ms, and these tests need "resident wins" to dominate the
# resident's OWN pool handicap (its pinned prefix blocks lower the
# least-pressure score by up to 0.25) on any machine, however fast the
# tiny model's measured build is
FC = dict(probe_interval_ms=5.0, miss_ms=2000.0,
          suspect_misses=2, dead_misses=4, prefix_queue_slot_ms=0.01)

# PRE/OPRE: two full pages (16 tokens) — block sharing without a COW
# boundary; KPRE: one page, leaving room for a KSTEPS stream within
# max_seq (8 + 3 + 20 = 31 <= 32)
PRE = list(range(1, 17))
OPRE = list(range(17, 33))
KPRE = list(range(33, 41))
SUF = [50, 51, 52]


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.key(0), CFG)


@pytest.fixture(scope="module")
def prefix_refs(params):
    """Single-engine reference streams (greedy decode is deterministic,
    so per-prompt streams are placement-invariant): "prefix" for
    PRE+SUF, "other" for OPRE+SUF, "kill" for KPRE+SUF at KSTEPS. The
    fixture also pins the PR-4 base invariant the fleet tests stand on:
    a prefix-cached stream equals the full-prompt stream."""
    eng = ServingEngine(params, CFG, ServingConfig(**BASE))
    eng.start()
    try:
        lid = eng.register_prefix(PRE)
        pre = list(eng.submit(SUF, prefix=lid,
                              max_new_tokens=STEPS).stream())
        full = list(eng.submit(PRE + SUF, max_new_tokens=STEPS).stream())
        assert pre == full, "prefix admission must be token-invisible"
        other = list(eng.submit(OPRE + SUF, max_new_tokens=STEPS).stream())
        klid = eng.register_prefix(KPRE)
        kill = list(eng.submit(SUF, prefix=klid,
                               max_new_tokens=KSTEPS).stream())
        return {"prefix": pre, "other": other, "kill": kill}
    finally:
        eng.stop()


class PinPolicy(RoutePolicy):
    """Route everything to one named engine; survivors rank by name."""

    def __init__(self, name="a"):
        self.name = name

    def score(self, name, signals):
        if signals.draining:
            return None
        return 1.0 if name == self.name else 0.0


def _fleet(params, names=("a", "b", "c"), faults_for=None, fc=None,
           **fleet_kw):
    faults_for = faults_for or {}
    engines = {
        n: ServingEngine(params, CFG, ServingConfig(
            **BASE, faults=faults_for.get(n)))
        for n in names
    }
    cfg = FleetConfig(**{**FC, **(fc or {})}, **fleet_kw)
    return EngineFleet(engines, cfg), engines


def _wait(pred, timeout=60.0, msg="condition"):
    t0 = time.perf_counter()
    while not pred():
        if time.perf_counter() - t0 > timeout:
            raise AssertionError(f"timed out waiting for {msg}")
        time.sleep(0.002)


@pytest.fixture()
def remote_member(params):
    """Factory: one started engine behind an in-proc loopback EngineHost,
    proxied as a RemoteEngine (the test_crosshost idiom)."""
    opened = []

    def build(host="h0", name="r0"):
        eng = ServingEngine(params, CFG, ServingConfig(**BASE))
        eng.start()
        srv = EngineHost({name: eng})
        a, b, link = loopback_pair(delay_s=0.0)
        threading.Thread(target=srv.serve_channel, args=(b,),
                         daemon=True).start()
        client, engines = connect_host(a, host=host)
        t = SimpleNamespace(eng=eng, srv=srv, link=link, client=client,
                            rem=engines[name])
        opened.append(t)
        return t

    yield build
    for t in opened:
        t.client.close()
        t.srv.stop()


# ------------------------------------------------------- directory units


def test_prefix_id_content_addressing():
    """The pid is a pure function of the token CONTENT: container and
    dtype presentation don't matter, token values do."""
    import numpy as np

    a = prefix_id([1, 2, 3])
    assert a == prefix_id([1, 2, 3])
    assert a == prefix_id(np.asarray([1, 2, 3], np.int64))
    assert a == prefix_id(jnp.asarray([1, 2, 3], jnp.int32))
    assert a != prefix_id([1, 2, 4])
    assert a != prefix_id([1, 2])
    assert len(a) == 16 and int(a, 16) >= 0  # 16 hex chars


def test_directory_lifecycle_unit():
    """Register/hit/release/unregister walk the refcount state machine;
    a pid with no residents survives ONLY in the host tier."""
    d = PrefixDirectory()
    pid = prefix_id([1, 2, 3])
    d.on_event("a", "register", pid, lid=7, tokens=[1, 2, 3], length=3)
    assert d.residents(pid) == {"a": 7}
    assert d.tokens_of(pid) == [1, 2, 3]
    # re-register is idempotent and refreshes the local id
    d.on_event("a", "register", pid, lid=9)
    assert d.residents(pid) == {"a": 9}
    d.on_event("a", "hit", pid)
    d.on_event("a", "hit", pid)
    d.on_event("a", "release", pid)
    s = d.stats()
    assert s["prefix_directory_hits"] == 2
    assert s["prefix_live_refs"] == 1
    assert s["prefix_pids"] == 1 and s["prefix_resident_replicas"] == 1
    d.on_event("a", "release", pid)
    d.on_event("a", "release", pid)  # floor at zero, never negative
    assert d.stats()["prefix_live_refs"] == 0
    # a remote's hit is stamped at route time: hits move, refs don't
    d.note_route_hit(pid, "a")
    s = d.stats()
    assert s["prefix_directory_hits"] == 3 and s["prefix_live_refs"] == 0
    d.note_miss()
    assert d.stats()["prefix_directory_misses"] == 1
    # the last unregister deletes a pid the host tier doesn't hold
    d.on_event("a", "unregister", pid, lid=9)
    assert d.residents(pid) == {} and d.tokens_of(pid) is None
    assert d.stats()["prefix_pids"] == 0
    # events for unknown engines/pids are tolerated no-ops on state
    d.on_event("ghost", "release", pid)
    d.on_event("ghost", "unregister", pid)

    # host tier keeps a pid alive through a fence-time engine drop
    pid2 = prefix_id([4, 5])
    d.on_event("b", "register", pid2, lid=1, tokens=[4, 5], length=2)
    d.put_host(pid2, {"tokens": [4, 5], "len": 2}, {"plane": None})
    d.drop_engine("b")
    assert d.residents(pid2) == {} and d.in_host_tier(pid2)
    assert d.tokens_of(pid2) == [4, 5]
    meta, _payload = d.get_host(pid2)
    assert meta["len"] == 2
    assert d.stats()["prefix_pids"] == 1
    assert d.stats()["prefix_host_tier"] == 1


def test_route_bonus_arithmetic():
    """White-box: registrations feed a 0.7/0.3 EMA of the measured
    per-token build cost; the bonus converts avoided prefill into
    least-pressure score units at 0.25 per queue slot."""
    d = PrefixDirectory(queue_slot_ms=50.0)
    assert d.route_bonus(16) == 0.0  # nothing measured, nothing resident
    assert d.ms_per_token() is None
    d.on_event("a", "register", prefix_id(list(range(10))), lid=0,
               tokens=list(range(10)), length=10, build_ms=100.0)
    assert d.ms_per_token() == pytest.approx(10.0)
    assert d.route_bonus(16) == pytest.approx(0.25 * 16 * 10.0 / 50.0)
    # second measurement at 20 ms/token: EMA -> 0.7*10 + 0.3*20 = 13
    d.on_event("a", "register", prefix_id(list(range(5))), lid=1,
               tokens=list(range(5)), length=5, build_ms=100.0)
    assert d.ms_per_token() == pytest.approx(13.0)
    assert d.route_bonus(8) == pytest.approx(0.25 * 8 * 13.0 / 50.0)


def test_directory_hot_cold_candidates():
    """The monitor's two policies: hot needs hits + headroom + a
    routable non-resident; cold needs zero refs + idleness."""
    d = PrefixDirectory()
    pid = prefix_id([1, 2, 3, 4])
    d.on_event("a", "register", pid, lid=3, tokens=[1, 2, 3, 4], length=4)
    assert d.hot_candidate(1, 2, ["a", "b"]) is None  # zero hits yet
    d.on_event("a", "hit", pid)
    assert d.hot_candidate(1, 2, ["a", "b"]) == (pid, [1, 2, 3, 4], "a")
    assert d.hot_candidate(2, 2, ["a", "b"]) is None  # below min_hits
    assert d.hot_candidate(1, 1, ["a", "b"]) is None  # replica cap reached
    assert d.hot_candidate(1, 2, ["a"]) is None       # nowhere to put it
    # a live ref pins it hot regardless of age
    assert d.cold_candidate(0.0, ["a"]) is None
    d.on_event("a", "release", pid)
    time.sleep(0.01)
    assert d.cold_candidate(0.005, ["a"]) == (pid, "a", 3)
    assert d.cold_candidate(60.0, ["a"]) is None  # not idle long enough
    assert d.cold_candidate(0.0, ["b"]) is None   # resident not routable


# -------------------------------------------------- prefix-aware routing


def test_prefix_route_steers_to_resident_and_falls_back(
        params, prefix_refs):
    """The bonus out-scores equal-pressure peers (including the
    resident's own pinned-block pool handicap) and the stream ships
    suffix-only; an unregistered prefix falls back to a token-equal
    full-prompt submit. Accounting contract: each prefix-aware submit
    is EXACTLY one directory hit or one miss."""
    fleet, _engines = _fleet(params)
    fleet.start()
    try:
        cpid = fleet.register_prefix(PRE, engine="b")
        assert set(fleet.prefixdir.residents(cpid)) == {"b"}
        # the build fed the cost EMA through the listener, and the tiny
        # queue-slot denominator makes the bonus decisive
        assert fleet.prefixdir.ms_per_token() is not None
        assert fleet.prefixdir.route_bonus(len(PRE)) > 0.25
        req = fleet.submit(SUF, prefix_tokens=PRE, max_new_tokens=STEPS)
        toks = list(req.stream())
        assert req.status == Status.OK
        assert toks == prefix_refs["prefix"]
        s = fleet.stats()
        assert s["prefix_routes"] == 1
        assert s["engines"]["b"]["prefix_hits"] == 1
        assert s["engines"]["a"]["prefix_hits"] == 0
        assert s["prefix_directory_hits"] == 1
        assert s["prefix_directory_misses"] == 0

        req2 = fleet.submit(SUF, prefix_tokens=OPRE, max_new_tokens=STEPS)
        toks2 = list(req2.stream())
        assert toks2 == prefix_refs["other"]
        s = fleet.stats()
        assert s["prefix_routes"] == 1  # the fallback is NOT a prefix route
        assert s["prefix_directory_hits"] == 1
        assert s["prefix_directory_misses"] == 1
        for n in ("a", "b", "c"):
            assert s["engines"][n]["prefix_install_copies"] == 0
    finally:
        fleet.stop()


def test_prefix_route_ties_break_by_name(params, prefix_refs):
    """Two equal-pressure residents carry the same bonus: the name
    order decides, every time."""
    fleet, _engines = _fleet(params)
    fleet.start()
    try:
        fleet.register_prefix(PRE, engine="c")
        cpid = fleet.register_prefix(PRE, engine="b")
        assert set(fleet.prefixdir.residents(cpid)) == {"b", "c"}
        req = fleet.submit(SUF, prefix_tokens=PRE, max_new_tokens=STEPS)
        assert list(req.stream()) == prefix_refs["prefix"]
        s = fleet.stats()
        assert s["engines"]["b"]["prefix_hits"] == 1
        assert s["engines"]["c"]["prefix_hits"] == 0
    finally:
        fleet.stop()


def test_pid_api_validation(params, prefix_refs):
    """The content pid is the fleet-level name: register is idempotent
    across the fleet, pid-only submits resolve tokens through the
    directory, and inconsistent or unknown names fail typed."""
    fleet, _engines = _fleet(params, names=("a", "b"))
    fleet.start()
    try:
        cpid = fleet.register_prefix(PRE, engine="a")
        assert cpid == prefix_id(PRE)
        # idempotent: resident anywhere -> no second build
        assert fleet.register_prefix(PRE) == cpid
        assert set(fleet.prefixdir.residents(cpid)) == {"a"}
        req = fleet.submit(SUF, pid=cpid, max_new_tokens=STEPS)
        assert list(req.stream()) == prefix_refs["prefix"]
        with pytest.raises(ValueError):
            fleet.submit(SUF, pid="0123456789abcdef")
        with pytest.raises(ValueError):
            fleet.submit(SUF, prefix_tokens=PRE, pid=prefix_id(OPRE))
    finally:
        fleet.stop()


def test_seeded_zipf_draw_is_accounted_and_routed_to_residents(
        params, prefix_refs):
    """Traffic, not one submit: twelve requests drawn by a seeded zipf law
    over four registered prefixes, placed hottest first on the least
    loaded of three engines, with hot replication on. Every prefix-aware
    submit lands as exactly one directory hit or one miss, whatever the
    monitor replicated meanwhile; the share routed onto a resident is
    above what routing blind to residency could reach (replicas over
    engines); admission copied nothing; and every stream is its prefix's
    reference stream."""
    import numpy as np

    names, n_requests, max_replicas = ("a", "b", "c"), 12, 2
    prefixes = [PRE, OPRE, list(range(33, 49)), list(range(48, 64))]
    rng = np.random.default_rng(7)
    weights = 1.0 / np.arange(1, len(prefixes) + 1) ** 1.2
    weights /= weights.sum()
    draw = [int(i) for i in rng.choice(len(prefixes), size=n_requests,
                                       p=weights)]
    assert len(set(draw)) > 1  # the draw mixes hot and cold
    fleet, _engines = _fleet(params, names=names, fc={
        "prefix_replicate_hits": 3, "prefix_max_replicas": max_replicas})
    fleet.start()
    try:
        load = dict.fromkeys(names, 0.0)
        for i in np.argsort(-weights):
            tgt = min(names, key=lambda n: (load[n], n))
            fleet.register_prefix(prefixes[i], engine=tgt)
            load[tgt] += float(weights[i])
        reqs = [fleet.submit(SUF, prefix_tokens=prefixes[i],
                             max_new_tokens=STEPS) for i in draw]
        streams = [list(r.stream()) for r in reqs]
        assert all(r.status == Status.OK for r in reqs)
        # a local resident stamps its hit from the loop thread at the
        # share: every stream has ended, so every share has happened
        s = fleet.stats()
    finally:
        fleet.stop()
    assert (s["prefix_directory_hits"]
            + s["prefix_directory_misses"]) == n_requests
    assert s["prefix_routes"] / n_requests > max_replicas / len(names)
    for n in names:
        assert s["engines"][n]["prefix_install_copies"] == 0
    by_prefix = {}
    for i, toks in zip(draw, streams):
        assert by_prefix.setdefault(i, toks) == toks
    assert by_prefix[0] == prefix_refs["prefix"]
    assert by_prefix[1] == prefix_refs["other"]


# ------------------------------------------------- replication and spill


def test_hot_prefix_replicates_without_copies(params, prefix_refs):
    """One hit past the threshold and the monitor rebuilds the prefix
    on the non-resident peer through the chunked-prefill path — zero
    staged installs, zero per-admission copies, and the replica serves
    token-equal."""
    fleet, _engines = _fleet(params, names=("a", "b"),
                             fc={"prefix_replicate_hits": 1,
                                 "prefix_max_replicas": 2})
    fleet.start()
    try:
        cpid = fleet.register_prefix(PRE, engine="a")
        req = fleet.submit(SUF, prefix_tokens=PRE, max_new_tokens=STEPS)
        assert list(req.stream()) == prefix_refs["prefix"]
        _wait(lambda: len(fleet.prefixdir.residents(cpid)) == 2,
              msg="hot replication onto the second engine")
        # the monitor publishes the resident before it bumps the counter
        _wait(lambda: fleet.stats()["prefix_replications"] >= 1,
              msg="replication counted")
        s = fleet.stats()
        for n in ("a", "b"):
            assert s["engines"][n]["prefix_install_copies"] == 0
            assert s["engines"][n]["prefix_tier_installs"] == 0
        # the cap holds: no further replication churn is possible
        assert fleet.prefixdir.hot_candidate(1, 2, ["a", "b"]) is None
        req2 = fleet.submit(SUF, prefix_tokens=PRE, max_new_tokens=STEPS)
        assert list(req2.stream()) == prefix_refs["prefix"]
    finally:
        fleet.stop()


def test_export_install_token_equal(params, prefix_refs):
    """The movement primitives, no fleet: export snapshots the blocks
    (plus the stored final logits plane) through the staging gather,
    install lands them in a DIFFERENT engine's pool under the same
    content pid, and the suffix stream is byte-identical. A second
    install of the same pid is answered idempotently."""
    a = ServingEngine(params, CFG, ServingConfig(**BASE))
    b = ServingEngine(params, CFG, ServingConfig(**BASE))
    a.start()
    b.start()
    try:
        lid = a.register_prefix(PRE)
        meta, payload = export_prefix(a, lid)
        assert meta["pid"] == prefix_id(PRE)
        assert meta["len"] == len(PRE)
        assert LOGITS_PLANE in payload
        assert a.stats()["prefix_exports"] == 1
        res = install_prefix(b, meta, payload)
        assert res["installed"] is True and res["pid"] == meta["pid"]
        toks = list(b.submit(SUF, prefix=res["lid"],
                             max_new_tokens=STEPS).stream())
        assert toks == prefix_refs["prefix"]
        sb = b.stats()
        assert sb["prefix_tier_installs"] == 1
        assert sb["prefix_install_copies"] == 0
        assert sb["prefix_hits"] == 1
        res2 = install_prefix(b, meta, payload)
        assert res2["installed"] is False and res2["lid"] == res["lid"]
        assert b.stats()["prefix_tier_installs"] == 1
    finally:
        a.stop()
        b.stop()


def test_cold_spill_then_any_engine_installs(params, prefix_refs):
    """An idle zero-ref prefix spills to the shared host tier (export +
    unregister — device memory freed, pid kept alive tier-side); a later
    pid submit installs it on whichever engine wins the route and
    streams token-equal, still with zero per-admission copies."""
    fleet, _engines = _fleet(params, names=("a", "b"),
                             fc={"prefix_spill_idle_s": 0.05})
    fleet.start()
    try:
        cpid = fleet.register_prefix(PRE, engine="a")
        _wait(lambda: (fleet.prefixdir.in_host_tier(cpid)
                       and not fleet.prefixdir.residents(cpid)),
              msg="cold spill to the host tier")
        s = fleet.stats()
        assert s["prefix_spills"] >= 1
        assert s["engines"]["a"]["prefix_exports"] == 1
        # zero residents, yet the pid still resolves through the tier
        assert fleet.prefixdir.tokens_of(cpid) == PRE
        req = fleet.submit(SUF, pid=cpid, max_new_tokens=STEPS)
        toks = list(req.stream())
        assert toks == prefix_refs["prefix"]
        s = fleet.stats()
        assert s["prefix_installs"] >= 1
        assert sum(s["engines"][n]["prefix_tier_installs"]
                   for n in ("a", "b")) >= 1
        for n in ("a", "b"):
            assert s["engines"][n]["prefix_install_copies"] == 0
        # the accounting contract survives the spill/install churn:
        # the one prefix-aware submit is one hit XOR one miss
        assert (s["prefix_directory_hits"]
                + s["prefix_directory_misses"]) == 1
    finally:
        fleet.stop()


# ----------------------------------------------------- fabric round-trips


def test_remote_prefix_install_token_equal(params, prefix_refs,
                                           remote_member):
    """Both wire paths: a payload-carrying ``prefix_in`` ask installs a
    locally exported prefix on a loopback remote (idempotent on retry),
    and a wire ``register_prefix`` builds one host-side — each serving
    a token-equal suffix stream through the proxy."""
    t = remote_member()
    a = ServingEngine(params, CFG, ServingConfig(**BASE))
    a.start()
    try:
        lid = a.register_prefix(PRE)
        meta, payload = export_prefix(a, lid)
        res = install_prefix(t.rem, meta, payload)
        assert res["installed"] is True
        toks = list(t.rem.submit(SUF, prefix=res["lid"],
                                 max_new_tokens=STEPS).stream())
        assert toks == prefix_refs["prefix"]
        assert t.eng.stats()["prefix_tier_installs"] == 1
        assert t.eng.stats()["prefix_install_copies"] == 0
        res2 = install_prefix(t.rem, meta, payload)
        assert res2["installed"] is False and res2["lid"] == res["lid"]
        lid2 = t.rem.register_prefix(OPRE)
        # the proxy mirrors enough to rebuild full history on failover
        assert t.rem._prefix_meta[lid2]["tokens"] == OPRE
        toks2 = list(t.rem.submit(SUF, prefix=lid2,
                                  max_new_tokens=STEPS).stream())
        assert toks2 == prefix_refs["other"]
    finally:
        a.stop()


def test_remote_fleet_prefix_route(params, prefix_refs, remote_member):
    """A REMOTE resident is a first-class route target: the wire
    registration mirrors into the directory (build cost included), the
    pid submit steers to the proxy over an idle local peer, and the hit
    is stamped at route time (a remote's loop thread can't report
    here)."""
    t = remote_member()
    engines = {"r0": t.rem,
               "e1": ServingEngine(params, CFG, ServingConfig(**BASE))}
    fleet = EngineFleet(engines, FleetConfig(**FC))
    fleet.start()
    try:
        _wait(lambda: t.rem._beat_ns != 0, msg="remote warm-up beat")
        cpid = fleet.register_prefix(PRE, engine="r0")
        assert set(fleet.prefixdir.residents(cpid)) == {"r0"}
        assert fleet.prefixdir.ms_per_token() is not None
        req = fleet.submit(SUF, pid=cpid, max_new_tokens=STEPS)
        toks = list(req.stream())
        assert toks == prefix_refs["prefix"]
        s = fleet.stats(include_engines=False)
        assert s["prefix_routes"] == 1
        assert s["prefix_directory_hits"] == 1
        assert s["prefix_directory_misses"] == 0
    finally:
        fleet.stop()


# --------------------------------------------------------------- failover


def test_failover_prefix_reuse(params, prefix_refs):
    """A survivor already holding the dead engine's prefix rebuilds the
    session AROUND it: the registered blocks are shared (never
    re-prefilled), only the private tail recomputes, and the stream
    stays token-equal end to end."""
    plan = FaultPlan()
    # throttle the doomed engine's decode (~10ms/token) so the armed
    # death lands mid-stream, not after a free-run to completion
    plan.arm("delayed_fetch", count=100000, arg=0.01)
    fleet, engines = _fleet(params, names=("a", "b"),
                            faults_for={"a": plan},
                            fc={"route_policy": PinPolicy("a")})
    fleet.start()
    try:
        cpid = fleet.register_prefix(KPRE, engine="a")
        fleet.register_prefix(KPRE, engine="b")
        req = fleet.submit(SUF, prefix_tokens=KPRE, max_new_tokens=KSTEPS)
        assert fleet._assigned[req] == "a"
        it = req.stream()
        head = [next(it), next(it)]
        plan.arm("engine_death")  # die at the very next flush boundary
        toks = head + list(it)
        assert req.status == Status.OK
        assert toks == prefix_refs["kill"]
        sb = engines["b"].stats()
        assert sb["failover_prefix_reuses"] == 1
        # the registered page was MAPPED into the rebuilt slot
        assert sb["prefix_blocks_shared"] >= 1
        evs = [e for e in engines["b"].trace.events()
               if e["event"] == "fault_recompute"]
        assert len(evs) == 1
        # val is the recomputed TAIL length — the white-box contract
        # that the prefix positions were shared, never re-prefilled
        n_total = len(KPRE) + len(SUF) + len(toks)
        assert 0 <= evs[0]["val"] <= n_total - len(KPRE)
        s = fleet.stats(include_engines=False)
        assert s["failovers"] == 1
        assert plan.snapshot()["injected"]["engine_death"] == 1
        # the fence swept the corpse's residency; the survivor's stands
        assert set(fleet.prefixdir.residents(cpid)) == {"b"}
    finally:
        fleet.stop()
