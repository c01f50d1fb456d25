"""Engine fleet: supervision, routing, failover (ISSUE 14 tentpole).

Fast tier. The organizing claim under test: an engine can die WITHOUT
SAYING GOODBYE — its loop thread vanishes mid-stream with no cleanup, no
terminals, no extract possible — and every stream it held still finishes
token-equal on a survivor, rebuilt from the fleet's flush-boundary
session ledger through the existing recompute-on-fault prefill path.
Layered:

- supervision: missed heartbeats walk the SUSPECT -> DEAD ladder with
  hysteresis — a SUSPECT-but-alive engine (probe_loss seam) is NEVER
  failed over and returns to HEALTHY on its next fresh beat;
- routing: the pluggable RoutePolicy (least-pressure default, the
  shed.py instance/class/"module:attr" loading shape) scores engines on
  EngineSignals — draining engines are never targets, attested duty
  steers traffic off hot chips, pool-occupancy imbalance triggers
  background rebalancing migrations;
- failover: kill-one-of-three mid-stream with every stream token-equal
  to a single-engine reference, ledger staleness (die between flushes ->
  the rebuild resumes at exactly the last recorded token — no
  duplicates, no gaps), cancel racing failover, and the fleet's reap
  restoring the corpse's audit invariants (the conftest ``leak_check``
  rides every engine these tests build — dead ones included).
"""

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from vtpu.models import ModelConfig, init_params
from vtpu.serving import (
    EngineFleet,
    FaultPlan,
    FaultSpec,
    FleetConfig,
    LeastPressureRoutePolicy,
    RoutePolicy,
    ServingConfig,
    ServingEngine,
    Status,
)
from vtpu.serving.fleet import load_route_policy

CFG = ModelConfig(
    vocab=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
    max_seq=32, head_dim=16, dtype=jnp.float32, use_pallas=False,
)
# an int8 pool: a rebuild from the ledger recomputes the quantised pages,
# and has to land on the tokens the first build gave. Two layers, so that
# what the second reads of the first's cache reaches the logits
LAYOUTS = {"exact": CFG,
           "int8": dataclasses.replace(CFG, kv_int8=True, n_layers=2)}
PAGE = 8
# long enough that an armed kill always lands MID-stream: the client
# takes a few head tokens then arms, and the engine keeps producing in
# the gap — a short budget can fully drain first on a loaded box,
# leaving the death nothing to catch (prompt 6 + 20 < max_seq 32)
STEPS = 20
BASE = dict(slots=2, prefill_buckets=(8,), max_new_tokens=STEPS,
            kv_page=PAGE, kv_swap=8)
# probes every 5 ms; a beat older than 2 s is a miss (WIDE on purpose:
# the loop beats every <= ~50 ms even idle, but on a loaded CI box a
# LIVE loop thread can be starved for over a second — a tight window
# would false-positive into fencing an alive engine, whose designed
# degrade is CANCELLED terminals, not these tests' scenarios; only a
# dead loop or a probe_loss injection walks the ladder here); 2 misses
# -> SUSPECT, 4 -> DEAD, so real-death detection costs ~2 s per kill.
FC = dict(probe_interval_ms=5.0, miss_ms=2000.0,
          suspect_misses=2, dead_misses=4)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.key(0), CFG)


def _prompt(seed, n=5):
    return [int(t) for t in jax.random.randint(
        jax.random.key(seed), (n,), 1, CFG.vocab, jnp.int32)]


P1, P2, P3 = _prompt(1, 5), _prompt(2, 6), _prompt(3, 5)


def _reference(params, cfg=CFG):
    eng = ServingEngine(params, cfg, ServingConfig(**{**BASE, "slots": 3}))
    eng.start()
    try:
        return [list(eng.submit(p, max_new_tokens=STEPS).stream())
                for p in (P1, P2, P3)]
    finally:
        eng.stop()


@pytest.fixture(scope="module")
def refs(params):
    """Single-engine reference streams for P1/P2/P3 (greedy decode is
    deterministic, so per-prompt streams are slot-count-invariant)."""
    return _reference(params)


class PinPolicy(RoutePolicy):
    """Route everything to one named engine (deterministic placement
    through the front door); survivors rank by name when it is gone."""

    def __init__(self, name="a"):
        self.name = name

    def score(self, name, signals):
        if signals.draining:
            return None
        return 1.0 if name == self.name else 0.0


def _fleet(params, names=("a", "b", "c"), faults_for=None, fc=None,
           cfg=CFG, **fleet_kw):
    """Build a fleet of fresh engines; ``faults_for`` maps engine name ->
    FaultPlan (the engine-side seams)."""
    faults_for = faults_for or {}
    engines = {
        n: ServingEngine(params, cfg, ServingConfig(
            **BASE, faults=faults_for.get(n)))
        for n in names
    }
    cfg = FleetConfig(**{**FC, **(fc or {})}, **fleet_kw)
    return EngineFleet(engines, cfg), engines


def _wait(pred, timeout=30.0, msg="condition"):
    t0 = time.perf_counter()
    while not pred():
        if time.perf_counter() - t0 > timeout:
            raise AssertionError(f"timed out waiting for {msg}")
        time.sleep(0.002)


# ------------------------------------------------------------- validation


def test_fleet_validation(params):
    one = ServingEngine(params, CFG, ServingConfig(**BASE))
    with pytest.raises(ValueError, match="at least 2"):
        EngineFleet({"a": one})
    no_swap = ServingEngine(params, CFG, ServingConfig(
        slots=2, prefill_buckets=(8,), max_new_tokens=STEPS, kv_page=PAGE))
    with pytest.raises(ValueError, match="kv_swap"):
        EngineFleet({"a": one, "b": no_swap})
    other_geo = ServingEngine(params, CFG, ServingConfig(
        **{**BASE, "kv_page": 4}))
    with pytest.raises(ValueError, match="geometry"):
        EngineFleet({"a": one, "b": other_geo})
    two = ServingEngine(params, CFG, ServingConfig(**BASE))
    with pytest.raises(ValueError, match="suspect_misses"):
        EngineFleet({"a": one, "b": two},
                    FleetConfig(suspect_misses=3, dead_misses=2))
    with pytest.raises(ValueError, match="FaultPlan"):
        EngineFleet({"a": one, "b": two}, FleetConfig(faults=object()))


def test_route_policy_loading():
    assert isinstance(load_route_policy(None), LeastPressureRoutePolicy)
    # class -> instantiated; instance -> as-is; string -> imported (the
    # shed.py policy-program loading shape, byte for byte)
    assert isinstance(load_route_policy(PinPolicy), PinPolicy)
    pin = PinPolicy("b")
    assert load_route_policy(pin) is pin
    # string loading re-imports the module, so compare by behavior, not
    # class identity (pytest's import path differs from the spec's)
    loaded = load_route_policy("tests.test_fleet:PinPolicy")
    assert type(loaded).__name__ == "PinPolicy"
    assert loaded.score("a", __import__("vtpu.serving.shed",
                        fromlist=["EngineSignals"]).EngineSignals()) == 1.0
    with pytest.raises(ValueError, match="module:attr"):
        load_route_policy("no-colon")
    with pytest.raises(ValueError, match="score"):
        load_route_policy(object())


# ---------------------------------------------------------------- routing


def test_routing_prefers_least_pressure(params):
    """The default policy routes to the engine with the most free pool /
    least queue pressure; a draining engine is never a target."""
    fleet, engines = _fleet(params, names=("a", "b"))
    fleet.start()
    try:
        # occupy 'a' with two long-budget streams (pool pages + slots)
        holders = [engines["a"].submit(_prompt(50 + j), max_new_tokens=STEPS)
                   for j in range(2)]
        for r in holders:
            assert r.out.get(timeout=60) is not None  # streaming
        req = fleet.submit(P1, max_new_tokens=STEPS)
        assert fleet._assigned[req] == "b"
        assert list(req.stream())  # completes on b
        # draining engines are filtered out of routing entirely
        engines["b"]._draining = True
        try:
            req2 = fleet.submit(P1, max_new_tokens=2)
            assert fleet._assigned[req2] == "a"
            list(req2.stream())
        finally:
            engines["b"]._draining = False
        for r in holders:
            list(r.stream())
    finally:
        fleet.stop()


def test_routing_steers_off_high_duty(params):
    """ISSUE 14 satellite wiring check: attested duty (the stubbed
    calibration-mirror supplier) reaches the route policy — equal
    engines split by duty alone."""
    engines = {
        n: ServingEngine(params, CFG, ServingConfig(
            **BASE, duty_supplier=(lambda: 0.9) if n == "a" else
            (lambda: 0.05)))
        for n in ("a", "b")
    }
    fleet = EngineFleet(engines, FleetConfig(**FC))
    fleet.start()
    try:
        req = fleet.submit(P1, max_new_tokens=2)
        assert fleet._assigned[req] == "b"
        list(req.stream())
    finally:
        fleet.stop()


# --------------------------------------------------------------- failover


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_kill_one_of_three_failover_token_equal(params, refs, layout):
    """The acceptance bar: one of three engines dies without saying
    goodbye while holding two live streams and one still-waiting request
    (slots=2). Every stream finishes token-equal on a survivor —
    started sessions rebuilt from the ledger through recompute-on-fault,
    the waiting one re-queued from the fleet's assignment record —
    failover_sessions equals the dead engine's session count, and the
    corpse's and the survivors' pools audit clean (the reap; leak_check
    re-checks at teardown). Over the exact pool and the int8 one."""
    cfg = LAYOUTS[layout]
    if cfg is not CFG:
        params = init_params(jax.random.key(0), cfg)
        refs = _reference(params, cfg)
    plan = FaultPlan()
    # throttle the doomed engine's decode (~10ms/token): recompute
    # needs the history to still FIT a prefill bucket (prompt 5 +
    # bucket 8 leaves ~3 tokens of headroom), and an unthrottled engine
    # free-runs past it between the head reads and the arm() on a
    # loaded box — the death must land while the rebuild is possible
    plan.arm("delayed_fetch", count=100000, arg=0.01)
    fleet, engines = _fleet(params, faults_for={"a": plan}, cfg=cfg,
                            fc={"route_policy": PinPolicy("a")})
    fleet.start()
    try:
        reqs = [fleet.submit(p, max_new_tokens=STEPS)
                for p in (P1, P2, P3)]
        assert [fleet._assigned[r] for r in reqs] == ["a", "a", "a"]
        its = [r.stream() for r in reqs]
        # the two slotted streams deliver a couple of tokens; P3 waits
        heads = [[next(its[j]), next(its[j])] for j in (0, 1)]
        plan.arm("engine_death")  # die at the very next flush boundary
        streams = [heads[0] + list(its[0]), heads[1] + list(its[1]),
                   list(its[2])]
        assert [r.status for r in reqs] == [Status.OK] * 3
        assert streams == refs, "failover must be token-invisible"
        s = fleet.stats()
        assert s["failovers"] == 1
        assert s["failover_sessions"] == 3
        assert s["failover_faulted"] == 0
        assert s["engine_states"]["a"] == "DEAD"
        assert plan.snapshot()["injected"]["engine_death"] == 1
        # the reap restored the corpse's audit invariants, and the
        # survivors hold nothing of the sessions they finished
        for eng in engines.values():
            se = eng.stats()
            assert se["kv_pool_free"] == se["kv_pool_blocks"]
            assert se["active_slots"] == 0 and se["parked_sessions"] == 0
            assert se["swap_host_free"] == se["swap_host_blocks"]
        # survivors carried the rebuilt sessions (migrate-in counters)
        moved = sum(fleet.stats()["engines"][n]["migrations_in"]
                    for n in ("b", "c"))
        assert moved == 3
    finally:
        fleet.stop()


def test_ledger_staleness_die_between_flushes(params, refs):
    """The staleness bound: the ledger records at flush boundaries, so a
    death between flushes loses only the never-delivered in-flight
    dispatch — the rebuild resumes at exactly the last recorded (=last
    delivered) token and regenerates the rest deterministically: no
    duplicates, no gaps, whole stream token-equal."""
    plan = FaultPlan()
    # throttle the doomed engine (~30ms/token) so the client's reads
    # stay caught up with production: prompt 5 + 3 delivered tokens is
    # EXACTLY the (8,) prefill bucket — one extra free-run token and
    # the rebuild is impossible (see _can_recompute)
    plan.arm("delayed_fetch", count=100000, arg=0.03)
    fleet, engines = _fleet(params, names=("a", "b"),
                            faults_for={"a": plan},
                            fc={"route_policy": PinPolicy("a")})
    fleet.start()
    try:
        req = fleet.submit(P1, max_new_tokens=STEPS)
        it = req.stream()
        head = [next(it) for _ in range(3)]
        # the ledger now holds [.. 3 delivered tokens ..]; any dispatch
        # in flight past them dies with the engine
        plan.arm("engine_death")
        tail = list(it)
        assert head + tail == refs[0]
        assert req.status == Status.OK
        assert len(head + tail) == STEPS  # no duplicates, no gaps
        assert fleet.stats()["failover_sessions"] == 1
    finally:
        fleet.stop()


def test_cancel_racing_failover(params):
    """A client cancel landing while its engine's corpse is being failed
    over resolves to exactly one typed terminal — the fleet honors the
    abandon (CANCELLED) instead of rebuilding a stream nobody wants, and
    the sibling stream still fails over token-equal."""
    plan = FaultPlan()
    # throttled like the kill test: the death must land while both
    # streams are still mid-flight and rebuildable (prompt + delivered
    # within the (8,) prefill bucket)
    plan.arm("delayed_fetch", count=100000, arg=0.01)
    fleet, engines = _fleet(params, names=("a", "b"),
                            faults_for={"a": plan},
                            fc={"route_policy": PinPolicy("a")})
    fleet.start()
    try:
        keep = fleet.submit(P1, max_new_tokens=STEPS)
        drop = fleet.submit(P2, max_new_tokens=STEPS)
        kit, dit = keep.stream(), drop.stream()
        khead = [next(kit), next(kit)]
        next(dit)
        plan.arm("engine_death")
        drop.cancel()  # races the DEAD declaration + rebuild
        ktail = list(kit)
        list(dit)
        assert keep.status == Status.OK
        # the cancel wins the race in practice (failover waits out the
        # miss ladder); a completed-first OK is the only tolerated other
        # outcome of the race, never a hang or a double terminal
        assert drop.status in (Status.CANCELLED, Status.OK)
        ref = ServingEngine(params, CFG, ServingConfig(**BASE))
        ref.start()
        try:
            want = list(ref.submit(P1, max_new_tokens=STEPS).stream())
        finally:
            ref.stop()
        assert khead + ktail == want
    finally:
        fleet.stop()


def test_suspect_recovery_never_fails_over(params, refs):
    """Hysteresis pinned: probe_loss eats two consecutive probes of a
    HEALTHY-and-streaming engine — it goes SUSPECT (deprioritized), is
    NEVER failed over, and returns to HEALTHY on its next fresh beat
    with its stream untouched."""
    # probes walk sorted names each round: arrivals 0,2,4,... are 'a',
    # 1,3,5,... are 'b' — eat b's probes in rounds 0 and 1 only
    fleet_plan = FaultPlan([FaultSpec("probe_loss", at=1),
                            FaultSpec("probe_loss", at=3)])
    fleet, engines = _fleet(params, names=("a", "b"),
                            fc={"route_policy": PinPolicy("b"),
                                "faults": fleet_plan})
    fleet.start()
    try:
        req = fleet.submit(P1, max_new_tokens=STEPS)
        assert fleet._assigned[req] == "b"
        _wait(lambda: fleet.stats()["suspects"] >= 1,
              msg="SUSPECT transition")
        _wait(lambda: fleet.stats()["engine_states"]["b"] == "HEALTHY",
              msg="SUSPECT recovery")
        assert list(req.stream()) == refs[0]
        s = fleet.stats()
        assert req.status == Status.OK
        assert s["failovers"] == 0 and s["failover_sessions"] == 0
        assert s["probe_misses"] >= 2
        assert fleet_plan.snapshot()["injected"]["probe_loss"] == 2
    finally:
        fleet.stop()


# ------------------------------------------------------- drain + rebalance


def test_fleet_drain_routes_to_survivors(params, refs):
    """fleet.drain: the PR-12 rolling evacuation driven by the router —
    live, parked and waiting sessions all land on the best-scored
    survivor, the source ends empty with admission refused, and every
    stream completes token-equal."""
    fleet, engines = _fleet(params, fc={"route_policy": PinPolicy("a")})
    fleet.start()
    try:
        reqs = [fleet.submit(p, max_new_tokens=STEPS)
                for p in (P1, P2, P3)]
        its = [r.stream() for r in reqs]
        heads = [[next(its[0])], [next(its[1])], []]
        engines["a"].park(reqs[0])
        _wait(lambda: reqs[0] in engines["a"]._parked
              or reqs[0].status is not None, msg="park settles")
        report = fleet.drain("a")
        assert report["migrated"] >= 1 and report["faulted"] == 0
        streams = [h + list(it) for h, it in zip(heads, its)]
        assert streams == refs
        assert all(r.status == Status.OK for r in reqs)
        sa = engines["a"].stats()
        assert sa["active_slots"] == 0 and sa["parked_sessions"] == 0
        assert sa["queued"] == 0
        assert sa["kv_pool_free"] == sa["kv_pool_blocks"]
        assert fleet.stats()["failovers"] == 0  # a drain is no death
        with pytest.raises(RuntimeError, match="draining"):
            engines["a"].submit(P1)
        # the fleet front door still serves — routed around the drained
        # engine, not through it
        extra = fleet.submit(P1, max_new_tokens=2)
        assert fleet._assigned[extra] != "a"
        list(extra.stream())
    finally:
        fleet.stop()


def test_rebalance_migrates_off_pressured_engine(params, refs):
    """Background rebalancing: a pool-occupancy gap past the threshold
    moves one session per probe round from the most- to the least-
    pressured engine — transparently (the stream just keeps going) and
    counted as rebalance_migrations."""
    fleet, engines = _fleet(
        params, names=("a", "b"), fc={"route_policy": PinPolicy("a")},
        rebalance_threshold=0.2)
    fleet.start()
    try:
        req = fleet.submit(P1, max_new_tokens=STEPS)
        it = req.stream()
        head = [next(it)]
        _wait(lambda: fleet.stats()["rebalance_migrations"] >= 1,
              msg="rebalance migration")
        assert fleet._assigned[req] == "b"
        assert head + list(it) == refs[0]
        assert req.status == Status.OK
        assert fleet.stats()["engines"]["b"]["migrations_in"] >= 1
    finally:
        fleet.stop()


def test_journey_migrate_once_stitched(params, refs):
    """ISSUE 15 tentpole, cooperative half: a session that migrates once
    (fleet.migrate_session) yields ONE stitched journey span — two hops
    under the jid (route -> migrate), per-hop token counts summing to
    exactly the delivered stream (token conservation), and a migration
    blackout window between the source's last and the destination's
    first delivered token."""
    fleet, engines = _fleet(params, names=("a", "b"),
                            fc={"route_policy": PinPolicy("a")})
    fleet.start()
    try:
        req = fleet.submit(P1, max_new_tokens=STEPS)
        it = req.stream()
        head = [next(it), next(it)]
        rep = fleet.migrate_session(req, "b")
        assert rep["path"] in ("resident", "host", "recompute")
        assert head + list(it) == refs[0]
        assert req.status == Status.OK
    finally:
        fleet.stop()
    # stop() runs the final journey-end pass: the stitch is settled
    j = fleet.trace.journeys()[req.jid]
    assert j["ended"] and j["terminal"] == "OK"
    assert j["n_hops"] == 2
    assert [h["kind"] for h in j["hops"]] == ["route", "migrate"]
    assert [h["engine"] for h in j["hops"]] == ["a", "b"]
    assert all(h["tokens"] > 0 for h in j["hops"])
    # the correctness contract: per-hop tokens sum to the delivered
    # stream — nothing double-counted across the handoff, nothing lost
    assert j["tokens"] == j["delivered"] == STEPS
    assert j["conserved"] is True and j["truncated"] is False
    (b,) = j["blackouts"]
    assert b["kind"] == "migration"
    assert b["ms"] is not None and b["ms"] >= 0
    assert b["src_last_tok_ns"] <= b["dst_first_tok_ns"]
    # per-hop latency attribution is well-formed
    assert all(h["ttft_ms"] is None or h["ttft_ms"] >= 0
               for h in j["hops"])
    s = fleet.stats()
    assert s["journeys_ended"] >= 1 and s["journeys_conserved"] >= 1
    assert s["migration_blackout_p50_ms"] is not None


def test_journey_failover_stitched_with_bundle(params, refs):
    """ISSUE 15 tentpole, crash half: a session rebuilt by failover
    yields ONE journey span (route -> failover) with token conservation
    and a failover blackout window bracketing the kill — and the DEAD
    engine leaves a post-mortem bundle (flight recorder) that is
    JSON-parseable, carries the corpse's ring/stats/signals/ledger
    census, and dumps as valid JSONL. The corpse still audits clean
    (leak_check re-checks at teardown): the black box is a SNAPSHOT, the
    reap still ran."""
    import io
    import json

    plan = FaultPlan()
    # throttled like the kill test: a 2-hop journey needs the death to
    # land mid-stream with the rebuild still inside the prefill bucket
    plan.arm("delayed_fetch", count=100000, arg=0.01)
    fleet, engines = _fleet(params, names=("a", "b"),
                            faults_for={"a": plan},
                            fc={"route_policy": PinPolicy("a")})
    fleet.start()
    try:
        req = fleet.submit(P1, max_new_tokens=STEPS)
        it = req.stream()
        head = [next(it), next(it)]
        t_arm = time.monotonic_ns()
        plan.arm("engine_death")  # die at the very next flush boundary
        assert head + list(it) == refs[0]
        assert req.status == Status.OK
    finally:
        fleet.stop()
    j = fleet.trace.journeys()[req.jid]
    assert j["n_hops"] == 2
    assert [h["kind"] for h in j["hops"]] == ["route", "failover"]
    assert j["tokens"] == j["delivered"] == STEPS
    assert j["conserved"] is True and j["truncated"] is False
    (b,) = j["blackouts"]
    assert b["kind"] == "failover" and b["ms"] > 0
    # the window brackets the kill: the corpse's last delivered token
    # precedes the death (armed at t_arm, fired at the next flush), and
    # the survivor's first token follows it
    assert b["dst_first_tok_ns"] > t_arm
    assert b["src_last_tok_ns"] <= b["dst_first_tok_ns"]

    # flight recorder: the corpse's black box, snapshotted at fencing
    bundle = fleet.trace.bundles()["a"]
    assert bundle == json.loads(json.dumps(bundle)), "bundle must be JSON"
    assert bundle["engine"] == "a" and bundle["reason"] == "dead"
    assert bundle["stats"]["generated_tokens"] >= 2
    assert bundle["signals"] is not None
    census = bundle["ledger"]
    assert any(c["jid"] == req.jid and c["delivered"] >= 2
               and not c["unstarted"] for c in census)
    evs = bundle["events"]
    assert any(e["event"] == "first_token" for e in evs)
    assert isinstance(bundle["chrome"]["traceEvents"], list)
    sio = io.StringIO()
    n_lines = fleet.trace.dump_bundle("a", sio)
    lines = sio.getvalue().splitlines()
    assert n_lines == len(lines) > 2
    parsed = [json.loads(ln) for ln in lines]
    assert parsed[0]["kind"] == "postmortem"
    assert parsed[-1]["kind"] == "chrome"

    # merged chrome dump: one pid per engine + the fleet-control track,
    # with the supervision/failover control events as instants
    doc = fleet.trace.chrome_trace()
    pids = {e["pid"] for e in doc["traceEvents"]}
    assert pids >= {1, 2, 3}  # control + two engines
    instants = {e["name"] for e in doc["traceEvents"]
                if e["ph"] == "i" and e["pid"] == 1}
    assert {"route", "probe_miss", "dead", "fence",
            "failover_rebuild"} <= instants
    assert any(e["ph"] == "X" and "blackout" in e["name"]
               for e in doc["traceEvents"] if e["pid"] == 1)
    s = fleet.stats()
    assert s["postmortem_bundles"] == 1
    assert s["failover_blackout_p50_ms"] is not None
    assert s["rebuild_p50_ms"] is not None


def test_fleet_stats_and_ledger_shape(params, refs):
    """The ledger records started sessions at flush boundaries (the
    exact migrate-handshake metadata), and stats() carries the fleet
    counters plus per-engine snapshots under engine names. The engine
    decodes on while the test looks, so the entry is held to the
    reference stream at whatever boundary it was recorded, not to the
    last token the client happened to have read."""
    fleet, engines = _fleet(params, names=("a", "b"),
                            fc={"route_policy": PinPolicy("a")})
    fleet.start()
    try:
        req = fleet.submit(P1, max_new_tokens=STEPS)
        it = req.stream()
        head = [next(it), next(it)]
        _wait(lambda: req in fleet._ledger.get("a", {}),
              msg="ledger records the started session")
        with fleet._mu:
            entry = dict(fleet._ledger["a"][req])
        # the exact metadata-first handshake payload (PR 12's meta)
        assert not entry["unstarted"]
        assert entry["tokens"][:len(P1)] == P1
        said = entry["tokens"][len(P1):] + [entry["pending"]]
        assert said and said == refs[0][:len(said)]
        assert entry["seq_len"] == len(entry["tokens"])
        assert entry["hist_exact"] is True
        assert entry["n_pages"] >= 1
        s = fleet.stats()
        assert s["ledger_sessions"] >= 1
        assert set(s["engines"]) == {"a", "b"}
        assert s["engines"]["a"]["generated_tokens"] >= 2
        assert head + list(it)  # drain
    finally:
        fleet.stop()
