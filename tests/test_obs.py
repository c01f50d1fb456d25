"""Observability subsystem (vtpu/obs): trace ring, tick profiler, exporter.

Fast tier. Three layers:

- unit: the bounded event ring (wraparound, ordering, drop accounting),
  the latency substrate with the ring disabled, and the phase histograms'
  Prometheus bucket shapes;
- engine: the acceptance-bar lifecycle round trip — a park -> evict ->
  swap-out -> swap-in -> resume session (and a parallel drop ->
  recompute-on-fault one) whose JSONL events reconstruct the exact span
  sequence and whose Chrome dump is valid ``trace_event`` JSON;
- lost time: the process's pause watch (a made clock for the rule; a held
  interpreter and a full collection for the classes; the thread and the
  collector's listener come and go with the engines) and the tick
  profiler's long samples (``long_ms`` / ``long_count``, the plain rule on
  a stalled fetch, ``tick_long``'s tick id);
- exporter: the coverage static check (every stats() key maps to a
  ``vtpu_serving_*`` family or is explicitly allowlisted — new engine
  counters cannot silently drift out of the exporter) and the merged
  MonitorCollector exposition staying duplicate-free.
"""

import contextlib
import gc
import io
import json
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from vtpu.models import ModelConfig, init_params
from vtpu.obs.export import (
    ALLOWLIST,
    COUNTERS,
    GAUGES,
    HIST_COUNTERS,
    SPECIAL,
    ServingCollector,
)
from vtpu.obs import pauses
from vtpu.obs.tickprof import BoundedHistogram, TickProfiler
from vtpu.obs.trace import (
    DROP_RESTORE_SEQUENCE,
    SWAP_RESTORE_SEQUENCE,
    RequestTrace,
    subsequence,
)
from vtpu.serving import ServingConfig, ServingEngine
from vtpu.serving.faults import FaultPlan, FaultSpec

CFG = ModelConfig(
    vocab=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
    max_seq=64, head_dim=16, dtype=jnp.float32, use_pallas=False,
)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.key(0), CFG)


def _prompt(seed, n):
    return [int(t) for t in jax.random.randint(
        jax.random.key(seed), (n,), 1, CFG.vocab, jnp.int32)]


# ------------------------------------------------------------------- unit


def test_trace_ring_bounded_wraparound():
    tr = RequestTrace(capacity=8)
    for i in range(20):
        tr.record("token", rid=i)
    evs = tr.snapshot()
    assert len(evs) == 8
    # oldest events fell off; the survivors are the newest, in order
    assert [e[3] for e in evs] == list(range(12, 20))
    assert [e[0] for e in evs] == sorted(e[0] for e in evs)
    assert tr.events_recorded == 20
    assert tr.events_dropped == 12
    # timestamps are monotonic_ns stamps, non-decreasing in seq order
    ts = [e[1] for e in evs]
    assert ts == sorted(ts)


def test_trace_disabled_ring_keeps_latency_substrate():
    """capacity=0 turns the event ring off, but the ITL/TTFT/queue-wait
    reservoirs stay live — stats() percentiles must never vanish when an
    operator disables event recording."""
    tr = RequestTrace(capacity=0)
    tr.record("token", rid=1)
    assert tr.snapshot() == [] and tr.events_recorded == 0
    assert tr.events_dropped == 0
    tr.note_itl(0.002)
    tr.note_ttft(0.5)
    tr.note_queue_wait(0.1)
    assert tr.itl_gaps() == [0.002]
    assert tr.ttft_samples() == [0.5]
    assert tr.queue_wait_samples() == [0.1]
    assert tr.itl_hist.count == 1 and tr.ttft_hist.count == 1


def test_span_parked_window_closes_on_retire_without_resume():
    """Cancel-while-parked retires with no resume event: the parked
    window must still fold into parked_ms (regression: it read 0.0)."""
    tr = RequestTrace(capacity=64)
    for ev, slot in (("submit", -1), ("admit", 0), ("first_token", 0),
                     ("park", 0)):
        tr.record(ev, 1, slot)
    time.sleep(0.01)
    tr.record("retire", 1)
    s = tr.spans()[1]
    assert s["parks"] == 1
    assert s["parked_ms"] >= 9.0
    assert s["retire_ns"] is not None


def test_chrome_trace_deferred_park_resume_slice_is_queued():
    """A session parked BEFORE admission resumes back into the waiting
    line: the resume..admit window must render as 'queued', not
    'streaming' (regression: every resume opened a streaming slice)."""
    tr = RequestTrace(capacity=64)
    for ev in ("submit", "park", "resume", "admit", "first_token",
               "retire"):
        tr.record(ev, 7)
        time.sleep(0.002)
    slices = [e for e in tr.chrome_trace()["traceEvents"]
              if e["ph"] == "X" and e["tid"] == 7]
    names = [e["name"] for e in sorted(slices, key=lambda e: e["ts"])]
    # queued (submit->park is still pre-admission), parked, queued again
    # (resume->admit), then streaming only from admit on
    assert names == ["queued", "parked", "queued", "streaming"]


def test_chrome_trace_pid_name_override():
    """ISSUE 15 satellite: chrome_trace() accepts pid/name/t0_ns so
    multi-engine dumps merge without rid collisions — and the DEFAULT
    output is byte-identical to the pre-override format (pid 1,
    'vtpu-serving', own-earliest-event origin)."""
    tr = RequestTrace(capacity=64)
    for ev in ("submit", "admit", "first_token", "token", "retire"):
        tr.record(ev, 3)
    default = tr.chrome_trace()
    explicit = tr.chrome_trace(pid=1, name="vtpu-serving")
    assert json.dumps(default) == json.dumps(explicit)
    assert all(e["pid"] == 1 for e in default["traceEvents"])
    meta = default["traceEvents"][0]
    assert meta["name"] == "process_name"
    assert meta["args"]["name"] == "vtpu-serving"
    # override: every event re-pids, the process renames, and a shifted
    # origin moves every timestamp by the same offset
    t0 = min(e[1] for e in tr.snapshot())
    shifted = tr.chrome_trace(pid=7, name="engine:b", t0_ns=t0 - 1_000_000)
    assert all(e["pid"] == 7 for e in shifted["traceEvents"])
    assert shifted["traceEvents"][0]["args"]["name"] == "engine:b"
    base = {(e["ph"], e["name"]): e["ts"]
            for e in default["traceEvents"] if "ts" in e}
    for e in shifted["traceEvents"]:
        if "ts" in e:
            assert e["ts"] == pytest.approx(
                base[(e["ph"], e["name"])] + 1000.0)


def test_span_first_last_token_stamps():
    """spans() exposes first/last DELIVERED token stamps (first_token OR
    token — a migrated-in hop never records first_token): the endpoints
    journey stitching measures blackout windows between."""
    tr = RequestTrace(capacity=64)
    tr.record("migrate_in", 4)
    tr.record("resume", 4)
    for _ in range(3):
        tr.record("token", 4)
        time.sleep(0.001)
    tr.record("retire", 4)
    s = tr.spans()[4]
    assert s["first_token_ns"] is None  # no first_token event on this hop
    assert s["first_tok_ns"] is not None
    assert s["last_tok_ns"] > s["first_tok_ns"]
    assert s["tokens"] == 3


def test_fleettrace_unit_ring_journeys_bundle_shapes():
    """FleetTrace unit semantics: the control ring is bounded with drop
    accounting; a two-hop journey stitches per-engine spans into one
    span with per-hop tokens, a blackout window, and the conservation
    verdict; the SLO histograms note exactly once at journey end."""
    from vtpu.obs.fleettrace import FleetTrace

    ft = FleetTrace(capacity=4)
    for i in range(10):
        ft.control("probe_miss", engine="a", val=i)
    assert ft.events_recorded == 10
    assert ft.events_dropped == 6
    assert [e["val"] for e in ft.events()] == list(range(6, 10))

    # synthetic two-engine journey: 2 tokens on 'a', 3 on 'b'
    ta, tb = RequestTrace(capacity=64), RequestTrace(capacity=64)
    ft.attach("a", ta)
    ft.attach("b", tb)
    ta.record("submit", 0)
    ta.record("first_token", 0)
    ta.record("token", 0)
    jid = ft.begin_journey("a", 0)
    assert jid >= 0
    time.sleep(0.002)
    ft.hop(jid, "b", 5, "failover")
    for _ in range(3):
        tb.record("token", 5)
    tb.record("retire", 5)
    ft.end_journey(jid, delivered=5, terminal="OK")
    ft.end_journey(jid, delivered=99, terminal="FAULTED")  # idempotent
    j = ft.journeys()[jid]
    assert j["n_hops"] == 2 and j["ended"]
    assert [h["kind"] for h in j["hops"]] == ["route", "failover"]
    assert [h["tokens"] for h in j["hops"]] == [2, 3]
    assert j["tokens"] == 5 and j["delivered"] == 5
    assert j["conserved"] is True and j["truncated"] is False
    assert j["terminal"] == "OK"
    (b,) = j["blackouts"]
    assert b["kind"] == "failover" and b["ms"] > 0
    assert ft.failover_blackout_hist.count == 1
    assert ft.migration_blackout_hist.count == 0
    assert ft.hops_hist == {2: 1}
    s = ft.stats()
    assert s["journeys_ended"] == 1 and s["journeys_conserved"] == 1
    assert s["failover_blackout_p50_ms"] == pytest.approx(b["ms"], rel=1e-3)

    # a hop whose events the ring never saw voids conservation honestly
    jid2 = ft.begin_journey("a", 777)
    ft.end_journey(jid2, delivered=4, terminal="OK")
    # single-hop journeys skip span derivation; a MISSING multi-hop rid
    # marks the stitch truncated instead of failing conservation
    jid3 = ft.begin_journey("a", 888)
    ft.hop(jid3, "b", 999, "rescue")
    ft.end_journey(jid3, delivered=4, terminal="OK")
    j3 = ft.journeys()[jid3]
    assert j3["truncated"] is True and j3["conserved"] is False

    # disabled plane: every recorder is a no-op
    off = FleetTrace(capacity=0)
    off.control("route", engine="a")
    assert off.begin_journey("a", 0) == -1
    assert off.events_recorded == 0 and off.journeys() == {}


def test_bounded_histogram_prom_buckets():
    h = BoundedHistogram(edges_ms=(1.0, 10.0, 100.0))
    for ms in (0.5, 5.0, 50.0, 500.0, 0.2):
        h.note_ms(ms)
    assert h.count == 5
    assert h.max_ms == 500.0
    buckets, total_s = h.prom_buckets()
    # cumulative counts at le=0.001s, 0.01s, 0.1s, +Inf
    assert [b[1] for b in buckets] == [2.0, 3.0, 4.0, 5.0]
    assert buckets[-1][0] == "+Inf"
    assert total_s == pytest.approx(0.5557)


def test_tick_profiler_phases():
    prof = TickProfiler()
    prof.note("dispatch", 0.001)
    prof.note("dispatch", 0.003)
    prof.note("fetch", 0.0001)
    snap = prof.snapshot()
    assert set(snap) == {"admission", "dispatch", "fetch", "deliver",
                         "swap_drain", "idle_wait"}
    assert snap["dispatch"]["count"] == 2
    assert snap["dispatch"]["mean_ms"] == pytest.approx(2.0)
    assert snap["fetch"]["count"] == 1
    assert snap["deliver"]["count"] == 0


# ------------------------------------------------- engine lifecycle trace


def test_lifecycle_round_trips_through_trace(params):
    """The acceptance bar: a park -> evict -> swap-out -> swap-in ->
    resume lifecycle round-trips through the trace — the JSONL events
    reconstruct the exact span sequence for BOTH restore paths (host-tier
    swap-in and drop + recompute-on-fault), the derived spans carry the
    parked/resume attribution, and the Chrome dump is valid
    ``trace_event`` JSON."""
    # lc_new fills the context: the park below must land while the stream
    # is still running (a finished request makes park a documented no-op),
    # so the window between reading two tokens and the park settling has
    # to cover many remaining ticks — warm-compile engines made the old
    # 24-token budget a losable race on fast boxes
    page, lc_prompt, lc_new = 8, 8, 48
    pages_per = -(-(lc_prompt + lc_new) // page)
    eng = ServingEngine(params, CFG, ServingConfig(
        slots=2, prefill_buckets=(16,), max_new_tokens=lc_new,
        prefill_chunk=16, kv_page=page, kv_pool_blocks=2 * pages_per,
        kv_swap=pages_per))  # host tier holds ONE session's pages
    eng.start()
    try:
        wave1 = [eng.submit(_prompt(900 + i, lc_prompt),
                            max_new_tokens=lc_new) for i in range(2)]
        for r in wave1:
            for _ in range(2):
                assert r.out.get(timeout=60) is not None
        # park one at a time: park order is the eviction LRU axis, so
        # wave1[0] deterministically takes the host tier and wave1[1]
        # deterministically drops
        for i, r in enumerate(wave1):
            eng.park(r)
            t0 = time.perf_counter()
            while eng.stats()["parked_sessions"] < i + 1:
                assert time.perf_counter() - t0 < 60, "park stalled"
                time.sleep(0.002)
        wave2 = [eng.submit(_prompt(910 + i, lc_prompt),
                            max_new_tokens=lc_new) for i in range(2)]
        for r in wave2:
            list(r.stream())
        for r in wave1:
            eng.resume(r)
            list(r.stream())
        stats = eng.stats()
        events = eng.trace.events()
        spans = eng.trace.spans()
        chrome = eng.trace.chrome_trace()
        jsonl = io.StringIO()
        n_written = eng.trace.to_jsonl(jsonl)
    finally:
        eng.stop()

    assert stats["swap_out_bytes"] > 0 and stats["swap_in_bytes"] > 0
    assert stats["fault_recomputes"] == 1
    by_rid = {}
    for e in events:
        by_rid.setdefault(e["rid"], []).append(e["event"])
    assert subsequence(SWAP_RESTORE_SEQUENCE, by_rid[wave1[0].rid])
    assert subsequence(DROP_RESTORE_SEQUENCE, by_rid[wave1[1].rid])
    # the dropped session must NOT report a swap-in, nor the swapped one
    # a recompute — the two restore paths stay distinguishable
    assert "swap_in" not in by_rid[wave1[1].rid]
    assert "fault_recompute" not in by_rid[wave1[0].rid]
    for r in wave1:
        s = spans[r.rid]
        assert s["tokens"] == lc_new
        assert s["parks"] == 1 and s["parked_ms"] > 0
        assert len(s["resume_latency_ms"]) == 1
        assert s["ttft_ms"] is not None and s["queue_wait_ms"] is not None
        assert s["queue_wait_ms"] <= s["ttft_ms"]
        # the park..resume silence is resume latency, never an ITL sample
        assert len(s["itl_ms"]) == lc_new - 2
    assert spans[wave1[0].rid]["swap_out_bytes"] > 0
    assert spans[wave1[0].rid]["swap_in_bytes"] > 0
    assert spans[wave1[1].rid]["fault_recomputes"] == 1

    # JSONL: one parseable record per event, same content as events()
    lines = [json.loads(ln) for ln in jsonl.getvalue().splitlines()]
    assert len(lines) == n_written == len(events)
    assert lines == events

    # Chrome dump: valid trace_event JSON — a traceEvents list whose every
    # entry carries a phase and a name (the format Perfetto loads)
    assert json.loads(json.dumps(chrome)) == chrome
    tev = chrome["traceEvents"]
    assert isinstance(tev, list) and len(tev) > 0
    assert all(isinstance(e, dict) and "ph" in e and "name" in e
               for e in tev)
    slices = [e for e in tev if e["ph"] == "X"]
    assert {"queued", "streaming", "parked"} <= {e["name"] for e in slices}
    assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in slices)


def test_device_loop_flush_trace_semantics(params):
    """Trace fidelity at decode_loop_k > 1 (ISSUE 11 satellite): per-token
    events inside a device flush share ONE host observation, so the engine
    records a ``loop_flush`` event carrying k per delivery and emits the k
    token events with interpolated-but-flagged timestamps (val=1). The
    pinned semantics: every flush-delivered token event is flagged, stamps
    are non-decreasing per request (the interpolation floors at the
    previous delivery), and derived ITL spans stay well-defined — no
    negative gaps, one span sample per decoded token."""
    k, steps = 4, 10
    eng = ServingEngine(params, CFG, ServingConfig(
        slots=2, prefill_buckets=(8,), max_new_tokens=steps,
        decode_loop_k=k))
    eng.start()
    try:
        r = eng.submit(_prompt(77, 5), max_new_tokens=steps)
        assert len(list(r.stream())) == steps
        events = eng.trace.events()
        spans = eng.trace.spans()
        stats = eng.stats()
    finally:
        eng.stop()
    flushes = [e for e in events if e["event"] == "loop_flush"]
    assert flushes and all(e["val"] == k for e in flushes)
    assert stats["loop_flushes"] == len(flushes)
    toks = [e for e in events if e["event"] == "token" and e["rid"] == r.rid]
    assert len(toks) == steps - 1  # first_token is its own (observed) event
    assert all(e["val"] == 1 for e in toks), "flush tokens must be flagged"
    ts = [e["ts_ns"] for e in toks]
    assert ts == sorted(ts), "interpolated stamps must stay monotonic"
    s = spans[r.rid]
    # 1 first_token + (steps-1) flush tokens -> steps-1 derived gaps
    assert len(s["itl_ms"]) == steps - 1
    assert all(gap >= 0 for gap in s["itl_ms"])
    # the observed events around the flush window stay un-flagged
    first = [e for e in events if e["event"] == "first_token"
             and e["rid"] == r.rid]
    assert first and first[0]["ts_ns"] <= ts[0]


def test_tick_profiler_per_tick_attribution():
    """The per-inner-tick attribution the device loop reports through
    tick_phase_ms: a note covering k ticks amortizes its duration, so
    mean_ms_per_tick == mean_ms / k while the histogram keeps the
    observed per-pass durations (Prometheus buckets unchanged)."""
    prof = TickProfiler()
    prof.note("deliver", 0.004, ticks=4)
    prof.note("deliver", 0.004, ticks=4)
    snap = prof.snapshot()["deliver"]
    assert snap["count"] == 2 and snap["ticks"] == 8
    assert snap["mean_ms"] == pytest.approx(4.0)
    assert snap["mean_ms_per_tick"] == pytest.approx(1.0)
    # default ticks=1 keeps the two means equal (the classic loop)
    prof2 = TickProfiler()
    prof2.note("fetch", 0.002)
    snap2 = prof2.snapshot()["fetch"]
    assert snap2["ticks"] == snap2["count"] == 1
    assert snap2["mean_ms_per_tick"] == snap2["mean_ms"]


def test_trace_off_engine_still_reports_percentiles(params):
    """trace_events=0: no lifecycle events, but ITL/TTFT/queue-wait
    percentiles (the reservoir views) keep flowing into stats()."""
    eng = ServingEngine(params, CFG, ServingConfig(
        slots=2, prefill_buckets=(8,), max_new_tokens=4, trace_events=0))
    eng.start()
    try:
        reqs = [eng.submit(_prompt(i, 5), max_new_tokens=4)
                for i in range(2)]
        for r in reqs:
            assert len(list(r.stream())) == 4
        stats = eng.stats()
    finally:
        eng.stop()
    assert stats["trace_enabled"] is False
    assert stats["trace_events_recorded"] == 0
    assert eng.trace.snapshot() == []
    assert stats["itl_p50_ms"] is not None
    assert stats["ttft_p50_ms"] is not None
    assert stats["queue_wait_p50_ms"] is not None
    assert stats["device_gets_per_tick"] == 1.0


def test_tracing_adds_no_fetch_and_no_sync(params):
    """What the ring costs, counted and not timed: the same requests
    through an engine with the ring off and one with it on give the same
    streams with one fetch a tick on both and the same blocking admission
    syncs (none); the arm with the ring recorded events, the other
    none."""
    prompts = [_prompt(40 + i, 5) for i in range(4)]

    def arm(trace_events):
        eng = ServingEngine(params, CFG, ServingConfig(
            slots=2, prefill_buckets=(8,), max_new_tokens=6,
            trace_events=trace_events))
        eng.start()
        try:
            reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
            return [list(r.stream()) for r in reqs], eng.stats()
        finally:
            eng.stop()

    off_streams, off = arm(0)
    on_streams, on = arm(16384)
    assert on_streams == off_streams
    assert on["device_gets_per_tick"] == off["device_gets_per_tick"] == 1.0
    assert on["admission_syncs"] == off["admission_syncs"] == 0
    assert on["trace_events_recorded"] > 0
    assert off["trace_events_recorded"] == 0


def test_fleet_plane_off_records_nothing_on_stitches_every_request(params):
    """The fleet's half of the same count, over two fleets of three that
    differ only in whether the plane is on (the engines' rings, the
    control ring, the journeys): off records no event and ends no
    journey; on ends one journey a request, every one of them with its
    hops' tokens summing to what was delivered; and neither adds a fetch
    to a tick or a sync to an admission."""
    from vtpu.serving import EngineFleet, FleetConfig

    prompts = [_prompt(60 + i, 5) for i in range(6)]

    def arm(on):
        engines = {n: ServingEngine(params, CFG, ServingConfig(
            slots=2, prefill_buckets=(8,), max_new_tokens=6, kv_page=8,
            kv_swap=4, trace_events=16384 if on else 0))
            for n in ("a", "b", "c")}
        fleet = EngineFleet(engines, FleetConfig(
            miss_ms=2000.0, trace_events=4096 if on else 0))
        fleet.start()
        try:
            reqs = [fleet.submit(p, max_new_tokens=6) for p in prompts]
            streams = [list(r.stream()) for r in reqs]
            # journeys close on the monitor's prune pass
            t0 = time.perf_counter()
            while on and fleet.stats()["journeys_ended"] < len(reqs):
                assert time.perf_counter() - t0 < 30, "journeys never ended"
                time.sleep(0.002)
            return streams, fleet.stats()
        finally:
            fleet.stop()

    off_streams, off = arm(False)
    on_streams, on = arm(True)
    assert on_streams == off_streams
    for fs in (off, on):
        assert all(s["device_gets_per_tick"] in (None, 1.0)
                   and s["admission_syncs"] == 0
                   for s in fs["engines"].values())
    assert off["fleet_trace_events_recorded"] == 0
    assert off["journeys_ended"] == 0
    assert all(s["trace_events_recorded"] == 0
               for s in off["engines"].values())
    assert on["fleet_trace_events_recorded"] > 0
    assert sum(s["trace_events_recorded"]
               for s in on["engines"].values()) > 0
    assert on["journeys_ended"] == on["journeys_conserved"] == len(prompts)


def test_shed_and_fault_events_attribute_stream_ends(params):
    """Failure-domain trace fidelity (ISSUE 12 satellite): a shed and a
    contained fault land as ``shed``/``fault`` events in the ring, the
    retire event carries the typed terminal code, and the derived spans
    say WHY each stream ended (``terminal``/``sheds``/``faults``) — the
    post-mortem a JSONL consumer reads. The Chrome dump stays valid with
    the new instants aboard."""
    from vtpu.serving import FaultPlan, FaultSpec, Status

    plan = FaultPlan([FaultSpec("dispatch_exc", at=3)])
    eng = ServingEngine(params, CFG, ServingConfig(
        slots=2, prefill_buckets=(8,), max_new_tokens=6, faults=plan))
    eng.start()
    try:
        shed = eng.submit(_prompt(40, 5), max_new_tokens=6, deadline_ms=0)
        assert list(shed.stream()) == []
        reqs = [eng.submit(_prompt(41 + i, 5), max_new_tokens=6)
                for i in range(2)]
        for r in reqs:
            list(r.stream())
        events = eng.trace.events()
        spans = eng.trace.spans()
        chrome = eng.trace.chrome_trace()
    finally:
        eng.stop()
    assert shed.status == Status.SHED_DEADLINE
    faulted = [r for r in reqs if r.status == Status.FAULTED]
    ok = [r for r in reqs if r.status == Status.OK]
    assert len(faulted) == 1 and len(ok) == 1
    by_rid = {}
    for e in events:
        by_rid.setdefault(e["rid"], []).append(e)
    assert any(e["event"] == "shed" for e in by_rid[shed.rid])
    assert any(e["event"] == "fault" for e in by_rid[faulted[0].rid])
    # retire events carry the typed terminal code the spans decode
    assert spans[shed.rid]["terminal"] == "SHED_DEADLINE"
    assert spans[shed.rid]["sheds"] == 1
    assert spans[faulted[0].rid]["terminal"] == "FAULTED"
    assert spans[faulted[0].rid]["faults"] == 1
    assert spans[ok[0].rid]["terminal"] == "OK"
    assert spans[ok[0].rid]["faults"] == 0
    # the dump stays loadable with shed/fault instants aboard
    assert json.loads(json.dumps(chrome)) == chrome
    names = {e["name"] for e in chrome["traceEvents"] if e["ph"] == "i"}
    assert {"shed", "fault"} <= names


# ------------------------------------------------------------- lost time


class _MadeClock:
    """A clock the watcher's sleeps advance: each takes the period and
    what ``late_ms`` plans for it; ``cpu`` and the collector follow the
    plan too. The sleep after the last planned one says stop."""

    def __init__(self, late_ms, cpu_ms=(), gc_ms=()):
        self.ns, self.cpu_s, self.i = 10 ** 12, 0.0, 0
        self.late_ms, self.cpu_ms, self.gc_ms = late_ms, cpu_ms, gc_ms
        self.watch = None

    def now(self):
        return self.ns

    def cpu(self):
        return self.cpu_s

    def sleep(self, seconds):
        if self.i == len(self.late_ms):
            return True
        i, self.i = self.i, self.i + 1
        gc_ms = self.gc_ms[i] if i < len(self.gc_ms) else 0.0
        if gc_ms:  # a full collection that starts 2 ms into the sleep
            self.ns += 2_000_000
            self.watch._on_gc("start", {"generation": 2})
            self.ns += int(gc_ms * 1e6)
            self.watch._on_gc("stop", {"generation": 2, "collected": 7})
            self.ns -= 2_000_000 + int(gc_ms * 1e6)
        self.ns += int(seconds * 1e9) + int(self.late_ms[i] * 1e6)
        self.cpu_s += (self.cpu_ms[i] if i < len(self.cpu_ms) else 0.0) / 1e3
        return False


def _made_watch(**plan):
    clock = _MadeClock(**plan)
    watch = pauses.PauseWatch(clock=clock.now, cpu=clock.cpu)
    clock.watch = watch
    watch.run(clock.sleep)
    return watch.snapshot()


def test_pause_watch_counts_nothing_in_a_quiet_second():
    """200 sleeps that wake on time or up to 20 ms late: no pause."""
    snap = _made_watch(late_ms=[0.0, 0.3, 4.0, 19.9, 20.0] * 40)
    assert snap["host"]["count"] == 0 and snap["host"]["total_ms"] == 0.0
    assert snap["recent"] == {"host": [], "gc": []}
    assert (snap["period_ms"], snap["late_ms"]) == (5, 20)


def test_pause_watch_notes_a_late_wake_with_what_classes_it():
    """Three pauses of 108 ms: the machine's (nothing of the process
    ran), a held interpreter (the process's CPU time grew by as much) and
    a full collection (timed by the listener inside the sleep)."""
    snap = _made_watch(late_ms=[0, 0, 108, 0, 108, 0, 108, 20.5],
                       cpu_ms=[5, 5, 0.4, 5, 112, 5, 109, 1],
                       gc_ms=[0, 0, 0, 0, 0, 0, 104])
    rows = snap["recent"]["host"]
    assert [r[1:] for r in rows] == [
        [108.0, 0.4, 0.0], [108.0, 112.0, 0.0], [108.0, 109.0, 104.0],
        [20.5, 1.0, 0.0]]
    # each begins where its sleep began: after the sleeps before it
    assert rows[0][0] == 10 ** 12 + 2 * 5_000_000
    assert rows[1][0] == rows[0][0] + 113_000_000 + 5_000_000
    assert snap["host"]["count"] == 4
    assert snap["host"]["total_ms"] == pytest.approx(3 * 108 + 20.5)
    assert snap["host"]["max_ms"] == 108.0
    assert snap["gc"]["2"] == {"count": 1, "total_ms": 104.0,
                               "max_ms": 104.0}
    assert snap["gc"]["0"]["count"] == 0
    [[start, ms, generation, collected]] = snap["recent"]["gc"]
    assert (ms, generation, collected) == (104.0, 2, 7)
    assert start == rows[2][0] + 2_000_000


def test_pause_watch_rings_hold_the_last_64():
    snap = _made_watch(late_ms=[30.0 + i for i in range(70)])
    assert snap["host"]["count"] == 70
    assert [r[1] for r in snap["recent"]["host"]] == [
        30.0 + i for i in range(6, 70)]


def _planted_pause(watch, plant, at_least_ms):
    """Run ``plant`` with the watch on; the longest pause it noted."""
    before = watch.host.count
    deadline = time.monotonic() + 10.0
    while True:
        plant()
        time.sleep(0.05)  # the watcher's wake after the hold
        rows = [r for r in watch.snapshot()["recent"]["host"]
                if r[1] >= at_least_ms]
        if watch.host.count > before and rows:
            return max(rows, key=lambda r: r[1])
        assert time.monotonic() < deadline, "the hold was never seen"


def test_pause_watch_sees_a_held_interpreter():
    """Another thread in one C call that keeps the interpreter (a sum
    over a range sized to 0.1 s): the watcher wakes late, and the process's
    CPU time grew across the pause: ``cpu_ms`` near ``ms``, no collection."""
    t0 = time.perf_counter()
    sum(range(2_000_000))
    n = int(2_000_000 * 0.1 / (time.perf_counter() - t0))
    watch = pauses.PauseWatch()
    watch.acquire()
    try:
        def hold():
            t = threading.Thread(target=lambda: sum(range(n)))
            t.start()
            t.join(timeout=30)

        start, ms, cpu_ms, gc_ms = _planted_pause(watch, hold, 50.0)
    finally:
        watch.release()
    assert cpu_ms >= 0.5 * min(ms, 100.0)
    assert gc_ms <= 0.2 * ms
    assert start <= time.monotonic_ns()


def test_pause_watch_sees_a_collection_of_a_large_heap():
    """A full collection over a large heap holds the interpreter as long
    as a stop of the machine; the listener's time inside the pause says
    whose it was: ``gc_ms`` near ``ms``."""
    shared = [[] for _ in range(64)]
    heap = [shared * 1 for _ in range(180_000)]  # 11 M references to visit
    watch = pauses.PauseWatch()
    watch.acquire()
    try:
        start, ms, cpu_ms, gc_ms = _planted_pause(watch, gc.collect, 25.0)
        snap = watch.snapshot()
    finally:
        watch.release()
        del heap
    assert gc_ms >= 0.7 * ms
    full = [r for r in snap["recent"]["gc"] if r[2] == 2]
    assert full and max(r[1] for r in full) >= 0.7 * ms
    assert snap["gc"]["2"]["count"] >= 1
    assert snap["gc"]["2"]["total_ms"] >= snap["gc"]["2"]["max_ms"] >= gc_ms


def test_pause_watch_comes_and_goes_with_its_engines(params, monkeypatch):
    """Two engines of a process share the one watch; the last to stop
    takes the listener off ``gc.callbacks`` and ends the thread, and a
    later start brings both back (twice in one process)."""
    # a watch of the test's own in the process's place: an engine that an
    # earlier test of this process left running holds the real one
    watch = pauses.PauseWatch()
    monkeypatch.setattr(pauses, "WATCH", watch)

    def listening():
        return [c for c in gc.callbacks
                if getattr(c, "__self__", None) is watch]

    threads = []
    for _ in range(2):
        a, b = (ServingEngine(params, CFG, ServingConfig(
            slots=2, prefill_buckets=(8,), max_new_tokens=4))
            for _ in range(2))
        assert not watch.running and not listening()
        a.start()
        b.start()
        try:
            assert watch.running and len(listening()) == 1
            threads.append(watch._thread)
            assert threads[-1].name == "vtpu-pause-watch"
            assert a.stats()["pauses"]["late_ms"] == 20
        finally:
            a.stop()
            assert watch.running and len(listening()) == 1
            b.stop()
        b.stop()  # idempotent: no second release
        assert not watch.running and not listening()
        assert not threads[-1].is_alive()
    assert threads[0] is not threads[1]
    # the counters outlive the thread: monotonic for the process
    assert a.stats()["pauses"]["host"]["count"] == watch.host.count


def test_long_counters_are_monotonic_and_a_windows_growth_is_the_excess():
    prof = TickProfiler(tick=lambda: 41)
    prof.note("deliver", 0.004)
    prof.note("deliver", 0.0499)  # under the rule
    prof.note("dispatch", 0.060)  # set-up's, before the window
    before = prof.snapshot()
    assert before["deliver"]["long_count"] == 0
    assert before["dispatch"]["long_ms"] == pytest.approx(60.0)
    t0 = time.monotonic_ns()
    prof.note("deliver", 0.120)
    prof.note("dispatch", 0.055)
    prof.note("swap_drain", 0.050)
    prof.note("idle_wait", 0.9)  # never long: the loop chose to wait
    prof.note("admission", 0.010)
    after = prof.snapshot()
    grown = {p: (after[p]["long_count"] - before[p]["long_count"],
                 after[p]["long_ms"] - before[p]["long_ms"])
             for p in after}
    assert grown == {
        "deliver": (1, pytest.approx(120.0)),
        "dispatch": (1, pytest.approx(55.0)),
        "swap_drain": (1, pytest.approx(50.0)),
        "idle_wait": (0, 0.0), "admission": (0, 0.0), "fetch": (0, 0.0)}
    assert after["deliver"]["max_ms"] == pytest.approx(120.0)
    rows = prof.long_snapshot()
    assert [r[0] for r in rows] == ["dispatch", "deliver", "dispatch",
                                    "swap_drain"]
    phase, tick, start_ns, ms, excess = rows[1]
    assert (tick, ms, excess) == (41, 120.0, 120.0)
    # a sample that ends now began its length ago
    assert abs(start_ns - (t0 - 120_000_000)) < 50_000_000
    for i in range(100):
        prof.note("deliver", 0.051)
    assert len(prof.long_snapshot()) == 64
    assert prof.snapshot()["deliver"]["long_count"] == 101


@pytest.mark.parametrize("k", [1, 4])
def test_plain_rule_judges_only_fetches_of_decode_steps_alone(k):
    """The rule on a made timeline, per inner tick (a k-tick flush is one
    sample of k ticks): no judgement before 16 plain samples; a fetch that
    carries chunks, or follows one that did, is never long under 1 s; a
    plain one over twice the mean plus 10 ms is, by what lies over the
    mean, and stays out of the mean."""
    launched = [0]
    prof = TickProfiler(tick=lambda: 7, prefill=lambda: launched[0])

    def fetch(ms, chunk=0):
        launched[0] += chunk
        prof.note("fetch", k * ms / 1e3, ticks=k)
        return prof.snapshot()["fetch"]

    for _ in range(15):
        fetch(10.0)
    assert fetch(200.0)["long_count"] == 0  # the 16th: no mean yet
    for _ in range(640):  # the mean forgets it: its memory is 64 samples
        fetch(10.0)
    assert fetch(29.0)["long_count"] == 0  # under 2 x 10 + 10
    # chunks of unequal length, each pass slower than any plain one
    for chunk, ms in ((512, 140.0), (512, 150.0), (131, 60.0)):
        assert fetch(ms, chunk)["long_count"] == 0
    # the loop is one tick deep: the fetch after a launch's own waits for
    # the launch
    assert fetch(150.0)["long_count"] == 0
    # a host phase beside or after a launch may block on the device's
    # queue: not judged under a second either
    launched[0] += 512
    prof.note("dispatch", 0.198)
    prof.note("admission", 0.130)
    assert fetch(150.0)["long_count"] == 0
    prof.note("dispatch", 0.080)
    assert fetch(150.0)["long_count"] == 0
    host = prof.snapshot()
    assert host["dispatch"]["long_count"] == host["admission"][
        "long_count"] == 0
    prof.note("deliver", 0.110)  # a plain pass again: a stop in deliver
    assert prof.snapshot()["deliver"]["long_ms"] == pytest.approx(110.0)
    got = fetch(118.0)  # and a stop of 108 ms in a plain fetch
    assert got["long_count"] == 1
    assert got["long_ms"] == pytest.approx(k * 108.0, rel=0.05)
    assert fetch(10.0)["long_count"] == 1
    # whatever the tick held, a second is long, whole
    got = fetch(1200.0 / k, chunk=512)
    assert got["long_count"] == 2
    assert got["long_ms"] == pytest.approx(k * 108.0 + 1200.0, rel=0.05)
    launched[0] += 512
    prof.note("admission", 1.5)
    assert prof.snapshot()["admission"]["long_ms"] == pytest.approx(1500.0)
    assert [r[:2] for r in prof.long_snapshot()] == [
        ["deliver", 7], ["fetch", 7], ["fetch", 7], ["admission", 7]]


class _Spans:
    """In the profiler spans' place: every span's name, tick and length."""

    def __init__(self):
        self.seen = []

    @contextlib.contextmanager
    def __call__(self, name, **ids):
        t0 = time.perf_counter()
        yield
        self.seen.append((name, ids.get("tick"), time.perf_counter() - t0))


def test_plain_rule_counts_a_delayed_fetch_on_decode_only_passes(params):
    """A stall of 0.3 s planted inside one fetch of a toy engine that is
    only decoding: ``long_ms`` grows by it to within 10 %, and the row of
    ``tick_long`` carries the tick id that pass's span carries."""
    plan = FaultPlan([FaultSpec("delayed_fetch", at=30, arg=0.3)])
    eng = ServingEngine(params, CFG, ServingConfig(
        slots=2, prefill_buckets=(8,), max_new_tokens=56, faults=plan))
    spans = eng.tick_profile._span = _Spans()
    eng.start()
    try:
        r = eng.submit(_prompt(3, 5), max_new_tokens=56)
        assert len(list(r.stream())) == 56
        stats = eng.stats()
    finally:
        eng.stop()
    fetch = stats["tick_phase_ms"]["fetch"]
    rows = [r for r in stats["tick_long"] if r[0] == "fetch"
            and r[3] >= 290.0]
    assert len(rows) == 1 and fetch["long_count"] >= 1
    phase, tick, start_ns, ms, excess = rows[0]
    assert 270.0 <= excess <= ms <= 345.0
    assert fetch["long_ms"] >= excess - 0.01
    stalled = [(name, t) for name, t, s in spans.seen
               if name == "vtpu.tick.fetch" and s >= 0.29]
    assert stalled == [("vtpu.tick.fetch", tick)]
    # the stall did not enter the mean a plain pass is held against
    assert eng.tick_profile._plain_mean < 100.0


class _SlowChunks(FaultPlan):
    """A device that takes 4 ms a prompt token: the fetch of a pass that
    launched a chunk, and the one after it (the loop is one tick deep),
    stall by the chunk's length."""

    def __init__(self):
        super().__init__([])
        self.engine, self.seen, self.owed, self.ticks = None, 0, 0.0, []

    def fire(self, seam):
        if seam != "delayed_fetch":
            return super().fire(seam)
        now = self.engine._stats["prefill_tokens"]
        stall, self.owed = self.owed + 0.004 * (now - self.seen), \
            0.004 * (now - self.seen)
        self.seen = now
        if not stall:
            return None
        self.ticks.append(self.engine._tick_count())
        return FaultSpec("delayed_fetch", arg=stall)


def test_plain_rule_counts_nothing_on_passes_that_carry_chunks(params):
    """A stream decoding alone, then beside a prompt that arrives in
    chunks of 16, 16 and 8: every pass with a chunk is far over twice the
    plain passes' mean plus 10 ms, and none is counted."""
    plan = _SlowChunks()
    eng = ServingEngine(params, CFG, ServingConfig(
        slots=2, prefill_buckets=(16,), prefill_chunk=16, max_new_tokens=56,
        faults=plan))
    plan.engine = eng
    eng.start()
    try:
        a = eng.submit(_prompt(4, 5), max_new_tokens=56)
        stream = a.stream()
        head = [next(stream) for _ in range(30)]  # 30 passes decoding alone
        b = eng.submit(_prompt(5, 40), max_new_tokens=4)
        assert len(list(b.stream())) == 4
        assert len(head + list(stream)) == 56
        stats = eng.stats()
    finally:
        eng.stop()
    assert stats["prefill_chunks"] >= 3
    # B's three chunks stalled their fetches and the ones after them
    assert len(plan.ticks) >= 4
    assert stats["tick_phase_ms"]["fetch"]["max_ms"] >= 60.0
    assert eng.tick_profile._plain_n >= 16
    counted = [r for r in stats["tick_long"]
               if r[0] == "fetch" and r[1] in plan.ticks]
    assert counted == []


# ---------------------------------------------------------------- exporter


def test_exporter_covers_every_stats_key(params):
    """The satellite static check: every counter/gauge stats() returns has
    a vtpu_serving_* mapping (or an explicit allowlist entry), so a new
    engine counter cannot silently drift out of the exporter."""
    eng = ServingEngine(params, CFG, ServingConfig(
        slots=2, prefill_buckets=(16,), max_new_tokens=4,
        prefill_chunk=16, kv_page=8, kv_swap=2))
    mapped = set(COUNTERS) | set(GAUGES) | set(HIST_COUNTERS) | SPECIAL \
        | ALLOWLIST
    missing = sorted(k for k in eng.stats() if k not in mapped)
    assert not missing, (
        f"stats() keys with no vtpu_serving_* family and no allowlist "
        f"entry: {missing} — map them in vtpu/obs/export.py (COUNTERS/"
        f"GAUGES/HIST_COUNTERS) or allowlist them explicitly")


def test_exporter_covers_every_fleet_stats_key(params):
    """The fleet half of the coverage check: every top-level key
    EngineFleet.stats() returns maps to a vtpu_serving_fleet_* family or
    is explicitly special/allowlisted — fleet counters cannot drift out
    of the exporter any more than engine counters can."""
    from vtpu.obs.export import (
        FLEET_ALLOWLIST, FLEET_COUNTERS, FLEET_GAUGES, FLEET_SPECIAL)
    from vtpu.serving import EngineFleet, FleetConfig

    mk = lambda: ServingEngine(params, CFG, ServingConfig(  # noqa: E731
        slots=2, prefill_buckets=(16,), max_new_tokens=4,
        kv_page=8, kv_swap=2))
    fleet = EngineFleet({"a": mk(), "b": mk()}, FleetConfig())
    mapped = set(FLEET_COUNTERS) | set(FLEET_GAUGES) | FLEET_SPECIAL \
        | FLEET_ALLOWLIST
    missing = sorted(k for k in fleet.stats() if k not in mapped)
    assert not missing, (
        f"EngineFleet.stats() keys with no vtpu_serving_fleet_* family "
        f"and no allowlist entry: {missing} — map them in "
        f"vtpu/obs/export.py (FLEET_COUNTERS/FLEET_GAUGES) or allowlist "
        f"them explicitly")


def test_fleet_families_shape(params):
    """A registered fleet exports twice: member engines join the ordinary
    vtpu_serving_* families under 'fleet/engine' labels, and the fleet
    counters/health states export as vtpu_serving_fleet_* families."""
    from vtpu.serving import EngineFleet, FleetConfig

    mk = lambda: ServingEngine(params, CFG, ServingConfig(  # noqa: E731
        slots=2, prefill_buckets=(8,), max_new_tokens=4,
        kv_page=8, kv_swap=2))
    fleet = EngineFleet({"a": mk(), "b": mk()}, FleetConfig())
    fleet.start()
    try:
        r = fleet.submit(_prompt(1, 5), max_new_tokens=4)
        assert len(list(r.stream())) == 4
        # the monitor closes journeys on its prune cadence; wait for the
        # finished stream's journey to end before scraping the hop family
        t0 = time.perf_counter()
        while fleet.stats()["journeys_ended"] < 1:
            assert time.perf_counter() - t0 < 30, "journey never ended"
            time.sleep(0.002)
        col = ServingCollector()
        col.register_fleet("f0", fleet)
        fams = list(col.collect())
    finally:
        fleet.stop()
    names = [f.name for f in fams]
    assert len(names) == len(set(names)), "duplicate family names"
    by_name = {f.name: f for f in fams}
    tokens = by_name["vtpu_serving_tokens_generated"]
    engines = {s.labels["engine"] for s in tokens.samples}
    assert engines == {"f0/a", "f0/b"}
    assert sum(s.value for s in tokens.samples) == 4.0
    probes = by_name["vtpu_serving_fleet_probes"]
    assert probes.samples[0].labels["fleet"] == "f0"
    health = by_name["vtpu_serving_fleet_engine_health"]
    assert {(s.labels["fleet"], s.labels["engine"], s.value)
            for s in health.samples} == {("f0", "a", 1.0), ("f0", "b", 1.0)}
    assert by_name["vtpu_serving_fleet_failovers"].samples[0].value == 0.0
    # the journey plane's families ride the same registration: journey
    # accounting, the hop-count counter, and the stitched-SLO histograms
    assert by_name["vtpu_serving_fleet_journeys_ended"].samples
    hops = by_name["vtpu_serving_fleet_journey_hops"]
    assert {(s.labels["hops"], s.value) for s in hops.samples} == {("1", 1.0)}
    for fam in ("fleet_failover_blackout_seconds",
                "fleet_migration_blackout_seconds", "fleet_rebuild_seconds"):
        h = by_name["vtpu_serving_" + fam]
        assert any(s.name.endswith("_bucket") for s in h.samples)
    # the engine-side ring-health gauges joined the scrape too
    cap = by_name["vtpu_serving_trace_ring_capacity"]
    assert all(s.value == 16384.0 for s in cap.samples)


def test_serving_families_shape(params):
    eng = ServingEngine(params, CFG, ServingConfig(
        slots=2, prefill_buckets=(8,), max_new_tokens=4))
    eng.start()
    try:
        r = eng.submit(_prompt(1, 5), max_new_tokens=4)
        assert len(list(r.stream())) == 4
        col = ServingCollector({"engine0": eng})
        fams = list(col.collect())
    finally:
        eng.stop()
    names = [f.name for f in fams]
    assert len(names) == len(set(names)), "duplicate family names"
    assert all(n.startswith("vtpu_serving_") for n in names)
    by_name = {f.name: f for f in fams}
    tokens = by_name["vtpu_serving_tokens_generated"]
    assert tokens.samples and tokens.samples[0].labels["engine"] == "engine0"
    assert tokens.samples[0].value == 4.0
    # span histograms ride the same scrape, with bucket samples
    ttft = by_name["vtpu_serving_ttft_seconds"]
    assert any(s.name.endswith("_bucket") for s in ttft.samples)
    assert sum(1 for s in ttft.samples if s.name.endswith("_count")) == 1
    phases = by_name["vtpu_serving_tick_phase_seconds"]
    assert {"admission", "dispatch", "fetch", "deliver", "swap_drain",
            "idle_wait"} == {
        s.labels["phase"] for s in phases.samples if "phase" in s.labels}


def test_monitor_collector_merges_serving(params, tmp_path):
    """MonitorCollector(serving=...) yields the libvtpu/region families
    AND the vtpu_serving_* set from one collect() — the single-scrape
    contract — with no duplicate family names."""
    from vtpu.monitor.lister import ContainerLister
    from vtpu.monitor.metrics import MonitorCollector

    eng = ServingEngine(params, CFG, ServingConfig(
        slots=2, prefill_buckets=(8,), max_new_tokens=4))
    (tmp_path / "containers").mkdir()
    lister = ContainerLister(str(tmp_path))
    col = MonitorCollector(lister, node_name="n1",
                           serving=ServingCollector({"e": eng}))
    fams = list(col.collect())
    names = [f.name for f in fams]
    assert len(names) == len(set(names)), "merged exposition has dup names"
    assert "vtpu_memory_used_bytes" in names
    assert "vtpu_serving_tokens_generated" in names
    assert "vtpu_serving_tick_phase_seconds" in names
