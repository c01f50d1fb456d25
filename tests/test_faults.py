"""Failure domains (ISSUE 12): typed terminals, deadlines + shedding,
crash containment, worker supervision, the fetch watchdog, and the
deterministic fault-injection plane (vtpu/serving/faults).

Fast tier. The organizing claim under test: every failure has a DOMAIN
(exactly one request, one worker, or one degraded route — never the
engine) and every seam has a SWITCH (a FaultPlan injection that drives
its recovery path reproducibly). Each test pairs one injection seam with
its promised recovery, asserts the typed terminal the affected request
ends with, and — via the conftest ``leak_check`` fixture riding every
engine-constructing test — that nothing the failure touched leaked.
"""

import dataclasses
import queue as _queue
import time

import jax
import jax.numpy as jnp
import pytest

from vtpu.models import ModelConfig, init_params
from vtpu.serving import (
    FaultPlan,
    FaultSpec,
    PriorityDeadlineShedPolicy,
    Request,
    ServingConfig,
    ServingEngine,
    Status,
    Terminal,
)
from vtpu.serving.shed import ShedPolicy, load_shed_policy

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


def _serving(**kw):
    base = dict(slots=2, prefill_buckets=(16,), max_new_tokens=6)
    base.update(kw)
    return ServingConfig(**base)


def _drain_all(reqs):
    return [list(r.stream()) for r in reqs]


# ------------------------------------------------------- typed terminals


def test_terminal_status_ok_and_cancelled(params):
    """Every stream ends with exactly one typed terminal: a clean run is
    OK, a cancel is CANCELLED — and the sentinel is a Terminal object on
    the queue, never a silent close."""
    eng = ServingEngine(params, CFG, _serving())
    eng.start()
    try:
        ok = eng.submit(_prompt(1, 5), max_new_tokens=4)
        assert len(list(ok.stream())) == 4
        assert ok.status == Status.OK
        victim = eng.submit(_prompt(2, 5), max_new_tokens=64)
        assert victim.out.get(timeout=30) is not None  # streaming
        victim.cancel()
        victim.cancel()  # idempotent
        tail = list(victim.stream())
        assert victim.status == Status.CANCELLED
        assert all(isinstance(t, int) for t in tail)
    finally:
        eng.stop()


def test_finish_idempotent_single_sentinel():
    """Request.finish delivers ONE Terminal no matter how many enders
    race it; the first status wins and later ones are dropped."""
    req = Request(tokens=jnp.zeros((1,), jnp.int32))
    assert req.finish(Status.SHED_DEADLINE) is True
    assert req.finish(Status.FAULTED) is False
    assert req.status == Status.SHED_DEADLINE
    sentinels = []
    while True:
        try:
            sentinels.append(req.out.get_nowait())
        except _queue.Empty:
            break
    assert len(sentinels) == 1
    assert isinstance(sentinels[0], Terminal)
    assert sentinels[0].status == Status.SHED_DEADLINE
    # stream() terminates on the typed sentinel (already consumed above)
    req2 = Request(tokens=jnp.zeros((1,), jnp.int32))
    req2.out.put(7)
    req2.finish(Status.OK)
    assert list(req2.stream()) == [7]


# -------------------------------------------------- deadlines + shedding


def test_deadline_shed_before_admission(params):
    """A request already past its deadline sheds from the WaitQueue
    before admission: empty stream, typed SHED_DEADLINE terminal, shed
    counter + trace event — and the line behind it is untouched."""
    eng = ServingEngine(params, CFG, _serving())
    eng.start()
    try:
        late = eng.submit(_prompt(3, 5), max_new_tokens=4, deadline_ms=0)
        live = eng.submit(_prompt(4, 5), max_new_tokens=4)
        assert list(late.stream()) == []
        assert late.status == Status.SHED_DEADLINE
        assert len(list(live.stream())) == 4
        assert live.status == Status.OK
        stats = eng.stats()
        events = {e["event"] for e in eng.trace.events()
                  if e["rid"] == late.rid}
    finally:
        eng.stop()
    assert stats["shed_deadline"] == 1
    assert stats["shed_overload"] == 0
    assert "shed" in events


def test_deadline_shed_mid_stream_at_flush_boundary(params):
    """A deadline elapsing mid-stream aborts at the next flush boundary:
    the stream is cut short with SHED_DEADLINE, tokens already delivered
    stand, and the slot frees for other traffic."""
    eng = ServingEngine(params, CFG, _serving())
    eng.start()
    try:
        req = eng.submit(_prompt(5, 5), max_new_tokens=48,
                         deadline_ms=60_000.0)
        got = [req.out.get(timeout=30) for _ in range(2)]
        assert all(isinstance(t, int) for t in got)
        # the deadline elapses mid-stream (rewound white-box so the test
        # never races engine warmup or box speed): the next tick head
        # must shed at the flush boundary
        req.deadline_ns = time.monotonic_ns() - 1
        got += list(req.stream())
        assert req.status == Status.SHED_DEADLINE
        assert 2 <= len(got) < 48
        follow = eng.submit(_prompt(6, 5), max_new_tokens=4)
        assert len(list(follow.stream())) == 4
        stats = eng.stats()
    finally:
        eng.stop()
    assert stats["shed_deadline"] == 1


def test_overload_shed_default_policy_lowest_priority_first(params):
    """shed_queue_depth bounds the waiting line; the default policy sheds
    lowest QoS first, so whatever the submission/tick interleaving, the
    highest-priority burst member is the one that survives to stream."""
    eng = ServingEngine(params, CFG, _serving(
        slots=1, shed_queue_depth=1))
    eng.start()
    try:
        hog = eng.submit(_prompt(7, 5), max_new_tokens=48)
        assert hog.out.get(timeout=30) is not None  # slot occupied
        burst = [eng.submit(_prompt(10 + i, 5), max_new_tokens=4,
                            priority=i) for i in range(4)]
        streams = _drain_all(burst)
        assert list(hog.stream()) is not None
        stats = eng.stats()
    finally:
        eng.stop()
    shed = [r for r in burst if r.status == Status.SHED_OVERLOAD]
    served = [r for r in burst if r.status == Status.OK]
    assert len(shed) == 3 and len(served) == 1
    assert served[0] is burst[-1]  # highest priority survives
    assert len(streams[-1]) == 4
    assert stats["shed_overload"] == 3


class _ShedHighestFirst(ShedPolicy):
    def select(self, waiters, need):
        return sorted(waiters, key=lambda r: -r.priority)[:need]


def test_custom_shed_policy_loads_and_applies(params):
    """The policy is a pluggable program: an instance (or class, or
    'module:attr' string) replaces the default — here an inverted policy
    sheds the HIGHEST priority, so the survivor flips."""
    # the user-loadable string form resolves classes and instances alike
    assert isinstance(load_shed_policy(
        "vtpu.serving.shed:PriorityDeadlineShedPolicy"),
        PriorityDeadlineShedPolicy)
    with pytest.raises(ValueError, match="module:attr"):
        load_shed_policy("not-a-spec")
    eng = ServingEngine(params, CFG, _serving(
        slots=1, shed_queue_depth=1, shed_policy=_ShedHighestFirst))
    eng.start()
    try:
        hog = eng.submit(_prompt(7, 5), max_new_tokens=48)
        assert hog.out.get(timeout=30) is not None
        burst = [eng.submit(_prompt(20 + i, 5), max_new_tokens=4,
                            priority=i) for i in range(4)]
        _drain_all(burst)
        list(hog.stream())
    finally:
        eng.stop()
    served = [r for r in burst if r.status == Status.OK]
    assert len(served) == 1 and served[0] is burst[0]  # lowest survives


class _BrokenPolicy(ShedPolicy):
    def select(self, waiters, need):
        raise TypeError("policy bug")


def test_broken_shed_policy_does_not_kill_the_loop(params):
    """A user-loaded policy program raising inside select() is contained
    like any other pluggable user code: the tick skips that shed pass,
    the engine keeps serving, and the line drains normally (nothing
    shed, nothing lost)."""
    eng = ServingEngine(params, CFG, _serving(
        slots=1, shed_queue_depth=1, shed_policy=_BrokenPolicy))
    eng.start()
    try:
        hog = eng.submit(_prompt(25, 5), max_new_tokens=16)
        assert hog.out.get(timeout=30) is not None
        burst = [eng.submit(_prompt(26 + i, 5), max_new_tokens=4)
                 for i in range(3)]
        streams = _drain_all(burst)
        list(hog.stream())
        stats = eng.stats()
    finally:
        eng.stop()
    assert all(r.status == Status.OK for r in burst)
    assert all(len(s) == 4 for s in streams)
    assert stats["shed_overload"] == 0


# ----------------------------------------------------- crash containment


def test_dispatch_exception_contained_to_one_request(params):
    """An exception escaping one request's deliver path retires ONLY that
    slot (typed FAULTED); the other stream is token-equal to a fault-free
    run and the engine keeps serving afterwards."""
    prompts = [_prompt(30, 5), _prompt(31, 7)]
    ref_eng = ServingEngine(params, CFG, _serving())
    ref_eng.start()
    try:
        ref = _drain_all([ref_eng.submit(p, max_new_tokens=6)
                          for p in prompts])
    finally:
        ref_eng.stop()

    plan = FaultPlan([FaultSpec("dispatch_exc", at=3)])
    eng = ServingEngine(params, CFG, _serving(faults=plan))
    eng.start()
    try:
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        streams = _drain_all(reqs)
        follow = eng.submit(_prompt(32, 5), max_new_tokens=4)
        assert len(list(follow.stream())) == 4
        stats = eng.stats()
        events = [e for e in eng.trace.events() if e["event"] == "fault"]
    finally:
        eng.stop()
    faulted = [i for i, r in enumerate(reqs) if r.status == Status.FAULTED]
    ok = [i for i, r in enumerate(reqs) if r.status == Status.OK]
    assert len(faulted) == 1 and len(ok) == 1
    assert streams[ok[0]] == ref[ok[0]]
    assert stats["faulted_requests"] == 1
    assert stats["faults_injected"] == 1
    assert events and events[0]["rid"] == reqs[faulted[0]].rid


def test_dispatch_exception_contained_under_decode_loop_k(params):
    """Containment is k-deep under the device loop: a fault in one slot's
    flush column kills only that request; the other stream stays
    token-equal to its fault-free (k=1-equal) reference."""
    prompts = [_prompt(33, 5), _prompt(34, 7)]
    ref_eng = ServingEngine(params, CFG, _serving())
    ref_eng.start()
    try:
        ref = _drain_all([ref_eng.submit(p, max_new_tokens=8)
                          for p in prompts])
    finally:
        ref_eng.stop()
    plan = FaultPlan([FaultSpec("dispatch_exc", at=2)])
    eng = ServingEngine(params, CFG, _serving(
        decode_loop_k=4, faults=plan))
    eng.start()
    try:
        reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
        streams = _drain_all(reqs)
        stats = eng.stats()
    finally:
        eng.stop()
    faulted = [i for i, r in enumerate(reqs) if r.status == Status.FAULTED]
    ok = [i for i, r in enumerate(reqs) if r.status == Status.OK]
    assert len(faulted) == 1 and len(ok) == 1
    assert streams[ok[0]] == ref[ok[0]]
    assert stats["faulted_requests"] == 1
    assert stats["decode_loop_k"] == 4


@pytest.mark.parametrize("tp", [2])
def test_dispatch_exception_contained_under_tp(params, tp):
    """Containment under a tensor-parallel paged engine: the head-sharded
    pool's blocks release exactly like single-chip (the leak_check
    fixture audits the allocator), and the surviving stream matches the
    fault-free tp run."""
    from vtpu.parallel.mesh import make_axis_mesh

    if len(jax.devices()) < tp:
        pytest.skip("needs >= 2 devices")
    mesh = make_axis_mesh("tp", tp)
    prompts = [_prompt(35, 5), _prompt(36, 7)]
    serving = _serving(kv_page=8)
    ref_eng = ServingEngine(params, CFG, serving, mesh=mesh)
    ref_eng.start()
    try:
        ref = _drain_all([ref_eng.submit(p, max_new_tokens=6)
                          for p in prompts])
    finally:
        ref_eng.stop()
    plan = FaultPlan([FaultSpec("dispatch_exc", at=3)])
    eng = ServingEngine(params, CFG, _serving(kv_page=8, faults=plan),
                        mesh=mesh)
    eng.start()
    try:
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        streams = _drain_all(reqs)
    finally:
        eng.stop()
    faulted = [i for i, r in enumerate(reqs) if r.status == Status.FAULTED]
    ok = [i for i, r in enumerate(reqs) if r.status == Status.OK]
    assert len(faulted) == 1 and len(ok) == 1
    assert streams[ok[0]] == ref[ok[0]]


# --------------------------------------------------- injection seams: pool


def test_alloc_exhaust_injection_exercises_backpressure(params):
    """Injected allocator exhaustion runs the real backpressure path —
    the admission parks, is retried, and completes token-equal to an
    uninjected run (the fault changes WHEN, never WHAT)."""
    prompts = [_prompt(40, 5)]
    serving_kw = dict(kv_page=8, kv_pool_blocks=16)
    ref_eng = ServingEngine(params, CFG, _serving(**serving_kw))
    ref_eng.start()
    try:
        ref = _drain_all([ref_eng.submit(p, max_new_tokens=6)
                          for p in prompts])
    finally:
        ref_eng.stop()
    plan = FaultPlan([FaultSpec("alloc_exhaust", at=0, count=2)])
    eng = ServingEngine(params, CFG, _serving(faults=plan, **serving_kw))
    eng.start()
    try:
        streams = _drain_all([eng.submit(p, max_new_tokens=6)
                              for p in prompts])
        stats = eng.stats()
    finally:
        eng.stop()
    assert streams == ref
    assert stats["pool_blocked_admissions"] >= 1
    assert stats["faults_injected"] >= 1


def _overcommit_serving(**kw):
    page, prompt_len, new = 8, 8, 24
    pages_per = -(-(prompt_len + new) // page)
    base = dict(slots=2, prefill_buckets=(16,), max_new_tokens=new,
                prefill_chunk=16, kv_page=page,
                kv_pool_blocks=2 * pages_per, kv_swap=2 * pages_per)
    base.update(kw)
    return ServingConfig(**base), prompt_len, new


def _park_evict_resume(params, plan):
    """One park -> evict (pool pressure) -> resume round trip under the
    given FaultPlan; returns (stream, stats, engine-free-blocks-ok)."""
    serving, prompt_len, new = _overcommit_serving(faults=plan)
    eng = ServingEngine(params, CFG, serving)
    eng.start()
    try:
        victim = eng.submit(_prompt(50, prompt_len), max_new_tokens=new)
        got = [victim.out.get(timeout=60) for _ in range(2)]
        assert all(isinstance(t, int) for t in got)
        eng.park(victim)
        t0 = time.perf_counter()
        while eng.stats()["parked_sessions"] < 1:
            assert time.perf_counter() - t0 < 60, "park stalled"
            time.sleep(0.002)
        # pool pressure: a second wave forces the parked pages out
        wave = [eng.submit(_prompt(60 + i, prompt_len), max_new_tokens=new)
                for i in range(2)]
        _drain_all(wave)
        eng.resume(victim)
        got += list(victim.stream())
        stats = eng.stats()
    finally:
        eng.stop()
    return got, stats, victim


def test_swap_d2h_loss_routes_to_recompute(params):
    """An eviction whose host spill is lost (injected D2H loss) drops the
    pages; resume rebuilds through recompute-on-fault and the stream is
    token-equal to the fault-free park/resume run."""
    ref, ref_stats, _ = _park_evict_resume(params, None)
    got, stats, victim = _park_evict_resume(
        params, FaultPlan([FaultSpec("swap_d2h_loss", at=0)]))
    assert got == ref
    assert victim.status == Status.OK
    assert stats["fault_recomputes"] >= 1
    assert stats["faults_injected"] >= 1
    # the lost spill never paid D2H bytes for the victim's pages
    assert stats["swap_out_bytes"] <= ref_stats["swap_out_bytes"]


def test_swap_h2d_loss_routes_to_recompute(params):
    """A resume whose host restore is lost (injected H2D loss) drops its
    host pages and rebuilds through prefill — token-equal, typed OK, and
    the host pool pages return (leak_check audits the engine)."""
    ref, _, _ = _park_evict_resume(params, None)
    got, stats, victim = _park_evict_resume(
        params, FaultPlan([FaultSpec("swap_h2d_loss", at=0)]))
    assert got == ref
    assert victim.status == Status.OK
    assert stats["fault_recomputes"] >= 1
    assert stats["faults_injected"] >= 1


# ------------------------------------------------- worker crash recovery


def _disagg_serving(**kw):
    from vtpu.serving import DisaggConfig

    base = dict(slots=2, prefill_buckets=(16,), max_new_tokens=6,
                prefill_chunk=16, kv_page=8,
                disagg=DisaggConfig(prefill_workers=1),
                worker_retry_backoff_ms=5.0)
    base.update(kw)
    return ServingConfig(**base)


def test_worker_death_requeues_and_restarts(params):
    """A prefill worker dying mid-claim has a one-request blast radius:
    the supervisor releases its reservation, re-queues the request
    (bounded backoff), restarts the worker, and the stream completes
    token-equal to the fault-free disagg run."""
    prompts = [_prompt(70, 12)]
    ref_eng = ServingEngine(params, CFG, _disagg_serving())
    ref_eng.start()
    try:
        ref = _drain_all([ref_eng.submit(p, max_new_tokens=6)
                          for p in prompts])
    finally:
        ref_eng.stop()
    plan = FaultPlan([FaultSpec("worker_death", at=0)])
    eng = ServingEngine(params, CFG, _disagg_serving(faults=plan))
    eng.start()
    try:
        req = eng.submit(prompts[0], max_new_tokens=6)
        stream = list(req.stream())
        stats = eng.stats()
        restarts = [e for e in eng.trace.events()
                    if e["event"] == "worker_restart"]
    finally:
        eng.stop()
    assert stream == ref[0]
    assert req.status == Status.OK
    assert stats["worker_restarts"] == 1
    assert stats["faulted_requests"] == 0
    assert restarts and restarts[0]["rid"] == req.rid


def test_worker_death_bounded_retries_then_faulted(params):
    """Past worker_retry_limit deaths the request terminates FAULTED —
    and the restarted worker serves the next request normally (the fault
    plan's schedule has run dry by then)."""
    limit = 2
    plan = FaultPlan([FaultSpec("worker_death", at=0, count=limit + 1)])
    eng = ServingEngine(params, CFG, _disagg_serving(
        faults=plan, worker_retry_limit=limit))
    eng.start()
    try:
        doomed = eng.submit(_prompt(71, 12), max_new_tokens=6)
        assert list(doomed.stream()) == []
        assert doomed.status == Status.FAULTED
        follow = eng.submit(_prompt(72, 12), max_new_tokens=6)
        assert len(list(follow.stream())) == 6
        assert follow.status == Status.OK
        stats = eng.stats()
    finally:
        eng.stop()
    assert stats["worker_restarts"] == limit + 1
    assert stats["faulted_requests"] == 1
    assert stats["faults_injected"] == limit + 1


# ------------------------------------------------------- fetch watchdog


def test_watchdog_degrades_device_loop_to_per_token(params):
    """A stalled fetch (injected delay) trips the watchdog, which clamps
    the k-tick device loop to per-token flushes — same executable, no
    recompile, stream token-equal to the classic loop."""
    prompts = [_prompt(80, 5)]
    ref_eng = ServingEngine(params, CFG, _serving())
    ref_eng.start()
    try:
        ref = _drain_all([ref_eng.submit(p, max_new_tokens=10)
                          for p in prompts])
    finally:
        ref_eng.stop()
    plan = FaultPlan([FaultSpec("delayed_fetch", at=1, arg=0.05)])
    eng = ServingEngine(params, CFG, _serving(
        decode_loop_k=4, fetch_watchdog_ms=10.0, faults=plan))
    eng.start()
    try:
        streams = _drain_all([eng.submit(p, max_new_tokens=10)
                              for p in prompts])
        stats = eng.stats()
        degrades = [e for e in eng.trace.events()
                    if e["event"] == "degrade"]
    finally:
        eng.stop()
    assert streams == ref
    assert stats["watchdog_degrades"] == 1
    assert degrades and degrades[0]["val"] == 1
    assert eng._loop_cap == 1


def test_watchdog_reroutes_paged_attn_to_gather(params):
    """The second degradation rung: a forced-kernel paged engine whose
    fetch stalls reroutes to the gather chain (token-equal by contract);
    subsequent ticks attribute to the gather counter."""
    prompts = [_prompt(81, 5)]
    serving_kw = dict(kv_page=8, max_new_tokens=12)
    ref_eng = ServingEngine(params, CFG, _serving(
        paged_attn="gather", **serving_kw))
    ref_eng.start()
    try:
        ref = _drain_all([ref_eng.submit(p, max_new_tokens=12)
                          for p in prompts])
    finally:
        ref_eng.stop()
    plan = FaultPlan([FaultSpec("delayed_fetch", at=1, arg=0.05)])
    eng = ServingEngine(params, CFG, _serving(
        paged_attn="kernel", fetch_watchdog_ms=10.0, faults=plan,
        **serving_kw))
    eng.start()
    try:
        streams = _drain_all([eng.submit(p, max_new_tokens=12)
                              for p in prompts])
        stats = eng.stats()
    finally:
        eng.stop()
    assert streams == ref
    assert stats["watchdog_degrades"] == 1
    assert stats["paged_attn_kernel_ticks"] > 0   # before the trip
    assert stats["paged_attn_gather_ticks"] > 0   # after the reroute
    assert eng._paged_attn == "gather"


def test_watchdog_recovers_device_loop_after_grace_window(params):
    """ISSUE 13 satellite: the full degrade->recover cycle on rung 1.
    A stalled fetch clamps the k-tick device loop to per-token flushes;
    once fetch latency stays under the watchdog for the
    fetch_watchdog_recover_ms grace window, the ladder un-degrades —
    the flush cap returns to k, the recovery is counted and traced, and
    the rung re-arms (a relapse can trip it again). Streams token-equal
    throughout (both transitions are lossless by contract)."""
    prompts = [_prompt(85, 5), _prompt(86, 5)]
    ref_eng = ServingEngine(params, CFG, _serving())
    ref_eng.start()
    try:
        ref = [list(ref_eng.submit(p, max_new_tokens=12).stream())
               for p in prompts]
    finally:
        ref_eng.stop()
    plan = FaultPlan([FaultSpec("delayed_fetch", at=1, arg=0.05)])
    eng = ServingEngine(params, CFG, _serving(
        decode_loop_k=4, fetch_watchdog_ms=10.0,
        fetch_watchdog_recover_ms=1.0, faults=plan))
    eng.start()
    try:
        # two sequential sessions: the first trips the degrade, and the
        # healthy fetches across both carry the recovery streak past the
        # (tiny) grace window
        streams = [list(eng.submit(p, max_new_tokens=12).stream())
                   for p in prompts]
        stats = eng.stats()
        events = [e["event"] for e in eng.trace.events()]
    finally:
        eng.stop()
    assert streams == ref
    assert stats["watchdog_degrades"] == 1
    assert stats["watchdog_recoveries"] == 1
    assert "degrade" in events and "recover" in events
    assert eng._loop_cap == eng._loop_k == 4   # the clamp lifted
    assert eng._degrade_level == 0
    assert "loop_k1" in eng._degrade_rungs     # re-armed for a relapse


def _drive_watchdog_clock(monkeypatch, plan, stall_s=0.05, step_s=0.0005):
    """Put the fetch watchdog on a clock the test drives: every reading is
    ``step_s`` after the last, and the first one after ``plan`` fired its
    delayed_fetch is ``stall_s`` later still. What the watchdog then sees
    is the plan's stall and nothing else: not how long this machine takes
    over an interpreted kernel's tick."""
    from vtpu.serving import engine as engine_mod

    now, seen = 0.0, 0

    def clock():
        nonlocal now, seen
        fired = plan.snapshot()["injected"]["delayed_fetch"]
        now += step_s + stall_s * (fired - seen)
        seen = fired
        return now

    monkeypatch.setattr(engine_mod, "_watchdog_clock", clock)


def test_watchdog_recovery_restores_paged_attn_route(params, monkeypatch):
    """The rung-2 recovery: a forced-kernel paged engine degraded to the
    gather route re-lowers BACK to the kernel once latency recovers —
    kernel ticks resume after the recovery, streams token-equal across
    both re-lowers. The watchdog reads a driven clock: back on the kernel
    route a tick is interpreted here, and by the machine's own clock one
    of 11 ms trips a watchdog of 10 a second time."""
    prompts = [_prompt(87, 5), _prompt(88, 5)]
    serving_kw = dict(kv_page=8, max_new_tokens=12)
    ref_eng = ServingEngine(params, CFG, _serving(
        paged_attn="gather", **serving_kw))
    ref_eng.start()
    try:
        ref = [list(ref_eng.submit(p, max_new_tokens=12).stream())
               for p in prompts]
    finally:
        ref_eng.stop()
    plan = FaultPlan([FaultSpec("delayed_fetch", at=1, arg=0.001)])
    _drive_watchdog_clock(monkeypatch, plan)
    eng = ServingEngine(params, CFG, _serving(
        paged_attn="kernel", fetch_watchdog_ms=10.0,
        fetch_watchdog_recover_ms=1.0, faults=plan, **serving_kw))
    eng.start()
    try:
        streams = [list(eng.submit(p, max_new_tokens=12).stream())
                   for p in prompts]
        stats = eng.stats()
    finally:
        eng.stop()
    assert streams == ref
    assert stats["watchdog_degrades"] == 1
    assert stats["watchdog_recoveries"] == 1
    assert stats["paged_attn_gather_ticks"] > 0   # while degraded
    assert eng._paged_attn == "kernel"            # the route came back


# -------------------------------------------- shed policy engine signals


def test_shed_policy_receives_engine_signals(params):
    """ISSUE 13 satellite: a three-argument policy receives the
    EngineSignals pressure snapshot (queue depth, pool free/HWM, parked
    sessions, prefill backlog) so overload victims can be chosen by
    MEMORY pressure — here, the longest-prompt waiter sheds first when
    the pool is tight."""
    from vtpu.serving import EngineSignals

    seen = []

    class MemoryPressurePolicy(ShedPolicy):
        def select(self, waiters, need, signals=None):
            seen.append(signals)
            # memory-pressure order: biggest worst-case page need first
            return sorted(
                waiters, key=lambda r: -int(r.tokens.shape[0]))[:need]

    # white-box tick driving (the _tick_head discipline the overcommit
    # suite uses): a started engine this small drains its streams faster
    # than a burst can overflow the line, so the overload is staged
    # deterministically between two manual tick heads instead
    eng = ServingEngine(params, CFG, _serving(
        slots=1, kv_page=8, kv_swap=4, prefill_chunk=8,
        prefill_buckets=(16,), shed_queue_depth=1,
        shed_policy=MemoryPressurePolicy))
    try:
        live = eng.submit(_prompt(90, 5), max_new_tokens=8)
        eng._tick_head()  # live takes the only slot
        assert eng._slot_req[0] is live
        short = eng.submit(_prompt(91, 4), max_new_tokens=2)
        long_ = eng.submit(_prompt(92, 14), max_new_tokens=2)
        eng._tick_head()  # line overflows depth 1: the policy picks
        assert eng._stats["shed_overload"] == 1
    finally:
        eng.stop()
    # the longest waiter shed (memory pressure), the short one survived
    # to the line (the stop ends it CANCELLED, never SHED)
    assert long_.status == Status.SHED_OVERLOAD
    assert short.status == Status.CANCELLED
    assert seen and all(s is not None for s in seen)
    sig = seen[0]
    assert sig.queue_depth == 2
    assert sig.active_slots == 1
    assert sig.pool_free is not None and sig.pool_used_hwm is not None
    assert sig.parked_sessions == 0
    assert sig.now_ns > 0


def test_duty_supplier_populates_engine_signals(params):
    """ISSUE 14 satellite: the attested-duty field the ROADMAP called
    'still not plumbed in'. A ServingConfig.duty_supplier (stubbed here;
    fed from the libvtpu calibration region mirror in production)
    populates EngineSignals.duty, the shed policy receives it at the
    overload seam, a raising supplier degrades to duty=None instead of
    killing anything, and a non-callable is rejected at construction."""
    seen = []

    class DutyAwarePolicy(ShedPolicy):
        def select(self, waiters, need, signals=None):
            seen.append(signals)
            return sorted(waiters, key=lambda r: r.priority)[:need]

    eng = ServingEngine(params, CFG, _serving(
        slots=1, kv_page=8, kv_swap=4, prefill_buckets=(16,),
        shed_queue_depth=1, shed_policy=DutyAwarePolicy,
        duty_supplier=lambda: 0.75))
    try:
        sig = eng.signals()
        assert sig.duty == 0.75
        assert sig.draining is False
        assert sig.pool_blocks == eng._n_blocks - 1
        # and the shed seam delivers the same snapshot to the policy
        live = eng.submit(_prompt(96, 5), max_new_tokens=8)
        eng._tick_head()  # live takes the only slot
        assert eng._slot_req[0] is live
        eng.submit(_prompt(97, 5), max_new_tokens=2, priority=5)
        drop = eng.submit(_prompt(98, 5), max_new_tokens=2, priority=0)
        eng._tick_head()  # line overflows depth 1: the policy picks
        assert eng._stats["shed_overload"] == 1
        assert drop.status == Status.SHED_OVERLOAD
        assert seen and seen[0] is not None and seen[0].duty == 0.75
    finally:
        eng.stop()

    def boom():
        raise RuntimeError("supplier unavailable")

    eng2 = ServingEngine(params, CFG, _serving(duty_supplier=boom))
    try:
        assert eng2.signals().duty is None  # degrades, never raises
    finally:
        eng2.stop()
    with pytest.raises(ValueError, match="duty_supplier"):
        ServingEngine(params, CFG, _serving(duty_supplier=0.5))


def test_legacy_two_arg_shed_policy_still_works(params):
    """Back-compat pin: a policy program written against the PR-11
    two-argument select signature keeps working — the engine detects the
    arity at construction and omits the signals. Default policy behavior
    is unchanged (signals are delivered but ignored)."""

    class LegacyPolicy:
        def select(self, waiters, need):
            return sorted(waiters, key=lambda r: r.priority)[:need]

    from vtpu.serving.shed import accepts_signals

    assert accepts_signals(LegacyPolicy()) is False
    assert accepts_signals(PriorityDeadlineShedPolicy()) is True
    eng = ServingEngine(params, CFG, _serving(
        slots=1, shed_queue_depth=1, shed_policy=LegacyPolicy))
    try:
        live = eng.submit(_prompt(93, 5), max_new_tokens=8)
        eng._tick_head()  # live takes the only slot
        keep = eng.submit(_prompt(94, 5), max_new_tokens=2, priority=5)
        drop = eng.submit(_prompt(95, 5), max_new_tokens=2, priority=0)
        eng._tick_head()  # overflow: the legacy policy sheds priority 0
        assert eng._stats["shed_overload"] == 1
    finally:
        eng.stop()
    assert drop.status == Status.SHED_OVERLOAD
    assert keep.status == Status.CANCELLED  # survived to the stop


# ---------------------------------- a schedule of faults in one engine life
#
# Every test above pairs one seam with its recovery. These run a whole
# schedule, several seams over traffic that parks, evicts, sheds, hands
# off, migrates and fails over within one life of the engines, and hold
# the run to what must be true after any of it: every request ends typed,
# a stream no fault touched says what its fault-free run said, the
# allocator, the host tier and the slots are back where they began, every
# seam the schedule configured fired, and no recovery added a fetch.

SOAK_NEW = 24   # a budget a park or a kill lands inside (8 + 24 < max_seq)
SOAK_PAGE = 8
# ~5 ms a tick: the engine decodes whether or not the client reads, and a
# stream of 24 unthrottled ticks can end between a test's two head reads
# and its park, leaving nothing to evict and a seam that never fires
THROTTLE = FaultSpec("delayed_fetch", at=0, count=100000, arg=0.005)


def _take(req, n):
    """Up to n tokens off the raw queue, fewer where a fault or a shed
    ended the stream first."""
    got = []
    while len(got) < n:
        item = req.out.get(timeout=120)
        if item is None or isinstance(item, Terminal):
            break
        got.append(item)
    return got


def _drain_typed(req, timeout=120.0):
    """The rest of the stream. By status and not by stream(): _take may
    have consumed the one Terminal already, and a second blocking get
    would wait for ever."""
    got = []
    t0 = time.perf_counter()
    while req.status is None:
        try:
            item = req.out.get(timeout=0.05)
        except _queue.Empty:
            # a leak that starves admission shows here, as a failure
            assert time.perf_counter() - t0 < timeout, "stream never ended"
            continue
        if item is None or isinstance(item, Terminal):
            break
        got.append(item)
    while True:  # tokens precede finish(): nothing arrives after this
        try:
            item = req.out.get_nowait()
        except _queue.Empty:
            return got
        if item is not None and not isinstance(item, Terminal):
            got.append(item)


def _settled(eng, timeout=60.0):
    """stats() once nothing is active, parked, queued or admitting."""
    t0 = time.perf_counter()
    while True:
        s = eng.stats()
        if (s["active_slots"] == 0 and s["parked_sessions"] == 0
                and s["queued"] == 0 and s["admitting_slots"] == 0):
            return s
        assert time.perf_counter() - t0 < timeout, "engine never settled"
        time.sleep(0.01)


def _wait_stat(eng, key, want, timeout=60.0):
    t0 = time.perf_counter()
    while eng.stats()[key] < want:
        assert time.perf_counter() - t0 < timeout, f"{key} never reached {want}"
        time.sleep(0.002)


def _serve(params, serving, prompts):
    """One engine's life over ``prompts``, each to its typed end:
    (requests, streams, the settled stats)."""
    eng = ServingEngine(params, CFG, serving)
    eng.start()
    try:
        reqs = [eng.submit(p, max_new_tokens=SOAK_NEW) for p in prompts]
        streams = [_drain_typed(r) for r in reqs]
        return reqs, streams, _settled(eng)
    finally:
        eng.stop()


def _soak_core(params):
    """A paged int8 pool with a host tier half the size of what parks:
    deadlines, an overload burst, parks, evictions whose spill is lost,
    a blocked reservation and a fault in one request's delivery."""
    cfg = dataclasses.replace(CFG, kv_int8=True)
    waves, prompt_len = 2, 8
    pages_per = -(-(prompt_len + SOAK_NEW) // SOAK_PAGE)

    def serving(faults, shed):
        return _serving(
            slots=waves, max_new_tokens=SOAK_NEW, prefill_chunk=16,
            kv_page=SOAK_PAGE, kv_pool_blocks=waves * pages_per + 1,
            kv_swap=max(waves * pages_per // 2, 1),
            shed_queue_depth=2 if shed else 0, faults=faults)

    def traffic(eng, chaos):
        """The same submits in the same order in both arms; the chaos arm
        has two requests past their deadline before them."""
        reqs, streams = [], []

        def submit(seed, **kw):
            reqs.append(eng.submit(_prompt(seed, prompt_len),
                                   max_new_tokens=SOAK_NEW, **kw))
            streams.append([])
            return len(reqs) - 1

        late = [submit(500 + j, deadline_ms=0) for j in range(2 * chaos)]
        wave1 = [submit(100 + j, priority=5) for j in range(waves)]
        for i in wave1:
            streams[i] += _take(reqs[i], 2)
        # the burst goes in while every slot is busy: with a bounded line
        # the lowest priorities are shed at the next tick head, before
        # the parks below free a slot
        for j in range(2 + waves):
            submit(600 + j, priority=0)
        if chaos:
            _wait_stat(eng, "shed_overload", 1)
        live = [i for i in wave1 if reqs[i].status is None]
        for i in live:
            eng.park(reqs[i])
        _wait_stat(eng, "parked_sessions", len(live))
        # a second wave and what is left of the burst press the parked
        # pages out: spilled, or dropped where the spill is lost
        for j in range(waves):
            submit(200 + j, priority=5)
        for i in live:
            eng.resume(reqs[i])
        for i, req in enumerate(reqs):
            streams[i] += _drain_typed(req)
        return reqs, streams, late

    ref_eng = ServingEngine(params, cfg, serving(FaultPlan([THROTTLE]), False))
    ref_eng.start()
    try:
        _, want, _ = traffic(ref_eng, chaos=0)
    finally:
        ref_eng.stop()
    # the seams the run is held to are pinned to arrivals that exist at
    # any load; the seeded part lays reproducible chaos over them, and
    # whatever it hits has to pass the same checks
    plan = FaultPlan(
        [THROTTLE,
         FaultSpec("alloc_exhaust", at=0),   # the first reservation blocks
         FaultSpec("swap_d2h_loss", at=0),   # the first eviction's spill
         FaultSpec("dispatch_exc", at=9)]    # one delivery in mid-wave
        + list(FaultPlan.seeded(0, rates={
            "alloc_exhaust": 0.05, "swap_d2h_loss": 0.3,
            "swap_h2d_loss": 0.5}).specs))
    eng = ServingEngine(params, cfg, serving(plan, True))
    eng.start()
    try:
        reqs, streams, late = traffic(eng, chaos=1)
        settled = _settled(eng)
    finally:
        eng.stop()
    assert all(reqs[i].status == Status.SHED_DEADLINE for i in late)
    assert settled["shed_overload"] >= 1
    assert settled["fault_recomputes"] >= 1
    return dict(
        reqs=reqs, streams=streams, want=[None] * len(late) + want,
        settled={"engine": settled}, plans=[plan],
        seams=("alloc_exhaust", "swap_d2h_loss", "dispatch_exc"),
        gets_per_tick={"engine": (1.0,)})


def _soak_disagg(params):
    """The one prefill worker dies with a request claimed."""
    n = 2
    prompts = [_prompt(300 + j, 8) for j in range(n)]

    def run(faults):
        return _serve(params, _disagg_serving(
            max_new_tokens=SOAK_NEW, faults=faults), prompts)

    _, want, _ = run(None)
    plan = FaultPlan([FaultSpec("worker_death", at=0)])
    reqs, streams, settled = run(plan)
    assert all(r.status == Status.OK for r in reqs)
    assert settled["worker_restarts"] == 1
    assert settled["faulted_requests"] == 0
    assert settled["handoffs"] == n and settled["handoff_copies"] == 0
    return dict(reqs=reqs, streams=streams, want=want,
                settled={"engine": settled}, plans=[plan],
                seams=("worker_death",), gets_per_tick={"engine": (1.0,)})


def _soak_device_loop(params):
    """Under the two-tick device loop a fetch stalls past the watchdog
    and, flushes later, one request's delivery faults."""
    k, n = 2, 2
    prompts = [_prompt(400 + j, 8) for j in range(n)]

    def run(faults, wd):
        return _serve(params, _serving(
            max_new_tokens=SOAK_NEW, decode_loop_k=k, fetch_watchdog_ms=wd,
            faults=faults), prompts)

    _, want, _ = run(None, 0.0)
    plan = FaultPlan([FaultSpec("delayed_fetch", at=2, arg=0.03),
                      FaultSpec("dispatch_exc", at=5)])
    reqs, streams, settled = run(plan, 8.0)
    assert sorted(r.status for r in reqs) == sorted(
        [Status.OK] * (n - 1) + [Status.FAULTED])
    assert settled["watchdog_degrades"] >= 1
    # decode_ticks counts inner ticks after the degrade clamps a flush
    # too: the contract stays one fetch for k of them
    return dict(reqs=reqs, streams=streams, want=want,
                settled={"engine": settled}, plans=[plan],
                seams=("delayed_fetch", "dispatch_exc"),
                gets_per_tick={"engine": (round(1 / k, 4),)})


def _soak_migrate(params):
    """The first of three migrations loses its source after the metadata
    handshake; the destination rebuilds that session from its history."""
    from vtpu.serving import migrate

    n = 3
    prompts = [_prompt(700 + j, 8) for j in range(n)]

    def serving(faults=None):
        return _serving(slots=n, max_new_tokens=SOAK_NEW, prefill_chunk=16,
                        kv_page=SOAK_PAGE, kv_swap=8, faults=faults)

    _, want, _ = _serve(params, serving(), prompts)
    plan = FaultPlan([THROTTLE, FaultSpec("migrate_src_death", at=0)])
    src = ServingEngine(params, CFG, serving(plan))
    dst = ServingEngine(params, CFG, serving())
    src.start()
    dst.start()
    try:
        reqs = [src.submit(p, max_new_tokens=SOAK_NEW) for p in prompts]
        streams = [_take(r, 2) for r in reqs]
        # parked first: a parked session cannot finish, so the order of
        # extraction, and which session the seam hits, is the list's
        for r in reqs:
            src.park(r)
        _wait_stat(src, "parked_sessions", n)
        paths = [migrate(r, src, dst)["path"] for r in reqs]
        for j, r in enumerate(reqs):
            streams[j] += _drain_typed(r)
        settled = {"src": _settled(src), "dst": _settled(dst)}
    finally:
        src.stop()
        dst.stop()
    assert all(r.status == Status.OK for r in reqs)
    assert paths == ["recompute"] + ["resident"] * (n - 1)
    assert settled["dst"]["migrate_recomputes"] >= 1
    assert settled["src"]["migration_copies"] == 0
    assert settled["dst"]["migration_copies"] == 0
    return dict(reqs=reqs, streams=streams, want=want, settled=settled,
                plans=[plan], seams=("migrate_src_death",),
                gets_per_tick={"src": (None, 1.0), "dst": (1.0,)})


def _soak_fleet(params):
    """One engine of three dies with every session of the fleet on it."""
    from vtpu.obs.fleettrace import validate_bundle
    from vtpu.serving import EngineFleet, FleetConfig, RoutePolicy

    class PinA(RoutePolicy):
        def score(self, name, signals):
            if signals.draining:
                return None
            return 1.0 if name == "a" else 0.0

    n = 2
    prompts = [_prompt(800 + j, 8) for j in range(n)]

    def serving(faults=None):
        return _serving(slots=n, max_new_tokens=SOAK_NEW, prefill_chunk=16,
                        kv_page=SOAK_PAGE, kv_swap=8, faults=faults)

    _, want, _ = _serve(params, serving(), prompts)
    plan = FaultPlan([THROTTLE])
    engines = {"a": ServingEngine(params, CFG, serving(plan)),
               "b": ServingEngine(params, CFG, serving()),
               "c": ServingEngine(params, CFG, serving())}
    # a wide window for a miss: a live loop that a loaded machine starves
    # for a second must not be declared dead
    fleet = EngineFleet(engines, FleetConfig(
        probe_interval_ms=5.0, miss_ms=2000.0, suspect_misses=2,
        dead_misses=4, route_policy=PinA))
    fleet.start()
    try:
        reqs = [fleet.submit(p, max_new_tokens=SOAK_NEW) for p in prompts]
        streams = [_take(r, 2) for r in reqs]
        plan.arm("engine_death")  # the next flush boundary kills 'a'
        for j, r in enumerate(reqs):
            streams[j] += _drain_typed(r)
        fs = fleet.stats()
        # the corpse too: the fleet's reap gave back what it held
        settled = {name: _settled(eng) for name, eng in engines.items()}
    finally:
        fleet.stop()
    assert all(r.status == Status.OK for r in reqs)
    assert fs["failovers"] == 1 and fs["failover_sessions"] == n
    assert fs["failover_faulted"] == 0
    assert fs["engine_states"]["a"] == "DEAD"
    journeys = fleet.trace.journeys()
    assert all(journeys[r.jid]["conserved"] is True
               and journeys[r.jid]["n_hops"] == 2 for r in reqs)
    assert validate_bundle(fleet.trace.bundles().get("a"))
    # the survivors only: the corpse died with a tick dispatched and never
    # fetched, which is what a crash loses, so its own ratio reads short
    return dict(reqs=reqs, streams=streams, want=want, settled=settled,
                plans=[plan], seams=("engine_death",),
                gets_per_tick={"b": (None, 1.0), "c": (None, 1.0)})


@pytest.mark.parametrize("soak", [
    _soak_core, _soak_disagg, _soak_device_loop, _soak_migrate, _soak_fleet,
], ids=["core", "disagg", "device_loop", "migrate", "fleet"])
def test_seeded_schedule_across_seams_leaves_nothing_behind(params, soak):
    run = soak(params)
    # every request ends with a type, never with a silent close
    assert all(r.status in Status.ALL for r in run["reqs"])
    # a fault changes when and who, never what an untouched stream says
    untouched = [i for i, r in enumerate(run["reqs"])
                 if r.status == Status.OK and run["want"][i] is not None]
    assert untouched
    for i in untouched:
        assert run["streams"][i] == run["want"][i], f"stream {i} diverged"
    # the allocator, the host tier and the slots are back where they began
    for name, s in run["settled"].items():
        assert s["kv_pool_free"] == s["kv_pool_blocks"], name
        assert s["swap_host_free"] == s["swap_host_blocks"], name
        assert s["active_slots"] == 0 and s["parked_sessions"] == 0, name
    # every seam the schedule configured fired
    for seam in run["seams"]:
        assert sum(p.snapshot()["injected"].get(seam, 0)
                   for p in run["plans"]) >= 1, seam
    # and no recovery path added a fetch
    for name, allowed in run["gets_per_tick"].items():
        assert run["settled"][name]["device_gets_per_tick"] in allowed, name


# ------------------------------------------------------- FaultPlan unit


def test_fault_plan_schedule_and_counters():
    plan = FaultPlan([FaultSpec("dispatch_exc", at=1, count=2),
                      FaultSpec("delayed_fetch", at=0, arg=0.25)])
    assert plan.fire("dispatch_exc") is None          # arrival 0
    assert plan.fire("dispatch_exc") is not None      # arrival 1
    assert plan.fire("dispatch_exc") is not None      # arrival 2
    assert plan.fire("dispatch_exc") is None          # arrival 3
    spec = plan.fire("delayed_fetch")
    assert spec is not None and spec.arg == 0.25
    snap = plan.snapshot()
    assert snap["arrivals"]["dispatch_exc"] == 4
    assert snap["injected"]["dispatch_exc"] == 2
    assert plan.injected_total == 3
    with pytest.raises(ValueError, match="unknown fault seam"):
        FaultSpec("nope")


def test_fault_plan_seeded_is_deterministic():
    """The seeded schedule is a pure function of (seed, rates): two plans
    from the same seed fire at identical arrival indices; a different
    seed yields a different schedule (at these rates, overwhelmingly)."""
    rates = {"dispatch_exc": 0.3, "alloc_exhaust": 0.2}

    def fire_pattern(plan, n=64):
        return [(s, i) for s in sorted(rates)
                for i in range(n) if plan._sched[s].get(i)]

    a = FaultPlan.seeded(7, rates)
    b = FaultPlan.seeded(7, rates)
    c = FaultPlan.seeded(8, rates)
    assert fire_pattern(a) == fire_pattern(b)
    assert fire_pattern(a) != fire_pattern(c)
    assert a.injected_total == 0  # schedules don't count until fired
