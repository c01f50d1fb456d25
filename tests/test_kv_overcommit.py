"""KV overcommit: page eviction, host-RAM swap, recompute-on-fault (ISSUE 6).

Fast (non-slow) tier. The contract under test, layered like the change:

- WaitQueue: the admission line's O(1)-removal structure preserves the old
  list's FIFO + tombstone semantics exactly (unit + in-engine regression);
- park/resume is lossless: a parked-then-resumed session's stream is
  TOKEN-IDENTICAL to a never-parked run — for all three restore paths
  (pages still resident; swapped to the host tier and swapped back;
  dropped and rebuilt through the prefill path) and under a ('tp',) mesh
  (the head-sharded pool swaps per-chip shards);
- eviction policy: only parked sessions' PRIVATE pages are ever reclaimed
  — blocks with live decode mappings or prefix refcounts (> 1) stay
  resident — and admission under pool exhaustion evicts instead of
  hard-parking (pool_blocked_admissions stays 0 while parked pages cover
  the shortfall);
- cancel-while-parked and cancel-racing-resume release every resource a
  parked session held (pool blocks, prefix shares, host pages);
- kv_swap=None keeps the overcommit machinery fully dormant (counters
  present but zero; park/resume refuse).
"""

import dataclasses
import time

import jax
import jax.numpy as jnp
import pytest

from vtpu.models import ModelConfig, init_params
from vtpu.serving import (
    FaultPlan,
    FaultSpec,
    ServingConfig,
    ServingEngine,
    WaitQueue,
)

CFG = ModelConfig(
    vocab=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
    max_seq=32, head_dim=16, dtype=jnp.float32, use_pallas=False,
)
PAGE = 8
# 8 keeps every session's worst-case reservation at 2 pages (prompt 5-6 +
# budget 8 <= 16 tokens), so a 2-block pool holds exactly one session and
# the second admission MUST evict the parked first
STEPS = 8
# the common serving shape: small bucket + chunked prefill, so every parked
# sequence is rebuildable (recompute-only arms NEED a rebuild route — an
# unevictable parked session is correct backpressure, not what these tests
# measure)
BASE = dict(slots=2, prefill_buckets=(8,), max_new_tokens=STEPS,
            kv_page=PAGE, prefill_chunk=8)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.key(0), CFG)


def _prompt(seed, n):
    return [int(t) for t in jax.random.randint(
        jax.random.key(seed), (n,), 1, CFG.vocab, jnp.int32)]


P1, P2 = _prompt(1, 5), _prompt(2, 6)


@pytest.fixture(scope="module")
def refs(params):
    """Never-parked reference streams for P1/P2 (unconstrained pool)."""
    eng = ServingEngine(params, CFG, ServingConfig(**BASE))
    eng.start()
    try:
        return [list(eng.submit(p, max_new_tokens=STEPS).stream())
                for p in (P1, P2)]
    finally:
        eng.stop()


# ~10 ms a token on a started engine whose stream is parked after its
# first token. The engine decodes whether or not the client reads: on a
# loaded machine an unthrottled stream of 8 tokens ends before the park is
# asked for, and parking a finished request is a no-op nothing waits out.
THROTTLE = FaultSpec("delayed_fetch", at=0, count=100000, arg=0.01)


def _wait_parked(eng, req, timeout=10.0):
    """Parks apply asynchronously at the next settled tick; block until
    this one lands (or the request finished first — a test bug)."""
    t0 = time.perf_counter()
    while req not in eng._parked:
        assert req.status is None, "request finished before the park"
        assert time.perf_counter() - t0 < timeout, "park never landed"
        time.sleep(0.002)


def _park_evict_resume(params, serving, refs):
    """The canonical overcommit exercise: park P1 early, admit P2 into a
    pool too small for both (forcing eviction of the parked pages), then
    resume P1 and drain it. Returns (stream1, stream2, stats)."""
    eng = ServingEngine(params, CFG, dataclasses.replace(
        serving, faults=FaultPlan([THROTTLE])))
    eng.start()
    try:
        r1 = eng.submit(P1, max_new_tokens=STEPS)
        it1 = r1.stream()
        got1 = [next(it1)]  # ensure >= 1 delivered: the park can settle
        eng.park(r1)
        _wait_parked(eng, r1)
        r2 = eng.submit(P2, max_new_tokens=STEPS)
        got2 = list(r2.stream())
        eng.resume(r1)
        got1 += list(it1)
        stats = eng.stats()
    finally:
        eng.stop()
    assert got1 == refs[0] and got2 == refs[1]
    return got1, got2, stats


# ------------------------------------------------------------- WaitQueue


def test_waitqueue_fifo_and_tombstones():
    """The deque+tombstone structure preserves the old list semantics:
    FIFO head/pop, O(1) removal from anywhere, iteration in FIFO order
    over live entries (tombstoned mid-iteration included), len/contains."""
    a, b, c, d = object(), object(), object(), object()
    q = WaitQueue()
    for x in (a, b, c, d):
        q.append(x)
    assert len(q) == 4 and q.head() is a
    q.remove(b)  # tombstone from the middle
    assert len(q) == 3 and b not in q and a in q
    assert list(q) == [a, c, d]
    assert q.popleft() is a
    q.remove(c)  # tombstone the (current) head
    assert q.head() is d and q.popleft() is d
    assert len(q) == 0 and not q
    # batch-coalescing pattern: tombstone entries while iterating a snapshot
    q2 = WaitQueue()
    for x in (a, b, c):
        q2.append(x)
    for x in list(q2):
        if x is not b:
            q2.remove(x)
    assert list(q2) == [b] and q2.popleft() is b
    # remove-then-append (the park-waiting/resume cycle) must not yield
    # the re-added entry twice — a duplicate would let batch coalescing
    # admit one request into two slots
    q3 = WaitQueue()
    for x in (a, b, c):
        q3.append(x)
    q3.remove(b)
    q3.append(b)
    assert list(q3) == [a, b, c] and len(q3) == 3
    assert [q3.popleft() for _ in range(3)] == [a, b, c] and not q3


def test_engine_fifo_order_with_mid_queue_cancel(params):
    """In-engine ordering regression for the WaitQueue swap: one slot, a
    3-deep line, the middle request cancelled while queued — survivors
    admit strictly FIFO and the cancelled one streams nothing."""
    serving = ServingConfig(slots=1, prefill_buckets=(8,), max_new_tokens=3)
    eng = ServingEngine(params, CFG, serving)
    try:
        reqs = [eng.submit(_prompt(30 + i, 5), max_new_tokens=3)
                for i in range(3)]
        reqs[1].cancel()
        eng.start()
        streams = [list(r.stream()) for r in reqs]
        assert streams[1] == []
        assert len(streams[0]) == 3 and len(streams[2]) == 3
        stats = eng.stats()
        assert stats["admissions"] == 2
    finally:
        eng.stop()


# ----------------------------------------------- park / resume lifecycles


def test_park_resume_resident_token_equal(params, refs):
    """No memory pressure: a parked session's pages stay pool-resident and
    resume is a pure table-row remap — stream equal to never-parked, zero
    swap traffic, park/resume counted."""
    eng = ServingEngine(params, CFG, ServingConfig(
        **BASE, kv_swap=8, faults=FaultPlan([THROTTLE])))
    eng.start()
    try:
        r1 = eng.submit(P1, max_new_tokens=STEPS)
        it1 = r1.stream()
        got = [next(it1)]
        eng.park(r1)
        _wait_parked(eng, r1)
        eng.resume(r1)
        got += list(it1)
        stats = eng.stats()
    finally:
        eng.stop()
    assert got == refs[0]
    assert stats["parks"] == 1 and stats["resumes"] == 1
    assert stats["evicted_blocks"] == 0
    assert stats["swap_out_bytes"] == 0 and stats["swap_in_bytes"] == 0
    assert stats["swap_faults"] == 0
    assert stats["kv_pool_free"] == stats["kv_pool_blocks"]


def test_eviction_swap_in_token_equal(params, refs):
    """Pool of 2 blocks, two sessions needing 2 each: admitting the second
    EVICTS the parked first to the host tier (D2H) instead of hard-parking;
    resume swaps it back (H2D). Both streams token-equal, pool drains, the
    high-water mark records full occupancy, and the decode tick's transfer
    contract survives (exactly one batched device_get per tick — the swap
    path performs no fetch on the tick path)."""
    serving = ServingConfig(**BASE, kv_pool_blocks=2, kv_swap=8)
    _, _, stats = _park_evict_resume(params, serving, refs)
    assert stats["parks"] == 1 and stats["resumes"] == 1
    assert stats["evicted_blocks"] == 2
    assert stats["swap_out_bytes"] > 0 and stats["swap_in_bytes"] > 0
    assert stats["swap_faults"] == 1 and stats["fault_recomputes"] == 0
    # eviction covered the shortfall: admission never hard-parked
    assert stats["pool_blocked_admissions"] == 0
    assert stats["kv_pool_free"] == stats["kv_pool_blocks"] == 2
    assert stats["kv_pool_used_hwm"] == 2
    assert stats["swap_host_free"] == stats["swap_host_blocks"]
    assert stats["device_gets_per_tick"] == 1.0


def test_recompute_on_fault_equals_swap_in(params, refs):
    """kv_swap=0 (no host tier): eviction DROPS the pages and resume
    rebuilds the KV through the prefill path — the recompute stream equals
    the swap-in stream (both equal the never-parked reference)."""
    swap = ServingConfig(**BASE, kv_pool_blocks=2, kv_swap=8)
    drop = ServingConfig(**BASE, kv_pool_blocks=2, kv_swap=0)
    s_swap = _park_evict_resume(params, swap, refs)
    s_drop = _park_evict_resume(params, drop, refs)
    assert s_swap[0] == s_drop[0] and s_swap[1] == s_drop[1]
    stats = s_drop[2]
    assert stats["fault_recomputes"] == 1 and stats["swap_faults"] == 1
    assert stats["swap_out_bytes"] == 0 and stats["swap_in_bytes"] == 0
    assert stats["evicted_blocks"] == 2
    assert stats["kv_pool_free"] == stats["kv_pool_blocks"]


def test_crossover_prefers_recompute_over_swap_in(params, refs):
    """kv_swap_recompute_tokens at max_seq: resume recomputes even though
    the host pages exist (re-prefilling a short sequence beats a swap-in
    round trip), and the host pages are returned unread."""
    serving = ServingConfig(**BASE, kv_pool_blocks=2, kv_swap=8,
                            kv_swap_recompute_tokens=CFG.max_seq)
    _, _, stats = _park_evict_resume(params, serving, refs)
    assert stats["fault_recomputes"] == 1
    assert stats["swap_out_bytes"] > 0  # the eviction still spilled
    assert stats["swap_in_bytes"] == 0  # ...but resume never read it back
    assert stats["swap_host_free"] == stats["swap_host_blocks"]
    assert stats["kv_pool_free"] == stats["kv_pool_blocks"]


def test_fourfold_oversubscription_takes_both_restore_paths(params):
    """One run at four times the pool: eight sessions, parked a wave of
    two at a time after two tokens each, over a pool that holds one wave
    and a host tier that holds half of what parks. Evictions past the tier
    drop, so one run restores by swap-in and by recompute; every stream
    equals the same request's on a pool with room for all, and the tick's
    one fetch stands through every park, eviction and restore."""
    ratio, slots, prompt_len, new = 4, 2, 5, 24
    pages_per = -(-(prompt_len + new) // PAGE)
    pool = slots * pages_per            # exactly one live wave fits
    n = ratio * pool // pages_per
    prompts = [_prompt(100 + i, prompt_len) for i in range(n)]
    common = dict(BASE, slots=slots, max_new_tokens=new)
    ref = ServingEngine(params, CFG, ServingConfig(**common))
    ref.start()
    try:
        want = [list(r.stream()) for r in [
            ref.submit(p, max_new_tokens=new) for p in prompts]]
    finally:
        ref.stop()
    eng = ServingEngine(params, CFG, ServingConfig(
        **common, kv_pool_blocks=pool, kv_swap=n * pages_per // 2,
        faults=FaultPlan([THROTTLE])))
    eng.start()
    try:
        reqs, got = [], []
        for w0 in range(0, n, slots):
            wave = [eng.submit(p, max_new_tokens=new)
                    for p in prompts[w0:w0 + slots]]
            got += [[r.out.get(timeout=60) for _ in range(2)] for r in wave]
            for r in wave:
                eng.park(r)
            for r in wave:
                _wait_parked(eng, r)
            reqs += wave
        for r, toks in zip(reqs, got):
            eng.resume(r)
            toks += list(r.stream())
        stats = eng.stats()
    finally:
        eng.stop()
    assert got == want
    assert stats["parks"] == stats["resumes"] == n
    assert stats["swap_out_bytes"] > 0 and stats["swap_in_bytes"] > 0
    assert stats["fault_recomputes"] > 0
    assert stats["device_gets_per_tick"] == 1.0
    assert stats["kv_pool_free"] == stats["kv_pool_blocks"] == pool
    assert stats["swap_host_free"] == stats["swap_host_blocks"]


# ------------------------------------------------- eviction policy limits


def test_prefix_shared_blocks_never_evicted(params):
    """White-box: a parked prefix-backed session holds its shared prefix
    blocks (refcount > 1) across an eviction that reclaims its private
    pages — shared blocks are never swapped, dropped, or released out from
    under the registry's live mapping."""
    serving = ServingConfig(**BASE, kv_swap=8, async_admission=False)
    eng = ServingEngine(params, CFG, serving)
    pre = list(range(1, 17))  # exactly 2 full pages: no COW boundary
    pid = eng.register_prefix(pre)  # loop not started: builds inline
    req = eng.submit([7, 8], max_new_tokens=4, prefix=pid)
    eng._tick_head()  # reserve + park on the chunked-admission path
    while eng._admitting:
        eng._advance_admissions()
    slot = eng._slot_req.index(req)
    shared = list(eng._slot_blocks[slot][:eng._slot_shared[slot]])
    assert len(shared) == 2
    assert all(eng._alloc.refcount(b) == 2 for b in shared)
    eng.park(req)
    eng._tick_head()
    entry = eng._parked[req]
    assert entry["shared"] == shared and len(entry["priv"]) >= 1
    n_priv = len(entry["priv"])
    # force a full reclaim: private pages evict, shared blocks stay mapped
    eng._reclaim(eng._alloc.n_blocks)
    assert entry["priv"] == [] and entry["host"] is not None
    assert all(eng._alloc.refcount(b) == 2 for b in shared)
    assert eng._stats["evicted_blocks"] == n_priv
    # cleanup path: cancel-while-parked releases the shares and host pages
    req.cancel()
    eng._tick_head()
    assert req not in eng._parked
    assert all(eng._alloc.refcount(b) == 1 for b in shared)  # registry only
    assert len(eng._host_free) == eng._swap_host_blocks
    eng.stop()


def test_cancel_mid_swap_and_racing_resume_release_all(params):
    """White-box cancel races: (a) cancel while the eviction's D2H is
    still in flight; (b) cancel landing between resume() and the restore.
    Both end the stream and return every block and host page."""
    import queue as _queue

    serving = ServingConfig(**BASE, kv_swap=8, async_admission=False)
    eng = ServingEngine(params, CFG, serving)
    usable = eng._n_blocks - 1

    def park_one(seed):
        req = eng.submit(_prompt(seed, 5), max_new_tokens=STEPS)
        eng._tick_head()
        eng.park(req)
        eng._tick_head()
        assert req in eng._parked
        return req

    def ended(req):
        # a cancelled stream now ends with ONE typed Terminal sentinel
        # (ISSUE 12), never a silent close or a bare None
        from vtpu.serving import Terminal
        items = []
        while True:
            try:
                items.append(req.out.get_nowait())
            except _queue.Empty:
                return (bool(items) and isinstance(items[-1], Terminal)
                        and items[-1].status == "CANCELLED"
                        and req.status == "CANCELLED")

    # (a) cancel with the snapshot still pending host-copy finalization
    req = park_one(50)
    eng._evict_entry(eng._parked[req])
    req.cancel()
    eng._tick_head()
    assert req not in eng._parked and ended(req)
    assert eng._alloc.free_blocks == usable
    assert len(eng._host_free) == eng._swap_host_blocks
    # (b) cancel racing a queued resume
    req = park_one(51)
    eng._evict_entry(eng._parked[req])
    eng.resume(req)
    req.cancel()
    eng._tick_head()
    assert req not in eng._parked and not eng._want_resume
    assert ended(req)
    assert eng._alloc.free_blocks == usable
    assert len(eng._host_free) == eng._swap_host_blocks
    eng.stop()


def test_park_before_admission_defers_and_resumes(params):
    """Parking a request still in the waiting line defers it (no pages to
    save); resume re-queues it through normal admission."""
    serving = ServingConfig(**{**BASE, "slots": 1}, kv_swap=8,
                            async_admission=False)
    eng = ServingEngine(params, CFG, serving)
    r1 = eng.submit(_prompt(60, 5), max_new_tokens=4)
    r2 = eng.submit(_prompt(61, 5), max_new_tokens=4)
    eng._tick_head()  # r1 takes the only slot; r2 waits
    eng.park(r2)
    eng._tick_head()
    assert r2 in eng._parked and eng._parked[r2].get("unstarted")
    assert r2 not in eng._waiting
    eng.resume(r2)
    eng._retire(0)  # free the slot so the re-queued r2 can admit
    eng._tick_head()
    assert eng._slot_req[0] is r2
    eng.stop()


def test_unrecomputable_entry_never_dropped_and_resident_resume(params):
    """White-box eviction-limit cases: (a) when earlier evictions consume
    the host room, a later UNRECOMPUTABLE parked entry must stay resident
    (never dropped — dropping would wedge its resume); (b) resuming a
    still-resident entry under the recompute crossover takes the free
    remap path and conserves every block (no leak, no rebuild)."""
    # no prefill_chunk and a tiny bucket: sequences past the bucket are
    # unrebuildable, so recompute_ok hinges on length alone
    serving = ServingConfig(slots=2, prefill_buckets=(8,), max_new_tokens=8,
                            kv_page=PAGE, kv_swap=2, async_admission=False,
                            kv_swap_recompute_tokens=32)
    eng = ServingEngine(params, CFG, serving)
    usable = eng._n_blocks - 1

    def park_one(seed):
        req = eng.submit(_prompt(seed, 5), max_new_tokens=8)
        eng._tick_head()
        eng.park(req)
        eng._tick_head()
        return eng._parked[req]

    e1 = park_one(90)
    e2 = park_one(91)
    # make e2 unrecomputable (as a long-sequence park would be) and ask
    # for more than the host tier can absorb: e1 spills into the 2-block
    # host room, e2 must be SKIPPED — resident, not dropped
    e2["recompute_ok"] = False
    eng._reclaim(usable + 1)
    assert e1["priv"] == [] and e1["host"] is not None and not e1["dropped"]
    assert len(e2["priv"]) == 2 and not e2["dropped"]
    # (b) resident resume under a crossover that would otherwise choose
    # recompute: the remap fast path runs, nothing reallocates or leaks
    free_before = eng._alloc.free_blocks
    eng.resume(e2["req"])
    eng._tick_head()
    slot = eng._slot_req.index(e2["req"])
    assert eng._slot_blocks[slot] and eng._alloc.free_blocks == free_before
    assert eng._stats["fault_recomputes"] == 0
    assert eng._stats["resumes"] == 1
    eng.stop()


def test_eviction_order_is_priority_then_lru(params):
    """White-box QoS contract: eviction takes the LOWEST Request.priority
    first, and least-recently-parked within a tier — a priority-9
    interactive session outlives priority-0 batch ones, and among equals
    the oldest park spills first."""
    serving = ServingConfig(**BASE, kv_swap=16, async_admission=False)
    eng = ServingEngine(params, CFG, serving)

    def park_one(seed, priority):
        req = eng.submit(_prompt(seed, 5), max_new_tokens=STEPS,
                         priority=priority)
        eng._tick_head()
        eng.park(req)
        eng._tick_head()
        return eng._parked[req]

    hi = park_one(95, priority=9)   # parked FIRST (oldest) but high QoS
    lo_old = park_one(96, priority=0)
    lo_new = park_one(97, priority=0)
    # one entry's worth of pressure: only the OLDEST low-priority evicts
    eng._reclaim(eng._alloc.free_blocks + 1)
    assert lo_old["priv"] == [] and lo_new["priv"] and hi["priv"]
    # more pressure: the younger low-priority goes next, high QoS survives
    eng._reclaim(eng._alloc.free_blocks + 1)
    assert lo_new["priv"] == [] and hi["priv"]
    eng.stop()


# --------------------------------------------------- dormant + mesh + API


def test_kv_swap_none_dormant_and_api_refusal(params):
    """kv_swap=None: the overcommit counters exist but stay zero (the
    bit-identical contract's observable half) and park/resume refuse."""
    eng = ServingEngine(params, CFG, ServingConfig(**BASE))
    stats = eng.stats()
    for key in ("parks", "resumes", "evicted_blocks", "swap_out_bytes",
                "swap_in_bytes", "swap_faults", "fault_recomputes"):
        assert stats[key] == 0
    assert stats["kv_swap"] is None and stats["parked_sessions"] == 0
    assert stats["swap_host_blocks"] is None
    req = eng.submit(_prompt(70, 4), max_new_tokens=2)
    with pytest.raises(ValueError, match="kv_swap"):
        eng.park(req)
    with pytest.raises(ValueError, match="kv_swap"):
        eng.resume(req)
    eng.stop()
    # and kv_swap without a paged pool is a config contradiction
    with pytest.raises(ValueError, match="paged"):
        ServingEngine(params, CFG, ServingConfig(
            slots=2, prefill_buckets=(8,), kv_swap=4))


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs 2 virtual devices")
def test_tp_mesh_eviction_roundtrip():
    """Eviction + swap-in compose with the ('tp',) head-sharded pool: the
    D2H snapshot gathers the head shard per chip, the H2D staging lands
    pre-sharded, and the resumed stream equals the never-parked tp run."""
    from vtpu.parallel.mesh import make_axis_mesh

    cfg = ModelConfig(
        vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_seq=32, head_dim=8, dtype=jnp.float32, use_pallas=False,
    )
    tp_params = init_params(jax.random.key(0), cfg)
    mesh = make_axis_mesh("tp", 2)
    p1 = [int(t) % cfg.vocab for t in _prompt(80, 5)]
    p2 = [int(t) % cfg.vocab for t in _prompt(81, 6)]

    eng = ServingEngine(tp_params, cfg, ServingConfig(**BASE), mesh=mesh)
    eng.start()
    try:
        want = [list(eng.submit(p, max_new_tokens=8).stream())
                for p in (p1, p2)]
    finally:
        eng.stop()
    serving = ServingConfig(**BASE, kv_pool_blocks=2, kv_swap=8,
                            faults=FaultPlan([THROTTLE]))
    eng = ServingEngine(tp_params, cfg, serving, mesh=mesh)
    eng.start()
    try:
        r1 = eng.submit(p1, max_new_tokens=8)
        it1 = r1.stream()
        got1 = [next(it1)]
        eng.park(r1)
        _wait_parked(eng, r1)
        r2 = eng.submit(p2, max_new_tokens=8)
        got2 = list(r2.stream())
        eng.resume(r1)
        got1 += list(it1)
        stats = eng.stats()
    finally:
        eng.stop()
    assert got1 == want[0] and got2 == want[1]
    assert stats["tp"] == 2
    assert stats["evicted_blocks"] > 0
    assert stats["swap_out_bytes"] > 0 and stats["swap_in_bytes"] > 0
    assert stats["kv_pool_free"] == stats["kv_pool_blocks"]
