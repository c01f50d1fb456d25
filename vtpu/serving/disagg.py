"""Disaggregated prefill/decode: role-split serving over the shared block pool.

The co-scheduled loop runs admission prefill and decode on ONE thread, so
under bursty admission TTFT p99 and background ITL p99 compete for the same
tick — ``ServingConfig.prefill_budget`` rations the conflict but cannot
remove it, and admission is gated on a FREE DECODE SLOT even though prefill
itself needs none. This module is the HAMi-style move applied to the data
plane: carve the one physical engine into role-specialized virtual workers
coordinated through shared state — here the PR-4/5 paged block pool.

Roles:

- ``PrefillWorker`` (one or more threads) drains the admission ``WaitQueue``
  and runs chunked prefill DIRECTLY into freshly allocated pool blocks with
  no slot and no page-table row — the exact ``register_prefix`` build
  discipline (``chunked_prefill_into_slot`` with explicit ``block_ids`` and
  the out-of-range slot sentinel, see vtpu/serving/adapters.py). The first
  token is sampled on device from the final chunk's logits and DELIVERED to
  the client straight from the worker: TTFT no longer waits for a decode
  slot to free. The filled blocks plus the pending first token form a
  handoff entry (the same shape as an overcommit parked entry).

- The decode loop INSTALLS handoffs: one fused table-row write maps the
  already-filled blocks into a freed slot and the session continues through
  the existing one-fetch decode tick. The install moves ZERO KV bytes —
  ``handoff_copies == 0`` is the contract, the same bar as
  ``prefix_install_copies`` — and the decode side's
  ``device_gets_per_tick == 1.0`` audit is untouched (worker fetches are
  its own thread's, counted like admission fetches).

- ``DisaggController`` re-partitions prefill vs decode capacity under load
  shifts: a token bucket refilled once per decode tick whose share steps
  between a floor (steady decode: prefill trickles) and a ceiling (burst
  backlog: prefill floods), bypassed entirely while nothing is decoding.
  Level changes are counted as ``repartitions``.

Pool-ownership rules (what makes a handoff racing an eviction safe):

- a worker's freshly allocated private blocks are refcount-1 and appear in
  NO parked entry, so the overcommit eviction policy (which only ever
  reclaims parked sessions' private pages) can never touch them;
- shared prefix blocks are mapped via ``share()`` (refcount > 1) and are
  never evicted by construction;
- on allocator exhaustion the worker never evicts on its own thread — it
  posts the needed block count and the loop thread (the parked-state
  owner) runs the reclaim at the next tick head.

Device-state discipline: every worker dispatch that consumes the engine's
donated pool state runs under the engine's state mutex, serialized against
the loop's tick-head + dispatch section. The loop releases the mutex before
its blocking fetch, so worker prefill dispatches interleave with decode at
block granularity — the controller's share is what bounds the ITL impact.

``ServingConfig.disagg = None`` keeps all of this dormant: no worker
threads, no lock contention on the loop, streams bit-identical to the
co-scheduled engine.
"""

from __future__ import annotations

import collections
import dataclasses
import logging
import threading
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from vtpu.obs.trace import TERMINAL_CODES
from vtpu.serving.faults import WorkerDeath

log = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class DisaggConfig:
    """Prefill/decode disaggregation knobs (``ServingConfig.disagg``).

    The capacity partition is denominated in prompt tokens per decode tick
    — the same unit as ``prefill_budget`` — but DYNAMIC: the controller
    steps the share between ``min_prefill_tokens`` (steady decode, empty
    backlog) and ``max_prefill_tokens`` (burst: backlog at or past
    ``backlog_high``), so a burst of new sessions gets prefill capacity
    exactly while it exists and live streams get it back the moment the
    backlog drains.

    Determinism: ``disagg=None`` is bit-identical to the co-scheduled
    loop. With disagg ON, greedy streams are token-equal to co-scheduled
    ones (pinned by tests/test_disagg.py); at ``temperature > 0`` first
    tokens draw from per-worker PRNG streams disjoint from the loop's
    admission stream, and with ``prefill_workers > 1`` claim order is a
    thread race — seeded sampling is NOT reproducible across modes or
    across multi-worker runs."""

    # dedicated prefill worker threads draining the admission queue
    prefill_workers: int = 1
    # prefill share floor: prompt tokens the worker may dispatch per decode
    # tick while the backlog is empty-ish and slots are decoding
    min_prefill_tokens: int = 64
    # share ceiling under burst backlog
    max_prefill_tokens: int = 1024
    # backlog (queued + in-prefill requests) at which the ceiling applies;
    # between 2 and this the controller grants the midpoint (a backlog of
    # 0 or 1 is empty-ish: the floor share trickles under live decode)
    backlog_high: int = 4
    # allowance accumulation cap, in ticks' worth of the current share (an
    # idle-ish worker may save up a small burst, never an unbounded one)
    burst_ticks: int = 2

    def validate(self) -> None:
        if not 1 <= self.prefill_workers <= 8:
            raise ValueError(
                f"prefill_workers must be in 1..8, got {self.prefill_workers}")
        if not 0 < self.min_prefill_tokens <= self.max_prefill_tokens:
            raise ValueError(
                "need 0 < min_prefill_tokens <= max_prefill_tokens, got "
                f"{self.min_prefill_tokens}/{self.max_prefill_tokens}")
        if self.backlog_high < 1 or self.burst_ticks < 1:
            raise ValueError("backlog_high and burst_ticks must be >= 1")


class DisaggController:
    """The dynamic capacity partition: a token bucket the decode loop
    refills once per tick with the CURRENT prefill share, which steps with
    backlog pressure (floor / mid / ceiling). Workers ``acquire()`` chunk
    tokens from it before every prefill dispatch; while nothing is
    decoding the bucket is bypassed (an idle engine prefills at full
    speed, the same rule as the prefill budget's idle bypass)."""

    def __init__(self, cfg: DisaggConfig, chunk: int):
        self.cfg = cfg
        self._chunk = int(chunk)
        self._cv = threading.Condition()
        self._level = "floor"
        self._share = cfg.min_prefill_tokens
        self._allowance = 0.0
        self.repartitions = 0

    def _target(self, backlog: int) -> tuple[str, int]:
        c = self.cfg
        if backlog >= c.backlog_high:
            return "ceiling", c.max_prefill_tokens
        if backlog > 1:
            return "mid", (c.min_prefill_tokens + c.max_prefill_tokens) // 2
        return "floor", c.min_prefill_tokens

    @property
    def prefill_share(self) -> int:
        return self._share

    @property
    def level(self) -> str:
        return self._level

    def on_tick(self, backlog: int) -> None:
        """One decode tick elapsed: re-evaluate the partition against the
        backlog and refill the allowance with the (possibly new) share.
        Called from the serving loop right after each decode dispatch."""
        with self._cv:
            level, share = self._target(backlog)
            if level != self._level:
                self._level = level
                self.repartitions += 1
            self._share = share
            cap = max(float(self._chunk), self.cfg.burst_ticks * float(share))
            self._allowance = min(self._allowance + share, cap)
            self._cv.notify_all()

    def acquire(self, tokens: int, idle, stop) -> bool:
        """Block until *tokens* of prefill allowance are available, the
        engine reports idle-decode (``idle()`` — bypass, no debit), or
        ``stop()``. Returns False only on stop."""
        with self._cv:
            while True:
                if stop():
                    return False
                if idle():
                    return True
                if self._allowance >= tokens:
                    self._allowance -= tokens
                    return True
                # bounded wait: idle/stop transitions have no notifier
                self._cv.wait(0.02)


class DisaggRuntime:
    """Everything the engine holds when disaggregation is on: the
    controller, the worker threads, the claimed set (requests a worker owns
    mid-prefill), the ready queue of completed handoffs awaiting a slot,
    and the worker-side counters ``stats()`` merges. Thread-safe by
    design: workers and the serving loop meet only through these."""

    def __init__(self, engine, cfg: DisaggConfig):
        cfg.validate()
        self.engine = engine
        self.cfg = cfg
        self.controller = DisaggController(cfg, engine._chunk)
        # set by the loop after _warm_executables: workers must never race
        # a first-use compile (the warm invariant) nor touch a cold state
        self.started = threading.Event()
        self._ready: "collections.deque[dict]" = collections.deque()
        self._claimed: set = set()
        self._mu = threading.Lock()  # claimed/ready/counters/need_blocks
        # serializes the head-peek -> reserve -> take sequence across
        # workers: without it two workers race the same queue head, both
        # reserving pages (and bumping the prefix share/COW counters)
        # before one loses take() — wasted allocator churn and counter
        # drift vs the slot-admission parity the tests pin
        self.claim_mu = threading.Lock()
        self._work_cv = threading.Condition(self._mu)
        self._need_blocks = 0
        self.counters = {
            "handoffs": 0,
            # device copies performed by the handoff path — the zero-copy
            # contract says this NEVER moves (the prefix boundary COW is
            # counted as prefix_cow_copies, exactly like slot admission)
            "handoff_copies": 0,
            "prefill_chunks": 0,
            "prefill_tokens": 0,
            "first_tokens": 0,
            "fetches": 0,
            "bytes_fetched": 0,
            "prefix_blocks_shared": 0,
            "prefix_cow_copies": 0,
            "pool_blocked_prefills": 0,
            # sessions fully served on the worker (budget exhausted or eos
            # at the first token) — they never install into a slot, so the
            # engine merges this into stats()['admissions'] to keep the
            # counter's meaning (requests that began service) mode-equal
            "worker_retired": 0,
            # failure-domain counters the engine merges into its own
            # totals: deadline sheds at the worker claim path, and
            # requests a worker-side failure terminated FAULTED
            "shed_deadline": 0,
            "faulted_requests": 0,
        }
        # worker-death recovery (loop thread only, via watch()): requests
        # waiting out their re-queue backoff. The per-request death count
        # feeding the bounded-retries-then-FAULTED policy lives ON the
        # request (_worker_deaths) so it dies with it — a runtime-side
        # map would accumulate one entry per recovered death forever
        self._retry: list = []  # [(eligible_monotonic_ns, Request)]
        self.workers = [
            PrefillWorker(self, i) for i in range(cfg.prefill_workers)]

    # ----------------------------------------------------------- lifecycle

    def start(self) -> None:
        for w in self.workers:
            w.start()

    def join(self, timeout: float = 5.0) -> None:
        deadline = time.perf_counter() + timeout
        for w in self.workers:
            w.join(max(deadline - time.perf_counter(), 0.1))

    # -------------------------------------------------------- shared state

    def bump(self, key: str, n: int = 1) -> None:
        with self._mu:
            self.counters[key] += n

    def counters_snapshot(self) -> dict:
        with self._mu:
            return dict(self.counters)

    def claim_request(self, req) -> None:
        with self._mu:
            self._claimed.add(req)

    def unclaim(self, req) -> None:
        with self._mu:
            self._claimed.discard(req)

    def push_ready(self, entry: dict) -> None:
        """Handoff: the entry (filled blocks + pending first token) becomes
        loop-visible BEFORE the claim drops, so ``owns()`` never has a gap
        a racing park command could fall through."""
        with self._mu:
            self._ready.append(entry)
            self._claimed.discard(entry["req"])

    def pop_ready(self) -> Optional[dict]:
        with self._mu:
            return self._ready.popleft() if self._ready else None

    def owns(self, req) -> bool:
        """Is *req* mid-prefill or awaiting install? The lifecycle drain
        treats owned requests like mid-chunked admissions: a park defers
        until the session reaches a slot."""
        with self._mu:
            return req in self._claimed or any(
                e["req"] is req for e in self._ready)

    @property
    def in_flight(self) -> int:
        with self._mu:
            return len(self._claimed)

    @property
    def ready_count(self) -> int:
        with self._mu:
            return len(self._ready)

    def owned(self) -> int:
        """Requests a worker holds mid-prefill plus completed handoffs
        awaiting a slot — in-flight admissions that have left the waiting
        line but are not streaming yet. stats() adds these back into
        ``queued`` so the gauge keeps meaning "submitted, not yet in a
        slot" in both modes."""
        with self._mu:
            return len(self._claimed) + len(self._ready)

    def backlog(self) -> int:
        """Queued + claimed + ready — the load signal the controller
        partitions on and stats() surfaces as ``prefill_backlog``."""
        return len(self.engine._waiting) + self.owned()

    def request_blocks(self, n: int) -> None:
        """Allocator miss on a worker: post the needed count for the loop
        thread (the parked-state owner) to reclaim at the next tick head —
        eviction never runs on a worker thread."""
        with self._mu:
            self._need_blocks = max(self._need_blocks, n)
        self.engine._wake.set()

    def take_needed_blocks(self) -> int:
        with self._mu:
            n, self._need_blocks = self._need_blocks, 0
            return n

    def notify_work(self) -> None:
        with self._work_cv:
            self._work_cv.notify_all()

    def wait_work(self, timeout: float) -> None:
        with self._work_cv:
            self._work_cv.wait(timeout)

    def on_tick(self) -> None:
        self.controller.on_tick(self.backlog())

    # ---------------------------------------------- worker crash recovery

    def watch(self) -> None:
        """Loop-thread supervisor, called from every tick head: a prefill
        worker that DIED (thread exited without cleanup — an escaped
        exception, an injected WorkerDeath) has a defined blast radius of
        exactly its claimed request. The supervisor releases the dead
        worker's reservation, re-queues the request with exponential
        backoff (bounded by ServingConfig.worker_retry_limit, then a
        typed FAULTED terminal), restarts the worker, and re-admits retry
        entries whose backoff elapsed. Runs only on the serving-loop
        thread — the owner of the parked/waiting/trace structures the
        recovery touches — so none of this races the live workers."""
        eng = self.engine
        from vtpu.serving.engine import Status

        now = time.monotonic_ns()
        for i, w in enumerate(self.workers):
            # ident is None until the thread starts: a not-yet-started
            # worker is pending, not dead (start() may still be running)
            if w.ident is None or w.is_alive() or eng._stop.is_set():
                continue
            cur, w.current = w.current, None
            eng._stats["worker_restarts"] += 1
            eng.trace.record(
                "worker_restart",
                cur["req"].rid if cur is not None else -1, i)
            log.warning("prefill worker %d died%s; restarting", i,
                        f" holding request {cur['req'].rid}"
                        if cur is not None else "")
            if cur is not None:
                req, res = cur["req"], cur["res"]
                with self._mu:
                    in_ready = any(e["req"] is req for e in self._ready)
                handed_off = (req.status is not None
                              or req in eng._slot_req
                              or in_ready)
                # the reservation is worker-held only until push_ready
                # moved ownership to the handoff entry (res emptied) —
                # releasing what remains is safe in every death window
                blocks = res["shared"] + res["priv"]
                if blocks:
                    eng._alloc.release(blocks)
                res["shared"], res["priv"] = [], []
                self.unclaim(req)
                if handed_off:
                    pass  # the handoff survives the worker: normal path
                elif req.cancelled:
                    eng._end_stream(req, req._abort or Status.CANCELLED)
                elif cur["delivered"]:
                    # the dead worker already delivered the first token:
                    # a re-prefill would replay it into the stream —
                    # fault instead of corrupting
                    eng._stats["faulted_requests"] += 1
                    eng.trace.record("fault", req.rid, -1)
                    eng._end_stream(req, Status.FAULTED)
                else:
                    attempts = getattr(req, "_worker_deaths", 0) + 1
                    req._worker_deaths = attempts
                    if attempts > eng.serving.worker_retry_limit:
                        eng._stats["faulted_requests"] += 1
                        eng.trace.record("fault", req.rid, -1)
                        eng._end_stream(req, Status.FAULTED)
                    else:
                        backoff = int(
                            eng.serving.worker_retry_backoff_ms * 1e6
                        ) * (2 ** (attempts - 1))
                        self._retry.append((now + backoff, req))
            replacement = PrefillWorker(self, w.wid)
            self.workers[i] = replacement
            replacement.start()
        if self._retry and not eng._stop.is_set():
            due = [r for t, r in self._retry if t <= now]
            self._retry = [(t, r) for t, r in self._retry if t > now]
            for req in due:
                if req.cancelled:
                    eng._end_stream(req, req._abort or Status.CANCELLED)
                    continue
                eng._waiting.append(req)
            if due:
                self.notify_work()

    def drain(self) -> None:
        """Shutdown sweep (loop thread, workers already joined): release
        every ready entry's blocks and end their streams — nothing a
        never-installed handoff holds may leak. A claimed request whose
        worker was abandoned mid-join still gets its end-of-stream
        sentinel (its blocks die with the engine)."""
        eng = self.engine
        from vtpu.serving.engine import Status

        while True:
            e = self.pop_ready()
            if e is None:
                break
            blocks = e["shared"] + e["priv"]
            if blocks:
                eng._alloc.release(blocks)
            # the worker delivered this entry's first token — it began
            # service, so it counts as an admission even though the
            # engine stopped before a slot freed (its co-scheduled
            # analog was counted at _begin_slot before stop)
            eng._stats["admissions"] += 1
            eng._end_stream(e["req"], e["req"]._abort or Status.CANCELLED)
        with self._mu:
            leftover = list(self._claimed)
            self._claimed.clear()
            retry = [r for _, r in self._retry]
            self._retry = []
        for req in leftover + retry:
            eng._end_stream(req, req._abort or Status.CANCELLED)


class PrefillWorker(threading.Thread):
    """A dedicated prefill engine: claims the oldest waiting request,
    reserves its pages, chunk-prefills into them with no slot, samples and
    DELIVERS the first token, and hands the decode loop the filled entry.
    See the module docstring for the ownership and locking rules."""

    def __init__(self, rt: DisaggRuntime, wid: int):
        super().__init__(daemon=True, name=f"vtpu-prefill-{wid}")
        self.rt = rt
        self.wid = wid
        # what this worker holds RIGHT NOW ({"req", "res", "delivered"}),
        # for the loop-thread supervisor (DisaggRuntime.watch): a dead
        # worker's claim is recovered from here — set on claim, cleared
        # on every graceful exit, deliberately LEFT SET by WorkerDeath
        # (a crash whose cleanup never ran is the state watch() exists
        # to mop up)
        self.current: Optional[dict] = None
        eng = rt.engine
        # per-worker PRNG stream for temperature>0 first tokens (the loop's
        # _admit_key is loop-thread state a worker must never split)
        self._key = jax.random.key(
            eng.serving.sampling_seed + 101 + wid)

    # ------------------------------------------------------------ thread

    def run(self) -> None:
        eng = self.rt.engine
        while not self.rt.started.wait(0.1):
            if eng._stop.is_set():
                return
        while not eng._stop.is_set():
            try:
                claim = self._claim()
            except Exception:
                # a claim failure must never kill the worker thread (with
                # one worker that would silently wedge ALL admission while
                # decode keeps running); _reserve_locked rolled back its
                # partial reservation before re-raising
                log.exception("prefill worker %d claim failed", self.wid)
                claim = None
            if claim is None:
                # block on the work condvar, not a fast poll (the PR-6
                # idle discipline): submit() and every tick head notify,
                # and the timeout matches the loop's own 50 ms idle wait
                self.rt.wait_work(0.05)
                continue
            req, res = claim
            self.current = {"req": req, "res": res, "delivered": False}
            try:
                self._prefill_one(req, res)
                self.current = None
            except WorkerDeath:
                # injected crash: die WITHOUT cleanup (self.current stays
                # set, blocks stay reserved, the claim stays claimed) —
                # precisely the wreckage the supervisor must recover
                return
            except Exception:
                log.exception("prefill worker %d failed on request %s",
                              self.wid, req.rid)
                # a worker-side failure is contained to this one request:
                # typed FAULTED terminal, reservation released, thread
                # lives on to serve the next claim
                self.rt.bump("faulted_requests")
                eng.trace.record("fault", req.rid, -1)
                self._release_all(req, res, status="FAULTED")
                self.current = None

    # ------------------------------------------------------------- claim

    def _claim(self):
        """Atomically take the oldest live waiting request WITH its page
        reservation, or None (empty line, cancelled head handled, pool
        dry — reclaim posted). FIFO head-of-line discipline matches the
        co-scheduled admission scheduler's. The whole sequence runs under
        the runtime's claim mutex so concurrent workers never reserve for
        the same head; the residual take() guard below only loses to the
        lifecycle drain's park-of-waiting, which takes no reservation."""
        with self.rt.claim_mu:
            return self._claim_locked()

    def _claim_locked(self):
        eng = self.rt.engine
        while True:
            head = eng._waiting.head()
            if head is None:
                return None
            if head.cancelled:
                if eng._waiting.take(head):
                    eng._end_stream(head, head._abort or "CANCELLED")
                # re-examine the NEW head immediately: returning None here
                # would sleep out a work-condvar timeout while a live
                # request sits right behind the cancelled one
                continue
            if (head.deadline_ns is not None
                    and time.monotonic_ns() > head.deadline_ns):
                # deadline shedding at the claim path, atomic via take():
                # the worker and the loop's tick-head shed can never both
                # own the request, and the counter merges into the same
                # stats()['shed_deadline'] total the co-scheduled engine
                # bumps
                if eng._waiting.take(head):
                    self.rt.bump("shed_deadline")
                    eng.trace.record("shed", head.rid, -1,
                                     TERMINAL_CODES["SHED_DEADLINE"])
                    eng._end_stream(head, "SHED_DEADLINE")
                continue
            res = self._reserve(head)
            if res == "unregistered":
                # prefix vanished between submit and claim: fail just this
                # request, exactly like the co-scheduled _admit path —
                # then re-examine the new head, same discipline as a
                # cancelled head (a live request behind the stale one
                # must not wait out a work-condvar timeout)
                if eng._waiting.take(head):
                    log.warning("request references unregistered prefix %s; "
                                "retiring it unserved", head.prefix)
                    self.rt.bump("faulted_requests")
                    eng.trace.record("fault", head.rid, -1)
                    eng._end_stream(head, "FAULTED")
                continue
            if res is None:
                return None
            break
        # claim BEFORE take: the lifecycle drain must never observe the
        # request in neither place (taken out of waiting but not yet
        # owned) — two drain passes through that gap would discard a
        # racing park command as "request finished". The transient
        # claimed-while-still-waiting overlap is benign (a gauge may read
        # one high for a moment); a lost take() race unclaims below.
        self.rt.claim_request(head)
        if not eng._waiting.take(head):
            # the lifecycle drain parked (or a cancel removed) the head
            # between peek and take: roll the claim and the reservation
            # back (counters were deferred to below, so nothing drifts)
            self.rt.unclaim(head)
            blocks = res["shared"] + res["priv"]
            if blocks:
                eng._alloc.release(blocks)
            return None
        # ownership confirmed: NOW the prefix counters may land (a bump
        # before take() would survive a lost race as phantom shares/COWs)
        if res["shared"]:
            self.rt.bump("prefix_blocks_shared", len(res["shared"]))
        if res["cow"]:
            self.rt.bump("prefix_cow_copies")
        now_ns = time.monotonic_ns()
        head.t_depart_ns = now_ns
        eng.trace.record("queue_depart", head.rid)
        if head.t_submit_ns:
            eng.trace.note_queue_wait((now_ns - head.t_submit_ns) / 1e9)
        return head, res

    def _reserve(self, req):
        """Slot-less page reservation — the worker half of
        ``_reserve_paged_locked``: prompt + the request's OWN budget pages,
        prefix full blocks shared read-only (zero copies), COW only the
        partial boundary block. Returns the reservation dict, None on a
        dry free list (reclaim posted, backpressure), or "unregistered"."""
        eng = self.rt.engine
        page = eng._page
        if req.prefix is not None:
            # get + share + COW-source read atomic against a caller-thread
            # unregister_prefix — the same lock discipline as the loop's
            # admission reserve
            with eng._prefix_lock:
                entry = eng._prefixes.get(req.prefix)
                if entry is None:
                    return "unregistered"
                return self._reserve_locked(req, entry, page)
        return self._reserve_locked(req, None, page)

    def _reserve_locked(self, req, entry, page: int):
        eng = self.rt.engine
        # the same arithmetic slot admission uses (engine._reserve_plan):
        # the budget clamp and page math cannot diverge between modes.
        # The share/COW/rollback sequence below deliberately mirrors
        # engine._reserve_paged_locked but CANNOT be shared with it: this
        # runs on a worker thread (plain alloc — eviction is posted to the
        # loop, never run here; counters deferred until take() confirms
        # ownership; COW under _state_mu). A semantic change to boundary-
        # block handling must land in BOTH places.
        base, budget, full, need_priv = eng._reserve_plan(req, entry)
        shared = entry["blocks"][:full] if entry is not None else []
        if need_priv > 0 and eng._fire_fault("alloc_exhaust"):
            # injected exhaustion at the WORKER reserve: the same
            # backpressure path a genuinely dry free list takes — post
            # the reclaim and retry on the next claim pass
            priv = None
        else:
            priv = eng._alloc.alloc(need_priv) if need_priv > 0 else []
        if priv is None:
            self.rt.bump("pool_blocked_prefills")
            self.rt.request_blocks(need_priv)
            return None
        cow = False
        try:
            if shared:
                eng._alloc.share(shared)
            if base % page:
                # copy-on-write for the prefix's partial boundary block —
                # counted (post-take, in _claim_locked) as a prefix COW
                # exactly like slot admission, never a handoff copy
                with eng._state_mu:
                    eng.state = eng._copy_block(
                        eng.state, jnp.int32(entry["blocks"][full]),
                        jnp.int32(priv[0]))
                cow = True
        except Exception:
            # a failed reserve must not bleed the pool: release the
            # partial reservation before the error reaches run()'s net
            eng._alloc.release(list(shared) + priv)
            raise
        return {"shared": list(shared), "priv": priv, "base": base,
                "budget": budget, "cow": cow,
                "prefix_tokens": list(entry["tokens"]) if entry else [],
                "last_logits": entry["last_logits"] if entry else None}

    # ----------------------------------------------------------- prefill

    def _release_all(self, req, res: dict,
                     status: Optional[str] = None) -> None:
        """Release the claim's reservation; with ``status``, also end the
        stream with that typed terminal (the request's own requested
        abort — cancel or shed — wins over a generic status, and finish's
        idempotence makes the worker-vs-loop race single-sentinel)."""
        eng = self.rt.engine
        blocks = res["shared"] + res["priv"]
        if blocks:
            eng._alloc.release(blocks)
        res["shared"], res["priv"] = [], []
        self.rt.unclaim(req)
        if status is not None:
            eng._end_stream(req, req._abort or status)

    def _idle(self) -> bool:
        eng = self.rt.engine
        return not any(r is not None for r in eng._slot_req)

    def _prefill_one(self, req, res: dict) -> None:
        eng = self.rt.engine
        serving = eng.serving
        n = int(req.tokens.shape[0])
        base, total = res["base"], res["base"] + n
        blocks = res["shared"] + res["priv"]
        c = eng._chunk
        ctx = eng.model.max_context
        # slot field carries the worker id: with prefill_workers > 1 the
        # Chrome dump splits the prefill lane into one track per worker
        # (overlapping slices on one tid would render as nested frames)
        eng.trace.record("prefill_start", req.rid, self.wid, n)
        if eng._fire_fault("worker_death"):
            # injected crash: the thread dies with its claim intact (run()
            # lets WorkerDeath escape) — the loop-thread supervisor owns
            # the recovery
            raise WorkerDeath(f"injected worker_death (worker {self.wid})")
        stop = eng._stop.is_set
        logits = None
        if n:
            pad = -(-n // c) * c
            padded = np.zeros((1, pad), np.int32)
            padded[0, :n] = np.asarray(req.tokens)
            for i in range(pad // c):
                if not self.rt.controller.acquire(c, self._idle, stop):
                    self._release_all(req, res, status="CANCELLED")
                    return
                if req.cancelled:
                    self._release_all(req, res, status="CANCELLED")
                    return
                off = i * c
                need = base + off + c
                kv_bucket = next(
                    (bkt for bkt in eng._kv_buckets if bkt >= need), ctx)
                wp = kv_bucket // eng._page
                row = np.zeros((wp,), np.int32)
                m = min(len(blocks), wp)
                row[:m] = blocks[:m]
                # the register_prefix discipline: explicit block_ids, slot
                # = the out-of-range sentinel so the length write drops —
                # a worker prefill can never touch live slot state
                with eng._state_mu:
                    logits, eng.state = eng._prefill_chunk(
                        eng.params, eng.state, padded[:, off:off + c],
                        jnp.int32(serving.slots), jnp.int32(base + off),
                        jnp.int32(min(base + off + c, total)),
                        kv_bucket=kv_bucket, unroll=eng._unroll,
                        block_ids=row)
                self.rt.bump("prefill_chunks")
                self.rt.bump("prefill_tokens",
                             min(base + off + c, total) - (base + off))
                eng.trace.record("prefill_chunk", req.rid, -1, c)
            last_row = logits[0, (total - base - 1) - (pad - c)]
        else:
            # empty suffix on a prefix-backed request: the first token
            # comes straight from the prefix's stored final logits
            last_row = res["last_logits"]
        if serving.temperature <= 0.0:
            tok_dev = eng._argmax1(last_row)
        else:
            self._key, sub = jax.random.split(self._key)
            tok_dev = eng._sample1(last_row, sub)
        # the worker's OWN fetch, off the decode tick path entirely — the
        # decode side's device_gets_per_tick contract never sees it
        tok = int(jax.device_get(tok_dev))
        self.rt.bump("fetches")
        self.rt.bump("bytes_fetched", 4)
        if req.cancelled or eng._stop.is_set():
            self._release_all(req, res, status="CANCELLED")
            return
        t_first = time.perf_counter()
        now_ns = time.monotonic_ns()
        eng.trace.record("first_token", req.rid, -1)
        if req.t_submit_ns:
            eng.trace.note_ttft((now_ns - req.t_submit_ns) / 1e9)
        if req.t_depart_ns:
            eng.trace.note_prefill_exec((now_ns - req.t_depart_ns) / 1e9)
        req.delivered += 1
        req.out.put(tok)
        if self.current is not None:
            # past this point a dead worker's request cannot be re-queued
            # (a re-prefill would replay the delivered first token): the
            # supervisor faults it instead
            self.current["delivered"] = True
        self.rt.bump("first_tokens")
        if res["budget"] - 1 <= 0 or tok == serving.eos_token:
            # the whole budget was the first token (or eos): the session
            # never needs a slot — retire here, blocks straight back.
            # Counted so stats()['admissions'] still means "requests that
            # began service", matching the co-scheduled _begin_slot bump
            # (installed handoffs are bumped by _install_handoffs).
            self.rt.bump("worker_retired")
            self._release_all(req, res, status="OK")
            return
        entry = {
            "req": req,
            "tokens": res["prefix_tokens"]
            + [int(x) for x in np.asarray(req.tokens).tolist()],
            "pending": tok,
            "budget": res["budget"] - 1,
            "seq_len": total,
            "n_pages": len(blocks),
            "shared": res["shared"],
            "priv": res["priv"],
            "hist_exact": True,
            "t_first": t_first,
        }
        # ownership transfer: from here the entry owns the blocks — a late
        # exception must not let run()'s _release_all double-release them
        res["shared"], res["priv"] = [], []
        self.rt.push_ready(entry)
        self.rt.bump("handoffs")
        eng.trace.record("handoff", req.rid, self.wid, len(blocks))
        # an idle loop blocks on _wake; a ready handoff must install now
        eng._wake.set()
