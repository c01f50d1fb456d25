"""Deterministic fault injection for the serving engine's failure domains.

Every recovery path the engine promises — shed, contain, re-queue,
degrade — is unreachable from a clean test run: the allocator never runs
dry on cue, workers don't die on schedule, and a device fetch stalls only
when real hardware misbehaves. This module makes each seam triggerable
ON SCHEDULE so tier-1, a seam a test and a schedule of them in one run
(tests/test_faults.py), exercises the recovery machinery reproducibly.

A ``FaultPlan`` is a set of ``FaultSpec``\\s, each naming a SEAM and the
arrival indices at which it fires. The engine (and the disagg prefill
workers) call ``plan.fire(seam)`` at every pass through an instrumented
seam; the plan counts the arrival and answers whether to inject. The
schedule is a pure function of the specs (or of the seed, for
``FaultPlan.seeded``) and the per-seam arrival order — no wall clock, no
global RNG — so the same plan over the same traffic injects at the same
points every run. That determinism is what the chaos gates stand on:
unaffected streams token-equal to the fault-free run, affected requests
terminating with their typed status, zero leaks after the soak.

Seams (where the engine consults the plan):

- ``alloc_exhaust``   block-pool reservation (loop `_alloc_reclaim` and
                      the disagg worker reserve) reports a dry free list
                      -> the backpressure / reclaim-assist paths run
- ``swap_d2h_loss``   an eviction's host spill is lost -> the pages drop
                      and resume takes the recompute-on-fault path
- ``swap_h2d_loss``   a resume's host restore is lost -> the entry drops
                      its host pages and rebuilds through prefill
- ``worker_death``    a disagg PrefillWorker dies mid-claim (the thread
                      exits without cleanup) -> the loop-thread supervisor
                      releases its reservation, re-queues the request with
                      bounded backoff, and restarts the worker
- ``dispatch_exc``    an exception escapes one request's deliver path ->
                      crash containment retires only that slot (FAULTED)
- ``delayed_fetch``   the device fetch stalls for ``arg`` seconds -> the
                      fetch watchdog trips and degrades the engine
                      gracefully instead of hanging the host
- ``migrate_src_death``  the SOURCE engine of a live session migration
                      dies after the metadata handshake but before the
                      payload ships (its pool is gone) -> the destination
                      rebuilds the session from its token history via the
                      recompute-on-fault prefill path
- ``migrate_payload_loss``  a migration's KV payload is lost in transit
                      (consulted at the DESTINATION install seam) -> the
                      destination falls back to recompute, or delivers a
                      typed FAULTED terminal when the session cannot be
                      rebuilt
- ``engine_death``    the serving loop thread dies AT A FLUSH BOUNDARY
                      without running any of its cleanup (no terminals, no
                      block releases — the in-process stand-in for a
                      SIGKILLed engine process): heartbeats stop, clients
                      hang, and the fleet supervisor
                      (vtpu/serving/fleet.EngineFleet) must detect the
                      silence, declare the engine DEAD and rebuild every
                      session it held on survivors from the session ledger
- ``probe_loss``      a fleet health probe is LOST (consulted by the fleet
                      monitor, once per engine per probe round in
                      sorted-name order): the probe counts as a miss even
                      though the engine is healthy — the deterministic
                      driver of the SUSPECT-but-alive hysteresis path

Thread-safe: workers and the serving loop hit seams concurrently; each
``fire`` takes the plan's lock (off the hot path — a seam consult is one
dict lookup when no plan is configured, and the plan itself is opt-in).

Timing-coupled seams (``engine_death`` especially: its arrival index is
the engine's flush-boundary count, which idle passes inflate under load)
can be armed mid-run with ``FaultPlan.arm(seam)`` — "fire at the NEXT
arrival" — so a test or bench can stream a known number of tokens first
and then kill the engine at the very next flush, deterministically.
"""

from __future__ import annotations

import dataclasses
import random
import threading
from typing import Dict, Iterable, Optional

# The instrumented seams, single-sourced so the engine, the tests and the
# chaos bench agree on the vocabulary.
SEAMS = (
    "alloc_exhaust",
    "swap_d2h_loss",
    "swap_h2d_loss",
    "worker_death",
    "dispatch_exc",
    "delayed_fetch",
    "migrate_src_death",
    "migrate_payload_loss",
    "engine_death",
    "probe_loss",
    # the fabric transport's seams (vtpu/serving/fabric/transport.py):
    # consulted by the loopback channel on every send — drop the message,
    # defer its delivery, or flip a payload byte after the CRCs were
    # computed (the receiver's checksum verify must convert it to the
    # recompute path, never to wrong tokens)
    "fabric_msg_loss",
    "fabric_delay",
    "fabric_payload_corrupt",
)


class FaultInjected(RuntimeError):
    """The exception an injected ``dispatch_exc`` raises — a stand-in for
    any exception escaping one request's dispatch/deliver path. Containment
    must treat it exactly like an organic bug: retire the one slot with a
    typed FAULTED terminal and keep every other stream going."""


class WorkerDeath(BaseException):
    """Kills a disagg PrefillWorker thread WITHOUT unwinding its cleanup —
    simulating a crash whose teardown never ran, which is exactly the state
    the loop-thread supervisor must recover from. BaseException so the
    worker's ordinary ``except Exception`` containment (which releases the
    reservation — too graceful for a crash) cannot swallow it."""


class EngineDeath(BaseException):
    """Kills the SERVING LOOP thread without running its shutdown sweep —
    the ``engine_death`` seam's payload, and the WorkerDeath discipline
    applied to the whole engine: no typed terminals are delivered, no
    blocks released, no lifecycle tickets failed. Every client of the
    engine is left hanging exactly as a SIGKILLed process would leave
    them, which is the state fleet failover (vtpu/serving/fleet) exists
    to recover from. BaseException so no containment ``except Exception``
    inside the loop can accidentally survive its own death."""


@dataclasses.dataclass(frozen=True)
class FaultSpec:
    """Fire at arrivals [at, at + count) of ``seam``. ``arg`` is the
    seam-specific payload (``delayed_fetch``: stall seconds)."""

    seam: str
    at: int = 0
    count: int = 1
    arg: float = 0.0

    def __post_init__(self):
        if self.seam not in SEAMS:
            raise ValueError(f"unknown fault seam {self.seam!r}; "
                             f"known: {SEAMS}")
        if self.at < 0 or self.count < 1:
            raise ValueError(f"need at >= 0 and count >= 1, got "
                             f"at={self.at} count={self.count}")


class FaultPlan:
    """A deterministic injection schedule over the named seams.

    ``fire(seam)`` counts one arrival at the seam and returns the matching
    FaultSpec when the schedule says inject (truthy), else None. Counters
    (arrivals and injections per seam) are exposed via ``snapshot()`` and
    ``injected_total`` — the engine surfaces the total as
    ``stats()["faults_injected"]``.
    """

    def __init__(self, specs: Iterable[FaultSpec] = ()):
        self.specs = tuple(specs)
        self._lock = threading.Lock()
        self._arrivals: Dict[str, int] = {s: 0 for s in SEAMS}
        self._injected: Dict[str, int] = {s: 0 for s in SEAMS}
        # seam -> {arrival index -> spec}; overlapping specs resolve to the
        # one declared first (declaration order is part of the schedule)
        self._sched: Dict[str, Dict[int, FaultSpec]] = {s: {} for s in SEAMS}
        for spec in self.specs:
            tbl = self._sched[spec.seam]
            for i in range(spec.at, spec.at + spec.count):
                tbl.setdefault(i, spec)

    @classmethod
    def seeded(cls, seed: int, rates: Dict[str, float], horizon: int = 256,
               args: Optional[Dict[str, float]] = None) -> "FaultPlan":
        """A pseudo-random-but-reproducible schedule: for each seam in
        ``rates``, each of the first ``horizon`` arrivals fires with the
        given rate, drawn from ``random.Random(seed)`` in sorted-seam
        order — the same seed always yields the same schedule. ``args``
        carries per-seam payloads (e.g. the delayed_fetch stall)."""
        args = args or {}
        specs = []
        for seam in sorted(rates):
            if seam not in SEAMS:
                raise ValueError(f"unknown fault seam {seam!r}")
            rng = random.Random((seed, seam).__repr__())
            for i in range(horizon):
                if rng.random() < rates[seam]:
                    specs.append(FaultSpec(seam, at=i, count=1,
                                           arg=args.get(seam, 0.0)))
        return cls(specs)

    def arm(self, seam: str, count: int = 1, arg: float = 0.0) -> FaultSpec:
        """Schedule *seam* to fire at its NEXT ``count`` arrivals — "kill
        it at the next flush boundary", armed mid-run. This is the
        deterministic handle for seams whose arrival index is timing-
        coupled (``engine_death``: idle passes count as arrivals, so a
        fixed ``at`` lands at a load-dependent moment): a test streams the
        tokens it wants first, then arms the seam, and the very next pass
        through the seam injects. Returns the spec it scheduled."""
        with self._lock:
            if seam not in SEAMS:
                raise ValueError(f"unknown fault seam {seam!r}; "
                                 f"known: {SEAMS}")
            spec = FaultSpec(seam, at=self._arrivals[seam], count=count,
                             arg=arg)
            self.specs = self.specs + (spec,)
            tbl = self._sched[seam]
            for i in range(spec.at, spec.at + spec.count):
                tbl.setdefault(i, spec)
            return spec

    def fire(self, seam: str) -> Optional[FaultSpec]:
        """One arrival at ``seam``; returns the FaultSpec to inject or
        None. Thread-safe (workers and the loop share one plan)."""
        with self._lock:
            i = self._arrivals[seam]
            self._arrivals[seam] = i + 1
            spec = self._sched[seam].get(i)
            if spec is not None:
                self._injected[seam] += 1
            return spec

    @property
    def injected_total(self) -> int:
        with self._lock:
            return sum(self._injected.values())

    def snapshot(self) -> dict:
        """Per-seam arrival/injection counts — the chaos bench's audit of
        which seams actually fired."""
        with self._lock:
            return {
                "arrivals": dict(self._arrivals),
                "injected": dict(self._injected),
            }
