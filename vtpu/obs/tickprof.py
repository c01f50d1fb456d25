"""Tick-phase profiler: where a serving tick's host time actually goes.

Each loop pass notes the seconds it spent in each phase into a bounded
histogram, so a TTFT p99 outlier can be blamed on admission head-of-line
work vs the device fetch vs Python delivery bookkeeping vs swap-drain
housekeeping. The loop opens a phase with ``TickProfiler.phase()``, which
also holds a ``jax.profiler.TraceAnnotation`` named ``vtpu.tick.<phase>``
open for the same interval: in a profiler session the loop thread's phases
lie on the trace's own clock beside the device's operations, so a device
gap can be put down to the host phase it began in. Every span carries a
``tick`` id (the engine's tick counter when it opened), which ties the
spans of one pass together. Outside a session an annotation is a flag test.

Phases (one histogram each):

- admission:  ``_tick_head`` minus swap drain — queue drain, chunk
              advancement, batched admission dispatch, lifecycle commands.
- dispatch:   building and issuing the decode/spec dispatch (host-side
              array builds + the async jit call).
- fetch:      the tick's single batched ``jax.device_get`` — on the
              pipelined loop this includes waiting for the device to
              finish the in-flight tick, i.e. it is the device-bound
              share of the tick.
- deliver:    pure-Python bookkeeping after the fetch (stream puts,
              budget/eos/retire, history).
- swap_drain: landing completed D2H swap-out snapshots in the host pool
              (opened inside admission, whose note excludes it).
- idle_wait:  the loop blocked with nothing to serve.

Everything is plain host arithmetic: a ``note()`` is one bisect over a
static bucket table plus four scalar updates, cheap enough for five calls
per tick. Writers are the serving-loop thread; ``snapshot()`` readers from
other threads see monotonic counters (benign racing, same contract as
``ServingEngine.stats()``).
"""

from __future__ import annotations

import contextlib
import time
from bisect import bisect_left
from typing import Callable, Optional

# Default bucket upper edges in MILLISECONDS. Tick phases live in the
# 10 us .. 100 ms range on real rigs; span latencies (TTFT/ITL/queue wait,
# see trace.py) reuse the same class with the wider LATENCY edges.
PHASE_BUCKETS_MS = (
    0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0,
    100.0, 250.0, 1000.0,
)
LATENCY_BUCKETS_MS = (
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0,
    1000.0, 2500.0, 5000.0, 10000.0,
)

PHASES = ("admission", "dispatch", "fetch", "deliver", "swap_drain",
          "idle_wait")
# the loop's own work: not the wait for the device, not the idle wait
HOST_PHASES = ("admission", "dispatch", "deliver", "swap_drain")


def host_ms_per_tick(tick_phase_ms: dict) -> Optional[float]:
    """Host milliseconds per inner decode tick outside the device fetch,
    from a ``stats()["tick_phase_ms"]`` snapshot: the totals of
    HOST_PHASES over the ticks the dispatches covered."""
    ticks = tick_phase_ms["dispatch"]["ticks"]
    if not ticks:
        return None
    return sum(tick_phase_ms[p]["total_ms"] for p in HOST_PHASES) / ticks


class BoundedHistogram:
    """Fixed-bucket monotonic histogram (count / sum / max + per-bucket
    counts). Monotonic on purpose: the Prometheus exporter publishes it as
    a real histogram family, so counts must only ever grow — a reservoir
    would make ``rate()`` lie."""

    __slots__ = ("edges_ms", "counts", "count", "total_ms", "max_ms",
                 "ticks")

    def __init__(self, edges_ms: tuple = PHASE_BUCKETS_MS):
        self.edges_ms = tuple(edges_ms)
        self.counts = [0] * (len(self.edges_ms) + 1)  # +1: the +Inf bucket
        self.count = 0
        self.total_ms = 0.0
        self.max_ms = 0.0
        # inner decode ticks the samples covered: with the multi-tick
        # device loop one loop pass serves k ticks, so per-TOKEN
        # attribution divides by ticks, not count (ticks == count when
        # every note covers one tick — the classic loop)
        self.ticks = 0

    def note_ms(self, ms: float, ticks: int = 1) -> None:
        self.counts[bisect_left(self.edges_ms, ms)] += 1
        self.count += 1
        self.total_ms += ms
        self.ticks += ticks
        if ms > self.max_ms:
            self.max_ms = ms

    def note(self, seconds: float, ticks: int = 1) -> None:
        self.note_ms(seconds * 1e3, ticks=ticks)

    @property
    def mean_ms(self) -> float:
        return self.total_ms / self.count if self.count else 0.0

    @property
    def mean_ms_per_tick(self) -> float:
        """Phase milliseconds amortized over the inner ticks the samples
        covered — the device-loop headline: a k-tick flush pays each host
        phase once, so its per-tick share is mean_ms / k."""
        return self.total_ms / self.ticks if self.ticks else 0.0

    def snapshot(self) -> dict:
        return {
            "count": self.count,
            "total_ms": round(self.total_ms, 4),
            "mean_ms": round(self.mean_ms, 4),
            "max_ms": round(self.max_ms, 4),
            "ticks": self.ticks,
            "mean_ms_per_tick": round(self.mean_ms_per_tick, 4),
        }

    def prom_buckets(self) -> tuple[list[tuple[str, float]], float]:
        """(cumulative (le, count) pairs with le in SECONDS, sum in
        seconds) — the shape HistogramMetricFamily.add_metric wants."""
        acc, out = 0, []
        for edge_ms, c in zip(self.edges_ms, self.counts):
            acc += c
            out.append((repr(edge_ms / 1e3), float(acc)))
        out.append(("+Inf", float(self.count)))
        return out, self.total_ms / 1e3


class TickProfiler:
    """One BoundedHistogram per decode-loop phase. ``tick`` gives the
    engine's tick counter, the id every span of ``phase()`` carries."""

    __slots__ = ("phases", "_tick", "_span", "_names", "_inner_s")

    def __init__(self, phases: tuple = PHASES,
                 edges_ms: tuple = PHASE_BUCKETS_MS,
                 tick: Callable[[], int] = lambda: 0):
        # imported here, not above: importing vtpu.obs (the exporter, the
        # benchmarks' summary line) stays free of JAX
        from jax.profiler import TraceAnnotation

        self.phases = {p: BoundedHistogram(edges_ms) for p in phases}
        self._tick = tick
        self._span = TraceAnnotation
        self._names = {p: f"vtpu.tick.{p}" for p in phases}
        # seconds of the phases closed inside the one now open (a phase
        # opened inside another is taken out of the outer one's note)
        self._inner_s = 0.0

    @contextlib.contextmanager
    def phase(self, name: str, ticks: int = 1, **ids):
        """Time the enclosed block into ``name``'s histogram, as ``note()``
        would, under a profiler span ``vtpu.tick.<name>`` with the ids
        ``tick`` and ``**ids``. Loop thread only."""
        with self._span(self._names[name], tick=self._tick(), **ids):
            outer, self._inner_s = self._inner_s, 0.0
            t0 = time.perf_counter()
            try:
                yield
            finally:
                dt = time.perf_counter() - t0
                self.phases[name].note(dt - self._inner_s, ticks=ticks)
                self._inner_s = outer + dt

    def note(self, phase: str, seconds: float, ticks: int = 1) -> None:
        """Record one phase sample. ``ticks`` is how many inner decode
        ticks the sample amortizes over (k for a device-loop flush): the
        histogram keeps the observed per-pass duration — Prometheus bucket
        semantics unchanged — while mean_ms_per_tick carries the
        per-inner-tick attribution."""
        self.phases[phase].note(seconds, ticks=ticks)

    def snapshot(self) -> dict:
        """{phase: {count, total_ms, mean_ms, max_ms}} — the stats() view
        that replaces the single host-EMA number with attribution."""
        return {p: h.snapshot() for p, h in self.phases.items()}
