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

What a long sample cost. A histogram's ``max_ms`` covers the engine's
life and its buckets hold no sums over an edge, so neither says what a
window lost. Each histogram therefore keeps two more monotonic counters,
``long_count`` and ``long_ms``: the samples judged *long* and their summed
*excess*; a window's growth of ``long_ms`` is the time the window lost in
that phase. The rules (``TickProfiler``):

- a pass is *plain* when its device work was decode steps alone. The loop
  is one tick deep, so a fetch waits for the launches issued since the
  fetch before last closed, and a host phase that issues or follows a
  prefill launch may block on the device's queue (measured, PR 37: a
  ``dispatch`` of 55-200 ms beside four chunks with the process running
  all through): the pass is plain when ``prefill`` (the engine's
  ``prefill_tokens`` counter, which every prefill launch bumps) has not
  moved since that fetch closed;
- a host phase (HOST_PHASES) on a plain pass is long at ``LONG_HOST_MS``
  or more, the excess the whole sample (the cells' host work a tick is
  1.3-10 ms: what crosses 50 ms there is the process not running);
  ``idle_wait`` never is;
- a ``fetch`` on a plain pass is long when it exceeds per inner tick
  ``PLAIN_FACTOR`` times the running mean of plain fetches plus
  ``PLAIN_SLACK_MS``, the excess what lies over that mean. No mean before
  ``PLAIN_MIN`` plain samples, and a long sample does not enter it;
- whatever its pass held, a sample of any of these phases is long at
  ``LONG_WHOLE_MS`` or more, whole (no tick of any cell is near a second).
  Under it a pass that carries chunks is not judged: its length is the
  chunks'. What such a pass lost shows in pauses.py's counters alone.

The last ``LONG_RING`` long samples are kept as ``[phase, tick, start_ns,
ms, excess_ms]`` (``stats()["tick_long"]``; ``start_ns`` on
``time.monotonic_ns()``, ``tick`` the id the pass's spans carry), beside
pauses.py's ring of the process's pauses: a long sample that a pause
covers was the machine's or the interpreter's, one that none covers, in a
``fetch``, the device's or the runtime's.

Everything is plain host arithmetic: a ``note()`` is one bisect over a
static bucket table plus four scalar updates and one comparison, cheap
enough for five calls per tick; a fetch adds one counter read and a
multiply. Writers are the serving-loop thread; ``snapshot()`` readers
from other threads see monotonic counters (benign racing, same contract
as ``ServingEngine.stats()``).
"""

from __future__ import annotations

import collections
import contextlib
import time
from bisect import bisect_left
from typing import Callable, Optional

# Default bucket upper edges in MILLISECONDS: the exporter's buckets. A
# host phase reads 10 us .. 10 ms and the cells' fetch 12-350 ms; the
# edges place a sample for a scrape's quantiles and nothing more. Time
# lost is read from ``long_ms``, not from them. Span latencies
# (TTFT/ITL/queue wait, see trace.py) and the process's pauses reuse the
# same class with the wider LATENCY edges.
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

LONG_HOST_MS = 50.0     # a plain host phase this long mostly did not run
LONG_WHOLE_MS = 1000.0  # no tick of any cell is near it (the longest 0.35 s)
PLAIN_MIN = 16          # plain samples before the plain rule judges
PLAIN_FACTOR = 2.0
PLAIN_SLACK_MS = 10.0
PLAIN_WINDOW = 64       # the running mean's memory, in plain samples
LONG_RING = 64


def host_ms_per_tick(tick_phase_ms: dict) -> Optional[float]:
    """Host milliseconds per inner decode tick outside the device fetch,
    from a ``stats()["tick_phase_ms"]`` snapshot: the totals of
    HOST_PHASES over the ticks the dispatches covered."""
    ticks = tick_phase_ms["dispatch"]["ticks"]
    if not ticks:
        return None
    return sum(tick_phase_ms[p]["total_ms"] for p in HOST_PHASES) / ticks


def ring_rows(ring) -> list:
    """A deque's rows, oldest first, while its one writer may append
    (``list(deque)`` raises if the deque changes under it: try again)."""
    for _ in range(3):
        try:
            return list(ring)
        except RuntimeError:
            continue
    return []


class BoundedHistogram:
    """Fixed-bucket monotonic histogram (count / sum / max + per-bucket
    counts). Monotonic on purpose: the Prometheus exporter publishes it as
    a real histogram family, so counts must only ever grow — a reservoir
    would make ``rate()`` lie."""

    __slots__ = ("edges_ms", "counts", "count", "total_ms", "max_ms",
                 "ticks", "long_count", "long_ms")

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
        # samples a caller judged long and their summed excess (see the
        # module text): what a window lost is the growth of long_ms
        self.long_count = 0
        self.long_ms = 0.0

    def note_long(self, excess_ms: float) -> None:
        self.long_count += 1
        self.long_ms += excess_ms

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
            "long_count": self.long_count,
            "long_ms": round(self.long_ms, 4),
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
    """One BoundedHistogram per decode-loop phase, and the judgement of
    which samples were long (module text). ``tick`` gives the engine's
    tick counter, the id every span of ``phase()`` carries; ``prefill``
    a counter that every prefill launch moves, by which a sample knows a
    plain pass."""

    __slots__ = ("phases", "long", "_tick", "_prefill", "_span", "_names",
                 "_inner_s", "_long_at", "_seen", "_plain_n", "_plain_mean")

    def __init__(self, phases: tuple = PHASES,
                 edges_ms: tuple = PHASE_BUCKETS_MS,
                 tick: Callable[[], int] = lambda: 0,
                 prefill: Callable[[], int] = lambda: 0):
        # imported here, not above: importing vtpu.obs (the exporter, the
        # benchmarks' summary line) stays free of JAX
        from jax.profiler import TraceAnnotation

        self.phases = {p: BoundedHistogram(edges_ms) for p in phases}
        self.long = collections.deque(maxlen=LONG_RING)
        self._tick = tick
        self._prefill = prefill
        self._span = TraceAnnotation
        self._names = {p: f"vtpu.tick.{p}" for p in phases}
        # seconds of the phases closed inside the one now open (a phase
        # opened inside another is taken out of the outer one's note)
        self._inner_s = 0.0
        # the length from which a sample is looked at again: every fetch
        # (its rule needs the counter read), a host phase at LONG_HOST_MS
        self._long_at = {p: (LONG_HOST_MS if p in HOST_PHASES else
                             0.0 if p == "fetch" else float("inf"))
                         for p in phases}
        # ``prefill`` at the close of the last two fetches, oldest first
        self._seen = (prefill(),) * 2
        self._plain_n = 0
        self._plain_mean = 0.0

    @contextlib.contextmanager
    def phase(self, name: str, ticks: int = 1, **ids):
        """Time the enclosed block into ``name``'s histogram, as ``note()``
        would, under a profiler span ``vtpu.tick.<name>`` with the ids
        ``tick`` and ``**ids``. Loop thread only."""
        tick = self._tick()
        with self._span(self._names[name], tick=tick, **ids):
            outer, self._inner_s = self._inner_s, 0.0
            t0 = time.perf_counter()
            try:
                yield
            finally:
                dt = time.perf_counter() - t0
                self._note(name, dt - self._inner_s, ticks, tick)
                self._inner_s = outer + dt

    def note(self, phase: str, seconds: float, ticks: int = 1) -> None:
        """Record one phase sample that ends now. ``ticks`` is how many
        inner decode ticks the sample amortizes over (k for a device-loop
        flush): the histogram keeps the observed per-pass duration —
        Prometheus bucket semantics unchanged — while mean_ms_per_tick
        carries the per-inner-tick attribution."""
        self._note(phase, seconds, ticks, self._tick())

    def _note(self, name: str, seconds: float, ticks: int, tick: int) -> None:
        ms = seconds * 1e3
        hist = self.phases[name]
        hist.note_ms(ms, ticks=ticks)
        if ms >= self._long_at[name]:
            excess = self._excess(name, ms, ticks)
            if excess:
                hist.note_long(excess)
                self.long.append([
                    name, tick, time.monotonic_ns() - int(seconds * 1e9),
                    round(ms, 3), round(excess, 3)])

    def _excess(self, name: str, ms: float, ticks: int) -> float:
        """What a sample of ``ms`` over ``ticks`` inner ticks lost by the
        module text's rules, 0.0 for one that is not long. Reached by
        every fetch and by a host phase of LONG_HOST_MS or more."""
        moved = self._prefill()
        plain = moved == self._seen[0]
        if name != "fetch":
            return ms if plain or ms >= LONG_WHOLE_MS else 0.0
        self._seen = (self._seen[1], moved)
        if plain:
            per = ms / ticks
            n, mean = self._plain_n, self._plain_mean
            if n >= PLAIN_MIN and per > PLAIN_FACTOR * mean + PLAIN_SLACK_MS:
                return ms - mean * ticks
            if ms < LONG_WHOLE_MS:
                self._plain_n = n = min(n + 1, PLAIN_WINDOW)
                self._plain_mean = mean + (per - mean) / n
        return ms if ms >= LONG_WHOLE_MS else 0.0

    def snapshot(self) -> dict:
        """{phase: {count, total_ms, mean_ms, max_ms, ticks,
        mean_ms_per_tick, long_count, long_ms}} — the stats() view that
        replaces the single host-EMA number with attribution."""
        return {p: h.snapshot() for p, h in self.phases.items()}

    def long_snapshot(self) -> list:
        """The ``stats()["tick_long"]`` view: the ring's rows, oldest
        first, at most LONG_RING."""
        return ring_rows(self.long)
