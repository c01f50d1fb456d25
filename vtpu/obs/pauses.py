"""Pause watch: the time a serving process loses whole.

A tick's phases (tickprof.py) say where the loop's time went; they cannot
say that the process did not run at all. One daemon thread a process
sleeps ``PERIOD_MS`` at a time, and a wake more than ``LATE_MS`` past its
due time is a *pause*: its lateness goes into a histogram and, with what
classes it, into a ring of the last ``RING``:

    [start_ns, ms, cpu_ms, gc_ms]

``start_ns`` is ``time.monotonic_ns()`` when the sleep began (the request
trace's clock, which the processes of a machine share), ``ms`` how late
the wake was, ``cpu_ms`` the growth of ``time.process_time()`` across the
sleep and ``gc_ms`` the collector's time that fell inside it. ``gc_ms``
near ``ms``: a collection held the interpreter. ``cpu_ms`` near ``ms``: a
thread of this process held it (one long C call) or kept the watcher off
its core. Both near 0: nothing of this process ran, so the machine
stopped (or every thread was off its core). ``cpu_ms`` is as good as the
kernel's accounting: where CPU time is sampled in ticks (the sandbox of
PR 37's chip runs: 10 ms, and 0-180 ms credited to a stop of 0.11 s) it
classes nothing, and only a second process does (hack/pause_probe.py).

A ``gc.callbacks`` listener times every collection: per generation
``count``, ``total_ms``, ``max_ms``, and those of ``GC_RING_MS`` or more
into a second ring as ``[start_ns, ms, generation, collected]``.

In a profiler session each sleep runs under a span ``vtpu.watch`` and each
generation-2 collection under ``vtpu.gc``, on the device trace's own
clock: a ``vtpu.watch`` span far longer than the period *is* the pause,
beginning where it began. Outside a session a span is a flag test.

The engines of a process share the one watch (``WATCH``):
``ServingEngine.start()`` acquires it and ``.stop()`` releases it; the
thread ends and the listener comes off ``gc.callbacks`` with the last
release. The counters are monotonic across restarts. Importing this
module imports no JAX (the spans' class is imported when the thread
starts, in a process whose engine has imported JAX long since).
"""

from __future__ import annotations

import collections
import contextlib
import gc
import threading
import time

from vtpu.obs.tickprof import (
    LATENCY_BUCKETS_MS,
    BoundedHistogram,
    ring_rows,
)

PERIOD_MS = 5
LATE_MS = 20
RING = 64
GC_RING_MS = 1.0


class PauseWatch:
    """The watcher thread and the collector's listener, counted in and out
    by ``acquire()`` / ``release()``. ``clock`` (ns) and ``cpu`` (s), with
    ``run``'s ``sleep``, are the tests' seam: a made clock shows the rule
    without a loaded machine's own pauses."""

    def __init__(self, clock=time.monotonic_ns, cpu=time.process_time,
                 span=None):
        self.host = BoundedHistogram(LATENCY_BUCKETS_MS)
        self.recent_host = collections.deque(maxlen=RING)
        self.recent_gc = collections.deque(maxlen=RING)
        # per generation [count, total_ms, max_ms]
        self.gc = [[0, 0.0, 0.0] for _ in range(3)]
        self._gc_total_ms = 0.0
        self._gc_open = None  # (start_ns, span) of the collection running
        self._clock, self._cpu = clock, cpu
        self._span = span
        self._lock = threading.Lock()
        self._users = 0
        self._thread = self._stop = None

    # ------------------------------------------------------ the watcher

    def _gc_ms_at(self, now_ns: int) -> float:
        """The collector's milliseconds so far, a running collection's
        elapsed part included."""
        open_ = self._gc_open
        return self._gc_total_ms + (
            (now_ns - open_[0]) / 1e6 if open_ else 0.0)

    def run(self, sleep) -> None:
        """The watcher's loop: ``sleep(seconds)`` a period at a time until
        it returns true (the thread's is its stop event's ``wait``)."""
        span = self._span or contextlib.nullcontext
        period_ns = PERIOD_MS * 1_000_000
        while True:
            t0 = self._clock()
            cpu0, gc0 = self._cpu(), self._gc_ms_at(t0)
            with span("vtpu.watch"):
                stopped = sleep(PERIOD_MS / 1e3)
            if stopped:
                return
            t1 = self._clock()
            late_ms = (t1 - t0 - period_ns) / 1e6
            if late_ms > LATE_MS:
                self.host.note_ms(late_ms)
                self.recent_host.append([
                    t0, round(late_ms, 3),
                    round((self._cpu() - cpu0) * 1e3, 3),
                    round(self._gc_ms_at(t1) - gc0, 3)])

    # ---------------------------------------------------- the collector

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            span = None
            if info["generation"] == 2 and self._span is not None:
                span = self._span("vtpu.gc", generation=2)
                span.__enter__()
            self._gc_open = (self._clock(), span)
            return
        open_ = self._gc_open
        if open_ is None:  # listening began inside this collection
            return
        self._gc_open = None
        t0, span = open_
        ms = (self._clock() - t0) / 1e6
        if span is not None:
            span.__exit__(None, None, None)
        row = self.gc[info["generation"]]
        row[0] += 1
        row[1] += ms
        if ms > row[2]:
            row[2] = ms
        self._gc_total_ms += ms
        if ms >= GC_RING_MS:
            self.recent_gc.append([t0, round(ms, 3), info["generation"],
                                   info["collected"]])

    # ------------------------------------------------------- the owners

    def acquire(self) -> None:
        """Count one more engine in; the first starts the thread and puts
        the listener on ``gc.callbacks``."""
        with self._lock:
            self._users += 1
            if self._users > 1:
                return
            if self._span is None:
                from jax.profiler import TraceAnnotation

                self._span = TraceAnnotation
            gc.callbacks.append(self._on_gc)
            self._stop = threading.Event()  # one a thread: none is revived
            self._thread = threading.Thread(
                target=self.run, args=(self._stop.wait,),
                name="vtpu-pause-watch", daemon=True)
            self._thread.start()

    def release(self) -> None:
        """Count one engine out; the last takes the listener off and ends
        the thread."""
        with self._lock:
            if self._users == 0:
                return
            self._users -= 1
            if self._users:
                return
            with contextlib.suppress(ValueError):
                gc.callbacks.remove(self._on_gc)
            self._gc_open = None
            self._stop.set()
            thread, self._thread = self._thread, None
        if thread is not None:
            thread.join(timeout=1.0)

    @property
    def running(self) -> bool:
        thread = self._thread
        return thread is not None and thread.is_alive()

    def snapshot(self) -> dict:
        """The ``stats()["pauses"]`` view: monotonic counters and the two
        rings, at most ``RING`` rows each."""
        host = self.host
        return {
            "host": {"count": host.count,
                     "total_ms": round(host.total_ms, 4),
                     "max_ms": round(host.max_ms, 4)},
            "gc": {str(g): {"count": row[0], "total_ms": round(row[1], 4),
                            "max_ms": round(row[2], 4)}
                   for g, row in enumerate(self.gc)},
            "recent": {"host": ring_rows(self.recent_host),
                       "gc": ring_rows(self.recent_gc)},
            "period_ms": PERIOD_MS,
            "late_ms": LATE_MS,
        }


# the process's one watch: what every engine of the process acquires
WATCH = PauseWatch()
