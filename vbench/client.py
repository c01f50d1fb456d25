"""The load generator: sends a mix to ``ServingEngine.submit()`` and stamps
every token as ``Request.stream()`` yields it, one reader thread a stream.

The engine's loop thread blocks in its device fetch for nearly all of a
tick, so readers get the interpreter at once; a stamp is taken when the
client has the token in hand, which is what a user would see.
"""

from __future__ import annotations

import threading
import time

from vbench.stamps import Record
from vbench.traffic import Planned


def _now() -> float:
    return time.monotonic_ns() / 1e9


class Client:
    """Drives one window. ``t0`` (absolute monotonic seconds of the
    window's start) is set by the caller: before the ramp in an open loop,
    at the moment of steady state in a saturated one. Stamps are kept
    absolute and rebased by ``records()``."""

    def __init__(self, engine):
        self.engine = engine
        self.t0 = None
        self._records: list[Record] = []
        self._threads: list[threading.Thread] = []
        self._lock = threading.Lock()
        self._order = threading.Lock()  # take and submit as one step
        self._closing = threading.Event()
        self.errors: list[str] = []

    # ------------------------------------------------------------ sending

    def _send(self, plan: Planned, due_abs: float) -> tuple:
        rec = Record(plan.index, len(plan.prompt), plan.max_new, due_abs,
                     plan.in_window, prompt=plan.prompt)
        rec.sent_s = _now()
        req = self.engine.submit(plan.prompt, max_new_tokens=plan.max_new)
        rec.request = req
        with self._lock:
            self._records.append(rec)
        return rec, req

    def _read(self, rec: Record, req) -> None:
        for tok in req.stream():
            rec.stamps.append(_now())
            rec.tokens.append(int(tok))
        rec.ended_s = _now()
        rec.status = req.status

    def _reader(self, rec: Record, req) -> None:
        try:
            self._read(rec, req)
        except Exception as e:  # a reader must not die silently
            self.errors.append(f"reader {rec.index}: {e!r}")

    def run_open(self, schedule: list[Planned], t0: float) -> threading.Thread:
        """Send ``schedule`` on its clock (due_s relative to ``t0``) from
        one dispatcher thread; returns it (join to wait for the last send)."""
        self.t0 = t0

        def dispatch():
            try:
                for plan in schedule:
                    due = t0 + plan.due_s
                    wait = due - _now()
                    if wait > 0:
                        time.sleep(wait)
                    if self._closing.is_set():
                        return
                    rec, req = self._send(plan, due)
                    th = threading.Thread(target=self._reader,
                                          args=(rec, req), daemon=True)
                    th.start()
                    self._threads.append(th)
            except Exception as e:
                self.errors.append(f"dispatcher: {e!r}")

        th = threading.Thread(target=dispatch, daemon=True)
        th.start()
        return th

    def run_saturated(self, backlog, outstanding: int) -> None:
        """Keep ``outstanding`` requests submitted and unfinished: each of
        that many threads sends the next of the backlog, reads it to its
        end, and takes another. The engine gets them in the backlog's
        order: a pool at its limit admits first come, first served, so two
        threads swapping their submits change how many streams fit."""

        def worker():
            try:
                while True:
                    with self._order:  # close() waits for a submit begun
                        if self._closing.is_set():
                            return
                        plan = backlog.take()
                        rec, req = self._send(plan, _now())
                    rec.due_s = rec.sent_s
                    self._read(rec, req)
            except Exception as e:
                self.errors.append(f"worker: {e!r}")

        for _ in range(outstanding):
            th = threading.Thread(target=worker, daemon=True)
            th.start()
            self._threads.append(th)

    # ------------------------------------------------------------ ending

    def close(self) -> None:
        """Send nothing more (streams in flight go on). Once this returns
        no saturated worker submits again: the engine may be stopped."""
        with self._order:
            self._closing.set()

    def join(self, timeout_s: float) -> bool:
        """Wait for every reader; call after the engine is stopped (its
        exit path ends the streams still open)."""
        end = _now() + timeout_s
        for th in self._threads:
            th.join(max(0.0, end - _now()))
        return not any(th.is_alive() for th in self._threads)

    def first_tokens_owed(self, t1: float) -> int:
        """Requests due before ``t1`` (absolute) still without a token."""
        with self._lock:
            return sum(1 for r in self._records
                       if r.in_window and r.due_s < t1 and not r.stamps
                       and r.status is None)

    def records(self) -> list[Record]:
        """Every record, times rebased to the window's start, with the
        engine's queue-departure stamp and, where the program keeps one,
        its commit trail read from its Request."""
        out = []
        with self._lock:
            for r in self._records:
                depart_ns, trail = (r.request.t_depart_ns,
                                    getattr(r.request, "trail", None))
                r.request = None
                r.trail = None if trail is None else [int(p) for p in trail]
                r.depart_s = (depart_ns / 1e9 - self.t0 if depart_ns
                              else float("nan"))
                r.due_s -= self.t0
                r.sent_s -= self.t0
                r.ended_s -= self.t0
                r.stamps = [s - self.t0 for s in r.stamps]
                out.append(r)
            self._records = []
        return out
