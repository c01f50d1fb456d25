"""What one run hands to the metric readers."""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

HOST_PHASES = ("admission", "dispatch", "deliver", "swap_drain")


@dataclasses.dataclass
class Run:
    records: list            # vbench.stamps.Record, times from window start
    seconds: float           # the window's length
    setup_s: float           # process start to window start
    give_up_s: float         # when the client stopped waiting (from start)
    stats0: dict             # engine.stats() at the window's start
    stats1: dict             # ... and at its end
    cfg: dict                # the configuration's file
    mix: dict                # the traffic mix's file
    peaks: dict              # this device's row of vbench/peaks.json
    step_cost: Callable      # (cfg, batch, live_tokens) -> (FLOPs, bytes)
    trace: Optional[dict] = None        # vbench.trace.reduce(), traced runs
    trace_span: Optional[tuple] = None  # (start, end) of the traced part
    trace_stats: Optional[tuple] = None  # stats() at its start and end

    def counter(self, name: str) -> float:
        """A monotonic counter's growth over the window."""
        return self.stats1[name] - self.stats0[name]

    def phase_ms(self, phases) -> float:
        """Host milliseconds the engine's loop noted in ``phases`` over
        the window (stats()["tick_phase_ms"] totals)."""
        a, b = self.stats0["tick_phase_ms"], self.stats1["tick_phase_ms"]
        return sum(b[p]["total_ms"] - a[p]["total_ms"] for p in phases)

    def ticks(self) -> int:
        return self.counter("decode_ticks") + self.counter("spec_ticks")
