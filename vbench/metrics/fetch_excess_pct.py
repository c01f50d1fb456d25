"""Decode step: share of the window lost in fetches judged long
(``long_ms`` of ``stats()["tick_phase_ms"]["fetch"]``: a fetch of 1 s or
more whole, and what a fetch of decode steps alone took over the running
mean of such fetches when it took over twice that mean plus 10 ms)."""

from vbench import pauses


def read(run):
    return pauses.lost_pct(run, lambda s: pauses.long_ms(s, ("fetch",)))
