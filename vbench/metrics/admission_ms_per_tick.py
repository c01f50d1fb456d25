"""Admission: host milliseconds in the tick head (queue drain, prefill
and chunk dispatch) per decode tick of the window."""


def read(run):
    ticks = run.ticks()
    return run.phase_ms(("admission",)) / ticks if ticks else None
