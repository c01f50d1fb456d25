"""Decode step: host milliseconds waiting in the tick's one device fetch,
per decode tick of the window (the device-bound share of a tick)."""


def read(run):
    ticks = run.ticks()
    return run.phase_ms(("fetch",)) / ticks if ticks else None
