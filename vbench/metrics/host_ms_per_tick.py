"""Engine loop: host milliseconds outside the device fetch (admission
head, dispatch, delivery, swap drain) per decode tick of the window."""

from vbench.rundata import HOST_PHASES


def read(run):
    ticks = run.ticks()
    return run.phase_ms(HOST_PHASES) / ticks if ticks else None
