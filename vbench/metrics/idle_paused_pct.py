"""Device: of the traced slice's idle time between launches, the share
that lies inside a pause of the process (a ``vtpu.watch`` span over 20 ms)
or a full collection (``vtpu.gc``). 0.0 where the device is never idle
between launches; None where the trace holds no ``vtpu.watch`` span."""

from vbench import pauses


def read(run):
    got = pauses.idle_paused()
    if got is None:
        return None
    idle, paused = got
    return 100.0 * paused / idle if idle else 0.0
