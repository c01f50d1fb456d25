"""Device: share of the device's idle time between launches that began
under no ``vtpu.tick.*`` span of the loop's thread: idle the trace cannot
put down to a host phase. 0 where the device is never idle between
launches; None where the trace holds no tick span."""

from vbench import scopes


def read(run):
    red = scopes.load()
    got = scopes.between_launch_idle(red) if red else None
    if got is None:
        return None
    idle, unnamed = got
    return 100.0 * unnamed / idle if idle else 0.0
