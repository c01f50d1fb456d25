"""Engine loop: how long before the device needed a decode launch the host
had issued it. Per launch: its start on the device minus the end of the
last ``vtpu.tick.dispatch`` span before it, over the time since the decode
launch before; the median, in percent. Near 100 the device never waits for
the host; near 0 every launch starts as soon as it is issued."""

import statistics

from vbench import scopes


def read(run):
    red = scopes.load()
    if red is None or not red["slack"]:
        return None
    return 100.0 * statistics.median(red["slack"])
