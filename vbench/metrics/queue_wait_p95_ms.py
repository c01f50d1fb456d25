"""Engine loop: 95th percentile of Request.t_depart_ns (the request left
the waiting line) minus due time, over the requests due in the window."""

import math

from vbench import stamps


def read(run):
    waits = [r.depart_s - r.due_s for r in run.records
             if r.in_window and not math.isnan(r.depart_s)]
    v = stamps.percentile(waits, 0.95)
    return None if v is None else v * 1e3
