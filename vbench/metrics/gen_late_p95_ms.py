"""Load generator: 95th percentile of send stamp minus due time over the
requests due in the window (a starved generator reads as a fast server)."""

from vbench import stamps


def read(run):
    late = [r.sent_s - r.due_s for r in run.records if r.in_window]
    v = stamps.percentile(late, 0.95)
    return None if v is None else v * 1e3
