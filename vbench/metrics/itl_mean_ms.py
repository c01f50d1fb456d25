"""All the time streams spent between consecutive tokens delivered in the
window, over the count of those gaps (zeros included)."""

from vbench import stamps


def read(run):
    gaps = stamps.window_gaps(run.records, 0.0, run.seconds)
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
