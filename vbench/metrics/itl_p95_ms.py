"""95th percentile of every gap between consecutive tokens delivered in
the window."""

from vbench import stamps


def read(run):
    return stamps.gap_percentile_ms(run.records, run.seconds, 0.95)
