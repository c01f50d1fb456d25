"""95th percentile of the window's token gaps, in a cell where it is not
steady enough for a bound: watched, not judged (the arithmetic of the
end-to-end metric ``itl_p95_ms``)."""

from vbench import stamps


def read(run):
    return stamps.gap_percentile_ms(run.records, run.seconds, 0.95)
