"""95th percentile, over every request due in the window, of first token
minus the time the request was due."""

from vbench import stamps


def read(run):
    v = stamps.percentile(stamps.ttfts(run.records, run.give_up_s), 0.95)
    return None if v is None else v * 1e3
