"""Output tokens delivered in the window over the window's length."""

from vbench import stamps


def read(run):
    n = stamps.window_tokens(run.records, 0.0, run.seconds)
    return n / run.seconds if n else None
