"""Process start to the window's start: weights, pool, warm-up (compile or
cache load) and the mix's ramp to steady state."""


def read(run):
    return run.setup_s
