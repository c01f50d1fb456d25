"""Recurrent state: the least time the chip could take for one decode
step's convolution, state update and gated norm (the family's
``ssm_step_cost`` over the device's peaks, for the streams live in the
traced part: each one's state read and written once) over the device time a
decode launch spent under those scopes, in percent. The step computes every
slot's row, live or not: the cost counts the live ones. None for a family
without that cost function or a program without the scopes."""

import importlib

from vbench import ssm_scopes, stamps


def read(run):
    if not run.trace_span:
        return None
    ref = importlib.import_module(f"vbench.reference.{run.cfg['family']}")
    cost = getattr(ref, "ssm_step_cost", None)
    ms = ssm_scopes.ms_per_step()
    if cost is None or not ms:
        return None
    a, b = run.trace_span
    n = 8
    batch = sum(stamps.live_tokens_at(
        run.records, a + (b - a) * (i + 0.5) / n)[0] for i in range(n)) / n
    if batch < 1:
        return None
    return 100.0 * ssm_scopes.least_ms(cost(run.cfg, batch), run.peaks) / ms
