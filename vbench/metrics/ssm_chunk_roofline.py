"""Recurrent state: the least time the chip could take for one prefill
chunk's convolution, chunked scan and gated norm (the family's
``ssm_chunk_cost`` over the device's peaks, FLOPs or bytes, whichever
bounds, for the true prompt tokens a chunk launch of the trace carried) over
the device time a chunk launch spent under those scopes, in percent. None
for a family without that cost function, a program without the scopes or a
trace without a chunk launch whose span it holds."""

import importlib

from vbench import scopes, ssm_scopes


def read(run):
    ref = importlib.import_module(f"vbench.reference.{run.cfg['family']}")
    cost = getattr(ref, "ssm_chunk_cost", None)
    ms = ssm_scopes.ms_per_chunk()
    red = scopes.load()
    if cost is None or not ms or not red or not red["prefill"]["launches"]:
        return None
    tokens = red["prefill"]["tokens"] / red["prefill"]["launches"]
    return 100.0 * ssm_scopes.least_ms(cost(run.cfg, tokens), run.peaks) / ms
