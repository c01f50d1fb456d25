"""Block generation: device milliseconds a pass's launch spends under the
scope ``block_attn`` (vbench/block_scopes.py): the walk of the slots' live
pages for the block's rows, the block's own keys, the join. None where the
trace holds no pass or the program has no such scope."""

from vbench import block_scopes


def read(run):
    if not run.trace:
        return None
    return block_scopes.ms_per_pass()
