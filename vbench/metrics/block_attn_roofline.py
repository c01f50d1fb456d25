"""Block generation: the least time the chip could take for one pass's
attention (the family's ``block_attn_pass_cost`` over the device's peaks:
every live cached token's key and value read once a layer, the block's own
rows, both products' operations for every row of the block) over the device
time a pass's launch spent under the scope ``block_attn``, in percent. The
streams are the slots that took a pass and the cached tokens what their
rows read, both the engine's own counts over the traced ticks
(``stats()["block_slot_passes"]`` and ``["attn_visible_tokens"]`` a pass
between the two snapshots that bracket the trace; the host's mirror of a
length lags the device by at most a block, so the tokens are never counted
high). None for a family without that cost function, a program without the
counters or the scope, or a trace without a pass."""

import importlib

from vbench import block_scopes


def read(run):
    if not run.trace_stats:
        return None
    ref = importlib.import_module(f"vbench.reference.{run.cfg['family']}")
    cost = getattr(ref, "block_attn_pass_cost", None)
    before, after = run.trace_stats
    if cost is None or "block_slot_passes" not in after:
        return None
    ticks = after["decode_ticks"] - before["decode_ticks"]
    ms = block_scopes.ms_per_pass()
    if ticks < 1 or not ms:
        return None
    streams = (after["block_slot_passes"]
               - before["block_slot_passes"]) / ticks
    tokens = (after["attn_visible_tokens"]
              - before["attn_visible_tokens"]) / ticks
    flops, byts = cost(run.cfg, streams, tokens)
    least = max(flops / run.peaks["bf16_flops_per_s"],
                byts / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ms / 1e3)
