"""Paged pool and attention route: the least time the chip could take for
one decode step's attention over the cache in the full layers (the family's
``full_attn_step_cost`` over the device's peaks: every live token's row of
key/value heads read once a full layer, both products' operations) over the
device time a decode launch spent under the scope ``paged_attn`` (the walk
of the pool's live pages), in percent. The cached tokens are the engine's
own count over the traced ticks (``stats()["attn_visible_tokens"]`` a
decode tick between the two snapshots that bracket the trace), not the
client's stamps. None for a family without that cost function, a program
without the counter or the scope, or a trace without a decode tick."""

import importlib

from vbench import scopes


def read(run):
    if not run.trace_stats:
        return None
    ref = importlib.import_module(f"vbench.reference.{run.cfg['family']}")
    cost = getattr(ref, "full_attn_step_cost", None)
    before, after = run.trace_stats
    if cost is None or not after.get("window_ring"):
        return None
    ticks = after["decode_ticks"] - before["decode_ticks"]
    ms = scopes.ms_per_step(scopes.load(), ("paged_attn",))
    if ticks < 1 or not ms:
        return None
    tokens = (after["attn_visible_tokens"]
              - before["attn_visible_tokens"]) / ticks
    flops, byts = cost(run.cfg, run.cfg["serving"]["slots"], tokens)
    least = max(flops / run.peaks["bf16_flops_per_s"],
                byts / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ms / 1e3)
