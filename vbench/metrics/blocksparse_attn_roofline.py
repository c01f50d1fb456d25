"""Sparse attention: the least time the chip could take for one decode
step's selection and attention in the block-sparse layers (the family's
``blocksparse_attn_step_cost`` over the device's peaks: the visible
compressed keys and the selected tokens' keys and values read once a layer,
both products for every query head) over the device time a decode launch
spent under the scopes ``indexer`` + ``select`` (vbench/latent_scopes.py)
and ``paged_attn`` + ``gather_attn`` (the selected pages' walk, whichever
route implements it), in percent. Streams and cached tokens are the
engine's own counts over the traced ticks (``stats()``: the slot-ticks of
``select_rows + select_rows_dense`` and ``attn_visible_tokens`` a decode
tick between the two snapshots that bracket the trace). None for a family
without that cost function, a program without the counters or the scopes,
or a trace without a decode tick."""

import importlib

from vbench import latent_scopes, scopes


def read(run):
    if not run.trace_stats:
        return None
    ref = importlib.import_module(f"vbench.reference.{run.cfg['family']}")
    cost = getattr(ref, "blocksparse_attn_step_cost", None)
    before, after = run.trace_stats
    if cost is None or "select_rows" not in after:
        return None
    ticks = after["decode_ticks"] - before["decode_ticks"]
    select = latent_scopes.ms_per_step(("indexer", "select"))
    walk = scopes.ms_per_step(scopes.load(), ("paged_attn", "gather_attn"))
    if ticks < 1 or not select or not walk:
        return None

    def a_tick(*names):
        return sum(after[n] - before[n] for n in names) / ticks

    flops, byts = cost(run.cfg, a_tick("select_rows", "select_rows_dense"),
                       a_tick("attn_visible_tokens"))
    least = max(flops / run.peaks["bf16_flops_per_s"],
                byts / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / ((select + walk) / 1e3)
