"""Sparse attention: the least time the chip could take for one decode
step's indexer, selection and latent attention (the family's
``sparse_attn_step_cost`` over the device's peaks, for the streams and
cached tokens live in the traced part) over the device time a decode
launch spent under those three scopes, in percent. None for a family
without that cost function or a program without the scopes."""

import importlib

from vbench import latent_scopes, stamps


def read(run):
    if not run.trace_span:
        return None
    ref = importlib.import_module(f"vbench.reference.{run.cfg['family']}")
    cost = getattr(ref, "sparse_attn_step_cost", None)
    ms = latent_scopes.ms_per_step(latent_scopes.NAMES)
    if cost is None or not ms:
        return None
    a, b = run.trace_span
    n = 8
    live = [stamps.live_tokens_at(run.records, a + (b - a) * (i + 0.5) / n)
            for i in range(n)]
    batch = sum(s for s, _ in live) / n
    tokens = sum(tk for _, tk in live) / n
    if batch < 1:
        return None
    flops, byts = cost(run.cfg, batch, tokens)
    least = max(flops / run.peaks["bf16_flops_per_s"],
                byts / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ms / 1e3)
