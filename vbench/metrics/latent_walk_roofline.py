"""Latent attention: the least time the chip could take for one decode
step's attention over the cached latents (the family's
``latent_attn_step_cost`` over the device's peaks, for the cached tokens
live in the traced part: each one's row read once and attended by every
head) over the device time a decode launch spent under the scope
``latent_attn`` (the walk of the pool's live pages), in percent. None for a
family without that cost function or a program without the scope."""

import importlib

from vbench import latent_scopes, stamps


def read(run):
    if not run.trace_span:
        return None
    ref = importlib.import_module(f"vbench.reference.{run.cfg['family']}")
    cost = getattr(ref, "latent_attn_step_cost", None)
    ms = latent_scopes.ms_per_step(("latent_attn",))
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
