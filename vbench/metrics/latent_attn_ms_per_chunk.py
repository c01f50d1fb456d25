"""Sparse attention: device milliseconds a prefill chunk's launch spends
in latent attention over its read window under the selection's mask (the
chunk route reads and multiplies the whole window: PERF.md section 6)."""

from vbench import latent_scopes


def read(run):
    return latent_scopes.ms_per_chunk(("latent_attn",))
