"""Sparse attention: device milliseconds a decode launch spends in the
indexer (its projections, its key's write, its scores over the read
window) and in the selection (top-k, the block ids' lookup)."""

from vbench import latent_scopes


def read(run):
    return latent_scopes.ms_per_step(("indexer", "select"))
