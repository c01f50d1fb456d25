"""Sparse attention: device milliseconds a prefill chunk's launch spends
in the indexer (its projections, its keys' write, its scores over the read
window) and in the selection (the k-th score a query, the mask)."""

from vbench import latent_scopes


def read(run):
    return latent_scopes.ms_per_chunk(("indexer", "select"))
