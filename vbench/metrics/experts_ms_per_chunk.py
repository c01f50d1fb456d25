"""Kernels: device milliseconds a prefill-chunk launch spends in the router
and the experts (scopes ``route`` + ``experts`` of the launches of
``jit_prefill_chunk_into_slot``, launches counted whole as
``latent_scopes.ms_per_chunk`` counts them: a sparse model's cells). None
for a trace without a chunk launch or a chunk program without an
``experts`` scope."""

from vbench import scopes
from vbench.latent_scopes import CHUNK


def of(red):
    """The metric of a reduced trace (``scopes.reduce``), or None."""
    row = red["programs"].get(CHUNK) if red else None
    if not row or not row["whole_s"] or "experts" not in row["scopes"]:
        return None
    launches = row["seconds"] / row["whole_s"]
    return 1e3 * sum(
        row["scopes"].get(s, 0.0) for s in ("route", "experts")) / launches


def read(run):
    return of(scopes.load())
