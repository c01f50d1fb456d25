"""Paged pool and attention route: device milliseconds a prefill-chunk
launch spends attending its gathered window (scope ``chunk_attn``, all
attention layers: the chunk kernel where the program's rule wires the
shapes in, XLA's scores, softmax and value product elsewhere;
vbench/chunk_scopes.py). None for a program without the scope or a trace
without a chunk launch."""

from vbench import chunk_scopes


def read(run):
    if not run.trace:
        return None
    return chunk_scopes.ms_per_chunk()
