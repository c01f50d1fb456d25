"""Sparse attention: of the window positions the window's prefill chunks
multiplied (a chunk kernel's walk: up to the chunk's last position rounded
up to its key block; XLA's code: the whole read window), the share that
lay at or before the chunk's last position, which are all a query of the
chunk can see (``stats()`` counters ``chunk_keys_live`` over
``chunk_keys_attended``). None where the program keeps no such counters or
no chunk was dispatched."""


def read(run):
    if "chunk_keys_attended" not in run.stats1:
        return None
    attended = run.counter("chunk_keys_attended")
    return 100.0 * run.counter("chunk_keys_live") / attended if attended else None
