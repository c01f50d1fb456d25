"""Paged pool and attention route: of the window's prefill chunks of a
family that caches heads, the share whose program attends its gathered
window in the chunk kernel (``vtpu/ops/chunk_attn.py``; ``stats()`` counters
``chunk_attn_kernel`` over ``chunk_attn_launches``). An alarm: it reads 100
where the rule wires the cell's shapes in, and a chunk that falls back to
XLA's code shows here before it shows in a time. None where the program
keeps no such counters or no chunk was dispatched."""


def read(run):
    if "chunk_attn_kernel" not in run.stats1:
        return None
    launches = run.counter("chunk_attn_launches")
    return 100.0 * run.counter("chunk_attn_kernel") / launches if launches else None
