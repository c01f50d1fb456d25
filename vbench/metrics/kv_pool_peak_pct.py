"""Paged pool: the allocator's high-water mark over the pool's usable
blocks, up to the window's end."""


def read(run):
    blocks = run.stats1.get("kv_pool_blocks")
    hwm = run.stats1.get("kv_pool_used_hwm")
    return 100.0 * hwm / blocks if blocks and hwm is not None else None
