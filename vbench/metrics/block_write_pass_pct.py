"""Block generation: of the passes slots took in the window, the share that
were writing passes (the clean block's keys and values into the pool; no
row answers): ``stats()`` counters ``block_write_passes`` over
``block_slot_passes``. A third at two commits a pass; what folding the
writing pass into the next block's first pass would take away. None where
the program keeps no such counters or no slot took a pass."""


def read(run):
    if "block_slot_passes" not in run.stats1:
        return None
    passes = run.counter("block_slot_passes")
    return (100.0 * run.counter("block_write_passes") / passes
            if passes else None)
