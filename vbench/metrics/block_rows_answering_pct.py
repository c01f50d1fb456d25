"""Block generation: of the rows of the slots that took a pass in the
window, the share that were masked and could answer (``stats()`` counters
``block_rows_masked`` over ``block_rows_dispatched``): the rest are rows
already committed, recomputed because the block's rows see each other, and
the rows of writing passes, which answer nothing. None where the program
keeps no such counters or no slot took a pass."""


def read(run):
    if "block_rows_dispatched" not in run.stats1:
        return None
    rows = run.counter("block_rows_dispatched")
    return 100.0 * run.counter("block_rows_masked") / rows if rows else None
