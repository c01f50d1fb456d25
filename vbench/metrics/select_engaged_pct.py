"""Sparse attention: of the dispatched slot-ticks of the window's decode
steps, the share whose query selected its blocks (``stats()`` counters
``select_rows`` over ``select_rows + select_rows_dense``; the rest saw at
most the model's ``dense_len`` tokens and attended all of them). An alarm:
about 100 in a cell whose sessions are all past ``dense_len``. None where
the program keeps no such counters or no tick dispatched a slot."""


def read(run):
    if "select_rows" not in run.stats1 or "select_rows" not in run.stats0:
        return None
    rows = run.counter("select_rows")
    every = rows + run.counter("select_rows_dense")
    return 100.0 * rows / every if every else None
