"""Latent attention: of the pool rows the window's decode steps walked
(every slot's live pages whole, one page for a slot that was not
dispatched), the share that were cached tokens a dispatched stream could
see (``stats()`` counters ``latent_rows_live`` over
``latent_rows_walked``). None where the program keeps no such counters or
no step walked a row."""


def read(run):
    if "latent_rows_walked" not in run.stats1:
        return None
    walked = run.counter("latent_rows_walked")
    return 100.0 * run.counter("latent_rows_live") / walked if walked else None
