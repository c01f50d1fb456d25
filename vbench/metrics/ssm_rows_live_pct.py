"""Recurrent state: of the slot rows the window's decode steps updated
(every slot's, the step's shape), the share that belonged to a dispatched
slot (``stats()`` counters ``ssm_rows_live`` over ``ssm_rows_stepped``).
None where the program keeps no such counters or no step ran."""


def read(run):
    if "ssm_rows_stepped" not in run.stats1:
        return None
    stepped = run.counter("ssm_rows_stepped")
    return 100.0 * run.counter("ssm_rows_live") / stepped if stepped else None
