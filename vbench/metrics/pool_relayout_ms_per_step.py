"""Paged pool and attention route: device milliseconds a decode launch
spends relaying the pool for the paged kernel (scope ``pool_relayout`` of
the ``jit_step`` programs, own time, over their launches)."""

from vbench import scopes


def read(run):
    return scopes.ms_per_step(scopes.load(), ("pool_relayout",))
