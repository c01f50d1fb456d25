"""Kernels: share of the decode programs' device time under no scope of
the vocabulary: how far the per-scope metrics can be trusted. None where no
operation carries a scope at all (a program from before the names)."""

from vbench import scopes


def read(run):
    red = scopes.load()
    got = scopes.decode_steps(red) if red else None
    if got is None:
        return None
    by_scope, _ = got
    return 100.0 * by_scope.get(scopes.UNSCOPED, 0.0) / sum(by_scope.values())
