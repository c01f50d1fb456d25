"""Kernels: device milliseconds a decode launch spends in the router and
the experts (a sparse model's cells)."""

from vbench import scopes


def read(run):
    return scopes.ms_per_step(scopes.load(), ("route", "experts"))
