"""Paged pool and attention route: device milliseconds a decode launch
spends in the paged attention kernel (scope ``paged_attn``)."""

from vbench import scopes


def read(run):
    return scopes.ms_per_step(scopes.load(), ("paged_attn",))
