"""Kernels: device milliseconds a decode launch spends in the weights'
products: the projections, the MLP or the router and experts, and the
output head."""

from vbench import scopes


def read(run):
    return scopes.ms_per_step(scopes.load(), (
        "qkv", "o_proj", "mlp", "route", "experts", "lm_head"))
