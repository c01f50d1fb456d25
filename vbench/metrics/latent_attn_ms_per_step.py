"""Sparse attention: device milliseconds a decode launch spends reading
the selected latent rows and attending over them."""

from vbench import latent_scopes


def read(run):
    return latent_scopes.ms_per_step(("latent_attn",))
