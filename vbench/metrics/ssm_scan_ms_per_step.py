"""Recurrent state: device milliseconds a decode launch spends in the
Mamba layers' convolution, state update and gated norm."""

from vbench import ssm_scopes


def read(run):
    return ssm_scopes.ms_per_step()
