"""Recurrent state: device milliseconds a prefill chunk's launch spends in
the Mamba layers' convolution, chunked scan and gated norm."""

from vbench import ssm_scopes


def read(run):
    return ssm_scopes.ms_per_chunk()
