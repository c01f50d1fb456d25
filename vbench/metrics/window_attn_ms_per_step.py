"""Window attention: device milliseconds a decode launch spends in the
window layers' attention over their rings and in the rings' writes (scopes
``window_attn`` + ``ring_write``, all window layers). None for a program
without those scopes."""

from vbench import window_scopes


def read(run):
    return window_scopes.ms_per_step()
