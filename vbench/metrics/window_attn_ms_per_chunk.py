"""Window attention: device milliseconds a prefill-chunk launch spends in
the window layers' attention (the ring's rows and the chunk's own keys
under the band mask) and in the rings' write-back (scopes ``window_attn`` +
``ring_write``, all window layers). None for a program without those
scopes or a trace without a chunk launch."""

from vbench import window_scopes


def read(run):
    return window_scopes.ms_per_chunk()
