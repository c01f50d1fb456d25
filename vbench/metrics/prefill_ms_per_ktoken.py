"""Admission and prefill: device milliseconds of the admission and chunk
programs per thousand true prompt tokens. The launches are those whose
``vtpu.admit.*`` span lies in the trace too, and the tokens what those
spans carry: the growth of ``stats()["prefill_tokens"]`` over the traced
part counts dispatches, which may run after the trace has stopped (two or
three chunks in five seconds make that a third of the number). No such
launch in the trace reads 0; a program without the counter, None."""

from vbench import scopes


def read(run):
    red = scopes.load()
    if red is None or not run.trace_stats:
        return None
    if "prefill_tokens" not in run.trace_stats[1]:
        return None
    got = red["prefill"]
    if not got["tokens"]:
        return 0.0
    return 1e3 * got["seconds"] / (got["tokens"] / 1e3)
