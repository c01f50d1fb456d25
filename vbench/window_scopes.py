"""Device time of the decode step and of the prefill chunk by the scopes that
the window family adds to the program's vocabulary: ``window_attn`` (a window
layer's read of its ring under the band mask, the sink in the softmax's
denominator) and ``ring_write`` (the ring's rows written in place).

vbench/scopes.py reduces a trace by its own copy of the vocabulary, which
does not hold these two (the program nests them under ``attn``, which it
does hold, so its readers keep their meaning: nothing of them is unscoped).
As vbench/latent_scopes.py and vbench/ssm_scopes.py do for theirs, this
module reads the same trace file once more and sums, over the launches of
one program (``jit_step``, or the chunk program), the own time of the
operations whose scope path holds one of the names, innermost first. A
program without them (the parent of PR 39, and every other family) gives
None throughout, never zero and never an error.
"""

from __future__ import annotations

import bisect
import os

from vbench import scopes

NAMES = ("window_attn", "ring_write")
CHUNK = "jit_prefill_chunk_into_slot"

_loaded = {}


def scope_of(tf_op: str):
    """The innermost of NAMES on an operation's scope path, or None."""
    for part in reversed(tf_op.rstrip(":").split("/")):
        if part in NAMES:
            return part
    return None


def by_program(raw: dict) -> dict:
    """{program: {scope: seconds}}: own time under each of NAMES inside the
    launches of the decode step and of the chunk program, in a loaded trace
    (``scopes.load_xplane``'s dict). An operation that holds others (the
    ``while`` of a run of layers) is charged what is left of it."""
    out = {scopes.DECODE: {}, CHUNK: {}}
    for dev in raw["devices"].values():
        modules = sorted(dev["modules"], key=lambda m: m[1])
        starts = [m[1] for m in modules]
        for op, own in scopes._own_time(dev["ops"]):
            name = scope_of(op[3])
            if name is None:
                continue
            i = bisect.bisect_right(starts, op[1]) - 1
            if i < 0 or op[1] >= modules[i][1] + modules[i][2]:
                continue
            row = out.get(scopes.module_key(modules[i][0]))
            if row is not None:
                row[name] = row.get(name, 0.0) + own / 1e12
    return out


def load(root: str = scopes.ROOT):
    """{program: {scope: seconds}} (decode step and chunk) of the newest
    trace under ``<root>/.vbench_out``, read once a process; None where
    there is no trace or no operation carries one of the names."""
    path = scopes.newest_xplane(os.path.join(root, ".vbench_out", "trace"))
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _loaded:
        _loaded.clear()
        got = by_program(scopes.load_xplane(path))
        _loaded[key] = got if any(got.values()) else None
    return _loaded[key]


def ms_per_step(root: str = scopes.ROOT):
    """Device milliseconds a decode launch spends under NAMES, or None (no
    trace, no decode launch in it, or a program without the names)."""
    got, red = load(root), scopes.load(root)
    steps = scopes.decode_steps(red) if red else None
    if not got or not got[scopes.DECODE] or steps is None:
        return None
    return 1e3 * sum(got[scopes.DECODE].values()) / steps[1]


def ms_per_chunk(root: str = scopes.ROOT):
    """Device milliseconds a chunk launch spends under NAMES, launches
    counted in whole ones as ``scopes.decode_steps`` counts steps; None
    where there is no trace, no chunk launch or no such name in one."""
    got, red = load(root), scopes.load(root)
    row = red["programs"].get(CHUNK) if red else None
    if not got or not got[CHUNK] or not row or not row["whole_s"]:
        return None
    return 1e3 * sum(got[CHUNK].values()) / (row["seconds"] / row["whole_s"])

