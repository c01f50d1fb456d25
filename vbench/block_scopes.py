"""Device time of a pass by the scope that the family ``blockdiff`` adds to
the program's vocabulary: ``block_attn`` (a pass's attention: the walk of a
slot's live pages for the block's rows, the block's own keys beside it, and
the two softmaxes joined).

vbench/scopes.py reduces a trace by its own copy of the vocabulary, which
does not hold this name (the program nests it under ``attn``, and the walk
inside it is the kernel ``paged_attn``, both of which it does hold, so its
readers keep their meaning: ``paged_attn_ms_per_step`` reads the walk, and
nothing of the rest is unscoped). As
vbench/window_scopes.py does for its two, this module reads the same trace
file once more and sums, over the launches of the pass program (``jit_step``:
a pass is this family's decode step), the own time of the operations whose
scope path holds the name. The kernel's own preparation of its queries lies
under ``pool_relayout`` inside the scope and is counted with it: it is part
of the pass's attention. A program without the name (the parent of PR 43,
and every other family) gives None throughout, never zero and never an
error.
"""

from __future__ import annotations

import bisect
import os

from vbench import scopes

NAMES = ("block_attn",)

_loaded = {}


def scope_of(tf_op: str):
    """The innermost of NAMES on an operation's scope path, or None."""
    for part in reversed(tf_op.rstrip(":").split("/")):
        if part in NAMES:
            return part
    return None


def by_program(raw: dict) -> dict:
    """{scope: seconds}: own time under each of NAMES inside the launches
    of the pass program, in a loaded trace (``scopes.load_xplane``'s dict).
    An operation that holds others is charged what is left of it."""
    out = {}
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
            if scopes.module_key(modules[i][0]) == scopes.DECODE:
                out[name] = out.get(name, 0.0) + own / 1e12
    return out


def load(root: str = scopes.ROOT):
    """{scope: seconds} of the newest trace under ``<root>/.vbench_out``,
    read once a process; None where there is no trace or no operation of a
    pass carries one of the names."""
    path = scopes.newest_xplane(os.path.join(root, ".vbench_out", "trace"))
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _loaded:
        _loaded.clear()
        _loaded[key] = by_program(scopes.load_xplane(path)) or None
    return _loaded[key]


def ms_per_pass(root: str = scopes.ROOT):
    """Device milliseconds a pass's launch spends under NAMES, launches
    counted in whole ones as ``scopes.decode_steps`` counts steps; None
    where there is no trace, no pass in it, or a program without the
    names."""
    got, red = load(root), scopes.load(root)
    steps = scopes.decode_steps(red) if red else None
    if not got or steps is None:
        return None
    return 1e3 * sum(got.values()) / steps[1]
