"""Device time of the decode step and of the prefill chunk by the scopes that
the latent family adds to the program's vocabulary: ``indexer``, ``select``,
``latent_attn``.

vbench/scopes.py reduces a trace by its own copy of the vocabulary, which
does not hold these three (the program nests them under ``attn``, which it
does hold, so its readers keep their meaning: nothing of them is
unscoped). This module reads the same trace file once more and sums, over
the launches of one program (``jit_step``, or the chunk program, where the
cell spends most of its device time), the own time of the operations whose
scope path holds one of the three, innermost first. A program without them
(the parent of PR 28) gives None throughout, never zero and never an error.
"""

from __future__ import annotations

import bisect
import os

from vbench import scopes

NAMES = ("indexer", "select", "latent_attn")
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
    (``scopes.load_xplane``'s dict)."""
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


def by_scope(raw: dict, program: str = scopes.DECODE) -> dict:
    """Seconds under each of NAMES inside the launches of ``program``."""
    return by_program(raw)[program]


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


def ms_per_step(names, root: str = scopes.ROOT):
    """Device milliseconds a decode launch spends under ``names``, or None
    (no trace, no decode launch in it, or a program without the names)."""
    got, red = load(root), scopes.load(root)
    steps = scopes.decode_steps(red) if red else None
    if not got or not got[scopes.DECODE] or steps is None:
        return None
    return 1e3 * sum(got[scopes.DECODE].get(n, 0.0) for n in names) / steps[1]


def ms_per_chunk(names, root: str = scopes.ROOT):
    """Device milliseconds a chunk launch spends under ``names``, launches
    counted in whole ones as ``scopes.decode_steps`` counts steps; None
    where there is no trace, no chunk launch or no such name in one."""
    got, red = load(root), scopes.load(root)
    row = red["programs"].get(CHUNK) if red else None
    if not got or not got[CHUNK] or not row or not row["whole_s"]:
        return None
    launches = row["seconds"] / row["whole_s"]
    return 1e3 * sum(got[CHUNK].get(n, 0.0) for n in names) / launches
