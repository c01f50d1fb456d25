"""Device time of the prefill chunk by the scope that the families that cache
heads put around a chunk's attention over its gathered window:
``chunk_attn`` (the chunk kernel of ``vtpu/ops/chunk_attn.py`` where the
program's rule wires the shapes in, XLA's code elsewhere; either way the
scores, the softmax and the value product of the chunk's queries, with what
the call prepares of them).

vbench/scopes.py reduces a trace by its own copy of the vocabulary, which
does not hold this name (the program nests it under ``attn`` or
``gather_attn``, both of which it does hold, so its readers keep their
meaning: nothing of it is unscoped). As vbench/latent_scopes.py does for its
three, this module reads the same trace file once more and sums, over the
launches of the chunk program, the own time of the operations whose scope
path holds the name. A program without it (the parent of PR 45, and the
latent families) gives None throughout, never zero and never an error.
"""

from __future__ import annotations

import bisect
import os

from vbench import latent_scopes, scopes

NAMES = ("chunk_attn",)
CHUNK = latent_scopes.CHUNK

_loaded = {}


def by_scope(raw: dict) -> dict:
    """{scope: seconds}: own time under each of NAMES inside the launches
    of the chunk program, in a loaded trace (``scopes.load_xplane``'s
    dict)."""
    out = {}
    for dev in raw["devices"].values():
        modules = sorted(dev["modules"], key=lambda m: m[1])
        starts = [m[1] for m in modules]
        for op, own in scopes._own_time(dev["ops"]):
            name = next((part for part in reversed(
                op[3].rstrip(":").split("/")) if part in NAMES), None)
            if name is None:
                continue
            i = bisect.bisect_right(starts, op[1]) - 1
            if i < 0 or op[1] >= modules[i][1] + modules[i][2]:
                continue
            if scopes.module_key(modules[i][0]) == CHUNK:
                out[name] = out.get(name, 0.0) + own / 1e12
    return out


def load(root: str = scopes.ROOT):
    """{scope: seconds} of the newest trace under ``<root>/.vbench_out``,
    read once a process; None where there is no trace or no operation of a
    chunk carries the name."""
    path = scopes.newest_xplane(os.path.join(root, ".vbench_out", "trace"))
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _loaded:
        _loaded.clear()
        _loaded[key] = by_scope(scopes.load_xplane(path)) or None
    return _loaded[key]


def ms_per_chunk(root: str = scopes.ROOT):
    """Device milliseconds a chunk launch spends under NAMES, launches
    counted in whole ones as ``latent_scopes.ms_per_chunk`` counts them;
    None where there is no trace, no chunk launch or no such name in one."""
    got, red = load(root), scopes.load(root)
    row = red["programs"].get(CHUNK) if red else None
    if not got or not row or not row["whole_s"]:
        return None
    return 1e3 * sum(got.values()) / (row["seconds"] / row["whole_s"])
