"""The time a run lost whole, as the program counts it (PR 37).

Two things for the metric files beside this one. ``lost_pct`` turns a
monotonic millisecond counter of ``stats()`` into a share of the window:
its growth from ``run.stats0`` to ``run.stats1`` over the window's length,
in percent, so a reading stands beside the 1 % bounds; None where the
program has no such counter (any commit before PR 37), never zero.

``idle_paused`` reads the traced slice once more (as latent_scopes.py and
ssm_scopes.py do): the host plane's ``vtpu.watch`` spans, one a sleep of
the program's pause watch on whatever thread it runs, of which one far
longer than the sleep *is* a pause of the process, and its ``vtpu.gc``
spans, one a full collection; against them the device's idle time between
launches, gap by gap. A program without the spans gives None, and its
device plane is not read again.
"""

from __future__ import annotations

import bisect
import os

from vbench import scopes

WATCH, GC = "vtpu.watch", "vtpu.gc"
LATE_MS = 20.0  # a watch span longer than this is a pause

_loaded = {}


def lost_pct(run, pick):
    """``pick(stats) -> milliseconds`` (KeyError or TypeError where the
    program lacks the counter): the window's growth as a percentage of
    the window."""
    try:
        grown = pick(run.stats1) - pick(run.stats0)
    except (KeyError, TypeError):
        return None
    return 100.0 * grown / (1000.0 * run.seconds)


def long_ms(stats: dict, phases) -> float:
    """The summed ``long_ms`` of ``phases`` in a ``stats()`` snapshot."""
    return sum(stats["tick_phase_ms"][p]["long_ms"] for p in phases)


def pause_spans(path: str):
    """[(start_ps, end_ps)] of the pauses in a trace file, merged and in
    order: ``vtpu.watch`` spans over LATE_MS and every ``vtpu.gc`` span.
    None where the file holds no ``vtpu.watch`` span at all."""
    watched, found = False, []
    for plane in scopes.read_xspace(path, lambda n: n == "/host:CPU"):
        for rows in plane["lines"].values():
            for name, start, dur, _, _ in rows:
                if name == WATCH:
                    watched = True
                    if dur > LATE_MS * 1e9:
                        found.append((start, start + dur))
                elif name == GC:
                    found.append((start, start + dur))
    if not watched:
        return None
    merged = []
    for start, end in sorted(found):
        if merged and start <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], end))
        else:
            merged.append((start, end))
    return merged


def idle_between_launches(raw: dict, pauses: list):
    """(idle picoseconds between launches, those of them inside a pause)
    over the devices of a loaded trace (``scopes.load_xplane``'s dict). A
    gap that begins and ends inside one launch is the program's own."""
    starts = [p[0] for p in pauses]
    idle = inside = 0
    for dev in raw["devices"].values():
        modules = sorted(dev["modules"], key=lambda m: m[1])
        begins = [m[1] for m in modules]

        def launch_at(t):
            i = bisect.bisect_right(begins, t) - 1
            if i >= 0 and t < modules[i][1] + modules[i][2]:
                return i
            return None

        end = None
        for _, start, dur, _ in sorted(dev["ops"], key=lambda o: o[1]):
            if end is not None and start > end and (
                    launch_at(end) is None
                    or launch_at(end) != launch_at(start)):
                idle += start - end
                i = max(bisect.bisect_right(starts, end) - 1, 0)
                for p0, p1 in pauses[i:]:
                    if p0 >= start:
                        break
                    inside += max(0, min(p1, start) - max(p0, end))
            end = start + dur if end is None else max(end, start + dur)
    return idle, inside


def idle_paused(root: str = scopes.ROOT):
    """(idle seconds between launches, those inside a pause) of the newest
    trace under ``<root>/.vbench_out/trace``, read once a process; None
    where there is no trace or it holds no ``vtpu.watch`` span."""
    path = scopes.newest_xplane(os.path.join(root, ".vbench_out", "trace"))
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _loaded:
        _loaded.clear()
        pauses = pause_spans(path)
        _loaded[key] = None if pauses is None else tuple(
            v / 1e12 for v in idle_between_launches(
                scopes.load_xplane(path), pauses))
    return _loaded[key]
