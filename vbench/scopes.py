"""A second reduction of a traced run's ``.xplane.pb``: what the program's
own names say.

``trace.py`` reads the device plane through ``jax.profiler.ProfileData``,
which shows an event's name and time but not its metadata, and the scope an
operation ran under (the ``jax.named_scope`` path, stat ``tf_op`` of the
event's metadata) lives there. So this module reads the file's protobuf
wire format itself (``read_xspace``; the schema is tsl's ``xplane.proto``)
and reduces it to three tables:

(a) device seconds by program and scope, an operation's own time only (an
    operation that holds others, a ``while``, is charged what is left),
(b) the engine loop's ``vtpu.*`` spans from the host plane, which lie on
    the same clock as the device's events,
(c) each gap of the device classed as ``in_program`` (inside a launch: the
    device's own), by the ``vtpu.tick.*`` span open on the loop's thread
    when the gap began, ``unnamed`` where none was, or ``outside_spans``
    where it began before the first recorded span or after the last (a span
    open when the profiler started is not in the trace).

    python -m vbench.scopes <dir or .xplane.pb>    # the tables, for a human

Two steps, as in ``trace.py``, so the second can be checked on a recorded
slice (vbench/data/recorded_scopes.json): ``load_xplane`` gives plain lists,

    {"devices": {"<plane>": {"ops": [[name, start_ps, dur_ps, tf_op], ...],
                             "modules": [[name, start_ps, dur_ps], ...]}},
     "spans": [[name, start_ps, dur_ps, {id: value}], ...]}

and ``reduce`` the tables. A program without scopes or spans (any commit
before PR 25) reduces too: everything is ``unscoped`` and ``unnamed``, and
the readers built on this return None.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import statistics
import struct
import sys

# vbench.run.ROOT, without importing the module that is running as __main__
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The program's scope vocabulary (vtpu/ops/__init__.py SCOPES; PERF.md
# section 3). Copied: the benchmark imports the program only in sut/.
VOCAB = ("embed", "qkv", "kv_write", "pool_relayout", "paged_attn",
         "gather_attn", "attn", "o_proj", "mlp", "route", "experts",
         "lm_head", "sample")
UNSCOPED = "unscoped"
DECODE = "jit_step"
PREFILL = ("jit_admit_step", "jit_prefill_chunk_into_slot")
ADMIT = "vtpu.admit."  # .batch and .chunk: one span a prefill launch
TICK = "vtpu.tick."
DISPATCH = TICK + "dispatch"


# ------------------------------------------------------------ the file

def _fields(buf):
    """(field number, wire type, value) of one protobuf message; a
    length-delimited value is a slice of ``buf``."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        number, wire = key >> 3, key & 7
        if wire in (0, 2):
            v = shift = 0
            while True:
                b = buf[i]
                i += 1
                v |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            if wire == 2:
                v, i = buf[i:i + v], i + v
        elif wire == 1:
            v, i = buf[i:i + 8], i + 8
        elif wire == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield number, wire, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _stat(buf, names: dict):
    """An XStat as (name, value); a reference resolves to its name."""
    key = value = None
    for number, _, v in _fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = struct.unpack("<d", v)[0]
        elif number in (3, 4):
            value = v
        elif number == 5:
            value = _text(v)
        elif number == 7:
            value = names.get(v, v)
    return names.get(key, key), value


def _entry(buf):
    """A map entry's (key, value)."""
    key = value = None
    for number, _, v in _fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _plane(buf, wanted) -> dict:
    """One XPlane whose name ``wanted`` accepts: its lines of events
    [name, start_ps, dur_ps, event's stats, metadata's stats]."""
    name, lines, metas, stat_names = "", [], [], {}
    for number, _, v in _fields(buf):
        if number == 2:
            name = _text(v)
            if not wanted(name):
                return None
        elif number == 3:
            lines.append(v)
        elif number == 4:
            metas.append(v)
        elif number == 5:
            key, meta = _entry(v)
            stat_names[key] = next(
                (_text(x) for n, _, x in _fields(meta) if n == 2), "")
    events = {}
    for v in metas:
        key, meta = _entry(v)
        label, stats = "", {}
        for number, _, x in _fields(meta):
            if number == 2:
                label = _text(x)
            elif number == 5:
                k, val = _stat(x, stat_names)
                stats[k] = val
        events[key] = (label, stats)
    out = {"name": name, "lines": {}}
    for v in lines:
        label, t0_ns, rows = "", 0, []
        for number, _, x in _fields(v):
            if number == 2:
                label = _text(x)
            elif number == 3:
                t0_ns = x
            elif number == 4:
                rows.append(x)
        got = []
        for row in rows:
            meta = offset = dur = 0
            stats = {}
            for number, _, x in _fields(row):
                if number == 1:
                    meta = x
                elif number == 2:
                    offset = x
                elif number == 3:
                    dur = x
                elif number == 4:
                    k, val = _stat(x, stat_names)
                    stats[k] = val
            ev_name, ev_stats = events.get(meta, ("", {}))
            got.append([ev_name, t0_ns * 1000 + offset, dur, stats,
                        ev_stats])
        out["lines"].setdefault(label, []).extend(got)
    return out


def read_xspace(path: str, wanted=lambda name: True) -> list:
    """The planes of an ``.xplane.pb`` whose names ``wanted`` accepts."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    planes = (_plane(v, wanted) for number, _, v in _fields(buf)
              if number == 1)
    return [p for p in planes if p is not None]


def load_xplane(path: str) -> dict:
    """Device operations with their scope paths, launches, and the
    ``vtpu.*`` spans of the host thread that holds the tick's."""
    devices, threads = {}, []
    planes = read_xspace(
        path, lambda n: n.startswith("/device:") or n == "/host:CPU")
    for plane in planes:
        if plane["name"].startswith("/device:"):
            ops = [[name, start, dur, meta.get("tf_op", "")]
                   for name, start, dur, _, meta
                   in plane["lines"].get("XLA Ops", [])]
            modules = [[name, start, dur] for name, start, dur, _, _
                       in plane["lines"].get("XLA Modules", [])]
            if ops or modules:
                devices[plane["name"]] = {"ops": ops, "modules": modules}
        else:
            for rows in plane["lines"].values():
                spans = [[name, start, dur, stats]
                         for name, start, dur, stats, _ in rows
                         if name.startswith("vtpu.")]
                if spans:
                    threads.append(spans)
    # the engine's loop thread: the one that holds the tick's spans
    loop = max(threads, default=[], key=lambda spans: sum(
        s[0].startswith(TICK) for s in spans))
    return {"devices": devices, "spans": sorted(loop, key=lambda s: s[1])}


def newest_xplane(where: str):
    """The newest ``.xplane.pb`` under a directory (or the file itself)."""
    if os.path.isfile(where):
        return where
    found = glob.glob(os.path.join(where, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


# ------------------------------------------------------- the reduction

def module_key(name: str) -> str:
    """A program's name without its id: ``jit_step(1234)`` -> ``jit_step``."""
    return re.sub(r"\(\d+\)$", "", name)


def scope_of(tf_op: str) -> str:
    """The innermost name of the vocabulary on an operation's scope path
    (``jit(step)/mlp/dot_general:`` -> ``mlp``), else ``unscoped``."""
    for part in reversed(tf_op.rstrip(":").split("/")):
        if part in VOCAB:
            return part
    return UNSCOPED


def short_name(name: str) -> str:
    """``%fusion.5 = bf16[...] fusion(...)`` -> ``fusion.5``."""
    m = re.match(r"%?([A-Za-z0-9_.\-]+)", name)
    return m.group(1) if m else name[:40]


def _own_time(ops):
    """[(op, own_ps)]: each operation's time less the operations that ran
    inside it (proper nesting, as the device plane records a ``while``)."""
    out, stack = [], []
    for op in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][0][1] + stack[-1][0][2] <= op[1]:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][1] -= op[2]
        stack.append([op, op[2]])
    out.extend(tuple(s) for s in stack)
    return out


def _span_at(spans, starts, t):
    """The innermost ``vtpu.tick.*`` span open at ``t`` on the loop's
    thread, or None."""
    i = bisect.bisect_right(starts, t)
    for name, start, dur, _ in reversed(spans[max(0, i - 8):i]):
        if start <= t < start + dur:
            return name
    return None


def reduce(raw: dict, top: int = 12) -> dict:
    """Seconds everywhere. ``programs``: per program key its launches,
    seconds, seconds by scope (own time; ``unscoped`` for the rest) and
    ``whole_s``, the mean length of a launch that the trace's edges did not
    cut. ``ops``: the ``top`` operations by own time as [program, scope,
    name, seconds]. ``spans``: per ``vtpu.*`` span name [count, seconds].
    ``tiling``: the tick spans' cover of their own extent. ``gaps``: idle
    seconds of the device by class. ``slack``: per decode launch, the wait
    between the end of the last dispatch span before it and its start on
    the device, as a share of the time since the decode launch before.
    ``prefill``: the launches of the admission and chunk programs whose
    ``vtpu.admit.*`` span is in the trace too (each launch takes the
    latest span not yet taken that began before it), their device seconds
    and the true prompt tokens those spans carry."""
    programs, op_rows, gaps, slack = {}, {}, {}, []
    prefill = {"launches": 0, "seconds": 0.0, "tokens": 0}
    admits = [s for s in raw["spans"] if s[0].startswith(ADMIT)]
    spans = raw["spans"]
    ticks = [s for s in spans if s[0].startswith(TICK)]
    tick_starts = [s[1] for s in ticks]
    ticks_end = max((s[1] + s[2] for s in ticks), default=0)
    dispatch_ends = sorted(s[1] + s[2] for s in ticks if s[0] == DISPATCH)
    for dev in raw["devices"].values():
        modules = sorted(dev["modules"], key=lambda m: m[1])
        starts = [m[1] for m in modules]

        def launch_at(t):
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0 and t < modules[i][1] + modules[i][2]:
                return i
            return None

        for i, (name, _, dur) in enumerate(modules):
            row = programs.setdefault(module_key(name), {
                "launches": 0, "seconds": 0.0, "scopes": {}, "whole": []})
            row["launches"] += 1
            row["seconds"] += dur / 1e12
            if 0 < i < len(modules) - 1:  # the edges may have cut the ends
                row["whole"].append(dur / 1e12)
        for op, own in _own_time(dev["ops"]):
            i = launch_at(op[1])
            key = module_key(modules[i][0]) if i is not None else "no_launch"
            scope = scope_of(op[3])
            row = programs.setdefault(key, {
                "launches": 0, "seconds": 0.0, "scopes": {}, "whole": []})
            row["scopes"][scope] = row["scopes"].get(scope, 0.0) + own / 1e12
            k = (key, scope, short_name(op[0]))
            op_rows[k] = op_rows.get(k, 0.0) + own / 1e12
        end = None
        for _, start, dur, _ in sorted(dev["ops"], key=lambda o: o[1]):
            if end is not None and start > end:
                if launch_at(end) is not None and launch_at(end) == \
                        launch_at(start):
                    cls = "in_program"
                elif not ticks or not (ticks[0][1] <= end < ticks_end):
                    cls = "outside_spans"
                else:
                    cls = _span_at(ticks, tick_starts, end) or "unnamed"
                gaps[cls] = gaps.get(cls, 0.0) + (start - end) / 1e12
            end = start + dur if end is None else max(end, start + dur)
        free = 0  # admits[:free] began before the launch; taken ones pop
        waiting = []
        for name, start, dur in modules:
            if module_key(name) not in PREFILL:
                continue
            while free < len(admits) and admits[free][1] <= start:
                waiting.append(admits[free])
                free += 1
            if waiting:
                span = waiting.pop()
                prefill["launches"] += 1
                prefill["seconds"] += dur / 1e12
                prefill["tokens"] += span[3].get("tokens", 0)
        decode = [m for m in modules if module_key(m[0]) == DECODE]
        for before, (_, start, _) in zip(decode, decode[1:]):
            i = bisect.bisect_right(dispatch_ends, start) - 1
            if i >= 0 and start > before[1]:
                slack.append((start - dispatch_ends[i]) / (start - before[1]))
    for row in programs.values():
        whole = row.pop("whole")
        row["whole_s"] = (sum(whole) / len(whole) if whole
                          else row["seconds"] / max(row["launches"], 1))
    by_span = {}
    for name, _, dur, _ in spans:
        row = by_span.setdefault(name, [0, 0.0])
        row[0] += 1
        row[1] += dur / 1e12
    return {
        "programs": programs,
        "ops": [[*k, v] for k, v in sorted(
            op_rows.items(), key=lambda kv: -kv[1])[:top]],
        "spans": by_span,
        "tiling": _tiling(ticks),
        "gaps": gaps,
        "slack": slack,
        "prefill": prefill,
    }


def _tiling(ticks) -> dict:
    """How well the outermost tick spans tile their extent: its length,
    the share no span covers, and how many carry a ``tick`` id."""
    if not ticks:
        return {"extent_s": 0.0, "uncovered_pct": None, "with_tick_id": 0,
                "spans": 0}
    covered, end = 0, None
    for _, start, dur, _ in ticks:
        if end is None or start >= end:
            covered += dur
            end = start + dur
        elif start + dur > end:
            covered += start + dur - end
            end = start + dur
    extent = end - ticks[0][1]
    return {"extent_s": extent / 1e12,
            "uncovered_pct": 100.0 * (1 - covered / extent) if extent else 0.0,
            "with_tick_id": sum("tick" in ids for _, _, _, ids in ticks),
            "spans": len(ticks)}


def cut(raw: dict, n_launches: int = 5) -> dict:
    """The events up to the end of the ``n_launches``-th launch on each
    device, names shortened and scope paths cut to their scope (``reduce``
    reads the same from both): a small recorded slice to test against."""
    devices, end = {}, 0
    for name, dev in raw["devices"].items():
        modules = sorted(dev["modules"], key=lambda m: m[1])[:n_launches]
        last = modules[-1][1] + modules[-1][2]
        end = max(end, last)
        devices[name] = {
            "ops": [[short_name(o[0]), o[1], o[2],
                     scope_of(o[3]).replace(UNSCOPED, "")]
                    for o in dev["ops"] if o[1] + o[2] <= last],
            "modules": modules}
    return {"devices": devices,
            "spans": [s for s in raw["spans"] if s[1] + s[2] <= end]}


# ------------------------------------------------------ for the readers

_loaded = {}


def load(root: str = ROOT):
    """The reduction of the newest trace under ``<root>/.vbench_out/trace/``
    (the directory run.py empties before it traces), read once a process;
    None where there is no trace or it holds no device plane."""
    path = newest_xplane(os.path.join(root, ".vbench_out", "trace"))
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _loaded:
        raw = load_xplane(path)
        _loaded.clear()
        _loaded[key] = reduce(raw) if raw["devices"] else None
    return _loaded[key]


def decode_steps(red: dict):
    """(seconds by scope of the decode programs, their launches counted in
    whole ones), or None where most of their time lies under no scope of
    the vocabulary: such a program is not named (the parent of PR 25 read
    0.004 % under names, my chip run), and its metrics are left out rather
    than read as zero."""
    row = red["programs"].get(DECODE)
    if not row or not row["whole_s"]:
        return None
    named = sum(v for k, v in row["scopes"].items() if k != UNSCOPED)
    if named < 0.5 * sum(row["scopes"].values()):
        return None
    return row["scopes"], row["seconds"] / row["whole_s"]


def ms_per_step(red, scopes) -> float:
    """Device milliseconds a decode launch spends under ``scopes``."""
    got = decode_steps(red) if red else None
    if got is None:
        return None
    by_scope, steps = got
    return 1e3 * sum(by_scope.get(s, 0.0) for s in scopes) / steps


def between_launch_idle(red: dict):
    """(idle seconds between launches, those of them under no tick span),
    or None where the trace holds no tick span at all."""
    if not any(name.startswith(TICK) for name in red["spans"]):
        return None
    outside = {k: v for k, v in red["gaps"].items()
               if k not in ("in_program", "outside_spans")}
    return sum(outside.values()), outside.get("unnamed", 0.0)


# ---------------------------------------------------------- for a human

def main(argv) -> int:
    where = argv[1] if len(argv) > 1 else os.path.join(
        ROOT, ".vbench_out", "trace")
    path = newest_xplane(where)
    if path is None:
        print(f"no .xplane.pb under {where}", file=sys.stderr)
        return 1
    red = reduce(load_xplane(path))
    print(f"# {path}")
    print("\n(a) device seconds by program and scope (own time)")
    for key, row in sorted(red["programs"].items(),
                           key=lambda kv: -kv[1]["seconds"]):
        total = sum(row["scopes"].values())
        print(f"{key}: {row['launches']} launches, {row['seconds']:.4f} s, "
              f"whole launch {1e3 * row['whole_s']:.2f} ms, operations "
              f"{total:.4f} s")
        for scope, s in sorted(row["scopes"].items(), key=lambda kv: -kv[1]):
            print(f"    {scope:14s} {s:9.4f} s  {100 * s / total:5.1f} %")
    print("\n    the operations that took most (program, scope, name, s)")
    for key, scope, name, s in red["ops"]:
        print(f"    {key:28s} {scope:14s} {name:40s} {s:.4f}")
    print("\n(b) the loop thread's spans")
    for name, (count, s) in sorted(red["spans"].items()):
        print(f"    {name:22s} {count:6d}  {s:9.4f} s")
    t = red["tiling"]
    print(f"    tick spans cover their {t['extent_s']:.3f} s but "
          f"{t['uncovered_pct']} %; {t['with_tick_id']} of {t['spans']} "
          f"carry a tick id")
    print("\n(c) device idle seconds by class")
    for cls, s in sorted(red["gaps"].items(), key=lambda kv: -kv[1]):
        print(f"    {cls:22s} {s:.6f}")
    p = red["prefill"]
    print(f"\nprefill: {p['launches']} launches with their span in the "
          f"trace, {p['seconds']:.4f} s, {p['tokens']} prompt tokens")
    if red["slack"]:
        print(f"\nhost slack, median over {len(red['slack'])} decode "
              f"launches: {100 * statistics.median(red['slack']):.2f} %")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
