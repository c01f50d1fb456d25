"""Reduction of a profiler trace to what the per-layer metrics read.

Two steps, so the second can be checked on a small recorded trace
(vbench/data/): ``load_xplane`` turns the profiler's ``.xplane.pb`` into a
plain dict of events per device, and ``reduce`` turns that into busy time,
the operations that took most time, the longest idle gaps and the time of
each compiled program (XLA module) under the name the trace gives it,
which ends in the program's id: ``jit_step(1234)``.

    {"devices": {"<plane name>": {"ops": [[name, start_ns, dur_ns], ...],
                                  "modules": [[name, start_ns, dur_ns], ...]}}}
"""

from __future__ import annotations

import glob
import os
import re

_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str) -> dict:
    """Device planes of an xplane file as plain lists (names as the trace
    gives them)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices = {}
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            continue
        ops, modules = [], []
        for line in plane.lines:
            if line.name == _OPS_LINE:
                ops = [[e.name, int(e.start_ns), int(e.duration_ns)]
                       for e in line.events]
            elif line.name == _MODULES_LINE:
                modules = [[e.name, int(e.start_ns), int(e.duration_ns)]
                           for e in line.events]
        if ops or modules:
            devices[plane.name] = {"ops": ops, "modules": modules}
    return {"devices": devices}


def _union_ns(intervals) -> int:
    """Total length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _clean(name: str) -> str:
    """A short plain name for an event. The device plane names an
    operation by its whole HLO line (``%reshape.9 = bf16[15,898,16,4096]{...}
    reshape(...)``): keep the operation's name and its result's type and
    shape, ``reshape.9_bf16_15_898_16_4096``."""
    m = re.match(r"%?([A-Za-z0-9_.\-]+) = \(?([a-z]+[0-9]*\[[0-9,]*\])", name)
    if m:
        name = f"{m.group(1)}_{m.group(2)}"
    return re.sub(r"[^A-Za-z0-9_.\-]+", "_", name).strip("_")[:80]


def cut(trace: dict, n_steps: int = 3) -> dict:
    """The events up to the end of the first ``n_steps`` launches of the
    most-launched program, for a small recorded trace to test against."""
    out = {}
    for dev, d in trace["devices"].items():
        counts = {}
        for name, _, _ in d["modules"]:
            counts[name] = counts.get(name, 0) + 1
        top = max(counts, key=counts.get)
        ends = [s + dur for name, s, dur in d["modules"] if name == top]
        end = sorted(ends)[min(n_steps, len(ends)) - 1]
        out[dev] = {k: [e for e in d[k] if e[1] + e[2] <= end]
                    for k in ("ops", "modules")}
    return {"devices": out}


def module_key(name: str) -> str:
    """A program's name without its id: ``jit_step(1234)`` -> ``jit_step``."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce(trace: dict, window_s: float = None, top: int = 10) -> dict:
    """window_s (unless given: first device event's start to the last's
    end, since the profiler starts a little after it is asked to),
    busy_s (union of device operations, averaged over devices),
    device_ops and idle_gaps (at most ``top`` each, seconds), and per
    program (module) [launches, seconds] summed over devices."""
    if not trace["devices"]:
        raise ValueError("the trace holds no device plane")
    busy, op_time, gaps, modules, lengths = [], {}, [], {}, []
    for dev in trace["devices"].values():
        ops = dev["ops"] or dev["modules"]
        spans = [(s, s + d) for _, s, d in ops]
        lengths.append((max(e for _, e in spans)
                        - min(s for s, _ in spans)) / 1e9)
        busy.append(_union_ns(spans) / 1e9)
        for name, _, d in ops:
            name = _clean(name)
            op_time[name] = op_time.get(name, 0) + d
        first = min(s for s, _ in spans)
        end = None
        for (s, e), (name, _, _) in sorted(zip(spans, ops)):
            if end is not None and s > end:
                gaps.append((s - end, f"{(s - first) / 1e9:.4f}s_before_"
                             f"{_clean(name)}"))
            end = e if end is None else max(end, e)
        for name, _, d in dev["modules"]:
            row = modules.setdefault(name, [0, 0.0])
            row[0] += 1
            row[1] += d / 1e9
    n = len(trace["devices"])
    ranked = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": sum(busy) / n,
        "window_s": max(lengths) if window_s is None else window_s,
        "device_ops": [[k, v / 1e9 / n] for k, v in ranked],
        "idle_gaps": [[name, d / 1e9]
                      for d, name in sorted(gaps, reverse=True)[:top]],
        "modules": modules,
        "devices": n,
    }
