"""The five readers of the time a run lost whole (PR 37) and
vbench/pauses.py: each counter's growth as a share of the window on made
``stats()`` snapshots, None on a program without the counter; the pauses of
a made host plane (written in the profiler's wire format, so
``scopes.read_xspace`` reads it as it reads a chip's file) against made
launches and against the launches of the slice recorded on the chip."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from vbench_toyroot import REPO  # noqa: E402

from vbench import manifest, pauses, scopes  # noqa: E402
from vbench.rundata import Run  # noqa: E402

MS = 10 ** 9  # a millisecond in the trace's picoseconds
PHASES = ("admission", "dispatch", "fetch", "deliver", "swap_drain",
          "idle_wait")


def _stats(host_ms=0.0, gc_ms=(0.0, 0.0, 0.0), long_ms=None):
    long_ms = long_ms or {}
    return {
        "pauses": {"host": {"count": 0, "total_ms": host_ms, "max_ms": 0.0},
                   "gc": {str(g): {"count": 0, "total_ms": ms, "max_ms": 0.0}
                          for g, ms in enumerate(gc_ms)},
                   "recent": {"host": [], "gc": []},
                   "period_ms": 5, "late_ms": 20},
        "tick_phase_ms": {p: {"total_ms": 0.0, "long_count": 0,
                              "long_ms": long_ms.get(p, 0.0)}
                          for p in PHASES}}


def _run(stats0, stats1, seconds=50.0):
    return Run(records=[], seconds=seconds, setup_s=40.0, give_up_s=seconds,
               stats0=stats0, stats1=stats1, cfg={}, mix={}, peaks={},
               step_cost=None)


# what each reader makes of the same two snapshots: 50 s of window, before
# it 1000 ms of everything (set-up's, which no reader may count)
BEFORE = _stats(1000.0, (1000.0, 1000.0, 1000.0),
                {p: 1000.0 for p in PHASES})
AFTER = _stats(1108.0, (1010.0, 1005.0, 1100.0),
               {"admission": 1000.0, "dispatch": 1104.0, "deliver": 1060.0,
                "swap_drain": 1000.0, "fetch": 3000.0, "idle_wait": 9000.0})
GROWTH = {"host_pause_pct": 100 * 0.108 / 50,
          "gc_pause_pct": 100 * 0.115 / 50,
          "host_phase_long_pct": 100 * 0.164 / 50,
          "fetch_excess_pct": 100 * 2.0 / 50}


@pytest.mark.parametrize("name", sorted(GROWTH))
def test_a_counter_reader_reads_the_windows_growth(name):
    read = manifest.reader(REPO, name)
    assert read(_run(BEFORE, AFTER)) == pytest.approx(GROWTH[name])
    # a clean window reads zero, whatever set-up lost
    assert read(_run(AFTER, AFTER)) == 0.0


@pytest.mark.parametrize("name", sorted(GROWTH))
def test_a_counter_reader_gives_none_without_the_key(name):
    """The parent of PR 37: no ``pauses``, and phases without ``long_ms``."""
    old = {"tick_phase_ms": {p: {"total_ms": 5.0, "max_ms": 1.0}
                             for p in PHASES}}
    read = manifest.reader(REPO, name)
    assert read(_run(old, old)) is None
    assert read(_run({}, {})) is None


# ------------------------------------------- a made file, as the profiler's

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _xspace(planes: dict) -> bytes:
    """{plane: {thread: [(span name, start_ps, dur_ps), ...]}} in the wire
    format of tsl's xplane.proto: XSpace.planes = 1; XPlane.name = 2,
    .lines = 3, .event_metadata = 4 (a map: key 1, value 2 with id 1 and
    name 2); XLine.name = 2, .timestamp_ns = 3, .events = 4;
    XEvent.metadata_id = 1, .offset_ps = 2, .duration_ps = 3."""
    out = b""
    for plane, threads in planes.items():
        names = sorted({s[0] for rows in threads.values() for s in rows})
        ids = {n: i + 1 for i, n in enumerate(names)}
        body = _field(2, plane)
        for thread, rows in threads.items():
            line = _field(2, thread) + _field(3, 0)
            for name, start, dur in rows:
                line += _field(4, _field(1, ids[name]) + _field(2, start)
                               + _field(3, dur))
            body += _field(3, line)
        for name, i in ids.items():
            body += _field(4, _field(1, i) + _field(
                2, _field(1, i) + _field(2, name)))
        out += _field(1, body)
    return out


def _trace_file(tmp_path, planes) -> str:
    d = tmp_path / ".vbench_out" / "trace" / "cell" / "plugins" / "profile"
    d.mkdir(parents=True)
    path = d / "made.xplane.pb"
    path.write_bytes(_xspace(planes))
    return str(path)


def _sleeps(start_ms, lengths_ms):
    rows, t = [], start_ms
    for ms in lengths_ms:
        rows.append(("vtpu.watch", int(t * MS), int(ms * MS)))
        t += ms
    return rows


def test_pause_spans_are_long_sleeps_and_collections_on_any_thread(tmp_path):
    path = _trace_file(tmp_path, {
        "/host:CPU": {
            # the watcher: sleeps of 5 ms, one of 113 (a stop of 108), one
            # of 19 (late, and under the rule)
            "vtpu-pause-watch": _sleeps(100, [5, 5, 113, 5, 19, 5]),
            # a full collection on another thread, and a tick's span
            "loop": [("vtpu.gc", 400 * MS, 30 * MS),
                     ("vtpu.tick.fetch", 90 * MS, 200 * MS)]},
        "/device:TPU:0": {"XLA Ops": [("vtpu.watch", 0, 900 * MS)]}})
    assert pauses.pause_spans(path) == [
        (110 * MS, 223 * MS), (400 * MS, 430 * MS)]


def test_a_trace_without_the_watchers_span_is_none(tmp_path):
    """The parent's: tick spans and launches, and no ``vtpu.watch``."""
    _trace_file(tmp_path, {"/host:CPU": {"loop": [
        ("vtpu.tick.fetch", 90 * MS, 200 * MS), ("vtpu.gc", 0, MS)]}})
    assert pauses.idle_paused(str(tmp_path)) is None
    assert pauses.idle_paused(str(tmp_path / "nothing_here")) is None


def _launches(gaps_ms):
    """Launches of 100 ms, each of two operations back to back, with
    ``gaps_ms[i]`` of idle before launch i + 1; the first starts at 0."""
    ops, modules, t = [], [], 0
    for gap in [0] + list(gaps_ms):
        t += gap
        modules.append(["jit_step(7)", t * MS, 100 * MS])
        ops.append(["%fusion.1 = bf16[8]", t * MS, 60 * MS, "jit(step)/mlp"])
        # 2 ms idle inside the launch: the program's own, never counted
        ops.append(["%fusion.2 = bf16[8]", (t + 62) * MS, 38 * MS, ""])
        t += 100
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "spans": []}


def test_idle_between_launches_inside_and_outside_a_pause():
    # launches at 0, 130, 240 and 380 ms: gaps of 30 [100, 130], 10
    # [230, 240] and 40 [340, 380]
    raw = _launches([30, 10, 40])
    assert pauses.idle_between_launches(raw, []) == (80 * MS, 0)
    # a pause over the first gap whole, none near the second, and one that
    # ends 15 ms into the third
    got = pauses.idle_between_launches(
        raw, [(95 * MS, 140 * MS), (300 * MS, 355 * MS)])
    assert got == (80 * MS, 45 * MS)
    # a pause inside a launch covers no idle time
    assert pauses.idle_between_launches(
        raw, [(10 * MS, 50 * MS)]) == (80 * MS, 0)


def test_the_recorded_slices_launches_against_made_pauses():
    with open(os.path.join(REPO, "vbench", "data",
                           "recorded_scopes.json")) as f:
        raw = json.load(f)
    idle, none = pauses.idle_between_launches(raw, [])
    assert idle > 0 and none == 0
    # the device's idle time between launches, as scopes.py classes it
    gaps = scopes.reduce(raw)["gaps"]
    assert idle / 1e12 == pytest.approx(
        sum(v for k, v in gaps.items() if k != "in_program"))
    modules = sorted(raw["devices"]["/device:TPU:0"]["modules"],
                     key=lambda m: m[1])
    first, last = modules[0], modules[-1]
    everything = [(first[1], last[1] + last[2])]
    assert pauses.idle_between_launches(raw, everything) == (idle, idle)
    # the one gap between the last two launches, inside a long sleep that
    # began in the middle of the one and ended in the middle of the other
    a, b = modules[-2], modules[-1]
    got = pauses.idle_between_launches(
        raw, [(a[1] + a[2] // 2, b[1] + b[2] // 2)])
    assert got[0] == idle and 0 < got[1] < idle


def test_idle_paused_pct_reads_the_made_trace(tmp_path, monkeypatch):
    """The reader end to end on a made file: two gaps of the device, one
    of them inside the watcher's long sleep."""
    raw = _launches([30, 10])
    dev = raw["devices"]["/device:TPU:0"]
    _trace_file(tmp_path, {
        "/host:CPU": {"vtpu-pause-watch": _sleeps(90, [5, 40, 5])},
        "/device:TPU:0": {
            "XLA Ops": [(o[0], o[1], o[2]) for o in dev["ops"]],
            "XLA Modules": [(m[0], m[1], m[2]) for m in dev["modules"]]}})
    real = pauses.idle_paused
    monkeypatch.setattr(pauses, "idle_paused", lambda: real(str(tmp_path)))
    read = manifest.reader(REPO, "idle_paused_pct")
    assert read(_run({}, {})) == pytest.approx(100 * 30 / 40)
