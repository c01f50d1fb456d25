"""The second reduction of a trace (vbench/scopes.py): seconds by scope,
the loop's spans matched to launches, the device's gaps by class, on
hand-made traces whose answers are plain and on the slice recorded on the
chip that vbench/data keeps; and each new reader on that slice."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from vbench_toyroot import REPO  # noqa: E402

from vbench import manifest, scopes  # noqa: E402
from vbench.rundata import Run  # noqa: E402

DATA = os.path.join(REPO, "vbench", "data")
MS = 10 ** 9  # a millisecond in the trace's picoseconds
NEW = ["pool_relayout_ms_per_step", "paged_attn_ms_per_step",
       "matmul_ms_per_step", "experts_ms_per_step", "step_unscoped_pct",
       "prefill_ms_per_ktoken", "host_slack_pct", "idle_unnamed_pct",
       "warmup_trace_lower_s", "warmup_load_s"]


def _recorded():
    with open(os.path.join(DATA, "recorded_scopes.json")) as f:
        raw = json.load(f)
    with open(os.path.join(DATA, "recorded_scopes.expect.json")) as f:
        return raw, json.load(f)


def _span(name, start_ms, dur_ms, **ids):
    return [name, int(start_ms * MS), int(dur_ms * MS), ids]


def _steps(dispatch_before_ms):
    """Four 100 ms decode launches, back to back from t = 100 ms, each of
    a 20 ms relayout, a 60 ms ``while`` that holds a 50 ms kernel, a 2 ms
    gap, 15 ms of MLP and 3 ms under no scope; the host dispatches launch
    i ``dispatch_before_ms`` before the device starts it and waits in
    fetch for the rest of the time."""
    ops, modules, spans = [], [], []
    for i in range(4):
        t = 100 + 100 * i
        modules.append(["jit_step(7)", t * MS, 100 * MS])
        for name, at, dur, path in (
                ("%reshape.1 = bf16[8]", 0, 20, "jit(step)/pool_relayout/r:"),
                ("%while.2 = s32[]", 20, 60, "jit(step)/while"),
                ("%paged_attn.3 = bf16[8]", 25, 50,
                 "jit(step)/while/body/paged_attn/pallas_call:"),
                ("%fusion.4 = bf16[8]", 82, 15, "jit(step)/mlp/dot_general:"),
                ("%copy.5 = bf16[8]", 97, 3, "jit(step)/transpose:")):
            ops.append([name, (t + at) * MS, dur * MS, path])
        d = t - dispatch_before_ms
        spans.append(_span("vtpu.tick.dispatch", d - 1, 1, tick=i))
        spans.append(_span("vtpu.tick.fetch", d, 99, tick=i + 1))
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "spans": sorted(spans, key=lambda s: s[1])}


def test_seconds_by_scope_are_own_time_and_sum_to_the_programs():
    red = scopes.reduce(_steps(95))
    row = red["programs"]["jit_step"]
    assert row["launches"] == 4 and row["seconds"] == pytest.approx(0.4)
    assert row["whole_s"] == pytest.approx(0.1)
    by = row["scopes"]
    assert by["pool_relayout"] == pytest.approx(4 * 0.020)
    assert by["paged_attn"] == pytest.approx(4 * 0.050)  # inside the while
    assert by["mlp"] == pytest.approx(4 * 0.015)
    # the while's own 10 ms and the copy's 3: under no name of the vocabulary
    assert by["unscoped"] == pytest.approx(4 * 0.013)
    assert sum(by.values()) == pytest.approx(0.4 - 4 * 0.002)
    assert red["ops"][0] == ["jit_step", "paged_attn", "paged_attn.3",
                             pytest.approx(0.2)]
    assert scopes.ms_per_step(red, ("pool_relayout",)) == pytest.approx(20.0)
    assert scopes.ms_per_step(red, ("mlp", "experts")) == pytest.approx(15.0)


def test_scope_of_takes_the_innermost_name_of_the_vocabulary():
    assert scopes.scope_of("jit(step)/mlp/route/top_k:") == "route"
    assert scopes.scope_of("jit(admit_step)/while/body/qkv/dot:") == "qkv"
    assert scopes.scope_of("jit(step)/transpose:") == "unscoped"
    assert scopes.scope_of("") == "unscoped"
    assert scopes.scope_of("experts") == "experts"  # a cut slice's form


@pytest.mark.parametrize("before_ms,slack_pct", [(95, 95.0), (2, 2.0)],
                         ids=["device_bound", "host_bound"])
def test_host_slack_matches_each_launch_to_the_dispatch_before_it(
        before_ms, slack_pct):
    """Device-bound: a launch is issued 95 ms of a 100 ms tick before the
    device reaches it. Host-bound: 2 ms before."""
    red = scopes.reduce(_steps(before_ms))
    assert len(red["slack"]) == 3  # the first launch has none before it
    assert [100 * s for s in red["slack"]] == pytest.approx([slack_pct] * 3)


def test_gaps_are_classed_by_where_they_began():
    raw = _steps(95)
    dev = raw["devices"]["/device:TPU:0"]
    # a 5 ms hole between launches 2 and 3, which begins under a fetch span,
    # and a 4 ms one between 3 and 4, which begins a millisecond after the
    # last fetch span ended and before a later span
    for op in dev["ops"]:
        if op[1] >= 300 * MS:
            op[1] += 5 * MS
        if op[1] >= 405 * MS:
            op[1] += 4 * MS
    for m in dev["modules"][2:]:
        m[1] += 5 * MS
    dev["modules"][3][1] += 4 * MS
    raw["spans"].append(_span("vtpu.tick.deliver", 420, 1, tick=4))
    gaps = scopes.reduce(raw)["gaps"]
    assert gaps["in_program"] == pytest.approx(4 * 0.002)
    assert gaps["vtpu.tick.fetch"] == pytest.approx(0.005)
    assert gaps["unnamed"] == pytest.approx(0.004)
    idle, unnamed = scopes.between_launch_idle(scopes.reduce(raw))
    assert (idle, unnamed) == (pytest.approx(0.009), pytest.approx(0.004))
    # a gap that began before the first span or after the last is neither
    raw["spans"] = [s for s in raw["spans"] if s[1] >= 350 * MS]
    late = scopes.reduce(raw)
    assert late["gaps"]["outside_spans"] == pytest.approx(0.009)
    assert scopes.between_launch_idle(late) == (0, 0.0)


def test_prefill_launches_take_the_tokens_of_their_spans():
    raw = _steps(95)
    dev = raw["devices"]["/device:TPU:0"]
    # an admission before the trace's first span (no tokens to be had), a
    # batch and a chunk with theirs, and a chunk dispatched but not yet run
    dev["modules"] += [["jit_admit_step(3)", 90 * MS, 5 * MS],
                       ["jit_admit_step(3)", 500 * MS, 30 * MS],
                       ["jit_prefill_chunk_into_slot(4)", 530 * MS, 35 * MS]]
    raw["spans"] += [_span("vtpu.admit.batch", 480, 1, n=2, bucket=256,
                           tokens=300),
                     _span("vtpu.admit.chunk", 482, 1, tokens=512),
                     _span("vtpu.admit.chunk", 560, 1, tokens=512)]
    raw["spans"].sort(key=lambda s: s[1])
    got = scopes.reduce(raw)["prefill"]
    assert got == {"launches": 2, "seconds": pytest.approx(0.065),
                   "tokens": 812}


def test_tick_spans_tile_and_carry_their_ids():
    t = scopes.reduce(_steps(95))["tiling"]
    assert t["spans"] == t["with_tick_id"] == 8
    assert t["uncovered_pct"] == pytest.approx(0.0)
    assert scopes.reduce({"devices": {}, "spans": []})["tiling"][
        "uncovered_pct"] is None


def test_a_program_without_names_reduces_and_reads_none():
    raw = _steps(95)
    for op in raw["devices"]["/device:TPU:0"]["ops"]:
        op[3] = ""
    raw["spans"] = []
    red = scopes.reduce(raw)
    assert red["programs"]["jit_step"]["scopes"] == {
        "unscoped": pytest.approx(0.4 - 4 * 0.002)}
    assert scopes.decode_steps(red) is None
    assert scopes.ms_per_step(red, ("mlp",)) is None
    assert scopes.between_launch_idle(red) is None and red["slack"] == []


def test_no_trace_is_none(tmp_path):
    assert scopes.newest_xplane(str(tmp_path)) is None
    assert scopes.load(str(tmp_path)) is None


def test_the_recorded_slice_reduces_to_what_was_read_on_the_chip():
    raw, want = _recorded()
    red = scopes.reduce(raw)
    step, admit = red["programs"]["jit_step"], red["programs"][
        "jit_admit_step"]
    assert step["launches"] == want["step_launches"]
    assert step["seconds"] == pytest.approx(want["step_s"])
    assert sum(step["scopes"].values()) == pytest.approx(
        want["step_s"], rel=1e-3)  # the sum check of the tables
    for scope, s in want["step_scopes_s"].items():
        assert step["scopes"][scope] == pytest.approx(s)
    assert admit["scopes"]["experts"] == pytest.approx(
        want["admit_experts_s"])
    assert red["ops"][0][1:3] == want["top_op"]
    assert red["spans"]["vtpu.tick.dispatch"][0] == want["dispatch_spans"]
    assert [100 * s for s in red["slack"]] == pytest.approx(
        want["slack_pct"])
    assert red["gaps"].keys() == set(want["gap_classes"])
    assert red["prefill"] == {k: pytest.approx(v)
                              for k, v in want["prefill"].items()}


@pytest.mark.parametrize("name", NEW)
def test_each_new_reader_gives_a_number_on_the_slice(name, monkeypatch):
    raw, want = _recorded()
    red = scopes.reduce(raw)
    monkeypatch.setattr(scopes, "load", lambda root=None: red)
    warm = {"total": 9.0, "trace_lower": 4.0, "compile": 0.5,
            "cache_load": 2.5, "run": 2.0, "programs": 9}
    stats = {"warmup_s": warm, "prefill_tokens": 100}
    run = Run(records=[], seconds=51.0, setup_s=40.0, give_up_s=51.0,
              stats0=stats, stats1=stats, cfg={}, mix={}, peaks={},
              step_cost=None, trace={"busy_s": 1.0},
              trace_span=(20.0, 25.0), trace_stats=(stats, stats))
    value = manifest.reader(REPO, name)(run)
    assert value == pytest.approx(want["readers"][name])
    # and on a program from before the names: nothing, and no error
    monkeypatch.setattr(scopes, "load", lambda root=None: scopes.reduce(
        {"devices": {k: {"ops": [[o[0], o[1], o[2], ""] for o in d["ops"]],
                         "modules": d["modules"]}
                     for k, d in raw["devices"].items()}, "spans": []}))
    old = Run(records=[], seconds=51.0, setup_s=40.0, give_up_s=51.0,
              stats0={}, stats1={}, cfg={}, mix={}, peaks={}, step_cost=None,
              trace={"busy_s": 1.0}, trace_span=(20.0, 25.0),
              trace_stats=({}, {}))
    assert manifest.reader(REPO, name)(old) is None
