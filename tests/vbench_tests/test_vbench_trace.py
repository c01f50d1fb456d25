"""The reduction from a trace to busy time, the top operations, the idle
gaps and the programs' times: on a hand-made trace whose answers are
plain, and on the small trace recorded on the chip that vbench/data keeps."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from vbench_toyroot import REPO  # noqa: E402

from vbench import manifest, trace  # noqa: E402
from vbench.rundata import Run  # noqa: E402
from vbench.stamps import Record  # noqa: E402


def _hand_made():
    # one device; times in ns. busy: [0,400) [400,700) overlap [600,900)
    # then a gap of 100, then [1000,1500): union 900 + 500 = 1400 ns
    ops = [["fusion.1", 0, 400], ["reshape.7", 400, 300],
           ["fusion.1", 600, 300], ["custom-call.3", 1000, 500]]
    mods = [["jit_step(11)", 0, 900], ["jit_step(22)", 1000, 500],
            ["jit_step(11)", 1600, 100]]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": mods}}}


def test_busy_is_the_union_of_device_operations():
    r = trace.reduce(_hand_made(), window_s=2e-6)
    assert r["busy_s"] == pytest.approx(1400e-9)
    assert r["window_s"] == 2e-6 and r["devices"] == 1


def test_window_is_the_span_of_the_device_events_unless_given():
    r = trace.reduce(_hand_made())
    assert r["window_s"] == pytest.approx(1500e-9)
    assert r["busy_s"] == pytest.approx(1400e-9)


def test_operations_rank_by_total_time_under_the_traces_names():
    r = trace.reduce(_hand_made(), window_s=2e-6)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(700e-9)]
    assert r["device_ops"][1] == ["custom-call.3", pytest.approx(500e-9)]
    assert len(r["device_ops"]) == 3


def test_idle_gaps_name_what_ran_next():
    r = trace.reduce(_hand_made(), window_s=2e-6)
    assert len(r["idle_gaps"]) == 1
    name, seconds = r["idle_gaps"][0]
    assert seconds == pytest.approx(100e-9) and "custom-call.3" in name


def test_programs_keep_their_ids_apart():
    r = trace.reduce(_hand_made(), window_s=2e-6)
    assert r["modules"]["jit_step(11)"] == [2, pytest.approx(1000e-9)]
    assert r["modules"]["jit_step(22)"] == [1, pytest.approx(500e-9)]
    assert trace.module_key("jit_step(11)") == "jit_step"


def test_at_most_ten_entries_each():
    ops = [[f"op.{i}", 20 * i, 10] for i in range(40)]
    r = trace.reduce({"devices": {"d": {"ops": ops, "modules": []}}}, 1e-6)
    assert len(r["device_ops"]) == 10 and len(r["idle_gaps"]) == 10


def test_two_devices_average():
    one = _hand_made()["devices"]["/device:TPU:0"]
    idle = {"ops": [["fusion.1", 0, 200]], "modules": []}
    r = trace.reduce({"devices": {"a": one, "b": idle}}, 2e-6)
    assert r["busy_s"] == pytest.approx((1400e-9 + 200e-9) / 2)


def test_a_trace_without_a_device_plane_is_refused():
    with pytest.raises(ValueError):
        trace.reduce({"devices": {}}, 1.0)


def test_no_xplane_file_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        trace.find_xplane(str(tmp_path))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(REPO, "vbench", "data",
                           "recorded_trace.json")) as f:
        return json.load(f)


def test_recorded_trace_reduces_to_its_known_numbers(recorded):
    """Cut from dsllm7b_decode's traced run on the chip (PR 24): the
    answers in vbench/data/recorded_trace.expect.json were worked out once
    from the events and are held here."""
    with open(os.path.join(REPO, "vbench", "data",
                           "recorded_trace.expect.json")) as f:
        want = json.load(f)
    r = trace.reduce(recorded, want["window_s"])
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert r["device_ops"][0][0] == want["top_op"]
    assert r["device_ops"][0][1] == pytest.approx(want["top_op_s"], rel=1e-9)
    step = max((v for k, v in r["modules"].items()
                if trace.module_key(k) == "jit_step"), key=lambda v: v[0])
    assert step[0] == want["step_launches"]
    assert step[1] == pytest.approx(want["step_s"], rel=1e-9)
    assert 0 < r["busy_s"] <= want["window_s"]


def test_roofline_reader_on_the_recorded_trace(recorded):
    """14 streams of 500 cached tokens each against the recorded step time:
    a share between 0 and 100 %, equal to the hand count."""
    import importlib

    with open(os.path.join(REPO, "vbench", "configs",
                           "deepseek-llm-7b-15l.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(REPO, "vbench", "data",
                           "recorded_trace.expect.json")) as f:
        want = json.load(f)
    cost = importlib.import_module("vbench.reference.dense").decode_step_cost
    recs = [Record(i, 400, 200, -5.0, False, stamps=[-4.0 + 0.01 * k
                                                     for k in range(101)],
                   tokens=[1] * 101) for i in range(14)]
    peaks = manifest.peaks(REPO, "TPU v5 lite")
    run = Run(records=recs, seconds=10.0, setup_s=1.0, give_up_s=11.0,
              stats0={}, stats1={}, cfg=cfg, mix={}, peaks=peaks,
              step_cost=cost, trace=trace.reduce(recorded, want["window_s"]),
              trace_span=(4.0, 4.0 + want["window_s"]),
              trace_stats=({"decode_ticks": 100}, {"decode_ticks": 103}))
    share = manifest.reader(REPO, "decode_step_roofline")(run)
    flops, byts = cost(cfg, 14, 14 * 501)
    least = max(flops / 197e12, byts / 819e9)
    assert share == pytest.approx(
        100 * least / (want["step_s"] / want["step_launches"]))
    assert 0 < share < 100
    # an engine that dispatched far more ticks than the trace holds steps
    # for gives no share, not a wrong one
    run.trace_stats = ({"decode_ticks": 0}, {"decode_ticks": 40})
    assert manifest.reader(REPO, "decode_step_roofline")(run) is None
    idle = manifest.reader(REPO, "device_idle_pct")(run)
    assert idle == pytest.approx(
        100 * (1 - want["busy_s"] / want["window_s"]))
