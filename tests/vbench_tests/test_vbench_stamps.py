"""The metric arithmetic on hand-made stamps, with a stall in them: the
mean and the tail must both move."""

import math
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from vbench_toyroot import REPO  # noqa: E402

from vbench import manifest, stamps  # noqa: E402
from vbench.rundata import Run  # noqa: E402
from vbench.stamps import Record  # noqa: E402


def _stream(index, first, n, gap, due=None):
    s = [first + i * gap for i in range(n)]
    r = Record(index, 10, n, due if due is not None else first - 0.05, True,
               sent_s=(due if due is not None else first - 0.05) + 0.001,
               depart_s=first - 0.02, stamps=s, tokens=[1] * n, status="OK",
               ended_s=s[-1])
    return r


def _run(records, seconds=10.0, **kw):
    base = dict(records=records, seconds=seconds, setup_s=12.5,
                give_up_s=seconds + 2, stats0={}, stats1={}, cfg={}, mix={},
                peaks={}, step_cost=None)
    base.update(kw)
    return Run(**base)


def _metric(name, run):
    return manifest.reader(REPO, name)(run)


def test_percentile_interpolates():
    assert stamps.percentile([], 0.5) is None
    assert stamps.percentile([3.0], 0.95) == 3.0
    assert stamps.percentile([0, 10], 0.5) == 5.0
    assert stamps.percentile(list(range(101)), 0.95) == pytest.approx(95.0)


def test_even_streams_give_their_gap():
    recs = [_stream(i, 0.5 + 0.01 * i, 50, 0.1) for i in range(4)]
    run = _run(recs)
    assert _metric("itl_mean_ms", run) == pytest.approx(100.0)
    assert _metric("itl_p95_ms", run) == pytest.approx(100.0)
    assert _metric("out_tokens_per_s", run) == pytest.approx(200 / 10.0)
    assert _metric("setup_s", run) == 12.5


def test_a_stall_moves_the_mean_and_the_tail():
    even = [_stream(i, 0.5, 50, 0.1) for i in range(4)]
    # three stalls a stream: 12 of 196 gaps (6 %) read 1.0 s
    stalled = []
    for i in range(4):
        r = _stream(i, 0.5, 50, 0.1)
        for k in (10, 20, 30):
            r.stamps = r.stamps[:k + 1] + [s + 0.9 for s in r.stamps[k + 1:]]
        stalled.append(r)
    a, b = _run(even), _run(stalled)
    mean_a, mean_b = _metric("itl_mean_ms", a), _metric("itl_mean_ms", b)
    assert mean_b == pytest.approx(100.0 + 900.0 * 12 / 196)
    assert mean_b > mean_a * 1.5
    assert _metric("itl_p95_ms", b) == pytest.approx(1000.0)
    assert _metric("itl_p95_ms", a) == pytest.approx(100.0)


def test_gaps_belong_to_the_window_by_their_later_stamp():
    r = _stream(0, -0.35, 10, 0.1)   # tokens at -0.35 .. 0.55
    gaps = stamps.window_gaps([r], 0.0, 0.5)
    assert len(gaps) == 5           # later stamps 0.05 .. 0.45
    assert stamps.window_tokens([r], 0.0, 0.5) == 5
    zero = Record(1, 4, 3, 0.0, True, stamps=[0.2, 0.2, 0.3], tokens=[1] * 3)
    assert stamps.window_gaps([zero], 0.0, 1.0)[0] == 0.0


def test_ttft_counts_from_the_due_time_and_failures_wait_to_the_end():
    recs = [_stream(i, 1.0 + i, 5, 0.1, due=0.9 + i) for i in range(19)]
    lost = Record(99, 10, 5, 2.0, True, sent_s=2.0)   # never answered
    run = _run(recs + [lost], give_up_s=12.0)
    t = stamps.ttfts(run.records, run.give_up_s)
    assert len(t) == 20 and max(t) == pytest.approx(10.0)
    assert _metric("ttft_p95_ms", run) > 100.0 * 1.5
    ramp = _stream(50, -3.0, 5, 0.1, due=-3.5)
    ramp.in_window = False
    assert len(stamps.ttfts(recs + [ramp], 12.0)) == 19


def test_generator_lateness_and_queue_wait():
    recs = [_stream(i, 1.0 + i, 5, 0.1, due=0.9 + i) for i in range(10)]
    run = _run(recs)
    assert _metric("gen_late_p95_ms", run) == pytest.approx(1.0)
    assert _metric("queue_wait_p95_ms", run) == pytest.approx(80.0)
    recs[0].depart_s = math.nan
    assert _metric("queue_wait_p95_ms", _run(recs)) == pytest.approx(80.0)


def test_live_tokens_counts_streams_between_first_and_last_token():
    a = _stream(0, 1.0, 11, 0.1)      # live 1.0 .. 2.0, prompt 10
    b = _stream(1, 1.5, 11, 0.1)
    cut = Record(2, 7, 50, 0.0, True, stamps=[0.5, 0.6], tokens=[1, 1])
    assert stamps.live_tokens_at([a, b, cut], 0.9) == (1, 7 + 2)
    assert stamps.live_tokens_at([a, b, cut], 1.55) == (3, 16 + 11 + 9)
    assert stamps.live_tokens_at([a, b], 2.35) == (1, 10 + 9)


def _phases(**ms):
    return {p: {"total_ms": ms.get(p, 0.0)}
            for p in ("admission", "dispatch", "fetch", "deliver",
                      "swap_drain")}


def test_per_tick_readers_take_the_stats_difference():
    s0 = {"decode_ticks": 100, "spec_ticks": 0,
          "tick_phase_ms": _phases(admission=10, fetch=1000, deliver=5),
          "paged_attn_kernel_ticks": 50, "paged_attn_gather_ticks": 50}
    s1 = {"decode_ticks": 300, "spec_ticks": 0,
          "tick_phase_ms": _phases(admission=110, fetch=41000, deliver=25,
                                   dispatch=60),
          "paged_attn_kernel_ticks": 200, "paged_attn_gather_ticks": 100,
          "kv_pool_blocks": 800, "kv_pool_used_hwm": 600}
    run = _run([], stats0=s0, stats1=s1)
    assert _metric("fetch_ms_per_tick", run) == pytest.approx(200.0)
    assert _metric("admission_ms_per_tick", run) == pytest.approx(0.5)
    assert _metric("host_ms_per_tick", run) == pytest.approx(0.9)
    assert _metric("kernel_route_pct", run) == pytest.approx(75.0)
    assert _metric("kv_pool_peak_pct", run) == pytest.approx(75.0)


@pytest.mark.parametrize("name", [
    "itl_mean_ms", "itl_p95_ms", "out_tokens_per_s", "ttft_p95_ms",
    "gen_late_p95_ms", "queue_wait_p95_ms", "prefill_tokens_per_admission",
    "decode_step_roofline", "device_idle_pct"])
def test_a_reader_with_nothing_to_read_returns_nothing(name):
    assert _metric(name, _run([])) is None


def test_prefill_tokens_per_admission_counts_departures_in_the_window():
    recs = [_stream(i, 1.0 + i, 5, 0.1) for i in range(3)]
    recs[0].prompt_len, recs[1].prompt_len, recs[2].prompt_len = 100, 200, 900
    recs[2].depart_s = -1.0
    assert _metric("prefill_tokens_per_admission", _run(recs)) == 150.0
