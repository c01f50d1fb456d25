"""The traffic generator: the same work on the same clock for every seed;
the seed makes the token ids."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from vbench_toyroot import REPO  # noqa: E402

from vbench import traffic  # noqa: E402

MIXES = ["chat", "longprompt", "decode"]


@pytest.mark.parametrize("name", MIXES)
def test_same_multiset_of_lengths_for_any_seed(name):
    mix = traffic.load_mix(name, REPO)
    g = mix["grid"]

    def block(seed):
        s = traffic.Stream(mix, seed, vocab=1000)
        return [s.take() for _ in range(3 * g)]

    a, b = block(1), block(2**31 + 12345)
    la = [(len(r.prompt), r.max_new) for r in a]
    lb = [(len(r.prompt), r.max_new) for r in b]
    for k in range(3):  # block by block, not only in total
        assert sorted(la[k * g:(k + 1) * g]) == sorted(lb[k * g:(k + 1) * g])
    assert la == lb, "the order is the mix's, the same for every seed"
    assert la[:g] != la[g:2 * g], "but blocks still differ"
    assert not np.array_equal(a[0].prompt[:4], b[0].prompt[:4]) or \
        not np.array_equal(a[1].prompt[:4], b[1].prompt[:4])


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_gives_the_same_requests(name):
    mix = traffic.load_mix(name, REPO)
    a = traffic.Stream(mix, 77, 500)
    b = traffic.Stream(mix, 77, 500)
    for _ in range(mix["grid"] + 3):
        x, y = a.take(), b.take()
        assert x.max_new == y.max_new
        assert np.array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", MIXES)
def test_lengths_stay_inside_the_mix(name):
    mix = traffic.load_mix(name, REPO)
    for plen, olen in traffic.length_pairs(mix):
        assert mix["prompt"]["min"] <= plen <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= olen <= mix["output"]["max"]
    prompts = sorted(p for p, _ in traffic.length_pairs(mix))
    # "median" is the log-normal's before the cut to [min, max]
    assert prompts[0] < mix["prompt"]["median"] < prompts[-1]


@pytest.mark.parametrize("seconds", [10, 51])
@pytest.mark.parametrize("schedule_seed", [2147483701, 31337])
def test_open_schedule_holds_the_same_count_and_work(seconds, schedule_seed):
    """Whatever the run's seed: the same requests due at the same times.
    Another ``schedule_seed`` in a mix's file is another draw of the times
    and the order, of the same work."""
    mix = dict(traffic.load_mix("chat", REPO), schedule_seed=schedule_seed)
    runs = [traffic.open_schedule(mix, seed, 1000, seconds)
            for seed in (5, 6, 2**31 + 7)]
    n = traffic.window_count(mix, seconds)
    assert n % mix["grid"] == 0
    works = []
    for sched in runs:
        due = [r for r in sched if r.in_window]
        ramp = [r for r in sched if not r.in_window]
        assert len(due) == n
        assert len(ramp) == round(mix["rate_per_s"] * mix["ramp_s"])
        assert all(0 <= r.due_s < seconds for r in due)
        assert all(-mix["ramp_s"] <= r.due_s < 0 for r in ramp)
        times = [r.due_s for r in sched]
        assert times == sorted(times)
        works.append(sorted((len(r.prompt), r.max_new) for r in due))
    assert works[0] == works[1] == works[2]
    for other in runs[1:]:
        assert [(r.due_s, len(r.prompt), r.max_new) for r in other] == \
            [(r.due_s, len(r.prompt), r.max_new) for r in runs[0]]
    assert not np.array_equal(runs[0][0].prompt, runs[1][0].prompt) or \
        not np.array_equal(runs[0][1].prompt, runs[1][1].prompt)
    redrawn = traffic.open_schedule(
        dict(mix, schedule_seed=schedule_seed + 1), 5, 1000, seconds)
    assert [r.due_s for r in redrawn] != [r.due_s for r in runs[0]]
    assert sorted((len(r.prompt), r.max_new) for r in redrawn
                  if r.in_window) == works[0]


def test_chat_keeps_the_schedule_that_was_measured():
    """The arrivals and the order that the bounds were measured under
    (PERF.md sections 2 and 6): 150 due in 51 s after 35 in the ramp, and
    the first of them as they were."""
    mix = traffic.load_mix("chat", REPO)
    assert mix["schedule_seed"] == 2147483701
    kept = traffic.open_schedule(mix, 123, 1000, 51)
    assert len(kept) == 185
    assert len([r for r in kept if r.in_window]) == 150
    assert [(round(r.due_s, 6), len(r.prompt), r.max_new)
            for r in kept[:3]] == [(-11.631091, 51, 144),
                                   (-10.974155, 184, 36),
                                   (-10.679894, 117, 163)]


def test_saturated_backlog_staggers_only_its_first_wave():
    mix = traffic.load_mix("longprompt", REPO)
    n = mix["ramp_stagger"]
    plain = traffic.Stream(mix, 9, 1000)
    stag = traffic.backlog(mix, 9, 1000)
    for i in range(n + mix["grid"]):
        p, s = plain.take(), stag.take()
        assert np.array_equal(p.prompt, s.prompt)
        if i < n:
            assert s.max_new <= p.max_new
        else:
            assert s.max_new == p.max_new
    first = [traffic.backlog(mix, 9, 1000).take().max_new]
    assert first[0] < max(o for _, o in traffic.length_pairs(mix))


@pytest.mark.parametrize("text", [
    '{"kind": "closed", "schedule_seed": 1}',
    '{"kind": "open"}',
])
def test_a_mix_of_unknown_kind_or_without_its_schedule_is_refused(
        tmp_path, text):
    os.makedirs(tmp_path / "vbench" / "traffic")
    (tmp_path / "vbench" / "traffic" / "bad.json").write_text(text)
    with pytest.raises(ValueError):
        traffic.load_mix("bad", str(tmp_path))
