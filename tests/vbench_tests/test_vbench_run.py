"""The rest of a run, driven on the CPU past the harness's look for a chip:
two toy cells, with their configurations, mixes and one metric ADDED to a
copy of the benchmark as files and entries only (vbench_toyroot.py).

Holds the shape of the last line, the comparison with the plain reference,
its control (a run with the reference in float8 put in the program's place
must come out as not correct, by the run's own comparison and limits) and
a run whose timed path is broken underneath (a token altered where it is
produced must come out as not correct).
"""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import vbench_toyroot  # noqa: E402
from vbench_toyroot import REPO  # noqa: E402

from vbench import check, manifest, run  # noqa: E402

CELLS = ["toy_dense_sat", "toy_moe_open"]
SECONDS = 2.0
SEED = 2**31 + 17
GAPS = ("logit_gap_max", "logit_gap_mean")


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("vbench_root"))
    return root, vbench_toyroot.build(root)


@pytest.fixture(scope="module")
def results(toy):
    """One sound run a toy cell."""
    root, _ = toy
    return {cell: run.run_cell(root, cell, SEED, SECONDS, False)
            for cell in CELLS}


@pytest.fixture(scope="module")
def controls(toy):
    """The same runs with the float8 reference in the program's place."""
    root, _ = toy
    return {cell: run.run_cell(root, cell, SEED, SECONDS, False,
                               control=True) for cell in CELLS}


@pytest.mark.parametrize("cell", CELLS)
def test_last_line_shape(toy, results, cell):
    _, man = toy
    res = json.loads(json.dumps(results[cell]))   # it must serialise
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "compared"]
    wanted = {m["name"]: m["unit"]
              for m in manifest.metrics_of(man, "end_to_end", cell)}
    assert set(res["metrics"]) == set(wanted)
    for name, m in res["metrics"].items():
        assert set(m) == {"value", "unit"} and m["unit"] == wanted[name]
        assert isinstance(m["value"], float) and m["value"] > 0
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert res["attempted"] > 0 and res["failed"] == 0
    for n in res["compared"].values():
        assert set(n) == {"value", "limit"}


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_run_agrees_with_the_plain_reference(results, cell):
    res = results[cell]
    c = res["compared"]
    assert res["correct"] is True
    assert c["logit_gap_max"]["value"] <= c["logit_gap_max"]["limit"]
    assert c["tokens_short_of_sample"]["value"] == 0
    assert c["streams_wrong_length"]["value"] == 0
    assert c["tokens_compared"]["value"] >= 20


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_comes_out_as_not_correct(controls, cell):
    """The reference computed in float8, put in the program's place: the
    run's own comparison, under the real names and limits, says false, and
    over a gap, not over a count."""
    res = controls[cell]
    c = res["compared"]
    assert res["correct"] is False
    over = [k for k, n in c.items()
            if n["limit"] is not None and n["value"] > n["limit"]]
    assert over and set(over) <= set(GAPS), c
    # the program underneath was sound all the while
    for k in GAPS:
        assert c[f"program_{k}"]["limit"] is None
        assert c[f"program_{k}"]["value"] <= c[k]["limit"]
        assert c[f"program_{k}"]["value"] < c[k]["value"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_changes_nothing_but_the_tokens_compared(
        results, controls, cell):
    sound, ctl = results[cell]["compared"], controls[cell]["compared"]
    assert set(ctl) - set(sound) == {f"program_{k}" for k in GAPS}
    for k in set(sound) - set(GAPS) - {"tokens_compared"}:
        assert ctl[k] == sound[k]
    for k in GAPS:
        assert ctl[k]["limit"] == sound[k]["limit"]


@pytest.mark.parametrize("numbers,want", [
    ({"a": {"value": 0, "limit": 0}}, True),
    ({"a": {"value": 1, "limit": 0}}, False),
    ({"a": {"value": 0.2, "limit": 0.3},
      "b": {"value": 9.0, "limit": None}}, True),
    ({"a": {"value": 0.2, "limit": 0.3},
      "b": {"value": 0.31, "limit": 0.3}}, False),
])
def test_verdict_holds_every_number_that_has_a_limit(numbers, want):
    assert check.verdict(numbers) is want


def test_saturated_cell_counts_only_what_the_window_saw(results):
    res = results["toy_dense_sat"]
    rate = res["metrics"]["out_tokens_per_s"]["value"]
    gap = res["metrics"]["itl_mean_ms"]["value"]
    # at most 4 slots, each a token every `gap` ms
    assert rate <= 4 * 1e3 / gap * 1.5


def test_open_cell_sends_the_count_the_mix_fixes(toy, results):
    root, _ = toy
    from vbench import traffic

    mix = traffic.load_mix("toy-open", root)
    assert results["toy_moe_open"]["attempted"] == \
        traffic.window_count(mix, SECONDS)


def test_a_token_altered_where_it_is_produced_is_not_correct(
        toy, monkeypatch):
    """The timed path broken underneath: the engine's on-device sampler
    returns the runner-up's neighbour, token + 1."""
    import vtpu.models.transformer as tf

    real = tf.sample_tokens

    def off_by_one(logits, keys, **kw):
        tok, lp, keys = real(logits, keys, **kw)
        return (tok + 1) % logits.shape[-1], lp, keys

    monkeypatch.setattr(tf, "sample_tokens", off_by_one)
    root, _ = toy
    res = run.run_cell(root, "toy_dense_sat", 5, SECONDS, False)
    assert res["correct"] is False
    c = res["compared"]
    assert c["logit_gap_max"]["value"] > c["logit_gap_max"]["limit"]
    assert c["logit_gap_mean"]["value"] > c["logit_gap_mean"]["limit"]


def test_traced_line_carries_the_cells_per_layer_metrics(toy, monkeypatch):
    """--trace 1 on the CPU, with the recorded chip trace standing in for
    the profiler's (the CPU's trace has no device plane): the line holds
    busy_s, window_s, the breakdown, and the per-layer metrics that found
    something to read, the added one among them."""
    from vbench import trace

    root, man = toy
    with open(os.path.join(REPO, "vbench", "data",
                           "recorded_trace.json")) as f:
        recorded = json.load(f)
    monkeypatch.setattr(trace, "load_xplane", lambda path: recorded)
    res = run.run_cell(root, "toy_dense_sat", 6, 3.0, True)
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "breakdown", "compared"]
    assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 1 <= len(res["breakdown"]["device_ops"]) <= 10
    assert len(res["breakdown"]["idle_gaps"]) <= 10
    listed = {m["name"] for m in
              manifest.metrics_of(man, "per_layer", "toy_dense_sat")}
    assert set(res["metrics"]) <= listed
    assert "toy_tokens_total" in res["metrics"]
    for name in ("host_ms_per_tick", "fetch_ms_per_tick",
                 "admission_ms_per_tick", "kv_pool_peak_pct",
                 "device_idle_pct"):
        assert name in res["metrics"], name
    # on the CPU the router never picks the kernel
    assert res["metrics"]["kernel_route_pct"]["value"] == 0.0


def _cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "vbench.run", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_without_an_accelerator_it_fails_and_prints_no_result():
    p = _cli(REPO, "--workload", "olmoe_chat", "--seed", "1", "--seconds",
             "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_without_the_program_it_fails_and_prints_no_result(toy):
    """A directory that holds only BENCHMARK.json and the paths."""
    root, _ = toy
    p = _cli(root, "--workload", "toy_dense_sat", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "vtpu" in p.stderr
