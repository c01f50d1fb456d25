"""The held experts' grouped route (vtpu/ops/grouped_ffn.py behind
``vtpu.models.moe.held_experts_ffn``) under the interpreter at toy widths,
against the all-rows code it replaces on a TPU; the route rule at the three
held-expert configurations' shapes; and the engine's counters of it
(``expert_rows``, ``expert_rows_grouped``) with the benchmark's metric over
them.

Tolerance: in float32 the two sides differ by the order of their sums (a
block of D or F at a time, a row's pairs one after another, against one
contraction over experts and F): outputs of size 1 agree to 1e-6, and 2e-5
is held. In bfloat16 both round the same products at the same places, so
they differ where a float32 sum straddles a rounding boundary: one step of
bfloat16 at the output's size.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_latent_sparse import PAGE, TOY, _both_sides
from vbench.metrics import experts_grouped_pct
from vtpu.models import moe
from vtpu.ops import grouped_ffn as G
from vtpu.serving import ServingConfig, ServingEngine
from vtpu.serving.adapters import LatentSlotModel

D, F = 256, 128
# (H, top_k, E) of mimo-v2.5-7l-ep16, deepseek-v3.2-5l-ep16, deepseek-v2-5l-ep8
HELD = {"mimo": (16, 8, 256), "dsv32": (16, 8, 256), "dsv2": (20, 6, 160)}
# slots, admission bucket, chunk: the rows of their three programs
PROGRAMS = {"mimo": (96, 256, 512), "dsv32": (16, 256, 512),
            "dsv2": (96, 256, 512)}


def _stacks(seed, h, dtype=jnp.float32, d=D, f=F):
    ks = jax.random.split(jax.random.key(seed), 3)
    return {"w_gate": jax.random.normal(ks[0], (h, d, f), dtype) * d ** -0.5,
            "w_up": jax.random.normal(ks[1], (h, d, f), dtype) * d ** -0.5,
            "w_down": jax.random.normal(ks[2], (h, f, d), dtype) * f ** -0.5}


def _routed(seed, t, h, top_k, e):
    """gates [T, H]: a row's ``top_k`` of ``e`` experts drawn evenly with
    weights in (0.1, 1), the first ``h`` columns."""
    rng = np.random.default_rng(seed)
    gates = np.zeros((t, e), np.float32)
    for row in gates:
        row[rng.choice(e, top_k, replace=False)] = rng.uniform(0.1, 1, top_k)
    return jnp.asarray(gates[:, :h])


def _grouped(lp, x, gates, top_k):
    return G.grouped_experts_ffn(
        x, gates, lp["w_gate"][None], lp["w_up"][None], lp["w_down"][None],
        0, top_k, interpret=True)


def _close(got, want, tol=2e-5):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= tol * max(1.0, np.abs(want).max())


@pytest.mark.parametrize("config", sorted(HELD))
@pytest.mark.parametrize("t", [1, 16, 96, 256, 512, 500])
def test_grouped_equals_all_rows(config, t):
    """Each held expert over its own rows alone against every held expert
    over all rows, the gates a router's: 8 of 256 or 6 of 160 a row, the
    holder's 16 or 20 columns. Most rows have no pair here and at 1 and 16
    rows most experts none."""
    h, top_k, e = HELD[config]
    lp = _stacks(t, h)
    x = jax.random.normal(jax.random.key(7 * t), (t, D), jnp.float32)
    gates = _routed(1000 + t, t, h, top_k, e)
    _close(_grouped(lp, x, gates, top_k), moe.held_experts_all_rows(lp, x, gates))


@pytest.mark.parametrize("t", [16, 512])
def test_grouped_equals_all_rows_in_bfloat16(t):
    """The cells' dtype: the products rounded where the all-rows einsums
    round theirs, the row's sum rounded once."""
    h, top_k, e = HELD["dsv32"]
    lp = _stacks(3, h, jnp.bfloat16)
    x = jax.random.normal(jax.random.key(t), (t, D), jnp.bfloat16)
    gates = _routed(t, t, h, top_k, e // 4)  # 4 x the pairs: every expert
    want = moe.held_experts_all_rows(lp, x, gates)
    got = _grouped(lp, x, gates, top_k).astype(jnp.bfloat16)
    _close(got, want, tol=2 ** -7)


@pytest.mark.parametrize("on", ["one_expert", "top_k_experts"])
@pytest.mark.parametrize("t,h,top_k", [(64, 4, 8), (300, 16, 8), (512, 20, 6)])
def test_every_row_on_the_same_experts_drops_nothing(t, h, top_k, on):
    """The routings the pair buffer is sized for: every row on one held
    expert, and every row on the same ``min(top_k, H)`` of them (the
    buffer's every tile live). Neither is capped."""
    k = min(top_k, h)
    lp = _stacks(h, h)
    x = jax.random.normal(jax.random.key(t), (t, D), jnp.float32)
    gates = np.zeros((t, h), np.float32)
    on_each = 1 if on == "one_expert" else k
    gates[:, h - on_each:] = np.random.default_rng(t).uniform(
        0.1, 1, (t, on_each))
    _, tm, tiles = G.plan(t, h, top_k)
    at = G.layout(jnp.pad(jnp.asarray(gates), ((0, -t % 16), (0, 0))),
                  tm, tiles)
    assert int(at["count"].sum()) == t * on_each
    assert int(at["live"]) == on_each * -(-t // tm) <= tiles
    _close(_grouped(lp, x, jnp.asarray(gates), top_k),
           moe.held_experts_all_rows(lp, x, jnp.asarray(gates)))


@pytest.mark.parametrize("t", [16, 256])
def test_an_expert_without_a_row_is_not_read(t):
    """Two of sixteen experts draw rows; the stacks of the others hold nan.
    The all-rows code multiplies them under a gate of zero (nan); the
    grouped route never reads them."""
    h, top_k = 16, 8
    lp = _stacks(5, h)
    drew = jnp.zeros((h,), bool).at[jnp.array([3, 11])].set(True)
    gates = np.zeros((t, h), np.float32)
    gates[::2, 3], gates[1::3, 11] = 0.5, 0.25
    gates = jnp.asarray(gates)
    x = jax.random.normal(jax.random.key(t), (t, D), jnp.float32)
    want = moe.held_experts_all_rows(lp, x, gates)
    holed = {k: jnp.where(drew[:, None, None], v, jnp.nan)
             for k, v in lp.items()}
    assert np.isnan(np.asarray(moe.held_experts_all_rows(holed, x, gates))).all()
    _, tm, tiles = G.plan(t, h, top_k)
    assert int(G.layout(gates, tm, tiles)["live"]) == 2
    _close(_grouped(holed, x, gates, top_k), want)


@pytest.mark.parametrize("case", ["no_pair_at_all", "chosen_with_gate_zero"])
def test_gates_that_hold_exact_zeros(case):
    """A pair is a gate other than zero: a launch without one gives zeros
    (one tile, no row picked), and a chosen expert whose gate is exactly
    zero adds nothing on either route."""
    t, h, top_k, e = 96, 16, 8, 256
    lp = _stacks(9, h)
    x = jax.random.normal(jax.random.key(2), (t, D), jnp.float32)
    if case == "no_pair_at_all":
        gates = jnp.zeros((t, h), jnp.float32)
    else:
        gates = np.array(_routed(4, t, h, top_k, e // 8))
        chosen = np.argwhere(gates != 0)
        assert len(chosen) > 40
        for r, c in chosen[::3]:
            gates[r, c] = 0.0
        gates = jnp.asarray(gates)
    got = _grouped(lp, x, gates, top_k)
    if case == "no_pair_at_all":
        assert not np.asarray(got).any()
    _close(got, moe.held_experts_all_rows(lp, x, gates))


def test_one_program_serves_two_routings():
    """The routing is data: a compiled program is traced once and gives
    each of two routings (one with an empty expert, one on a single expert)
    its own answer."""
    t, h, top_k, e = 256, 16, 8, 256
    lp = _stacks(1, h)
    x = jax.random.normal(jax.random.key(1), (t, D), jnp.float32)
    traces = []

    @jax.jit
    def program(lp, x, gates):
        traces.append(1)
        return _grouped(lp, x, gates, top_k)

    one = jnp.zeros((t, h), jnp.float32).at[:, 5].set(0.3)
    for gates in (_routed(1, t, h, top_k, e), one, _routed(2, t, h, top_k, e)):
        _close(program(lp, x, gates), moe.held_experts_all_rows(lp, x, gates))
    assert len(traces) == 1


# (D, F) of the three configurations
WIDTHS = {"mimo": (4096, 2048), "dsv32": (7168, 2048), "dsv2": (5120, 1536)}


@pytest.mark.parametrize("config", sorted(HELD))
@pytest.mark.parametrize("program", ["step", "admission", "chunk"])
def test_the_route_of_each_program(monkeypatch, config, program):
    """PERF.md section 3's rule: on a TPU every program of the three
    configurations is grouped (a step's 16 or 96 rows, the admission
    bucket's 256, a chunk's 512: the kernels were the faster at each on
    the chip); on the CPU none is."""
    t = PROGRAMS[config][("step", "admission", "chunk").index(program)]
    assert G.takes(t, *WIDTHS[config])
    assert moe.experts_grouped(t, *WIDTHS[config]) is False  # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe.experts_grouped(t, *WIDTHS[config]) is True


@pytest.mark.parametrize("t,d,f", [
    (512, 64, 32),      # toy widths: not whole in the chip's lanes
    (512, 7168, 2000),
    (2049, 4096, 2048),  # more rows than were compiled and timed
    (512, 8192, 8192),   # a block of both stacks would not stay in VMEM
    (2048, 7168, 4096),  # nor these rows beside blocks this wide
], ids=["toy_widths", "odd_expert_width", "too_many_rows", "wide_experts",
        "rows_and_width"])
def test_shapes_the_kernels_do_not_take(monkeypatch, t, d, f):
    """They run the all-rows code (``tests/test_tpu_compile.py`` compiles
    the most rows ``takes`` admits at the three configurations' widths)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not moe.experts_grouped(t, d, f)
    assert moe.experts_grouped(2048, 7168, 2048)
    assert moe.experts_grouped(256, 8192, 8192)


@pytest.mark.parametrize("top_k", [8, None])
@pytest.mark.parametrize("given", ["layer_of_a_stack", "dict"])
def test_a_layer_of_a_stack_is_read_in_place(monkeypatch, given, top_k):
    """``held_experts_ffn`` hands the kernels the stacks whole with the
    layer's index when it is given a layer of a stack (the models'
    ``LayerOfStack``), and layer 1 of two answers as its own slice does, which a
    dict of the layer's leaves goes in as under an axis of one; without the
    router's ``top_k`` the buffers are sized for a gate in every column
    and the answer is the same."""
    from vtpu.models.latent import LayerOfStack
    h, e, t = 16, 256, 32
    both = [_stacks(s, h) for s in (1, 2)]
    stack = {k: jnp.stack([lp[k] for lp in both]) for k in both[0]}
    x = jax.random.normal(jax.random.key(1), (t, D), jnp.float32)
    gates = _routed(1, t, h, 8, e // 4)
    leaf, layer = LayerOfStack(stack, 1).stacked("w_up")
    assert leaf is stack["w_up"] and layer == 1
    handed = []

    def interpreted(x, gates, w_gate, w_up, w_down, layer, top_k):
        handed.append((w_gate, w_up, w_down, layer, top_k))
        return G.grouped_experts_ffn(x, gates, w_gate, w_up, w_down, layer,
                                     top_k, interpret=True)

    monkeypatch.setattr(moe, "experts_grouped", lambda t, d, f: True)
    monkeypatch.setattr(moe, "grouped_experts_ffn", interpreted)
    lp = LayerOfStack(stack, 1) if given == "layer_of_a_stack" else both[1]
    got = moe.held_experts_ffn(lp, x, gates, top_k)
    _close(got, moe.held_experts_all_rows(both[1], x, gates))
    (w_gate, w_up, w_down, layer, k), = handed
    assert k == (top_k or h)
    if given == "layer_of_a_stack":
        assert (w_gate is stack["w_gate"] and w_up is stack["w_up"]
                and w_down is stack["w_down"] and layer == 1)
    else:
        assert w_gate.shape == (1,) + both[1]["w_gate"].shape and layer == 0


def _served(eng, prompts):
    eng.start()
    try:
        out = [list(eng.submit(p, max_new_tokens=3).stream())
               for p in prompts]
        return out, eng.stats()
    finally:
        eng.stop()


def test_the_engine_counts_its_launches_rows(monkeypatch):
    """Prompts of 70 and 100 in chunks of 64 (two each), a prompt of 9
    admitted whole in the bucket of 16, and the steps that follow, 3 slots
    each. With the kernels interpreted and a rule that takes the chunks
    (64 rows) and the steps (3 rows) and leaves the bucket (16 rows), the
    counters follow the launches' shapes and the streams are the all-rows
    program's token for token. On the CPU as it is nothing is grouped.
    ``experts_grouped_pct`` reads the two counters."""
    mc, params = _both_sides(TOY)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, 90, n).astype(np.int32) for n in (70, 9, 100)]

    def engine():
        return ServingEngine(
            serving=ServingConfig(
                slots=3, prefill_buckets=(16,), max_new_tokens=8,
                kv_page=PAGE, kv_pool_blocks=40, prefill_chunk=64),
            model=LatentSlotModel(params, mc, kv_page=PAGE,
                                  kv_pool_blocks=40, read_windows=(128,)))

    plain, stats = _served(engine(), prompts)
    steps = stats["decode_ticks"]
    assert stats["expert_rows"] == 4 * 64 + 16 + 3 * steps
    assert stats["expert_rows_grouped"] == 0
    calls = []

    def interpreted(*args):
        calls.append(args[0].shape[0])
        return G.grouped_experts_ffn(*args, interpret=True)

    monkeypatch.setattr(moe, "experts_grouped", lambda t, d, f: t != 16)
    monkeypatch.setattr(moe, "grouped_experts_ffn", interpreted)
    forced, stats = _served(engine(), prompts)
    assert stats["loop_error"] is None and forced == plain
    # the programs traced: steps, chunks, and a warmed admission of two
    assert set(calls) == {3, 32, 64}
    steps = stats["decode_ticks"]
    assert stats["expert_rows"] == 4 * 64 + 16 + 3 * steps
    assert stats["expert_rows_grouped"] == 4 * 64 + 3 * steps
    run = types.SimpleNamespace(
        stats1=stats, counter=lambda name: stats[name])
    assert experts_grouped_pct.read(run) == pytest.approx(
        100.0 * (4 * 64 + 3 * steps) / (4 * 64 + 16 + 3 * steps))
    run.stats1 = {"prefill_chunks": 4}  # a program without the counters
    assert experts_grouped_pct.read(run) is None
    run.stats1, run.counter = stats, lambda name: 0  # no launch in the window
    assert experts_grouped_pct.read(run) is None


def test_the_held_experts_bench_runs_at_a_cut_down_shape(tmp_path):
    """``benchmarks/held_experts_bench.py --tiny`` (the table PERF.md's PR 41
    entry chose the kernels and the rule with) runs on the CPU for one
    configuration at 16 rows: every candidate within a step of bfloat16 of
    the all-rows code, the kernels' row slots no more than its; no time is
    written there."""
    import json
    import pathlib
    import subprocess
    import sys

    root = pathlib.Path(__file__).resolve().parent.parent
    out = tmp_path / "bench.json"
    done = subprocess.run(
        [sys.executable, str(root / "benchmarks/held_experts_bench.py"),
         "--tiny", "--rows", "16", "--configs", "deepseek-v2-5l-ep8",
         "--out", str(out)], capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    table = json.loads(out.read_text())["table"]
    assert [r["candidate"] for r in table] == 2 * [
        "all_rows", "kernel", "gmm", "ragged_dot"]
    assert {r["routing"] for r in table} == {"router", "one_expert"}
    for row in table:
        assert "ms" not in row
        if row["candidate"] != "all_rows":
            assert row["distance"] < 0.1
        if row["candidate"] == "kernel":
            assert row["pairs"] <= row["row_slots"] <= 20 * 16
