"""Multi-tick device-resident decode loop (ISSUE 11 tentpole).

Fast (non-slow) tier. The contract under test, layered like the change:

- a k-tick flush is TOKEN-EQUAL to k single ticks for every layout the
  shared trunk serves — dense exact, paged, paged int8, MoE, and a tp=2
  head-sharded pool — because the loop body IS the unchanged decode step
  (transformer.multi_tick_decode feeds inner tick i's sampled token into
  tick i+1 on device);
- the transfer contract generalizes: ONE batched [B, k] fetch per flush,
  device_gets_per_token == 1/k exactly (decode_ticks counts inner ticks);
- per-slot early exit: a slot that hits its budget or eos inside the loop
  freezes in place — streams stop at EXACTLY their budget, frozen output
  columns carry the sentinel, loop_early_exits counts the freezes;
- retire/admit mid-flush invalidation: the PR-1 lookahead identity check
  generalized k-deep (a recycled slot's whole in-flight column drops);
- a park request lands during a flush defers to the flush boundary, and
  the host-replicated page-table/length state reconciles with the device
  at every boundary (the parked entry's seq_len equals the device length);
- decode_loop_k=1 is bit-identical to None (resolved to the classic loop);
- interaction guards raise precise errors for the one feature that needs
  host logits every tick (custom sample=); active speculation FUSES into
  the loop instead (tests/test_fused_spec.py).

conftest forces --xla_force_host_platform_device_count=8, so the tp=2 case
runs on CPU CI exactly like the paged-TP suite.
"""

import time

import jax
import jax.numpy as jnp
import pytest

from vtpu.models import ModelConfig, init_params
from vtpu.models.transformer import LOOP_PAD_TOKEN
from vtpu.serving import ServingConfig, ServingEngine

# one layer, and max_seq equal to the single prefill bucket below: the
# engine then warms exactly ONE decode read window per executable — this
# file builds ~25 engines, so every avoided trunk compile is tier-1 budget
CFG = ModelConfig(
    vocab=64, d_model=32, n_heads=4, n_layers=1, d_ff=64,
    max_seq=32, head_dim=8, dtype=jnp.float32, use_pallas=False,
)
CFG_INT8 = ModelConfig(
    vocab=64, d_model=32, n_heads=4, n_layers=1, d_ff=64,
    max_seq=32, head_dim=8, dtype=jnp.float32, use_pallas=False,
    kv_int8=True,
)
# long context for the park tests: the parked request must still hold a
# few hundred tokens of budget when the park command lands, or a k-deep
# engine can finish the whole stream before the lifecycle drain sees it
CFG_LONG = ModelConfig(
    vocab=64, d_model=32, n_heads=4, n_layers=1, d_ff=64,
    max_seq=512, head_dim=8, dtype=jnp.float32, use_pallas=False,
)
PAGE = 8
needs_devices = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs 2 virtual devices")


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.key(0), CFG)


@pytest.fixture(scope="module")
def params_int8():
    return init_params(jax.random.key(0), CFG_INT8)


def _prompt(seed, n, vocab=CFG.vocab):
    return [int(t) % vocab for t in jax.random.randint(
        jax.random.key(seed), (n,), 1, CFG.vocab, jnp.int32)]


def _serving(k, **kw):
    base = dict(slots=2, prefill_buckets=(32,), max_new_tokens=10,
                decode_loop_k=k)
    base.update(kw)
    return ServingConfig(**base)


def _run(params, serving, prompts, budgets=None, mesh=None, cfg=CFG,
         model=None):
    eng = ServingEngine(params, cfg, serving, mesh=mesh, model=model)
    eng.start()
    try:
        reqs = [eng.submit(p, max_new_tokens=(budgets[i] if budgets else 0))
                for i, p in enumerate(prompts)]
        streams = [list(r.stream()) for r in reqs]
        stats = eng.stats()
    finally:
        eng.stop()
    return streams, stats


# ------------------------------------------------- token equality across k


def test_streams_token_equal_across_k_dense(params):
    prompts = [_prompt(1, 5), _prompt(2, 7)]
    base, base_stats = _run(params, _serving(None), prompts)
    assert base_stats["decode_loop_k"] == 1
    assert base_stats["loop_flushes"] == 0
    for k in (4, 8):
        got, stats = _run(params, _serving(k), prompts)
        assert got == base, f"k={k} diverged"
        assert stats["decode_loop_k"] == k
        assert stats["loop_flushes"] > 0


def test_streams_token_equal_across_k_paged_with_logprobs(params):
    """Paged pool + logprobs under the loop: one [B, k] f32 plane rides
    the flush fetch, every delivered token carries its logprob entry
    (equal to the k=1 run's), and the inner scatters keep walking the
    table (every inner tick attributed to a paged read route)."""
    prompts = [_prompt(3, 5), _prompt(4, 6)]

    def run(k):
        eng = ServingEngine(params, CFG, _serving(
            k, kv_page=PAGE, logprobs=True))
        eng.start()
        try:
            reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
            toks = [list(r.stream()) for r in reqs]
            lps = [list(r.logprobs) for r in reqs]
            return toks, lps, eng.stats()
        finally:
            eng.stop()

    base, base_lps, _ = run(None)
    got, lps, stats = run(4)
    assert got == base
    # the first token has no logprob entry (prefill-derived); flush
    # tokens each do, pairing exactly like the classic loop's
    for g, l, bl in zip(got, lps, base_lps):
        assert len(l) == len(g) - 1 == len(bl)
        assert l == pytest.approx(bl, abs=1e-5)
    assert (stats["paged_attn_kernel_ticks"]
            + stats["paged_attn_gather_ticks"]) == stats["decode_ticks"]


def test_streams_token_equal_across_k_paged_int8_with_swap(params_int8):
    """int8 paged pool + the overcommit swap tier, both arms: kv_swap is
    dormant with no pressure (bit-identical streams), so the comparison
    doubles as the composes-with-swap guard — the loop constructs and
    serves with paged + int8 + kv_swap together."""
    prompts = [_prompt(5, 5), _prompt(6, 6)]
    base, _ = _run(params_int8, _serving(None, kv_page=PAGE, kv_swap=4),
                   prompts, cfg=CFG_INT8)
    got, stats = _run(params_int8, _serving(4, kv_page=PAGE, kv_swap=4),
                      prompts, cfg=CFG_INT8)
    assert got == base
    assert stats["decode_loop_k"] == 4 and stats["loop_flushes"] > 0


def test_streams_token_equal_across_k_moe():
    from vtpu.models.moe import MoEConfig, init_moe_params
    from vtpu.serving.adapters import MoeSlotModel

    cfg = MoEConfig(vocab=96, d_model=64, n_heads=2, n_layers=2, d_ff=64,
                    n_experts=4, top_k=2, max_seq=32, head_dim=32,
                    dtype=jnp.float32)
    mparams = init_moe_params(jax.random.key(5), cfg)
    prompts = [_prompt(21, 5, cfg.vocab), _prompt(22, 7, cfg.vocab)]

    def run(k):
        return _run(None, _serving(k, max_new_tokens=6), prompts,
                    model=MoeSlotModel(mparams, cfg))[0]

    assert run(4) == run(None)


@needs_devices
def test_streams_token_equal_across_k_tp2(params):
    from vtpu.parallel.mesh import make_axis_mesh

    mesh = make_axis_mesh("tp", 2)
    prompts = [_prompt(7, 5), _prompt(8, 6)]
    base, _ = _run(params, _serving(None, kv_page=PAGE), prompts, mesh=mesh)
    got, _ = _run(params, _serving(4, kv_page=PAGE), prompts, mesh=mesh)
    assert got == base


def test_k1_bit_identical_to_none(params):
    """decode_loop_k=1 resolves to the classic loop — same executables,
    same loop flavor, zero loop counters — while stats() still reports
    the resolved k."""
    prompts = [_prompt(9, 5)]
    eng = ServingEngine(params, CFG, _serving(1))
    assert eng._loop_k is None and eng._decode_loop is None
    eng.start()
    try:
        r = eng.submit(prompts[0], max_new_tokens=6)
        got = list(r.stream())
        stats = eng.stats()
    finally:
        eng.stop()
    base, base_stats = _run(params, _serving(None), prompts, budgets=[6])
    assert got == base[0]
    assert stats["decode_loop_k"] == 1 == base_stats["decode_loop_k"]
    assert stats["loop_flushes"] == 0
    assert stats["device_gets_per_tick"] == 1.0
    assert stats["device_gets_per_token"] == 1.0
    assert stats["pipelined"]


def test_multi_tick_stats_are_exported(params):
    """Every new stats() key the loop added maps to a vtpu_serving_*
    family — the exporter coverage check's contract, pinned here by name
    so the keys can never be quietly allowlisted away."""
    from vtpu.obs.export import COUNTERS, GAUGES, SPECIAL

    assert "loop_flushes" in COUNTERS and "loop_early_exits" in COUNTERS
    assert "decode_loop_k" in GAUGES
    assert "device_gets_per_token" in GAUGES
    assert "tick_phase_ms" in SPECIAL


# --------------------------------------------- transfer + early-exit walls


def test_fetch_contract_and_early_exit_exact_budget(params):
    """The two device-side walls in one engine. Transfer:
    device_gets_per_token == 1/k EXACTLY — one batched [B, k] fetch per
    flush, decode_ticks counting the k inner ticks each flush ran.
    Early exit: budgets deliberately not divisible by k, so each stream
    stops at EXACTLY its budget (the device froze the slot mid-flush)
    and the freezes are counted."""
    prompts = [_prompt(12, 5), _prompt(13, 6)]
    budgets = [5, 7]  # both % 4 != 0: the wall lands mid-flush
    streams, stats = _run(params, _serving(4, max_new_tokens=10), prompts,
                          budgets=budgets)
    assert stats["tick_fetches"] * 4 == stats["decode_ticks"]
    assert stats["device_gets_per_token"] == 0.25
    assert stats["device_gets_per_tick"] == 0.25
    assert stats["loop_flushes"] * 4 == stats["decode_ticks"]
    # a flush pays each host phase once for its k inner ticks: the
    # per-tick share in tick_phase_ms divides by them
    for phase in ("dispatch", "deliver"):
        h = stats["tick_phase_ms"][phase]
        assert h["ticks"] == 4 * h["count"] == stats["decode_ticks"]
        assert h["mean_ms_per_tick"] == pytest.approx(
            h["mean_ms"] / 4, abs=1e-3)
    assert [len(s) for s in streams] == budgets
    assert stats["loop_early_exits"] > 0
    base, _ = _run(params, _serving(None, max_new_tokens=10), prompts,
                   budgets=budgets)
    assert streams == base


def test_multi_tick_decode_pads_frozen_lanes_with_sentinel(params):
    """Function-level: the [B, k] output of a flush carries LOOP_PAD_TOKEN
    in every column past a slot's cap, counts equal the caps, and the
    carry holds each slot's final sampled token."""
    from vtpu.serving.adapters import (
        TransformerSlotModel, multi_tick_decode_step)

    model = TransformerSlotModel(params, CFG)
    state = model.init_state(2)
    # install two prompts at lengths 4 and 5 via the engine-shaped prefill
    for slot, n in ((0, 4), (1, 5)):
        padded = jnp.zeros((1, 8), jnp.int32).at[0, :n].set(
            jnp.asarray(_prompt(30 + slot, n), jnp.int32))
        _, state = model.prefill_into_slot(
            model.params, state, padded, jnp.int32(slot), jnp.int32(n))
    step = jax.jit(
        multi_tick_decode_step(model, 0.0, 0, 1.0, False, 4, -1),
        static_argnames=("kv_bucket", "unroll"))
    keys = jax.random.split(jax.random.key(0), 2)
    out, counts, carry, lps, state, _ = step(
        model.params, state, jnp.zeros((2,), jnp.int32),
        jnp.asarray([True, True]), keys,
        jnp.asarray([2, 4], jnp.int32), 0, unroll=True)
    out, counts, carry = jax.device_get((out, counts, carry))
    assert list(counts) == [2, 4]
    assert lps is None
    assert (out[0, 2:] == LOOP_PAD_TOKEN).all()
    assert (out[0, :2] != LOOP_PAD_TOKEN).all()
    assert (out[1] != LOOP_PAD_TOKEN).all()
    assert carry[0] == out[0, 1] and carry[1] == out[1, 3]
    # the frozen slot's length stopped advancing at its cap
    lens = jax.device_get(state["len"])
    assert lens[0] == 4 + 2 and lens[1] == 5 + 4


# --------------------------------------- lifecycle at the flush boundary


def test_retire_admit_mid_flush_invalidation(params):
    """Slot recycling under the k-deep lookahead: waves of staggered
    budgets force retires and re-admissions while flushes are in flight —
    every stream must match the k=1 run token for token (a recycled
    slot's orphaned in-flight column is dropped by the identity check,
    never delivered to the new occupant)."""
    prompts = [_prompt(40 + i, 4 + (i % 3)) for i in range(8)]
    budgets = [3, 9, 5, 11, 4, 7, 6, 10]
    base, _ = _run(params, _serving(None, max_new_tokens=12), prompts,
                   budgets=budgets)
    got, stats = _run(params, _serving(4, max_new_tokens=12), prompts,
                      budgets=budgets)
    assert got == base
    assert [len(s) for s in got] == budgets
    assert stats["admissions"] == 8


def test_park_during_flush_defers_to_boundary():
    """park() while a flush is in flight: the slot is excluded from the
    next dispatch, its in-flight tokens land, and the park settles at the
    boundary with zero token loss — the resumed stream equals the
    never-parked run. The budget is a few hundred tokens and the park
    lands right after the first token, so the request still holds many
    flushes of work when the lifecycle drain sees the command (a k-deep
    engine finishes a short stream before a late park can settle — that
    no-op-on-finished behavior is the documented park contract, not what
    this test pins)."""
    params = init_params(jax.random.key(0), CFG_LONG)
    budget = 300
    base, _ = _run(params, ServingConfig(
        slots=2, prefill_buckets=(8,), max_new_tokens=budget, kv_page=PAGE,
        kv_swap=16), [_prompt(50, 5)], budgets=[budget], cfg=CFG_LONG)
    eng = ServingEngine(params, CFG_LONG, ServingConfig(
        slots=2, prefill_buckets=(8,), max_new_tokens=budget, kv_page=PAGE,
        kv_swap=16, decode_loop_k=4))
    eng.start()
    try:
        r = eng.submit(_prompt(50, 5), max_new_tokens=budget)
        it = r.stream()
        got = [next(it)]
        eng.park(r)
        deadline = time.time() + 30
        while r not in eng._parked and time.time() < deadline:
            time.sleep(0.005)
        assert r in eng._parked, "park never settled at a flush boundary"
        entry = eng._parked[r]
        # host/device reconciliation at the boundary: the parked entry's
        # host-side length equals the device cache length for its slot,
        # and the pending-token invariant (exactly one delivered-but-
        # unwritten token) held through the flush
        park_ev = [e for e in eng.trace.snapshot() if e[2] == "park"][-1]
        slot = park_ev[4]
        dev_len = int(jax.device_get(eng.state["len"])[slot])
        assert entry["seq_len"] == dev_len
        assert len(entry["tokens"]) == entry["seq_len"]
        eng.resume(r)
        got += list(it)
        stats = eng.stats()
    finally:
        eng.stop()
    assert got == base[0]
    assert stats["parks"] == 1 and stats["resumes"] == 1


def test_page_table_host_device_reconciliation_after_flush():
    """After every flush the host-replicated page-table rows stay the
    truth: the device table row for a live slot equals the blocks the
    host allocator mapped, and the device length equals the host mirror
    (checked at a park-settled quiescent point, then at end-of-stream
    where the device length must equal prompt + budget - 1 — every
    consumed token's scatter landed through the table walk)."""
    params = init_params(jax.random.key(0), CFG_LONG)
    n, budget = 5, 200
    eng = ServingEngine(params, CFG_LONG, ServingConfig(
        slots=1, prefill_buckets=(8,), max_new_tokens=budget, kv_page=PAGE,
        kv_swap=16, decode_loop_k=4))
    eng.start()
    try:
        r = eng.submit(_prompt(60, n), max_new_tokens=budget)
        it = r.stream()
        got = [next(it)]
        eng.park(r)
        deadline = time.time() + 30
        while r not in eng._parked and time.time() < deadline:
            time.sleep(0.005)
        assert r in eng._parked
        entry = eng._parked[r]
        state = jax.device_get({k: eng.state[k] for k in ("table", "len")})
        blocks = entry["shared"] + entry["priv"]
        assert list(state["table"][0][:len(blocks)]) == blocks
        assert int(state["len"][0]) == entry["seq_len"]
        eng.resume(r)
        got += list(it)
        assert len(got) == budget
        # end of stream: budget tokens delivered, budget - 1 consumed
        # (the final token is never fed back), all through the table walk
        assert int(jax.device_get(eng.state["len"])[0]) == n + budget - 1
    finally:
        eng.stop()


# ------------------------------------------------------ interaction guards


def test_guard_custom_sampler_rejected(params):
    with pytest.raises(ValueError, match="requires device sampling"):
        ServingEngine(params, CFG, _serving(4),
                      sample=lambda logits: int(jnp.argmax(logits)))


def test_active_speculation_fuses_into_loop(params):
    """Active speculation no longer conflicts with the device loop: the
    draft moved on device, so spec_tokens + decode_loop_k construct the
    FUSED engine (tests/test_fused_spec.py owns the behavior)."""
    eng = ServingEngine(params, CFG, _serving(4, spec_tokens=3))
    assert eng._fused_spec and eng._decode_fused is not None
    assert eng._decode_loop is not None  # the cooloff fallback dispatch


def test_guard_inactive_speculation_composes(params):
    """spec_tokens that is already inert (a temperature sampler disables
    verification) must NOT trip the guard — the loop only conflicts with
    speculation that would actually run."""
    eng = ServingEngine(params, CFG, _serving(
        4, spec_tokens=3, temperature=0.7))
    assert eng._loop_k == 4 and eng._spec_tokens == 0


def test_guard_nonpositive_k_rejected(params):
    with pytest.raises(ValueError, match="decode_loop_k must be >= 1"):
        ServingEngine(params, CFG, _serving(0))


