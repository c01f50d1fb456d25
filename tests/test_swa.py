"""The window family (vtpu/models/swa.py, ``WindowSlotModel``,
vtpu/ops/window_attn.py and the walk over keys wider than values) at toy
widths on the CPU, against the benchmark's plain reference
(vbench/reference/swa.py: float32, no cache, a window layer's scores over
its band with the sink in the denominator) on the benchmark's own seeded
weights: hidden 64, eight query heads 24 wide for keys (8 of them rotated)
and 16 for values over 2 (full) and 4 (window) key/value heads, a window of
8, seven layers in the published order (0 1 1 1 1 0 1), prefill chunk 12
(no multiple of the window), 4 of 16 experts held.

Tolerances, and why. With float32 on both sides the two differ by the order
of their sums (a ring and a chunk's own keys against one band, blocks of
queries): they agree to about 1e-6 and 2e-5 is held (``F32_TOL``). The same
program in bfloat16 reads 0.01-0.05 off (``BF16_TOL`` 0.15 holds it, and it
fails ``F32_TOL``, asserted): the float32 limit tells a lower precision
apart, and each planted fault of the equations (a window one wider or
narrower, no sink, an unscaled value, rotary over the whole head) reads
over a hundred times it.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vbench import weights
from vbench.reference import common
from vbench.reference import swa as ref
from vbench.sut import swa as sut
from vtpu.models import swa as M
from vtpu.ops import causal_attention, window_attn
from vtpu.ops.decode_attn import wide_decode_attention
from vtpu.serving import ServingConfig, ServingEngine
from vtpu.serving.adapters import WindowSlotModel

F32_TOL = 2e-5
BF16_TOL = 0.15
SEED = 2**31 + 39
PAGE, CHUNK, WINDOW, CONTEXT = 8, 12, 8, 120

TOY = dict(
    family="swa", hidden_size=64, num_attention_heads=8, head_dim=24,
    v_head_dim=16, swa_head_dim=24, swa_v_head_dim=16,
    swa_num_attention_heads=8, num_key_value_heads=2,
    swa_num_key_value_heads=4, partial_rotary_factor=0.334,
    rope_theta=10000000, swa_rope_theta=10000, sliding_window=WINDOW,
    attention_value_scale=0.707, add_swa_attention_sink_bias=True,
    add_full_attention_sink_bias=False,
    hybrid_layer_pattern=[0, 1, 1, 1, 1, 0, 1, 1, 1],
    moe_layer_freq=[0, 1, 1, 1, 1, 1, 1, 1, 1], num_hidden_layers=7,
    intermediate_size=128, moe_intermediate_size=32, n_routed_experts=4,
    n_routed_experts_published=16, held_experts_first=4,
    num_experts_per_tok=4, n_group=1, topk_group=1, norm_topk_prob=True,
    routed_scaling_factor=None, n_shared_experts=None,
    scoring_func="sigmoid", topk_method="noaux_tc", layernorm_epsilon=1e-5,
    rms_norm_eps=1e-5,
    vocab_size=96, max_position_embeddings=CONTEXT, dtype="float32",
    output_head="lm_head")
BLOCKS = np.array([5, 9, 2, 7, 11, 3, 8, 12, 13, 14, 15, 16, 17, 18, 19],
                  np.int32)


def _both_sides(cfg=TOY, dtype=jnp.float32):
    """(program config, program params) over the benchmark's weights; the
    router, its bias and the sinks stay float32 in a bfloat16 program."""
    w = weights.make_all(SEED, ref.weight_specs(cfg),
                         cfg["num_hidden_layers"], ref.layer_kinds(cfg))
    keep = ("router", "route_bias", "sink")
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a if path[-1].key in keep else a.astype(dtype),
        sut.params_of(cfg, w))
    return sut.model_config(cfg, dtype), params


def _reference(toks, cfg=TOY):
    """Logits [S, V] of the plain reference's full forward."""
    specs = ref.weight_specs(cfg)
    key = weights.seed_key(SEED)
    g = weights.make_globals(key, specs)
    x = g["embed"][jnp.asarray(toks)].astype(jnp.float32)
    for l, kind in enumerate(ref.layer_kinds(cfg)):
        x = ref.layer(cfg, weights.make_layer(key, specs, l, kind), x, "f32",
                      kind)
    return np.asarray(common.head(cfg, g, x, "f32"))


@pytest.fixture(scope="module")
def prompt():
    return np.random.default_rng(3).integers(
        1, TOY["vocab_size"], 64).astype(np.int32)


@pytest.fixture(scope="module")
def reference(prompt):
    return _reference(prompt)


@pytest.fixture(scope="module")
def program():
    return _both_sides()


def _fresh_state(mc, slots=3):
    """A pool with slot 1 mapped to scattered blocks and junk in every
    ring: what earlier sessions left behind."""
    state = M.init_swa_state(mc, slots, PAGE, 40)
    state["table"] = state["table"].at[1].set(jnp.asarray(BLOCKS))
    state["wk"] = jnp.full_like(state["wk"], 3.0)
    state["wv"] = jnp.full_like(state["wv"], -2.0)
    return state


def _chunked(mc, params, state, toks, p, slot=1):
    """toks[:p] into ``slot`` in CHUNK-token chunks: (last logits [V],
    state)."""
    pad = -(-p // CHUNK) * CHUNK
    padded = np.zeros((1, pad), np.int32)
    padded[0, :p] = toks[:p]
    chunk = jax.jit(lambda st, c, off, new: M.swa_prefill_chunk(
        params, mc, st, c, jnp.int32(slot), off, new, CONTEXT,
        jnp.asarray(BLOCKS)))
    for off in range(0, pad, CHUNK):
        logits, state = chunk(state, jnp.asarray(padded[:, off:off + CHUNK]),
                              jnp.int32(off), jnp.int32(min(off + CHUNK, p)))
    return np.asarray(logits[0, (p - 1) - (pad - CHUNK)]), state


def _decode(mc, params, state, toks, start, stop, slot=1, route=None):
    """Positions start .. stop - 1 of ``toks`` through decode steps of
    ``slot`` alone: logits [stop - start, V]."""
    slots = state["len"].shape[0]
    step = jax.jit(lambda st, t: M.swa_decode_step(
        params, mc, st, t, jnp.arange(slots) == slot, CONTEXT,
        paged_attn=route))
    out = []
    for p in range(start, stop):
        logits, state = step(state, jnp.full((slots,), toks[p], jnp.int32))
        out.append(np.asarray(logits[slot]))
    return np.stack(out), state


def test_the_toy_is_the_published_pattern_in_small(program):
    mc, params = program
    assert ref.layer_kinds(TOY) == [
        "full_dense", "window_moe", "window_moe", "window_moe", "window_moe",
        "full_moe", "window_moe"]
    assert mc.layer_kinds == tuple(ref.layer_kinds(TOY))
    assert (mc.rope_dim, mc.window, CHUNK % mc.window) == (8, 8, 4)
    assert set(params["layers"]) == {"full_dense", "window_moe", "full_moe"}
    assert params["layers"]["window_moe"]["sink"].shape == (5, 8)
    assert "sink" not in params["layers"]["full_moe"]


def test_full_forward_is_the_references(program, prompt, reference):
    mc, params = program
    got = M.swa_forward(params, mc, jnp.asarray(prompt)[None])[0]
    assert np.max(np.abs(np.asarray(got) - reference)) < F32_TOL


@pytest.mark.parametrize("p", [5, 8, 13, 16])
def test_whole_prompt_admission_gives_the_references_last_logits(
        program, prompt, reference, p):
    """Right-padded to a bucket of 16, two prompts a dispatch, into slots
    whose rings hold junk."""
    mc, params = program
    state = _fresh_state(mc)
    state["table"] = state["table"].at[2].set(jnp.asarray(BLOCKS[::-1] + 20))
    padded = np.zeros((2, 16), np.int32)
    padded[0, :p] = prompt[:p]
    padded[1, :3] = prompt[:3]
    logits, state = jax.jit(lambda st: M.swa_prefill_rows(
        params, mc, st, jnp.asarray(padded), jnp.asarray([1, 2]),
        jnp.asarray([p, 3])))(state)
    assert np.max(np.abs(np.asarray(logits[0]) - reference[p - 1])) < F32_TOL
    assert np.max(np.abs(np.asarray(logits[1]) - reference[2])) < F32_TOL
    assert state["len"].tolist() == [0, p, 3]
    # ... and decode goes on from the rings and pages it left
    got, _ = _decode(mc, params, state, prompt, p, p + 4)
    assert np.max(np.abs(got - reference[p:p + 4])) < F32_TOL


@pytest.mark.parametrize("p", [7, 12, 13, 29, 40])
def test_chunks_that_are_no_multiple_of_the_window_carry_the_ring(
        program, prompt, reference, p):
    mc, params = program
    logits, state = _chunked(mc, params, _fresh_state(mc), prompt, p)
    assert np.max(np.abs(logits - reference[p - 1])) < F32_TOL
    assert int(state["len"][1]) == p


@pytest.mark.parametrize("route", [None, "kernel"])
def test_decode_past_three_windows_follows_the_reference(
        program, prompt, reference, route):
    """13 tokens in two chunks, then 30 decode steps (the ring wraps three
    times); the full layers by the gathered window and by the interpreted
    walk."""
    mc, params = program
    _, state = _chunked(mc, params, _fresh_state(mc), prompt, 13)
    got, state = _decode(mc, params, state, prompt, 13, 43, route=route)
    assert np.max(np.abs(got - reference[13:43])) < F32_TOL
    assert int(state["len"][1]) == 43


def test_sessions_of_unequal_length_share_a_decode_step(program, prompt):
    """Three slots at lengths 3, 21 and 10 (under, over and about a
    window), one of them idle every other step: each follows its own
    sequence's reference."""
    mc, params = program
    seqs = [np.roll(prompt, -7 * i) for i in range(3)]
    refs = [_reference(s) for s in seqs]
    lens = [3, 21, 10]
    state = M.init_swa_state(mc, 3, PAGE, 60)
    for slot in range(3):
        state["table"] = state["table"].at[slot].set(
            jnp.asarray(BLOCKS + 16 * slot))
    state["wk"] = jnp.full_like(state["wk"], 5.0)
    for slot, (s, n) in enumerate(zip(seqs, lens)):
        pad = -(-n // CHUNK) * CHUNK
        padded = np.zeros((1, pad), np.int32)
        padded[0, :n] = s[:n]
        for off in range(0, pad, CHUNK):
            _, state = M.swa_prefill_chunk(
                params, mc, state, jnp.asarray(padded[:, off:off + CHUNK]),
                jnp.int32(slot), jnp.int32(off), jnp.int32(min(off + CHUNK, n)),
                CONTEXT, jnp.asarray(BLOCKS + 16 * slot))
    step = jax.jit(lambda st, t, a: M.swa_decode_step(
        params, mc, st, t, a, CONTEXT))
    for i in range(12):
        active = np.array([True, i % 2 == 0, True])
        toks = np.array([s[n] for s, n in zip(seqs, lens)], np.int32)
        logits, state = step(state, jnp.asarray(toks), jnp.asarray(active))
        for slot in range(3):
            if active[slot]:
                want = refs[slot][lens[slot]]
                assert np.max(np.abs(np.asarray(logits[slot]) - want)) < F32_TOL
                lens[slot] += 1
    assert state["len"].tolist() == lens


def test_a_slot_reused_by_a_shorter_session_reads_nothing_of_the_last(
        program, prompt, reference):
    """A session of 40 tokens, then the same slot given to one of 5 (fewer
    than a window: most ring rows still hold the old session's) by a chunk,
    and to one of 6 by a whole-prompt admission."""
    mc, params = program
    other = np.roll(prompt, -11)
    _, state = _chunked(mc, params, _fresh_state(mc), other, 40)
    logits, state = _chunked(mc, params, state, prompt, 5)
    assert np.max(np.abs(logits - reference[4])) < F32_TOL
    got, state = _decode(mc, params, state, prompt, 5, 9)
    assert np.max(np.abs(got - reference[5:9])) < F32_TOL
    padded = np.zeros((1, 16), np.int32)
    padded[0, :6] = other[:6]
    logits, state = M.swa_prefill_rows(
        params, mc, state, jnp.asarray(padded), jnp.asarray([1]),
        jnp.asarray([6]))
    assert np.max(np.abs(np.asarray(logits[0]) - _reference(other)[5])) < F32_TOL


FAULTS = {
    "window_narrower": lambda mc, p: (dataclasses.replace(mc, window=7), p),
    "window_wider": lambda mc, p: (dataclasses.replace(mc, window=9), p),
    "no_sink": lambda mc, p: (mc, _without_sink(p)),
    "unscaled_value": lambda mc, p: (
        dataclasses.replace(mc, value_scale=1.0), p),
    "rotary_over_the_whole_head": lambda mc, p: (
        dataclasses.replace(mc, rope_dim=mc.head_dim), p),
}


def _without_sink(params):
    layers = {kind: ({**stack, "sink": jnp.full_like(stack["sink"], -1e9)}
                     if "sink" in stack else stack)
              for kind, stack in params["layers"].items()}
    return {**params, "layers": layers}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_of_the_equations_fails_the_comparison(
        program, prompt, reference, fault):
    """Chunked prefill then decode, as the sound program's tests run it,
    with one equation off: every one reads far over the tolerance."""
    mc, params = FAULTS[fault](*program)
    logits, state = _chunked(mc, params, _fresh_state(mc), prompt, 29)
    got, _ = _decode(mc, params, state, prompt, 29, 33)
    worst = max(np.max(np.abs(logits - reference[28])),
                np.max(np.abs(got - reference[29:33])))
    assert worst > 100 * F32_TOL, worst


def test_the_program_in_bfloat16_reads_a_bounded_gap_and_fails_float32s(
        prompt, reference):
    mc, params = _both_sides(dtype=jnp.bfloat16)
    logits, state = _chunked(mc, params, _fresh_state(mc), prompt, 29)
    got, _ = _decode(mc, params, state, prompt, 29, 35)
    worst = max(np.max(np.abs(logits - reference[28])),
                np.max(np.abs(got - reference[29:35])))
    assert F32_TOL < worst < BF16_TOL, worst


# -- the held share of the experts --------------------------------------------

def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """16 experts over 4 shares of 4: the parts each share's holder
    computes, in the reference and in the program, add up to what the
    uncut reference gives for the whole layer (there is no shared expert
    to count once)."""
    uncut = dict(TOY, n_routed_experts=16, held_experts_first=0)
    specs = ref.weight_specs(uncut)
    w = weights.make_layer(weights.seed_key(SEED), specs, 1, "window_moe")
    n = jax.random.normal(jax.random.key(5), (19, 64), jnp.float32)
    whole = ref.expert_block(uncut, w, n, "f32")
    parts, served = 0, 0
    for first in range(0, 16, 4):
        share = dict(uncut, n_routed_experts=4, held_experts_first=first)
        held = {k: (v[first:first + 4] if k.startswith("e_") else v)
                for k, v in w.items()}
        parts = parts + ref.expert_block(share, held, n, "f32")
        mc = dataclasses.replace(sut.model_config(TOY), held=(first, 4))
        lp = {"mlp_norm": jnp.ones((64,)), "router": w["router"],
              "route_bias": w["route_bias"],
              "w_gate": w["e_gate"][first:first + 4],
              "w_up": w["e_up"][first:first + 4],
              "w_down": w["e_down"][first:first + 4]}
        # the program's layer norms its input itself: hand it rows whose
        # norm is themselves
        rows = n / jnp.sqrt(jnp.mean(n * n, -1, keepdims=True) + mc.eps)
        served = served + M._expert_ffn(mc, lp, rows) - rows
    assert np.max(np.abs(np.asarray(parts - whole))) < 1e-5
    rows = n / jnp.sqrt(jnp.mean(n * n, -1, keepdims=True) + 1e-5)
    again = ref.expert_block(uncut, w, common.rms_norm(
        rows, jnp.ones((64,)), 1e-5), "f32")
    assert np.max(np.abs(np.asarray(served - again))) < 1e-4
    # four of sixteen chosen a token, their weights summing to one
    gates = ref.route_gates(uncut, w, n, "f32")
    assert np.all(np.sum(np.asarray(gates) > 0, axis=1) == 4)
    assert np.allclose(np.sum(np.asarray(gates), axis=1), 1.0, atol=1e-6)


# -- the ops -------------------------------------------------------------------

def test_spread_queries_and_own_values_are_each_others_inverse():
    q = jax.random.normal(jax.random.key(0), (3, 8, 24))
    spread = window_attn.spread_queries(q, 2)
    assert spread.shape == (3, 8, 48)
    # head 5 is of key/value head 1: its columns sit in the second half
    assert np.all(np.asarray(spread[:, 5, :24]) == 0)
    assert np.array_equal(np.asarray(spread[:, 5, 24:]), np.asarray(q[:, 5]))
    mixed = jax.random.normal(jax.random.key(1), (3, 8, 32))
    own = window_attn.own_values(mixed, 2)
    assert np.array_equal(np.asarray(own[:, 2]), np.asarray(mixed[:, 2, :16]))
    assert np.array_equal(np.asarray(own[:, 6]), np.asarray(mixed[:, 6, 16:]))


def test_ring_positions_and_the_rows_a_chunk_leaves():
    last = jnp.asarray([-1, 2, 7, 11])
    pos = np.asarray(window_attn.ring_positions(last, 4))
    assert pos.tolist() == [[-4, -3, -2, -1], [0, 1, 2, -1], [4, 5, 6, 7],
                            [8, 9, 10, 11]]
    ring = jnp.full((1, 4, 1), -1.0)
    own = (10.0 + jnp.arange(6.0))[None, :, None]     # positions 5 .. 10
    # a chunk at offset 5 of which 3 tokens are real (positions 5, 6, 7)
    got = window_attn.ring_after(ring, own, jnp.asarray([5]), jnp.asarray([8]))
    assert got[0, :, 0].tolist() == [-1.0, 10.0, 11.0, 12.0]
    # all six real: the ring holds positions 7 .. 10
    got = window_attn.ring_after(ring, own, jnp.asarray([5]), jnp.asarray([11]))
    assert got[0, :, 0].tolist() == [13.0, 14.0, 15.0, 12.0]


@pytest.mark.parametrize("lens", [[1, 70, 200], [64, 0, 129]])
def test_the_interpreted_walk_at_192_and_128_is_causal_attentions_arithmetic(
        lens):
    """Keys 192 wide, values 128, 16 query heads over 4 key/value heads, a
    page of 64: the walk over a slot's live pages against
    ``causal_attention`` over the gathered window with the values padded
    to the keys' width (its one head width), under the same lengths."""
    hq, hk, dk, dv, page, nb, wp = 16, 4, 192, 128, 64, 14, 4
    ks = jax.random.split(jax.random.key(7), 4)
    k_pool = jax.random.normal(ks[0], (2, nb, page, hk * dk), jnp.bfloat16)
    v_pool = jax.random.normal(ks[1], (2, nb, page, hk * dv), jnp.bfloat16)
    q = jax.random.normal(ks[2], (3, hq, dk), jnp.bfloat16)
    table = jnp.asarray([[3, 7, 1, 9], [2, 0, 0, 0], [5, 4, 8, 6]], jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    got = window_attn.own_values(wide_decode_attention(
        window_attn.spread_queries(q, hk), k_pool, v_pool, table, lens, 1,
        dk ** -0.5, interpret=True), hk)
    keys = k_pool[1][table].reshape(3, wp * page, hk, dk)
    values = jnp.pad(v_pool[1][table].reshape(3, wp * page, hk, dv),
                     ((0, 0), (0, 0), (0, 0), (0, dk - dv)))
    want = causal_attention(q[:, None], keys, values,
                            kv_len=lens[:, None])[:, 0, :, :dv]
    live = np.asarray(lens) > 0
    assert np.max(np.abs(np.asarray(got, np.float32)
                         - np.asarray(want, np.float32))[live]) < 0.02
    assert np.all(np.isfinite(np.asarray(got, np.float32)))


# -- the engine ------------------------------------------------------------------

def _engine(program, **serving):
    mc, params = program
    cfg = ServingConfig(
        slots=3, prefill_buckets=(16,), max_new_tokens=40, kv_page=PAGE,
        kv_pool_blocks=40, prefill_chunk=CHUNK, **serving)
    model = WindowSlotModel(params, mc, kv_page=PAGE, kv_pool_blocks=40,
                            read_windows=(32, 64), paged_attn=cfg.paged_attn)
    return ServingEngine(serving=cfg, model=model)


def test_the_engine_serves_the_references_tokens_and_counts_its_rings(
        program, prompt):
    """A short prompt admitted whole and a long one in chunks, both decoded
    past the window: every served token is the reference's first, the
    rings' bytes stand still while the sessions grow, and pages are
    charged for the full layers alone."""
    mc, _ = program
    eng = _engine(program)
    eng.start()
    try:
        before = eng.stats()
        a = eng.submit(prompt[:9], max_new_tokens=30)
        b = eng.submit(np.roll(prompt, -5)[:29], max_new_tokens=20)
        got_a, got_b = list(a.stream()), list(b.stream())
        after = eng.stats()
    finally:
        eng.stop()
    for toks, got in ((prompt[:9], got_a), (np.roll(prompt, -5)[:29], got_b)):
        seq = np.concatenate([toks, got]).astype(np.int32)
        want = np.argmax(_reference(seq), axis=-1)
        assert got == want[len(toks) - 1:-1].tolist()
    rings = 3 * 5 * WINDOW * 4 * (24 + 16) * 4       # float32 toy
    assert before["recurrent_state_bytes"] == rings
    assert after["recurrent_state_bytes"] == rings
    assert eng.state["wk"].nbytes + eng.state["wv"].nbytes == rings
    assert after["window_ring"] == WINDOW
    assert after["ring_bytes_per_position"] == 5 * 4 * (24 + 16) * 4
    # a cached token costs the two full layers' rows, and nothing else
    per_token = 2 * 2 * (24 + 16) * 4
    assert mc.kv_bytes_per_token == per_token
    assert after["kv_hbm_bytes"]["paged"] == 41 * PAGE * per_token
    assert (eng.state["k"].nbytes + eng.state["v"].nbytes
            == 41 * PAGE * per_token)
    assert after["kv_pool_used_hwm"] >= -(-49 // PAGE)
    # the window layers read at most a ring a slot a tick, the full
    # layers' walk all a slot holds
    ticks = after["decode_ticks"]
    assert 0 < after["window_rows_read"] <= 2 * WINDOW * ticks
    assert after["attn_visible_tokens"] > after["window_rows_read"]
    assert after["ssm_rows_stepped"] == 0


REFUSALS = {
    "mesh": (lambda p: WindowSlotModel(
        p[1], p[0], kv_page=PAGE, mesh=object()), "no sharding rule"),
    "dense_cache": (lambda p: WindowSlotModel(p[1], p[0]), "paged cache only"),
    "unknown_route": (lambda p: WindowSlotModel(
        p[1], p[0], kv_page=PAGE, paged_attn="window"),
        "paged_attn must be one of"),
    "speculation": (lambda p: _engine(p, spec_tokens=2), "no spec_step"),
    "swap": (lambda p: _engine(p, kv_swap=4), "cannot park or swap"),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_a_session_with_rings_cannot_do_is_refused_by_name(
        program, what):
    build, why = REFUSALS[what]
    with pytest.raises(ValueError, match=why):
        build(program)


def test_an_int8_cache_is_refused_by_name(program):
    mc, params = program

    class Int8(M.SwaConfig):
        kv_int8 = True

    with pytest.raises(ValueError, match="no int8 cache"):
        WindowSlotModel(params, Int8(**dataclasses.asdict(mc)), kv_page=PAGE)


@pytest.mark.parametrize("what,why", [
    ("register_prefix", "rings as they stood at the prefix's last token"),
    ("drain", "rings have no staging")])
def test_prefix_and_migration_are_refused_by_name(program, what, why):
    eng = _engine(program)
    with pytest.raises(ValueError, match=why):
        eng._refused(what)
    if what == "register_prefix":
        with pytest.raises(ValueError, match="cannot register_prefix"):
            eng.register_prefix(np.arange(1, 17, dtype=np.int32))
