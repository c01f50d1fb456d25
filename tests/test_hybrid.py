"""The hybrid family (vtpu/models/hybrid.py, ``HybridSlotModel``, the grouped
queries of vtpu/ops) at toy widths on the CPU, against the benchmark's plain
reference (vbench/reference/hybrid.py: float32, no cache, the state-space
recurrence a scan over time) on the benchmark's own seeded weights: hidden
128, two periods of five layers with one attention layer each, SSD chunk 8,
prefill chunk 16.

Tolerances, and why. Logits are compared with logits, the program's own
``logits_scaling`` and ``embedding_multiplier`` against
``reference.hybrid.logits`` and its ``mamba_in`` layer. The model's own
logits here are small (the embedding's range, vbench/reference/hybrid.py:
they spread by 0.016 and reach 0.07). With float32 on both sides the two
differ by the order of their sums (the chunked form against the
recurrence, blocks of attention): they agree to 1.3e-7 and 5e-6 is held
(``F32_TOL``; the issue's 1e-4 was for logits of size one). The same
program in bfloat16 reads 0.0018-0.0021 off (``BF16_TOL`` 0.01 holds it,
and it fails ``F32_TOL``, asserted): the float32 limit tells a lower
precision apart.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vbench import weights
from vbench.reference import hybrid as ref
from vbench.sut import hybrid as sut
from vtpu.models import hybrid as M
from vtpu.models.transformer import cached_attention, init_paged_kv_cache
from vtpu.ops import ssm_step
from vtpu.serving import ServingConfig, ServingEngine
from vtpu.serving.adapters import HybridSlotModel

F32_TOL = 5e-6
BF16_TOL = 0.01
SEED = 2**31 + 5
PAGE, CHUNK, WINDOW = 8, 16, 128
PERIOD = ["mamba", "mamba", "attention", "mamba", "mamba"]

TOY = dict(
    family="hybrid", hidden_size=128, shared_intermediate_size=256,
    num_attention_heads=4, num_key_value_heads=2, head_dim=64,
    layer_types=PERIOD * 2, num_hidden_layers=10, mamba_n_heads=8,
    mamba_d_head=32, mamba_d_state=16, mamba_n_groups=1, mamba_d_conv=4,
    mamba_chunk_size=8, mamba_expand=2, embedding_multiplier=12,
    attention_multiplier=0.015625, residual_multiplier=0.22, logits_scaling=8,
    rms_norm_eps=1e-5, vocab_size=96, max_position_embeddings=WINDOW,
    position_embedding_type="nope", tie_word_embeddings=True,
    dtype="float32", output_head="embed")
BLOCKS = np.array([5, 9, 2, 7, 11, 3, 8, 12, 13, 14, 15, 16, 17, 18, 19, 20],
                  np.int32)


def _both_sides(cfg=TOY, dtype=jnp.float32):
    """(program config, program params) over the benchmark's weights; the
    per-head float32 leaves stay float32 in a bfloat16 program."""
    w = weights.make_all(SEED, ref.weight_specs(cfg),
                         cfg["num_hidden_layers"], ref.layer_kinds(cfg))
    keep = ("dt_bias", "a_log", "d_skip")
    params = sut.params_of(cfg, w)
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a if path[-1].key in keep else a.astype(dtype), params)
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
    return np.asarray(ref.logits(cfg, g, x, "f32"))


@pytest.fixture(scope="module")
def prompt():
    return np.random.default_rng(3).integers(
        1, TOY["vocab_size"], 53).astype(np.int32)


@pytest.fixture(scope="module")
def reference(prompt):
    return _reference(prompt)


@pytest.fixture(scope="module")
def program():
    return _both_sides()


def _fresh_state(mc, slots=3):
    """A pool with slot 1 mapped to scattered blocks and junk in its
    recurrent rows: what an earlier session left behind."""
    state = M.init_hybrid_state(mc, slots, PAGE, 40)
    state["table"] = state["table"].at[1].set(jnp.asarray(BLOCKS))
    state["h"] = state["h"].at[:, 1].set(3.0)
    state["conv"] = state["conv"].at[:, 1].set(2.0)
    return state


def _chunked(mc, params, state, toks, p, slot=1, chunk_fn=None):
    """toks[:p] into ``slot`` in CHUNK-token chunks: (last logits [V],
    state)."""
    fn = chunk_fn or M.hybrid_prefill_chunk
    pad = -(-p // CHUNK) * CHUNK
    padded = np.zeros((1, pad), np.int32)
    padded[0, :p] = toks[:p]
    chunk = jax.jit(lambda st, c, off, new: fn(
        params, mc, st, c, jnp.int32(slot), off, new, WINDOW,
        jnp.asarray(BLOCKS)))
    for off in range(0, pad, CHUNK):
        logits, state = chunk(state, jnp.asarray(padded[:, off:off + CHUNK]),
                              jnp.int32(off), jnp.int32(min(off + CHUNK, p)))
    return np.asarray(logits[0, (p - 1) - (pad - CHUNK)]), state


def _through_the_state(mc, params, toks, p, paged_attn=None):
    """Chunked prefill of toks[:p] into slot 1, then decode of the rest a
    token a step beside two inactive slots: logits [S - p + 1, V] at the
    positions p - 1 .. S - 1."""
    first, state = _chunked(mc, params, _fresh_state(mc), toks, p)
    out = [first]
    step = jax.jit(lambda st, t, a: M.hybrid_decode_step(
        params, mc, st, t, a, WINDOW, paged_attn=paged_attn))
    active = jnp.asarray([False, True, False])
    for i in range(p, len(toks)):
        logits, state = step(
            state, jnp.asarray([0, toks[i], 7], jnp.int32), active)
        out.append(np.asarray(logits[1]))
    return np.stack(out)


# ------------------------------------------------------ the mixer alone


@pytest.mark.parametrize("length", [8, 16, 13, 37])
def test_chunked_ssd_equals_the_recurrence(length):
    """The chunked matrix form against the reference's scan over time, at
    lengths that are and are not multiples of the SSD chunk (8) and of the
    prefill chunk (16), from a carried state that is not zero."""
    rng = np.random.default_rng(length)
    h, p, n = 8, 32, 16
    x = rng.normal(size=(length, h, p)).astype(np.float32)
    dt = rng.uniform(0.004, 0.03, (length, h)).astype(np.float32)
    a = -rng.uniform(1, 16, h).astype(np.float32)
    b, c = (rng.normal(size=(length, n)).astype(np.float32) for _ in "bc")
    h0 = rng.normal(size=(h, p, n)).astype(np.float32)
    want_y, want_h = ref.ssm_scan(*map(jnp.asarray, (x, dt, a, b, c, h0)))
    got_y, got_h = M._ssd_chunked(
        jnp.asarray(x)[None], jnp.asarray(dt)[None], jnp.asarray(a),
        jnp.asarray(b)[None], jnp.asarray(c)[None], jnp.asarray(h0)[None], 8)
    np.testing.assert_allclose(got_y[0], want_y, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(got_h[0], want_h, atol=2e-5, rtol=1e-5)


def test_one_token_step_equals_the_recurrence():
    rng = np.random.default_rng(1)
    h, p, n = 8, 32, 16
    x, b, c = (rng.normal(size=s).astype(np.float32)
               for s in ((1, h, p), (1, n), (1, n)))
    dt = rng.uniform(0.004, 0.03, (1, h)).astype(np.float32)
    a = -rng.uniform(1, 16, h).astype(np.float32)
    h0 = rng.normal(size=(h, p, n)).astype(np.float32)
    want_y, want_h = ref.ssm_scan(*map(jnp.asarray, (x, dt, a, b, c, h0)))
    got_y, got_h = M._ssd_step(
        jnp.asarray(x)[None], jnp.asarray(dt)[None], jnp.asarray(a),
        jnp.asarray(b)[None], jnp.asarray(c)[None], jnp.asarray(h0)[None])
    np.testing.assert_allclose(got_y[0], want_y, atol=1e-6)
    np.testing.assert_allclose(got_h[0], want_h, atol=1e-6)


@pytest.fixture(params=["xla", "kernel"])
def route(request, monkeypatch):
    """Both routes of a step's state update: XLA's code, as every CPU run
    takes it (None), and the kernel forced for the programs traced from here
    on, which off a TPU runs interpreted (the list of its traced calls'
    ``interpret``)."""
    if request.param == "xla":
        return None
    calls = []

    def counted(*a, **kw):
        calls.append(kw.get("interpret"))
        return ssm_step.ssm_state_step(*a, **kw)

    monkeypatch.setattr(M, "step_in_kernel", lambda t: t == 1)
    monkeypatch.setattr(M, "ssm_state_step", counted)
    return calls


@pytest.mark.parametrize("layer", [0, 2, 4])
@pytest.mark.parametrize("tile", [2, 4])
@pytest.mark.parametrize("slots", [3, 8])
def test_state_kernel_equals_the_xla_step(monkeypatch, slots, tile, layer):
    """One layer of a stack of five through the kernel (interpreted), in
    tiles of fewer heads than the 8 there are: ``y`` and that layer of ``h``
    equal ``_ssd_step``'s to float32 rounding, every other layer of the
    stack and every row with ``dt == 0`` is bit-equal."""
    monkeypatch.setattr(ssm_step, "_TILE_HEADS", tile)
    rng = np.random.default_rng(100 * slots + 10 * tile + layer)
    lm, h, p, n = 5, 8, 32, 16
    x, b, c = (jnp.asarray(rng.normal(size=s).astype(np.float32))
               for s in ((slots, 1, h, p), (slots, 1, n), (slots, 1, n)))
    dt = rng.uniform(0.004, 0.03, (slots, 1, h)).astype(np.float32)
    dt[1] = 0  # an inactive slot
    dt = jnp.asarray(dt)
    a = jnp.asarray(-rng.uniform(1, 16, h).astype(np.float32))
    stack = jnp.asarray(rng.normal(size=(lm, slots, h, p, n)).astype(np.float32))
    want_y, want_h = M._ssd_step(x, dt, a, b, c, stack[layer])
    got_y, got = jax.jit(
        lambda st, l: ssm_step.ssm_state_step(
            st, l, *M._step_operands(x, dt, a, b, c), interpret=True)
    )(stack, jnp.int32(layer))
    np.testing.assert_allclose(got_y, want_y[:, 0], atol=1e-5, rtol=1e-6)
    np.testing.assert_allclose(got[layer], want_h, atol=1e-6, rtol=1e-6)
    for other in set(range(lm)) - {layer}:
        np.testing.assert_array_equal(got[other], stack[other])
    np.testing.assert_array_equal(got[layer, 1], stack[layer, 1])
    assert not np.array_equal(got[layer, 0], stack[layer, 0])


# ------------------------------------------- the whole model, in logits


def test_full_forward_equals_the_reference(program, prompt, reference):
    mc, params = program
    got = M.hybrid_forward(params, mc, jnp.asarray(prompt)[None])[0]
    np.testing.assert_allclose(got, reference, atol=F32_TOL)


@pytest.mark.parametrize("p", [16, 21, 40])
def test_prefill_then_decode_through_both_kinds_of_state(
        program, prompt, reference, p):
    """Chunks of 16 carrying state (one, one and a part, two and a part),
    then the recurrence a token a step and pages read through the table,
    against the reference's full forward; the slot's stale rows are not
    seen."""
    mc, params = program
    got = _through_the_state(mc, params, prompt, p)
    np.testing.assert_allclose(got, reference[p - 1:], atol=F32_TOL)


def test_bfloat16_is_told_apart(prompt, reference):
    """The same program in bfloat16 stays within ``BF16_TOL`` of the
    reference and outside ``F32_TOL``."""
    mc, params = _both_sides(dtype=jnp.bfloat16)
    off = np.abs(_through_the_state(mc, params, prompt, 21)
                 - reference[20:]).max()
    assert F32_TOL < off < BF16_TOL, off


@pytest.mark.parametrize("route", ["kernel", "gather"])
def test_grouped_query_paged_attention_on_both_routes(
        program, prompt, reference, route):
    """4 query heads over 2 key/value heads of 64 stored one row of 128
    lanes, scale 1/64, no rotary: decode through the forced route (the
    kernel interpreted here) against the reference."""
    mc, params = program
    got = _through_the_state(mc, params, prompt, 21, paged_attn=route)
    np.testing.assert_allclose(got, reference[20:], atol=F32_TOL)


def test_attention_layer_alone_against_the_reference(program):
    """One attention layer's mixer over a paged pool, both routes, against
    ``reference.hybrid.attention`` on the same normed input."""
    mc, params = program
    acfg = mc.attention
    rng = np.random.default_rng(7)
    s = 24
    x = jnp.asarray(rng.normal(size=(s, TOY["hidden_size"])), jnp.float32)
    key = weights.seed_key(SEED)
    w = weights.make_layer(key, ref.weight_specs(TOY), 2, "attention")
    want = ref.attention(TOY, w, ref.common.rms_norm(
        x, w["attn_norm"], TOY["rms_norm_eps"]), "f32")
    lp = {k: v[0] for k, v in params["attention"].items()}
    for route in ("kernel", "gather"):
        cache = init_paged_kv_cache(acfg, 1, PAGE, 40)
        cache["table"] = cache["table"].at[0].set(jnp.asarray(BLOCKS))
        outs = []
        for t in range(s):  # a token a step, as a decode tick reads it
            write_kv = M.slot_steps.decode_kv_writer(
                acfg, cache, jnp.asarray([True]))
            attend = cached_attention(
                acfg, cache, 1, WINDOW, write_kv, unroll=True,
                paged_attn=route)
            attn, kv = attend(0, lp, x[None, t:t + 1],
                              {"k": cache["k"], "v": cache["v"]})
            cache = {**cache, **kv, "len": cache["len"] + 1}
            outs.append(attn.reshape(1, -1) @ lp["wo"])
        np.testing.assert_allclose(jnp.concatenate(outs), want, atol=2e-5)


def _grouped_operands(rows, heads, dh, lens, dtype):
    """Pools of ``rows`` rows of 128 lanes a token over two layers, the
    slots' blocks scattered, queries of ``heads`` heads of ``dh`` for
    lengths ``lens`` [slots, T]."""
    slots, page, wp = len(lens), 16, 136
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    shape = (2, 1 + slots * wp, page, rows, 128)
    k_pool = jax.random.normal(keys[0], shape, dtype)
    v_pool = jax.random.normal(keys[1], shape, dtype)
    q = jax.random.normal(keys[2], (slots, lens.shape[1], heads, dh), dtype)
    table = jnp.asarray(1 + np.random.default_rng(3).permutation(
        slots * wp).reshape(slots, wp).astype(np.int32))
    return q, k_pool, v_pool, table


# a group is 1024 tokens (64 pages); a slot that reads one token, a page and
# a bit, an eighth of a group and a bit, two groups and a bit, and nothing
ONE_QUERY = [[1], [21], [130], [2100], [0]]
# the block pass's shape (every row of a slot reads the slot's cache):
# exactly a group, a group and a token, several groups, nothing, a page
BLOCK_ROWS = [[1024] * 4, [1025] * 4, [2100] * 4, [0] * 4, [16] * 4]
# the verify shape (a slot's rows read one token more each): over a group's
# edge, inside the last of several groups, from nothing, inside a page
RISING = [[1022, 1023, 1024, 1025], [2047, 2048, 2049, 2050], [0, 1, 2, 3],
          [5, 6, 7, 8], [2173, 2174, 2175, 2176]]
GROUPED_CASES = [
    pytest.param(4, 32, 64, ONE_QUERY, jnp.float32, 1e-6, id="4-32-f32"),
    pytest.param(1, 4, 64, ONE_QUERY, jnp.float32, 1e-6, id="1-4-f32"),
    pytest.param(2, 8, 64, ONE_QUERY, jnp.float32, 1e-6, id="2-8-f32"),
    pytest.param(4, 8, 64, ONE_QUERY, jnp.float32, 1e-6, id="4-8-f32"),
    pytest.param(4, 32, 64, ONE_QUERY, jnp.bfloat16, 0.01, id="4-32-bf16"),
    pytest.param(4, 32, 128, BLOCK_ROWS, jnp.float32, 2e-6,
                 id="sdar-block-f32"),
    pytest.param(4, 32, 128, BLOCK_ROWS, jnp.bfloat16, 0.01,
                 id="sdar-block-bf16"),
    pytest.param(4, 32, 128, RISING, jnp.float32, 2e-6,
                 id="sdar-rising-f32"),
    pytest.param(4, 8, 64, RISING, jnp.float32, 1e-6, id="4-8-rising-f32"),
    pytest.param(2, 8, 64, RISING, jnp.float32, 1e-6, id="2-8-rising-f32"),
    pytest.param(1, 4, 64, RISING, jnp.float32, 1e-6, id="1-4-rising-f32"),
    pytest.param(1, 4, 64, BLOCK_ROWS, jnp.bfloat16, 0.01,
                 id="1-4-block-bf16"),
    pytest.param(2, 8, 64, RISING, jnp.bfloat16, 0.02, id="2-8-rising-bf16"),
]


@pytest.mark.parametrize("rows,heads,dh,lens,dtype,tol", GROUPED_CASES)
def test_grouped_kernel_equals_the_gather_route(
        rows, heads, dh, lens, dtype, tol):
    """``paged_decode_attention`` over pools of ``rows`` rows of 128 lanes
    a token (2 heads of 64 a row, or 1 of 128: SDAR's) against
    ``paged_causal_attention`` on the same operands: 1, 2 and 4 rows; 8
    queries a slot, fewer (padded to a sublane tile) and SDAR's 32 a pool
    row; one query a slot, a block's four at one length and a verify
    chunk's four at rising lengths; slots that read nothing, a token, a
    page, exactly a group, a group and a token, several groups and a bit;
    float32 pools (read with a stride as they are) and bfloat16 ones (read
    through their 32-bit view); a layer that is not the first; scattered
    blocks."""
    from vtpu.ops.attention import paged_causal_attention
    from vtpu.ops.decode_attn import paged_decode_attention

    lens = np.asarray(lens, np.int32)
    q, k_pool, v_pool, table = _grouped_operands(rows, heads, dh, lens, dtype)
    scale = dh ** -0.5
    want = paged_causal_attention(
        q, k_pool[1], v_pool[1], table, jnp.asarray(lens), scale=scale)
    got = paged_decode_attention(
        q, k_pool, v_pool, table, jnp.asarray(lens), layer=1, scale=scale)
    live = lens > 0  # a query that reads nothing has no answer
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[live], np.asarray(want, np.float32)[live],
        atol=tol)
    assert np.isfinite(np.asarray(got, np.float32)).all()


@pytest.mark.parametrize("rows,heads,dh,lens,dtype,tol", [
    pytest.param(4, 32, 128, BLOCK_ROWS, jnp.float32, 1e-5,
                 id="sdar-block-f32"),
    pytest.param(4, 32, 128, BLOCK_ROWS, jnp.bfloat16, 0.01,
                 id="sdar-block-bf16"),
    pytest.param(4, 32, 128, RISING, jnp.float32, 1e-5,
                 id="sdar-rising-f32"),
    pytest.param(4, 8, 64, ONE_QUERY, jnp.float32, 1e-5, id="4-8-f32"),
    pytest.param(2, 8, 64, RISING, jnp.float32, 1e-5, id="2-8-rising-f32"),
    pytest.param(1, 4, 64, ONE_QUERY, jnp.float32, 1e-5, id="1-4-f32"),
])
def test_grouped_kernels_log_sum_exp_is_the_gathered_windows(
        rows, heads, dh, lens, dtype, tol):
    """The walk's second output (``lse=True``: what ``blockdiff._joined``
    joins the own block's softmax by) against ``log(sum(exp(scores)))``
    made straight from the gathered window in float32, a query head at a
    time; about -1e30 where a query read nothing; the first output
    unchanged by asking for the second."""
    from vtpu.ops.attention import gather_kv_pages
    from vtpu.ops.decode_attn import paged_decode_attention

    lens = np.asarray(lens, np.int32)
    q, k_pool, v_pool, table = _grouped_operands(rows, heads, dh, lens, dtype)
    scale = dh ** -0.5
    got, sums = paged_decode_attention(
        q, k_pool, v_pool, table, jnp.asarray(lens), layer=1, scale=scale,
        lse=True)
    alone = paged_decode_attention(
        q, k_pool, v_pool, table, jnp.asarray(lens), layer=1, scale=scale)
    assert sums.shape == q.shape[:3] and sums.dtype == jnp.float32
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(alone, np.float32))
    slots, t = lens.shape
    window = np.asarray(gather_kv_pages(k_pool[1], table), np.float64)
    window = window.reshape(slots, window.shape[1], -1, dh)  # [B, S, Hk, Dh]
    grouped = np.asarray(q, np.float64).reshape(
        slots, t, window.shape[2], -1, dh)
    scores = np.einsum("btkgd,bskd->btkgs", grouped, window) * scale
    read = np.arange(window.shape[1]) < lens[:, :, None, None, None]
    with np.errstate(divide="ignore"):
        want = np.log(np.sum(np.exp(np.where(read, scores, -np.inf)), -1))
    live = lens > 0
    np.testing.assert_allclose(
        np.asarray(sums)[live], want.reshape(slots, t, heads)[live], atol=tol)
    assert (np.asarray(sums)[~live] < -1e29).all()


@pytest.mark.parametrize("rows,heads,dtype", [
    pytest.param(3, 12, jnp.bfloat16, id="3-rows-of-16-bit"),
    pytest.param(4, 8, jnp.float8_e4m3fn, id="4-rows-of-8-bit"),
])
def test_grouped_walk_refuses_rows_that_fill_no_whole_words(
        rows, heads, dtype):
    """A pool row's tokens are read with a stride, which Mosaic gives for
    32-bit data alone: a 16-bit pool goes through its 32-bit view, two pool
    rows a word, so an odd number of rows above one has no such read, and
    no 8-bit pool has one (four rows a word are not unpacked)."""
    from vtpu.ops.decode_attn import paged_decode_attention

    lens = np.asarray([[5], [600]], np.int32)
    q, k_pool, v_pool, table = _grouped_operands(
        rows, heads, 64, lens, jnp.float32)
    with pytest.raises(ValueError, match="whole words"):
        paged_decode_attention(
            q.astype(dtype), k_pool.astype(dtype), v_pool.astype(dtype),
            table, jnp.asarray(lens), layer=1, scale=0.125)


def test_grouped_walk_reads_any_number_of_32_bit_rows():
    """A 32-bit pool is read with a stride as it is: three rows a token."""
    from vtpu.ops.attention import paged_causal_attention
    from vtpu.ops.decode_attn import paged_decode_attention

    lens = np.asarray([[5], [600]], np.int32)
    q, k_pool, v_pool, table = _grouped_operands(3, 12, 64, lens, jnp.float32)
    want = paged_causal_attention(
        q, k_pool[1], v_pool[1], table, jnp.asarray(lens), scale=0.125)
    got = paged_decode_attention(q, k_pool, v_pool, table, jnp.asarray(lens),
                                 layer=1, scale=0.125)
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_the_walk_bench_runs_at_cut_down_shapes(tmp_path):
    """``benchmarks/paged_attn_walk_bench.py --tiny`` at the two shapes of
    the grouped walk (the table PERF.md's PR 44 entry chose the kernel's
    form with) runs on the CPU, the kernel interpreted, at a group length
    of its own asking, and holds its rows to the gather route's and its
    log-sum-exp to the gathered window's; its times there are no speeds."""
    import json
    import pathlib
    import subprocess
    import sys

    root = pathlib.Path(__file__).resolve().parent.parent
    out = tmp_path / "bench.json"
    done = subprocess.run(
        [sys.executable, str(root / "benchmarks/paged_attn_walk_bench.py"),
         "--tiny", "--shapes", "granite4096,sdar4096", "--groups", "64",
         "--out", str(out)],
        capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    granite, sdar = json.loads(out.read_text())
    assert (granite["shape"], sdar["shape"]) == ("granite4096", "sdar4096")
    assert granite["device"] == sdar["device"] == "cpu"
    assert "error" not in granite and "error" not in sdar
    assert (granite["queries_a_slot"], sdar["queries_a_slot"]) == (1, 4)
    assert granite["group_tokens"] == sdar["group_tokens"] == 64
    assert granite["max_abs_err"] < 1e-5 and sdar["max_abs_err"] < 1e-5
    assert sdar["max_abs_err_lse"] < 1e-5
    assert "max_abs_err_lse" not in granite


def test_the_state_bench_runs_at_a_cut_down_shape(tmp_path):
    """``benchmarks/ssm_state_bench.py --tiny`` (the loop PERF.md's PR 36
    entry chose the state kernel's tile with) runs on the CPU, the kernel
    interpreted over a stack of three layers, and holds the kernel's state
    and readout to ``_ssd_step``'s; its times there are no speeds."""
    import json
    import pathlib
    import subprocess
    import sys

    root = pathlib.Path(__file__).resolve().parent.parent
    out = tmp_path / "bench.json"
    done = subprocess.run(
        [sys.executable, str(root / "benchmarks/ssm_state_bench.py"),
         "--tiny", "--out", str(out)],
        capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(out.read_text())
    assert result["device"] == "cpu" and result["shape"][0] == 3
    kernel, xla = result["rows"]
    assert (kernel["route"], xla["route"]) == ("kernel", "_ssd_step")
    assert kernel["y_distance"] < 1e-5 and kernel["h_distance"] < 1e-5


# ---------------------------------------------------------- admission


def test_chunked_equals_whole_prompt_equals_reference(
        program, prompt, reference):
    """The same 16-token-aligned prompt admitted whole (a bucket of 32) and
    in two chunks: the same logits, pages and recurrent rows."""
    mc, params = program
    p = 32
    last_c, chunked = _chunked(mc, params, _fresh_state(mc), prompt, p)
    whole_logits, whole = M.hybrid_prefill_rows(
        params, mc, _fresh_state(mc), jnp.asarray(prompt[:p])[None],
        jnp.asarray([1]), jnp.asarray([p]))
    np.testing.assert_allclose(last_c, reference[p - 1], atol=F32_TOL)
    np.testing.assert_allclose(whole_logits[0], reference[p - 1],
                               atol=F32_TOL)
    for key in ("h", "conv"):
        np.testing.assert_allclose(chunked[key][:, 1], whole[key][:, 1],
                                   atol=2e-5)
    used = BLOCKS[:p // PAGE]
    for key in ("k", "v"):
        np.testing.assert_allclose(chunked[key][:, used], whole[key][:, used],
                                   atol=2e-5)


def test_padded_rows_take_the_state_at_each_true_len(program, prompt):
    """A batch of two rows of different lengths in one padded bucket: each
    row's recurrent rows and logits are those of the row admitted alone at
    its own length, not of the padded end."""
    mc, params = program
    lens = [19, 27]
    padded = np.zeros((2, 32), np.int32)
    for i, n in enumerate(lens):
        padded[i, :n] = prompt[:n]
    state = M.init_hybrid_state(mc, 3, PAGE, 40)
    state["table"] = state["table"].at[0, :4].set(jnp.asarray(BLOCKS[:4]))
    state["table"] = state["table"].at[2, :4].set(jnp.asarray(BLOCKS[4:8]))
    logits, both = M.hybrid_prefill_rows(
        params, mc, state, jnp.asarray(padded), jnp.asarray([0, 2]),
        jnp.asarray(lens))
    for i, (slot, n) in enumerate(zip((0, 2), lens)):
        exact = jnp.asarray(prompt[:n])[None]
        x, _, conv, h = M._fresh_rows(params, mc, exact, jnp.asarray([n]))
        np.testing.assert_allclose(
            logits[i], M._head(params, mc, x[0, n - 1]), atol=F32_TOL)
        np.testing.assert_allclose(both["h"][:, slot], h[:, 0], atol=2e-5)
        np.testing.assert_allclose(both["conv"][:, slot], conv[:, 0],
                                   atol=2e-5)
        assert int(both["len"][slot]) == n


def test_inactive_slots_rows_are_bit_equal_after_a_step(
        program, prompt, route):
    mc, params = program
    _, state = _chunked(mc, params, _fresh_state(mc), prompt, 21)
    state["h"] = state["h"].at[:, 2].set(0.625)
    before = jax.tree_util.tree_map(np.asarray, state)
    _, after = M.hybrid_decode_step(
        params, mc, state, jnp.asarray([3, 4, 5], jnp.int32),
        jnp.asarray([False, True, False]), WINDOW)
    for key in ("h", "conv"):
        for slot in (0, 2):
            np.testing.assert_array_equal(after[key][:, slot],
                                          before[key][:, slot])
        assert not np.array_equal(after[key][:, 1], before[key][:, 1])
    for key in ("k", "v"):  # and no page but the active slot's was written
        changed = np.flatnonzero(np.any(
            np.asarray(after[key]) != before[key], axis=(0, 2, 3, 4)))
        assert set(changed) <= {int(BLOCKS[21 // PAGE])}
    np.testing.assert_array_equal(after["len"], before["len"] + [0, 1, 0])
    assert route is None or (route and all(route))  # traced, interpreted


# -------------------------------------------------- through the engine


def _serving(**kw):
    base = dict(slots=4, kv_page=PAGE, kv_pool_blocks=60,
                prefill_buckets=(16,), prefill_batch_sizes=(1, 2),
                prefill_chunk=CHUNK, prefill_budget=32, max_new_tokens=12)
    base.update(kw)
    return ServingConfig(**base)


def _engine(program, **kw):
    mc, params = program
    serving = _serving(**kw)
    model = HybridSlotModel(
        params, mc, kv_page=PAGE, kv_pool_blocks=serving.kv_pool_blocks,
        read_windows=(32, 64, WINDOW))
    return ServingEngine(serving=serving, model=model)


def _serve(eng, prompts, new=8):
    reqs = [eng.submit(p, max_new_tokens=new) for p in prompts]
    return [list(r.stream()) for r in reqs]


@pytest.fixture(scope="module")
def requests():
    rng = np.random.default_rng(11)
    return [rng.integers(1, TOY["vocab_size"], n).astype(np.int32)
            for n in (5, 16, 23, 9, 40, 31, 12, 57)]


@pytest.fixture(scope="module")
def served_alone(program, requests):
    """Each request's tokens from an engine that serves nothing else."""
    eng = _engine(program)
    eng.start()
    try:
        return [_serve(eng, [p])[0] for p in requests]
    finally:
        eng.stop()


def test_engine_serves_the_reference_greedy_tokens(
        program, requests, served_alone, route):
    """Through ``submit()`` on the pipelined loop: the first token of each
    stream is the reference's argmax at the prompt's last position. With the
    state kernel forced (interpreted) every decode tick counts as its, and
    the streams are the XLA route's token for token."""
    served = served_alone[:3]
    if route is not None:
        eng = _engine(program)
        eng.start()
        try:
            served = [_serve(eng, [p])[0] for p in requests[:3]]
            stats = eng.stats()
        finally:
            eng.stop()
        assert route and all(route)
        assert stats["ssm_kernel_ticks"] == stats["decode_ticks"] > 0
        assert served == served_alone[:3]
    for p, got in zip(requests[:3], served):
        want = _reference(p)[-1]
        assert got[0] == int(np.argmax(want))
        assert len(got) == 8


def test_eight_concurrent_requests_equal_each_served_alone(
        program, requests, served_alone):
    """Whole-prompt buckets (batched by two) and chunked admissions under a
    prefill budget, four slots for eight requests, so slots are freed and
    given to new sessions while others decode."""
    eng = _engine(program)
    eng.start()
    try:
        got = _serve(eng, requests)
        stats = eng.stats()
    finally:
        eng.stop()
    assert got == served_alone
    assert stats["prefill_chunks"] > 0 and stats["admissions"] == 8
    mc = program[0]
    assert stats["recurrent_state_bytes"] == 4 * mc.recurrent_bytes_per_slot
    assert 0 < stats["ssm_rows_live"] < stats["ssm_rows_stepped"]
    assert stats["ssm_rows_stepped"] == 4 * stats["decode_ticks"]
    assert stats["ssm_kernel_ticks"] == 0  # off a TPU a step is XLA's code


def test_a_slot_given_to_a_new_session_equals_a_fresh_engines(
        program, requests, served_alone):
    """One slot: every session after the first starts in a slot whose rows
    and pages an earlier one left behind."""
    eng = _engine(program, slots=1, prefill_batch_sizes=(1,))
    eng.start()
    try:
        got = [_serve(eng, [p])[0] for p in requests[:4]]
    finally:
        eng.stop()
    assert got == served_alone[:4]


def test_recurrent_bytes_at_the_published_sizes():
    """75.5 MB of state and 0.94 MB of window a session, whatever its
    length; 8 KB of keys and values a token."""
    mc = M.HybridConfig(
        d_model=2048, layer_types=tuple((["mamba"] * 5 + ["attention"]
                                         + ["mamba"] * 4) * 4),
        n_heads=32, n_kv_heads=8, head_dim=64, ssm_heads=64, ssm_head_dim=64,
        ssm_state=128)
    assert mc.n_ssm_layers == 36 and mc.conv_dim == 4352
    assert mc.recurrent_bytes_per_slot == 36 * (64 * 64 * 128 * 4
                                                + 3 * 4352 * 2)
    from vtpu.models.transformer import kv_bytes_per_token, kv_plane_shape
    assert kv_bytes_per_token(mc.attention) == 8192
    assert kv_plane_shape(mc.attention) == (4, 128)


# ------------------------------------------------------------ refusals


@pytest.mark.parametrize("option, named", [
    (dict(spec_tokens=2), "spec_tokens"),
    (dict(kv_swap=0), "kv_swap"),
    (dict(disagg=object()), "disagg"),
])
def test_refused_serving_options_raise_by_name(program, option, named):
    with pytest.raises(ValueError, match=named):
        _engine(program, **option)


@pytest.mark.parametrize("call", ["register_prefix", "drain"])
def test_refused_operations_raise_by_name(program, call):
    eng = _engine(program)
    with pytest.raises(ValueError, match=f"HybridSlotModel cannot {call}"):
        if call == "register_prefix":
            eng.register_prefix(np.arange(1, 20, dtype=np.int32))
        else:
            eng.drain(eng)


def test_park_needs_the_swap_tier_it_refuses(program):
    eng = _engine(program)
    with pytest.raises(ValueError, match="kv_swap"):
        eng.park(object())


@pytest.mark.parametrize("kw, named", [
    (dict(mesh=object()), "mesh"),
    (dict(kv_page=None), "kv_page"),
    (dict(paged_attn="fast"), "paged_attn"),
    (dict(read_windows=(20,)), "read window"),
])
def test_refused_adapter_arguments_raise_by_name(program, kw, named):
    mc, params = program
    args = dict(kv_page=PAGE, kv_pool_blocks=20)
    args.update(kw)
    with pytest.raises(ValueError, match=named):
        HybridSlotModel(params, mc, **args)


def test_int8_cache_is_refused_by_name(program):
    mc, params = program
    with pytest.raises(ValueError, match="int8"):
        HybridSlotModel(params, dataclasses.replace(mc, kv_int8=True),
                        kv_page=PAGE)


def test_unknown_layer_kinds_and_groups_are_refused():
    with pytest.raises(ValueError, match="layer_types"):
        M.HybridConfig(layer_types=("mamba", "window"))
    with pytest.raises(ValueError, match="ssm_groups"):
        M.HybridConfig(ssm_groups=2)
