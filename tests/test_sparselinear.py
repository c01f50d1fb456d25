"""The family of block-sparse attention beside linear attention
(vtpu/models/sparselinear.py, ``SparseLinearSlotModel``, vtpu/ops/blocksparse.py,
the state kernel with a ``B`` / ``C`` row a head) at toy widths on the CPU,
against the benchmark's plain reference (vbench/reference/sparselinear.py:
float32, no cache, the selection a query row on its own, the linear
recurrence a scan over time) on the benchmark's own seeded weights: hidden
128, five layers (S L L S L), blocks of 8 tokens, compressed windows of 4
every 2, a local window of 2 blocks, top-5, ``dense_len`` 40, prefill
chunks of 16 and of 12 (which split a window and a block).

Tolerances, and why. Logits are compared with logits, the program's own
``scale_emb`` and divisor against ``reference.sparselinear.logits`` and its
``sparse_in`` layer. They spread by 0.25 and reach 0.9. With float32 on both
sides the two differ by the order of their sums (the chunked form against
the recurrence, blocks of attention): they agree to 3e-6 and 3e-5 is held
(``F32_TOL``). The same program in bfloat16 reads up to 0.031 off while no
query selects (``BF16_TOL`` 0.08 holds it, and it fails ``F32_TOL``,
asserted) and 0.012 in the mean over all rows (``BF16_MEAN_TOL``; single
rows past ``dense_len`` move by up to 0.34 where the two precisions rank
two blocks the other way round): the float32 limit tells a lower precision
apart, and so it tells a selection replaced by the most recent blocks and
a carry lost at a chunk's boundary (both over 100 times the limit).
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vbench import weights
from vbench.reference import sparselinear as ref
from vbench.sut import sparselinear as sut
from vtpu.models import hybrid
from vtpu.models import sparselinear as M
from vtpu.ops import blocksparse, ssm_step
from vtpu.serving import ServingConfig, ServingEngine
from vtpu.serving.adapters import SparseLinearSlotModel

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32_TOL = 3e-5
BF16_TOL = 0.08
BF16_MEAN_TOL = 0.03
SEED = 2**31 + 5
PAGE, WINDOW = 8, 128
MIXERS = ["minicpm4", "lightning-attn", "lightning-attn", "minicpm4",
          "lightning-attn"]

TOY = dict(
    family="sparselinear", hidden_size=128, intermediate_size=256,
    num_attention_heads=4, num_key_value_heads=2, head_dim=32,
    lightning_nh=4, lightning_nkv=4, lightning_head_dim=32,
    lightning_chunk=8, mixer_types=MIXERS, num_hidden_layers=5,
    residual_depth=32, layer_indices=[0, 5, 13, 20, 28], scale_emb=12, scale_depth=1.4, dim_model_base=32,
    rope_theta=10000, rms_norm_eps=1e-6, vocab_size=96,
    max_position_embeddings=WINDOW,
    sparse_config=dict(kernel_size=4, kernel_stride=2, block_size=8,
                       window_size=16, init_blocks=1, topk=5, dense_len=40),
    attn_use_rope=False, lightning_use_rope=True, qk_norm=True,
    use_output_gate=True, use_output_norm=True, attn_use_output_gate=True,
    tie_word_embeddings=False, dtype="float32", output_head="head")
BLOCKS = np.array([5, 9, 2, 7, 11, 3, 8, 12, 13, 14, 15, 16, 17, 18, 19, 20],
                  np.int32)


def _both_sides(cfg=TOY, dtype=jnp.float32):
    """(program config, program params) over the benchmark's weights."""
    w = weights.make_all(SEED, ref.weight_specs(cfg),
                         cfg["num_hidden_layers"], ref.layer_kinds(cfg))
    params = jax.tree_util.tree_map(
        lambda a: a.astype(dtype), sut.params_of(cfg, w))
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
        1, TOY["vocab_size"], 120).astype(np.int32)


@pytest.fixture(scope="module")
def reference(prompt):
    return _reference(prompt)


@pytest.fixture(scope="module")
def program():
    return _both_sides()


def _fresh_state(mc, slots=3):
    """A pool with slot 1 mapped to scattered blocks and junk in its rows:
    what an earlier session left behind."""
    state = M.init_sparselinear_state(mc, slots, PAGE, 40)
    state["table"] = state["table"].at[1].set(jnp.asarray(BLOCKS))
    state["s"] = state["s"].at[:, 1].set(3.0)
    return state


def _chunked(mc, params, state, toks, p, chunk=16, slot=1, fill=0):
    """toks[:p] into ``slot`` in ``chunk``-token chunks, the last padded
    with ``fill``: (logits [p, V], state)."""
    pad = -(-p // chunk) * chunk
    padded = np.full((1, pad), fill, np.int32)
    padded[0, :p] = toks[:p]
    fn = jax.jit(lambda st, c, off, new: M.sparselinear_prefill_chunk(
        params, mc, st, c, jnp.int32(slot), off, new, WINDOW,
        jnp.asarray(BLOCKS)))
    out = []
    for off in range(0, pad, chunk):
        logits, state = fn(state, jnp.asarray(padded[:, off:off + chunk]),
                           jnp.int32(off), jnp.int32(min(off + chunk, p)))
        out.append(np.asarray(logits[0]))
    return np.concatenate(out)[:p], state


def _decoded(mc, params, state, toks, p, paged_attn=None):
    """toks[p:] a token a step into slot 1 beside two inactive slots:
    (logits [S - p, V], state)."""
    step = jax.jit(lambda st, t, a: M.sparselinear_decode_step(
        params, mc, st, t, a, WINDOW, paged_attn=paged_attn))
    active = jnp.asarray([False, True, False])
    out = []
    for i in range(p, len(toks)):
        logits, state = step(
            state, jnp.asarray([0, toks[i], 7], jnp.int32), active)
        out.append(np.asarray(logits[1]))
    return np.stack(out), state


# ----------------------------------------------------- against the reference


def test_the_selection_engages_and_unforced_blocks_win_and_lose(prompt):
    """At the toy's sizes a late query sees 15 blocks, keeps 5, three of
    them forced: of the other twelve two win and ten lose, and which two
    differs between queries and between key/value heads."""
    cfg = TOY
    specs = ref.weight_specs(cfg)
    key = weights.seed_key(SEED)
    w = ref.map_leaves(weights.make_layer(key, specs, 3, "sparse"))
    x = weights.make_globals(key, specs)["embed"][
        jnp.asarray(prompt)].astype(jnp.float32) * 12
    from vbench.reference import common
    n = common.rms_norm(x, w["attn_norm"], 1e-6)
    q = common.rms_norm((n @ w["wq"]).reshape(-1, 4, 32), w["q_norm"], 1e-6)
    k = common.rms_norm((n @ w["wk"]).reshape(-1, 2, 32), w["k_norm"], 1e-6)
    sp = ref.sparse_config(cfg)
    pos = jnp.arange(100, 120)
    keep = np.asarray(ref.kept_blocks(q[100:], ref.compressed_keys(k, sp),
                                      pos, sp, 15))
    assert keep.shape == (20, 2, 15) and (keep.sum(-1) == 5).all()
    mine = np.asarray(pos) // 8
    for r in range(20):  # forced: block 0, the query's own and the one before
        assert keep[r, :, 0].all() and keep[r, :, mine[r]].all()
        assert keep[r, :, mine[r] - 1].all()
        assert not keep[r, :, mine[r] + 1:].any()
    free = [keep[r, :, 1:mine[r] - 1] for r in range(20)]
    assert all((f.sum(-1) == 2).all() for f in free)      # two win ...
    assert all((~f).sum(-1).min() >= 8 for f in free)     # ... the rest lose
    assert len({tuple(f[h][:10]) for f in free for h in range(2)}) > 4
    assert any((f[0] != f[1]).any() for f in free)


def test_full_forward_matches_the_reference(program, prompt, reference):
    mc, params = program
    got = np.asarray(M.sparselinear_forward(params, mc,
                                            jnp.asarray(prompt)[None])[0])
    assert np.abs(got - reference).max() < F32_TOL
    assert reference.std() > 0.1  # logits that say something


@pytest.mark.parametrize("p,chunk", [(53, 16), (70, 16), (97, 16), (70, 12)])
def test_prefill_then_decode_through_the_state(program, prompt, reference, p,
                                               chunk):
    """Chunks that end inside a compressed window and inside a block
    (chunks of 12 start there too), then a token a step: every logit of
    the prompt and of the decoded rest against the reference's full
    forward. The decode steps cross ``dense_len`` (40) only in the chunks;
    p = 53 starts decoding at 53 > 40: every step selects."""
    mc, params = program
    logits, state = _chunked(mc, params, _fresh_state(mc), prompt, p, chunk)
    assert np.abs(logits - reference[:p]).max() < F32_TOL
    out, state = _decoded(mc, params, state, prompt, p)
    assert np.abs(out - reference[p:]).max() < F32_TOL
    assert int(state["len"][1]) == len(prompt) and int(state["len"][0]) == 0


def test_decode_from_a_short_prompt_crosses_dense_len(program, prompt,
                                                      reference):
    """A prompt of 20 (whole-prompt admission into a bucket of 32), then
    100 steps: dense up to 40 tokens, selecting after, compressed keys
    written by the steps that complete a window."""
    mc, params = program
    state = _fresh_state(mc)
    padded = np.zeros((1, 32), np.int32)
    padded[0, :20] = prompt[:20]
    logits, state = jax.jit(lambda st: M.sparselinear_prefill_rows(
        params, mc, st, jnp.asarray(padded), jnp.asarray([1]),
        jnp.asarray([20])))(state)
    assert np.abs(np.asarray(logits[0]) - reference[19]).max() < F32_TOL
    out, _ = _decoded(mc, params, state, prompt, 20)
    assert np.abs(out - reference[20:]).max() < F32_TOL


def test_the_kernel_route_walks_the_selected_pages(program, prompt,
                                                   reference):
    """``paged_attn="kernel"``: the grouped walk (interpreted) over the
    selection's table a key/value head, against the gather route."""
    mc, params = program
    _, state = _chunked(mc, params, _fresh_state(mc), prompt, 97)
    out, _ = _decoded(mc, params, state, prompt[:104], 97,
                      paged_attn="kernel")
    assert np.abs(out - reference[97:104]).max() < F32_TOL


def test_bfloat16_fails_the_float32_tolerance(prompt, reference):
    mc, params = _both_sides(dtype=jnp.bfloat16)
    logits, state = _chunked(mc, params, _fresh_state(mc), prompt, 70)
    out, _ = _decoded(mc, params, state, prompt, 70)
    err = np.abs(np.concatenate([logits, out]) - reference)
    print("bf16 error: max", err.max(), "mean", err.mean(),
          "up to dense_len: max", err[:40].max())
    assert F32_TOL < err[:40].max() < BF16_TOL   # no query selects yet
    # past dense_len a score rounded to bfloat16 can rank two blocks the
    # other way round, and at the toy's sizes a block is a fifth of what a
    # query attends: single rows move by tenths, the mean stays small
    assert err.mean() < BF16_MEAN_TOL


def test_a_selection_of_the_most_recent_blocks_is_caught(
        program, prompt, reference, monkeypatch):
    """The benchmark's first planted fault (hack/sala_recent_selection.py)."""
    import importlib.util
    import pathlib

    spec = importlib.util.spec_from_file_location(
        "sala_recent", pathlib.Path(__file__).parent.parent
        / "hack/sala_recent_selection.py")
    hack = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(hack)
    monkeypatch.setattr(blocksparse, "block_scores", hack.recent)
    mc, params = program
    logits, state = _chunked(mc, params, _fresh_state(mc), prompt, 70)
    out, _ = _decoded(mc, params, state, prompt, 70)
    # up to dense_len nothing selects: the fault cannot show
    assert np.abs(logits[:40] - reference[:40]).max() < F32_TOL
    assert np.abs(logits[48:] - reference[48:70]).max() > 100 * F32_TOL
    assert np.abs(out - reference[70:]).max() > 100 * F32_TOL


def test_rows_lost_at_a_chunk_boundary_are_caught(program, prompt, reference,
                                                  monkeypatch):
    """The benchmark's second planted fault (hack/sala_lost_rows.py)."""
    spec = importlib.util.spec_from_file_location(
        "sala_lost_rows", os.path.join(ROOT, "hack", "sala_lost_rows.py"))
    hack = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(hack)
    monkeypatch.setattr(M, "sparselinear_prefill_chunk",
                        hack.lossy(M.sparselinear_prefill_chunk))
    mc, params = program
    logits, state = _chunked(mc, params, _fresh_state(mc), prompt, 70)
    out, _ = _decoded(mc, params, state, prompt, 70)
    assert np.abs(logits[:16] - reference[:16]).max() < F32_TOL
    assert np.abs(logits[16:] - reference[16:70]).max() > 100 * F32_TOL
    assert np.abs(out - reference[70:]).max() > 100 * F32_TOL


# ------------------------------------------------------ padding and idleness


def test_padding_and_idle_slots_leave_rows_and_planes_bit_for_bit(program,
                                                                  prompt):
    """A chunk's pads move no recurrent row and write no compressed key; a
    decode step moves nothing of an inactive slot."""
    mc, params = program
    _, a = _chunked(mc, params, _fresh_state(mc), prompt, 53, chunk=16)
    # the same chunks, the last one's eleven pads another token: the same
    # program on other pads, so every bit that differs is a pad's doing
    _, b = _chunked(mc, params, _fresh_state(mc), prompt, 53, chunk=16,
                    fill=77)
    assert (np.asarray(a["s"]) == np.asarray(b["s"])).all()
    assert (np.asarray(a["ck"]) == np.asarray(b["ck"])).all()
    assert int(b["len"][1]) == 53
    # 53 tokens complete the windows that end by 52: j <= 24 (tokens 48..51)
    ck = np.asarray(a["ck"])[:, BLOCKS]          # [Ls * Hk, 16, 4, D]
    flat = ck.reshape(ck.shape[0], -1, ck.shape[-1])
    assert np.abs(flat[:, :25]).min(axis=-1).min() > 0
    assert (flat[:, 25:] == 0).all()
    # an idle step: every plane and row of the other slots as it stood
    before = a
    step = jax.jit(lambda st, t, act: M.sparselinear_decode_step(
        params, mc, st, t, act, WINDOW))
    _, after = step(before, jnp.asarray([3, 4, 5], jnp.int32),
                    jnp.asarray([False, False, False]))
    for key in ("k", "v", "ck", "s", "len", "table"):
        assert (np.asarray(after[key]) == np.asarray(before[key])).all(), key
    _, after = step(before, jnp.asarray([3, 4, 5], jnp.int32),
                    jnp.asarray([False, True, False]))
    assert (np.asarray(after["s"][:, 0]) == np.asarray(before["s"][:, 0])).all()
    assert (np.asarray(after["s"][:, 1]) != np.asarray(before["s"][:, 1])).any()
    assert int(after["len"][1]) == 54 and int(after["len"][2]) == 0


def test_a_slot_given_to_a_new_session_keeps_nothing(program, prompt,
                                                     reference):
    mc, params = program
    _, state = _chunked(mc, params, _fresh_state(mc), prompt[::-1], 90)
    logits, _ = _chunked(mc, params, state, prompt, 70)
    assert np.abs(logits - reference[:70]).max() < F32_TOL


# -------------------------------------------------- the state step, a head


def _step_operands(b=3, h=4, p=32, n=32, seed=0):
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.standard_normal(s), jnp.float32)  # noqa: E731
    return (f(2, b, h, p, n), jnp.exp(-jnp.abs(f(b, h))), f(b, h, p),
            f(b, h, n), f(b, h, n))


@pytest.mark.parametrize("layer", [0, 1])
def test_the_state_kernel_takes_a_row_a_head(layer):
    """``ssm_state_step`` (interpreted) with B and C ``[B, H, N]`` against
    the recurrence written out; the other layer of the stack as it stood."""
    stack, decay, dx, bm, cm = _step_operands()
    y, new = ssm_step.ssm_state_step(stack, layer, decay, dx, bm, cm,
                                     interpret=True)
    want = (stack[layer] * decay[..., None, None]
            + dx[..., None] * bm[:, :, None, :])
    assert np.allclose(np.asarray(new[layer]), np.asarray(want), atol=1e-6)
    assert (np.asarray(new[1 - layer]) == np.asarray(stack[1 - layer])).all()
    assert np.allclose(np.asarray(y), np.einsum(
        "bhpn,bhn->bhp", np.asarray(want), np.asarray(cm)), atol=1e-4)


def test_the_state_kernel_equals_ssd_step_a_row_a_head():
    stack, decay, dx, bm, cm = _step_operands(seed=1)
    xs = dx[:, None]
    dt = jnp.ones(decay.shape, jnp.float32)[:, None]
    a = jnp.log(decay[0])
    decay = jnp.broadcast_to(jnp.exp(a), decay.shape)
    y_ref, h_ref = hybrid._ssd_step(xs, dt, a, bm[:, None], cm[:, None],
                                    stack[1])
    y, new = ssm_step.ssm_state_step(stack, 1, decay, dx, bm, cm,
                                     interpret=True)
    assert np.allclose(np.asarray(new[1]), np.asarray(h_ref), atol=1e-6)
    assert np.allclose(np.asarray(y), np.asarray(y_ref[:, 0]), atol=1e-4)


def test_a_later_layer_of_the_published_model_forgets_more_slowly():
    """The decay by hand: slopes 2^(-8 (h + 1) / H) times 1 - l / (L - 1)
    + 1e-5, ``l`` a layer's place in the published model; the program's
    table is the reference's, layer for layer."""
    mc = M.SparseLinearConfig(
        layer_types=("sparse", "linear", "linear", "sparse", "linear"),
        lin_heads=32, layer_index=(0, 4, 12, 16, 28))
    a = np.asarray(M.decay_log(mc))
    assert a.shape == (3, 32)
    assert np.isclose(a[0, 31], -(1 / 256) * (1 - 4 / 31 + 1e-5), rtol=1e-6)
    assert np.isclose(a[2, 0], -2 ** -0.25 * (1 - 28 / 31 + 1e-5), rtol=1e-6)
    # the slowest head keeps a token for 294 steps at layer 4, 2640 at 28
    assert round(-1 / a[0, 31]) == 294 and round(-1 / a[2, 31]) == 2645
    for row, at in zip(a, (4, 12, 28)):
        assert np.allclose(np.exp(row), np.asarray(ref.decay(32, at, 32)),
                           rtol=1e-6)
    # a stage that does not say where its layers lie holds the first ones
    assert M.SparseLinearConfig().layer_index == (0, 1, 2, 3, 4)
    with pytest.raises(ValueError, match="layer_index"):
        M.SparseLinearConfig(layer_index=(0, 4, 8))
    with pytest.raises(ValueError, match="layer_index"):
        M.SparseLinearConfig(layer_index=(0, 4, 8, 12, 32))
    with pytest.raises(ValueError, match="layer_indices"):
        ref.layer_kinds({**TOY, "layer_indices": [0, 5, 5, 20, 28]})


@pytest.mark.parametrize("t,chunk", [(24, 8), (21, 8), (5, 8)])
def test_the_chunked_form_with_a_row_a_head_equals_the_scan(t, chunk):
    """``_ssd_chunked`` with B and C ``[B, T, H, N]`` against the
    reference's scan over time, padded tokens (dt 0) moving nothing."""
    r = np.random.default_rng(2)
    b, h, d = 2, 4, 16
    q, k, v = (jnp.asarray(r.standard_normal((b, t, h, d)), jnp.float32)
               for _ in range(3))
    s0 = jnp.asarray(r.standard_normal((b, h, d, d)), jnp.float32)
    mc = M.SparseLinearConfig(layer_types=("sparse", "linear"), lin_heads=h,
                              layer_index=(0, 28))
    a = M.decay_log(mc)[0]
    real = jnp.arange(t)[None, :] < jnp.asarray([t, t - 3])[:, None]
    dt = jnp.broadcast_to(real[..., None], (b, t, h)).astype(jnp.float32)
    y, s = hybrid._ssd_chunked(v, dt, a, k, q, s0, chunk)
    for i, n in enumerate((t, t - 3)):
        # the reference keeps S [H, dk, dv]; the program h [H, P = dv, N = dk]
        want_o, want_s = ref.linear_scan(
            q[i, :n], k[i, :n], v[i, :n], ref.decay(h, 28, mc.depth),
            jnp.swapaxes(s0[i], 1, 2))
        assert np.allclose(np.asarray(y[i, :n]), np.asarray(want_o),
                           atol=2e-4)
        assert np.allclose(np.asarray(s[i]),
                           np.asarray(jnp.swapaxes(want_s, 1, 2)), atol=2e-4)


def test_a_step_forced_into_the_kernel_serves_the_same(program, prompt,
                                                       reference, monkeypatch):
    """On the CPU a step takes ``_ssd_step``; the kernel's route (one visit
    of the stacked rows, interpreted) is forced by patching the rule."""
    monkeypatch.setattr(M, "step_in_kernel", lambda t: t == 1)
    mc, params = program
    _, state = _chunked(mc, params, _fresh_state(mc), prompt, 97)
    out, _ = _decoded(mc, params, state, prompt[:101], 97)
    assert np.abs(out - reference[97:101]).max() < F32_TOL


# ----------------------------------------------------------- the selection


def test_selected_pages_are_sorted_and_end_with_the_query_own_block():
    mc = M.SparseLinearConfig(topk=3, dense_len=16, window_size=8)
    score = jnp.asarray([[[jnp.inf, 0.3, 0.9, 0.9, jnp.inf, -jnp.inf]],
                         [[jnp.inf, jnp.inf, -jnp.inf, -jnp.inf, -jnp.inf,
                           -jnp.inf]]])
    tables = jnp.asarray([[11, 12, 13, 14, 15, 16], [21, 22, 23, 24, 25, 26]])
    pages, lens = blocksparse.selected_pages(
        score, jnp.asarray([35, 9]), tables, mc, mc.n_sel)
    # the tie between blocks 2 and 3 goes to the lower index; the walk
    # reads two whole blocks and four tokens of the query's own
    assert pages[0, 0].tolist() == [11, 13, 15]
    assert int(lens[0, 0]) == 2 * 8 + 35 % 8 + 1
    # a query that sees 10 tokens attends whole: both blocks, no null page
    assert pages[1, 0].tolist() == [21, 22, 0] and int(lens[1, 0]) == 10


def test_kept_mask_is_top_k_with_ties_to_the_lower_index():
    mc = M.SparseLinearConfig(topk=3, dense_len=16)
    score = jnp.asarray([[[[jnp.inf, 0.5, 0.5, 0.5, jnp.inf, -jnp.inf]],
                          [[jnp.inf, 0.1, 0.7, jnp.inf, -jnp.inf,
                            -jnp.inf]]]])
    keep = blocksparse.kept_mask(score, jnp.asarray([[39, 9]]), mc)
    assert keep[0, 0, 0].tolist() == [True, True, False, False, True, False]
    # at most dense_len tokens visible: every block up to the query's own
    assert keep[0, 1, 0].tolist() == [True, True, False, False, False, False]


# ------------------------------------------------------- through the engine


def _engine(mc, params, **kw):
    model = SparseLinearSlotModel(params, mc, kv_page=PAGE,
                                  kv_pool_blocks=60,
                                  read_windows=(64, WINDOW))
    serving = ServingConfig(
        slots=3, kv_page=PAGE, kv_pool_blocks=60, prefill_buckets=(16,),
        prefill_batch_sizes=(1,), prefill_chunk=16, prefill_budget=32,
        max_new_tokens=40, **kw)
    return ServingEngine(serving=serving, model=model)


def test_the_engine_serves_the_reference_s_greedy_tokens(program, prompt):
    """``submit`` / ``stream`` over chunked admission and the pipelined
    loop: two sessions, one past ``dense_len`` at admission, one crossing
    it while it decodes; every served token is the reference's first."""
    mc, params = program
    eng = _engine(mc, params)
    eng.start()
    try:
        reqs = [eng.submit(prompt[:n], max_new_tokens=m)
                for n, m in ((70, 12), (30, 24))]
        served = [list(r.stream()) for r in reqs]
        stats = eng.stats()
    finally:
        eng.stop()
    for (n, m), got in zip(((70, 12), (30, 24)), served):
        assert len(got) == m
        toks = np.concatenate([prompt[:n], np.asarray(got[:-1], np.int32)])
        want = _reference(toks)[n - 1:].argmax(-1)
        assert got == want.tolist()
    assert stats["select_rows"] > 0 and stats["select_rows_dense"] > 0
    assert 0 < stats["attn_selected_tokens"] < stats["attn_visible_tokens"]
    assert stats["ssm_rows_live"] > 0 and stats["ssm_kernel_ticks"] == 0
    assert stats["recurrent_state_bytes"] == 3 * 3 * 4 * 32 * 32 * 4
    assert stats["loop_error"] is None


@pytest.mark.parametrize("what,kw", [
    ("spec_step", dict(spec_tokens=2)),
])
def test_serving_options_are_refused_by_name(program, what, kw):
    mc, params = program
    with pytest.raises(ValueError, match=what):
        _engine(mc, params, **kw)


def test_what_the_family_cannot_serve_is_refused_by_name(program):
    mc, params = program
    kw = dict(kv_page=PAGE, kv_pool_blocks=60)
    with pytest.raises(ValueError, match="no sharding rule"):
        SparseLinearSlotModel(params, mc, mesh=object(), **kw)
    with pytest.raises(ValueError, match="paged cache only"):
        SparseLinearSlotModel(params, mc)
    with pytest.raises(ValueError, match="selects whole pages"):
        SparseLinearSlotModel(params, mc, kv_page=16, kv_pool_blocks=60)
    with pytest.raises(ValueError, match="no int8 cache"):
        SparseLinearSlotModel(
            params, dataclasses.replace(mc, kv_int8=True), **kw)
    with pytest.raises(ValueError, match="paged_attn must be"):
        SparseLinearSlotModel(params, mc, paged_attn="walk", **kw)
    model = SparseLinearSlotModel(params, mc, **kw)
    assert "snapshot" in model.refuses["register_prefix"]
    assert "staging" in model.refuses["drain"]

    class Serving:
        spec_tokens, kv_swap, disagg = 0, None, None

    model.check_serving(Serving)
    for field, value, match in (("kv_swap", object(), "park or swap"),
                                ("disagg", object(), "slot-less prefill"),
                                ("spec_tokens", 2, "spec_step")):
        bad = type("S", (Serving,), {field: value})
        with pytest.raises(ValueError, match=match):
            model.check_serving(bad)
    eng = _engine(mc, params)
    with pytest.raises(ValueError, match="snapshot"):
        eng.register_prefix(np.arange(1, 17, dtype=np.int32))
