"""The latent family (vtpu/models/latent.py, vtpu/ops/latent.py, the held
share in vtpu/models/moe.py, ``LatentSlotModel``) at toy widths on the
CPU, against the benchmark's plain reference (vbench/reference/latent.py:
float32, no cache, keys and values expanded a head, the selection a mask)
on the benchmark's own seeded weights.

Tolerances, and why. With float32 on both sides the two differ by the
order of their sums: logits of size 4 agree to 1e-5, and 2e-4 is held
(``F32_TOL``). The same program in bfloat16 reads 0.02-0.2 off, which that
limit refuses (asserted below): it tells a lower precision apart.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vbench import weights
from vbench.reference import common
from vbench.reference import latent as ref
from vbench.sut import latent as sut
from vtpu.models import latent as M
from vtpu.models.moe import grouped_route, held_experts_ffn
from vtpu.ops import latent as L
from vtpu.ops.rope import yarn_inv_freq
from vtpu.serving import ServingConfig, ServingEngine
from vtpu.serving.adapters import LatentSlotModel

F32_TOL = 2e-4
SEED = 2**31 + 5
PAGE, CHUNK = 8, 16

TOY = dict(
    family="latent", hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_attention_heads=4, q_lora_rank=48,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    index_n_heads=4, index_head_dim=16, index_topk=16, n_routed_experts=4,
    n_routed_experts_published=16, held_experts_first=4,
    num_experts_per_tok=4, n_group=4, topk_group=2, n_shared_experts=1,
    routed_scaling_factor=2.5, first_k_dense_replace=1, num_hidden_layers=3,
    vocab_size=96, max_position_embeddings=128, rope_theta=10000,
    rope_scaling=dict(beta_fast=32, beta_slow=1, factor=40, mscale=1,
                      mscale_all_dim=1, original_max_position_embeddings=16,
                      type="yarn"),
    rms_norm_eps=1e-6, dtype="float32", output_head="lm_head")


def _both_sides(cfg, dtype=jnp.float32):
    """(program config, program params) over the benchmark's weights."""
    w = weights.make_all(SEED, ref.weight_specs(cfg),
                         cfg["num_hidden_layers"], ref.layer_kinds(cfg))
    params = jax.tree_util.tree_map(
        lambda a: a if a.dtype == jnp.float32 and dtype != jnp.float32
        and a.ndim <= 2 and a.shape[-1] == cfg["n_routed_experts_published"]
        else a.astype(dtype), sut.params_of(cfg, w))
    return sut.model_config(cfg, dtype), params


def _reference(cfg, toks, given=None):
    """(logits [S, V], each layer's selection [S, S]) of the plain
    reference; ``given`` masks take the selections' place."""
    specs = ref.weight_specs(cfg)
    key = weights.seed_key(SEED)
    g = weights.make_globals(key, specs)
    x = g["embed"][jnp.asarray(toks)].astype(jnp.float32)
    masks = []
    for l, kind in enumerate(ref.layer_kinds(cfg)):
        w = weights.make_layer(key, specs, l, kind)
        masks.append(np.asarray(ref.selection(cfg, w, x)))
        x = ref.layer(cfg, w, x, "f32", kind,
                      None if given is None else jnp.asarray(given[l]))
    return np.asarray(common.head(cfg, g, x, "f32")), masks


def _as_indices(mask, k):
    """[S, S] bool -> [1, S, k] positions: a row's chosen, then the last
    position, which no earlier row may see and so does not count (the last
    row itself has its k chosen)."""
    s = mask.shape[0]
    return np.stack([np.concatenate(
        [np.flatnonzero(mask[t]), np.full(k, s - 1)])[:k]
        for t in range(s)]).astype(np.int32)[None]


def _as_mask(idx, s):
    """The program's selection as [S, S] bool (visible only): [1, S, K]
    indices from the gathering route, [1, S, W] bool from the masking."""
    if idx.dtype == bool:
        return idx[0, :, :s]
    mask = np.zeros((s, s), bool)
    for t in range(s):
        mask[t, idx[0, t][idx[0, t] <= t]] = True
    return mask


@pytest.fixture(scope="module")
def prompt():
    return np.random.default_rng(3).integers(
        1, TOY["vocab_size"], 64).astype(np.int32)


@pytest.fixture(scope="module")
def reference(prompt):
    return _reference(TOY, prompt)


def _through_the_pools(mc, params, toks, p, given=None):
    """Chunked prefill of toks[:p] into scattered pool blocks, then decode
    of the rest a token a step through the page table: logits [S - p + 1,
    V] at the positions p - 1 .. S - 1."""
    s = len(toks)
    state = M.init_latent_cache(mc, 2, PAGE, 40)
    blocks = np.array([5, 9, 2, 7, 11, 3, 8, 12, 13, 14, 15, 16, 17, 18, 19,
                       20], np.int32)
    state["table"] = state["table"].at[1].set(jnp.asarray(blocks))
    pad = -(-p // CHUNK) * CHUNK
    padded = np.zeros((1, pad), np.int32)
    padded[0, :p] = toks[:p]
    chunk = jax.jit(M.latent_prefill_chunk, static_argnums=(1, 7))
    for off in range(0, pad, CHUNK):
        window = 32 if off + CHUNK <= 32 else 64
        ids = blocks[:window // PAGE]
        sel = None if given is None else [g[:, off:off + CHUNK] for g in given]
        logits, state = chunk(
            params, mc, state, padded[:, off:off + CHUNK], jnp.int32(1),
            jnp.int32(off), jnp.int32(min(off + CHUNK, p)), window, ids, sel)
    out = [np.asarray(logits[0, (p - 1) - (pad - CHUNK)])]
    step = jax.jit(M.latent_decode_step, static_argnums=(1, 5))
    for t in range(p, s):
        sel = None if given is None else [g[:, t:t + 1] for g in given]
        sel = None if sel is None else [
            jnp.concatenate([jnp.zeros_like(x), x]) for x in sel]
        logits, state = step(
            params, mc, state, jnp.asarray([0, toks[t]], jnp.int32),
            jnp.asarray([False, True]), 64, sel)
        out.append(np.asarray(logits[1]))
    assert state["len"].tolist() == [0, s]
    return np.stack(out)


def test_full_forward_agrees_with_the_reference(prompt, reference):
    want, masks = reference
    mc, params = _both_sides(TOY)
    got, selected = M.latent_forward(params, mc, jnp.asarray(prompt)[None])
    assert np.abs(np.asarray(got[0]) - want).max() < F32_TOL
    # 64 positions against an index_topk of 16: the selection drops tokens
    assert all(m[-1].sum() == 16 and m[40].sum() == 16 for m in masks)
    for l, idx in enumerate(selected):
        assert (_as_mask(np.asarray(idx), 64) == masks[l]).all(), l


def test_chunked_prefill_then_decode_through_the_pools(prompt, reference):
    want, _ = reference
    mc, params = _both_sides(TOY)
    got = _through_the_pools(mc, params, prompt, 40)
    assert np.abs(got - want[39:]).max() < F32_TOL


def test_bfloat16_in_float32s_place_fails_that_tolerance(prompt, reference):
    """... and the overlap of the two sides' own selections is printed,
    with what bfloat16 reads given the reference's selection."""
    want, masks = reference
    mc, params = _both_sides(TOY, jnp.bfloat16)
    got, selected = M.latent_forward(params, mc, jnp.asarray(prompt)[None])
    own = np.abs(np.asarray(got[0]) - want).max()
    assert own > 10 * F32_TOL
    for l, idx in enumerate(selected):
        mine = _as_mask(np.asarray(idx), 64)
        print(f"layer {l}: {(mine & masks[l]).sum()} of {masks[l].sum()} "
              "selected positions are the reference's")
        assert (mine & masks[l]).sum() > 0.8 * masks[l].sum()
    given = [jnp.asarray(_as_indices(m, 16)) for m in masks]
    held, _ = M.latent_forward(params, mc, jnp.asarray(prompt)[None], given)
    want_given, _ = _reference(TOY, prompt, masks)
    assert np.abs(want_given - want).max() < 1e-5  # its own selection, given
    print(f"bfloat16 against the reference: {own:.4f} selecting for itself, "
          f"{np.abs(np.asarray(held[0]) - want).max():.4f} given the "
          "reference's selection")
    # no limit on that second number: at this size one rounding that swaps
    # a routed expert (4 of 16 chosen, 4 held) moves a logit by 1, whichever
    # route read the cache (0.13 gathering, 1.0 masking, the same selection)
    assert np.isfinite(np.asarray(held[0])).all()


def test_the_selection_given_is_tight_through_the_pools(prompt, reference):
    want, masks = reference
    mc, params = _both_sides(TOY)
    given = [jnp.asarray(_as_indices(m, 16)) for m in masks]
    got = _through_the_pools(mc, params, prompt, 40, given)
    assert np.abs(got - want[39:]).max() < F32_TOL


def test_absorbed_attention_equals_the_expanded_form():
    """Scores against the latent through the key up-projection, and the
    mix of latents through the value up-projection, are the expanded
    form's numbers: keys and values made a head from every latent."""
    rng = np.random.default_rng(0)
    n, t, h, r, dr, dn, dv, k = 2, 3, 4, 32, 8, 16, 16, 10
    q_nope = rng.standard_normal((n, t, h, dn), np.float32)
    q_pe = rng.standard_normal((n, t, h, dr), np.float32)
    rows = rng.standard_normal((n, t, k, r + dr), np.float32)
    wkv_b = rng.standard_normal((r, h, dn + dv), np.float32) / math.sqrt(r)
    valid = rng.random((n, t, k)) < 0.8
    valid[..., 0] = True
    scale = 0.3
    q_abs = np.einsum("nthd,rhd->nthr", q_nope, wkv_b[..., :dn])
    mixed = L.latent_attention(jnp.asarray(q_abs), jnp.asarray(q_pe),
                               jnp.asarray(rows), jnp.asarray(valid), scale)
    got = np.einsum("nthr,rhv->nthv", np.asarray(mixed), wkv_b[..., dn:])
    kv = np.einsum("ntkr,rhd->ntkhd", rows[..., :r], wkv_b)
    s = (np.einsum("nthd,ntkhd->nthk", q_nope, kv[..., :dn])
         + np.einsum("nthd,ntkd->nthk", q_pe, rows[..., r:])) * scale
    s = np.where(valid[:, :, None, :], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("nthk,ntkhv->nthv", p, kv[..., dn:])
    assert np.abs(got - want).max() < 1e-4


def _layer_inputs(rng, n, t, stored, given):
    """Random float32 planes, tables and queries of one layer at toy
    widths: 4 heads, rank 32 + 8 rotated (rows stored ``stored`` wide), a
    window of 64 positions in pages of 8, the queries its last ``t``."""
    h, r, dr, dn, dv, hi, di, page, w = 4, 32, 8, 16, 16, 4, 16, 8, 64

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape, np.float32))

    tables = jnp.asarray(
        1 + rng.permutation(n * w // page).reshape(n, w // page), jnp.int32)
    blocks = 1 + n * w // page
    positions = jnp.broadcast_to(jnp.arange(w - t, w, dtype=jnp.int32), (n, t))
    idx = None
    if given:  # 12 positions a query, those past its own do not count
        idx = jnp.asarray(np.stack([[rng.choice(w, 12, replace=False)
                                     for _ in range(t)] for _ in range(n)]),
                          jnp.int32).at[..., 0].set(positions)
    return dict(
        ckv=normal(2, blocks, page, stored), ik=normal(2, blocks, page, di),
        l=1, tables=tables, positions=positions, q_nope=normal(n, t, h, dn),
        q_pe=normal(n, t, h, dr), w_uk=normal(h, dn, r) / math.sqrt(r),
        w_uv=normal(h, r, dv) / math.sqrt(r), q_idx=normal(n, t, hi, di),
        w_idx=normal(n, t, hi), topk=12, scale=0.3, given=idx)


@pytest.mark.parametrize("stored,budget,given,tiles", [
    (40, None, False, (4, 32)),      # all whole, the threshold's selection
    (128, None, False, (4, 32)),     # ... a row stored wider than it is read
    (128, None, True, (4, 32)),      # ... a selection given
    (40, 1 << 15, False, (2, 32)),   # the heads two at a time
    (128, 1 << 13, False, (1, 16)),  # a head at a time, its queries in two
    (40, 1 << 13, True, (1, 16)),    # ... the same under a selection given
])
def test_a_chunks_expanded_form_equals_its_absorbed_form(
        monkeypatch, stored, budget, given, tiles):
    """The masking route in its two forms on one mask, float32: keys and
    values made from the window (the scores made twice, the quotient on
    the values' side) against ``masked_latent_attention`` through
    ``w_uv``. The budgets cut the selection into blocks of queries, the
    expanded form into groups of heads (and then blocks of queries) and
    the absorbed form into blocks of queries, as the cell's windows do
    (a chunk of 512 over 32 k: 8 heads a group, all its queries)."""
    if budget is not None:
        monkeypatch.setattr(L, "_BLOCK_BYTES", budget)
        monkeypatch.setattr(L, "_SCORE_BYTES", budget // 2)
    kw = _layer_inputs(np.random.default_rng(5), 2, 32, stored, given)
    got = {}
    for form, expand in (("absorbed", False), ("expanded", True)):
        monkeypatch.setattr(L, "expands_window", lambda *a, e=expand: e)
        got[form] = L.sparse_latent_attention(**kw)
    assert L._expanded_tiles(2, 32, 4, 64) == tiles
    (want, keep), (values, mask) = got["absorbed"], got["expanded"]
    assert values.shape == want.shape == (2, 32, 4, 16)
    assert (np.asarray(mask) == np.asarray(keep)).all()
    assert np.asarray(keep).sum(-1).max() == 12
    assert np.abs(np.asarray(values - want)).max() < 1e-4


def test_the_chunk_attention_bench_runs_at_a_cut_down_shape(tmp_path):
    """``benchmarks/latent_chunk_attn_bench.py --tiny`` (the table PERF.md's
    PR 34 entry chose the form with, and PR 38's the kernel) runs on the
    CPU and holds its two forms to each other in float32; its times there
    are no speeds."""
    import json
    import pathlib
    import subprocess
    import sys

    root = pathlib.Path(__file__).resolve().parent.parent
    out = tmp_path / "bench.json"
    done = subprocess.run(
        [sys.executable, str(root / "benchmarks/latent_chunk_attn_bench.py"),
         "--tiny", "--out", str(out)], capture_output=True, text=True,
        timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    got = json.loads(out.read_text())
    assert got["device"]["platform"] == "cpu"
    assert got["float32_highest_max_abs_diff"] < 1e-4
    assert got["rows"][0]["expanded_tiles"] == [8, 64]
    # the kernel's rows (interpreted): both masks, the chunk at the window's
    # end and at three quarters of it, each against the expanded form
    kernel = [r for r in got["rows"] if "mask" in r]
    assert [(r["mask"], r["chunk_end"]) for r in kernel] == [
        ("selection", 256), ("causal", 256), ("selection", 192),
        ("causal", 192)]
    assert all(r["kernel_64x2"]["max_abs_diff"] < 0.02 for r in kernel)


@pytest.mark.parametrize("window,heads", [
    (4096, 64), (8192, 32), (16384, 16), (24576, 8), (32768, 8)])
def test_the_expanded_forms_tiles_at_the_cells_windows(window, heads):
    """A chunk of 512 over each read window of `dsv32_longctx`: all its
    queries in one block, as many of the 128 heads a group as keep the
    group's scores under 512 MB as float32."""
    assert L._expanded_tiles(1, 512, 128, window) == (heads, 512)
    assert 512 * heads * window * 4 <= L._BLOCK_BYTES


@pytest.mark.parametrize("queries,expanded", [
    (1, False), (128, False), (170, False), (171, True), (256, True),
    (512, True)])
def test_the_form_follows_the_queries_at_the_published_widths(
        queries, expanded):
    """Rank 512, 128 + 128 a head: a window position and head costs
    ``t * 1088`` multiply-adds absorbed and ``131072 + t * 320``
    expanded, so the line lies between 170 and 171 queries: the step and
    a short chunk absorbed, the whole-prompt bucket of 256 and the
    engine's chunk of 512 expanded."""
    assert L.expands_window(queries, 512, 128, 128) is expanded
    absorbed = queries * ((512 + 64) + 512)
    made = 512 * (128 + 128) + queries * ((128 + 64) + 128)
    assert (made < absorbed) is expanded


def test_yarn_frequencies_against_hand_computed_values():
    """dim 64, base 10000, factor 40 over 4096, beta 32 / 1: the pair that
    turns 32 times in 4096 positions is 64 ln(4096 / 64 pi) / (2 ln 1e4) =
    10.47, the one that turns once 22.51: pairs 0-10 as trained, 23-31
    divided by 40, a linear blend over 10..23 between."""
    inv = np.asarray(yarn_inv_freq(64, 10000.0, 40.0, 4096, 32.0, 1.0))
    plain = [10000.0 ** (-i / 32) for i in range(32)]
    assert math.floor(64 * math.log(4096 / (32 * 2 * math.pi))
                      / (2 * math.log(10000.0))) == 10
    assert math.ceil(64 * math.log(4096 / (2 * math.pi))
                     / (2 * math.log(10000.0))) == 23
    for i in (0, 5, 10):
        assert inv[i] == pytest.approx(plain[i], rel=1e-6)
    for i in (23, 27, 31):
        assert inv[i] == pytest.approx(plain[i] / 40, rel=1e-6)
    for i in (11, 16, 22):
        ramp = (i - 10) / 13
        assert inv[i] == pytest.approx(
            plain[i] * (1 - ramp) + plain[i] / 40 * ramp, rel=1e-5)
    assert np.asarray(ref.yarn_inv_freq(dict(
        qk_rope_head_dim=64, rope_theta=10000.0, rope_scaling=dict(
            factor=40, original_max_position_embeddings=4096, beta_fast=32,
            beta_slow=1)))) == pytest.approx(inv, rel=1e-6)
    mc = M.LatentConfig()
    m = 0.1 * math.log(40) + 1
    assert mc.attn_scale == pytest.approx(24 ** -0.5 * m * m)


def test_routing_against_a_straight_line_computation():
    """Groups, bias, renormalisation and scale, a token at a time."""
    rng = np.random.default_rng(1)
    t, d, e, k, groups, kept, scale = 24, 32, 16, 4, 4, 2, 2.5
    x = rng.standard_normal((t, d)).astype(np.float32)
    w = (rng.standard_normal((d, e)) / math.sqrt(d)).astype(np.float32)
    bias = (rng.standard_normal(e) * 0.2).astype(np.float32)
    got = np.asarray(grouped_route(jnp.asarray(w), jnp.asarray(bias),
                                   jnp.asarray(x), k, groups, kept, scale))
    for i in range(t):
        g = 1 / (1 + np.exp(-(x[i].astype(np.float64) @ w)))
        choice = g + bias
        per = e // groups
        score = [np.sort(choice[j * per:(j + 1) * per])[-2:].sum()
                 for j in range(groups)]
        best = np.argsort(score)[-kept:]
        open_ = [j for j in range(e) if j // per in best]
        chosen = sorted(open_, key=lambda j: choice[j])[-k:]
        want = np.zeros(e)
        want[chosen] = g[chosen] / g[chosen].sum() * scale
        assert got[i] == pytest.approx(want, abs=1e-5), i
        assert (got[i] > 0).sum() == k
    assert (got.sum(-1) == pytest.approx(scale, rel=1e-5))


def test_the_shares_add_up_to_the_uncut_layer():
    """16 shares of one expert each: what each holder's expert layer adds
    (its routed part, the shared expert left out), summed, with the shared
    expert counted once, is the uncut layer; the uncut layer is the
    reference's with every expert held."""
    cfg = dict(TOY, n_routed_experts=16, held_experts_first=0)
    mc, params = _both_sides(cfg)
    lp = jax.tree_util.tree_map(lambda a: a[0], params["sparse"])
    x = jnp.asarray(np.random.default_rng(2).standard_normal(
        (1, 40, 64)).astype(np.float32))
    whole = M._sparse_ffn(mc, lp, x)
    n = M.rms_norm(x, lp["mlp_norm"], mc.eps).reshape(-1, 64)
    shared = M._swiglu(n, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    total = x + shared.reshape(x.shape)
    for i in range(16):
        share = dict(lp, **{k: lp[k][i:i + 1]
                            for k in ("w_gate", "w_up", "w_down")})
        one = M._sparse_ffn(M.dataclasses.replace(mc, held=(i, 1)), share, x)
        total = total + (one - x - shared.reshape(x.shape))
    assert np.abs(np.asarray(total - whole)).max() < 1e-5
    gates = grouped_route(lp["router"], lp["route_bias"], n, 4, 4, 2, 2.5)
    assert np.abs(np.asarray(
        held_experts_ffn(lp, n, gates) + shared
        - (whole - x).reshape(-1, 64))).max() < 1e-5
    key, specs = weights.seed_key(SEED), ref.weight_specs(cfg)
    w = weights.make_layer(key, specs, 1, "sparse")
    n_ref = common.rms_norm(x[0], w["mlp_norm"], 1e-6)
    want = ref.route_gates(cfg, w, n_ref, "f32")
    assert np.abs(np.asarray(gates - want)).max() < 1e-5


def _engine(mc, params, chunk=CHUNK, **kw):
    serving = ServingConfig(slots=3, prefill_buckets=(16,), max_new_tokens=8,
                            kv_page=PAGE, kv_pool_blocks=40,
                            prefill_chunk=chunk, **kw)
    model = LatentSlotModel(params, mc, kv_page=PAGE, kv_pool_blocks=40,
                            read_windows=(16, 32, 64))
    return ServingEngine(serving=serving, model=model)


def test_staggered_streams_through_the_engine_equal_single_streams(reference):
    """Submitted through ``ServingEngine.submit`` a moment apart (whole
    prompt, chunked, chunked into the widest window), each stream is token
    for token what it is alone, and what the reference puts first."""
    import time

    mc, params = _both_sides(TOY)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, 96, n).astype(np.int32) for n in (40, 9, 70)]
    eng = _engine(mc, params)
    assert eng._kv_buckets == (16, 32, 64, 128)
    eng.start()
    try:
        reqs = []
        for p in prompts:
            reqs.append(eng.submit(p, max_new_tokens=10))
            time.sleep(0.05)
        together = [list(r.stream()) for r in reqs]
        alone = [list(eng.submit(p, max_new_tokens=10).stream())
                 for p in prompts]
        stats = eng.stats()
    finally:
        eng.stop()
    assert together == alone and all(len(o) == 10 for o in together)
    for p, out in zip(prompts, together):
        toks = np.concatenate([p, np.asarray(out[:-1], np.int32)])
        logits, _ = _reference(TOY, toks)
        assert logits[len(p) - 1:].argmax(-1).tolist() == out
    assert stats["loop_error"] is None
    # 16 of what a tick sees is read once a stream is past index_topk
    assert 0 < stats["attn_selected_tokens"] < stats["attn_visible_tokens"]
    assert stats["kv_hbm_bytes"]["paged"] == 41 * PAGE * 3 * (128 + 16) * 4


@pytest.mark.parametrize("family,chunk,expanded", [
    ("latent", 16, False), ("latent", 64, True), ("dense", 8, False)])
def test_the_engine_counts_its_chunks_by_the_form_of_their_attention(
        family, chunk, expanded):
    """``chunk_attn_launches`` is every chunk dispatched for a model that
    selects, ``chunk_attn_expanded`` those whose length the shape rule
    expands (toy widths: more than 32 queries); a dense model counts its
    chunks too since PR 45 (its window holds heads: none expands)."""
    if family == "latent":
        mc, params = _both_sides(TOY)
        eng = _engine(mc, params, chunk)
        assert eng.model.chunk_attn_expands(chunk) is expanded
        assert L.expands_window(chunk, mc.kv_rank, mc.nope_dim,
                                mc.v_dim) is expanded
    else:
        from vtpu.models import ModelConfig, init_params
        cfg = ModelConfig(vocab=64, d_model=32, n_heads=2, n_layers=1,
                          d_ff=64, max_seq=128, head_dim=16,
                          dtype=jnp.float32, use_pallas=False)
        eng = ServingEngine(
            init_params(jax.random.key(0), cfg), cfg,
            ServingConfig(slots=2, prefill_buckets=(8,), max_new_tokens=4,
                          prefill_chunk=chunk))
    prompts = [np.random.default_rng(6).integers(1, 60, n).astype(np.int32)
               for n in (70, 100)]
    eng.start()
    try:
        for r in [eng.submit(p, max_new_tokens=3) for p in prompts]:
            assert len(list(r.stream())) == 3
        stats = eng.stats()
    finally:
        eng.stop()
    assert stats["loop_error"] is None
    chunks = sum(-(-len(p) // chunk) for p in prompts)
    assert stats["prefill_chunks"] == chunks
    assert stats["chunk_attn_launches"] == chunks
    assert stats["chunk_attn_expanded"] == (chunks if expanded else 0)


@pytest.mark.parametrize("what,match", [
    (dict(mesh=object()), "no mesh"),
    (dict(kv_page=None), "paged cache only"),
    (dict(read_windows=(20,)), "multiple of kv_page"),
])
def test_unsupported_construction_is_refused_by_name(what, match):
    mc = M.LatentConfig()
    with pytest.raises(ValueError, match=match):
        LatentSlotModel({}, mc, **{"kv_page": PAGE, **what})


@pytest.mark.parametrize("what,match", [
    (dict(spec_tokens=2), "spec_tokens=0"),
    (dict(kv_swap=4), "kv_swap=None"),
    (dict(paged_attn="kernel"), "built with paged_attn=None"),
])
def test_unsupported_serving_options_are_refused_by_name(what, match):
    mc = M.LatentConfig()
    params = M.init_latent_params(jax.random.key(0), mc)
    with pytest.raises(ValueError, match=match):
        _engine(mc, params, **what)


def test_init_params_serve_at_the_default_toy_size():
    """``init_latent_params`` and the default config: forward, then the
    same through a scratch pool of another page size."""
    mc = M.LatentConfig(dtype=jnp.float32)
    params = M.init_latent_params(jax.random.key(1), mc)
    toks = jnp.asarray(np.random.default_rng(5).integers(1, 256, (2, 48)),
                       jnp.int32)
    a, _ = M.latent_forward(params, mc, toks, page=8)
    b, _ = M.latent_forward(params, mc, toks, page=16)
    assert a.shape == (2, 48, 256)
    assert np.abs(np.asarray(a - b)).max() < 1e-5
