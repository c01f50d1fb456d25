"""Decoder-only transformer (LLaMA-style) in pure functional JAX.

TPU-first choices:
- layer parameters are STACKED along a leading axis and the layer loop is a
  single `lax.scan` -- one trace, one compiled body, no Python unrolling;
- bf16 params/activations, f32 softmax/normalization accumulators (MXU native);
- head_dim 128 so attention tiles land on the (8,128) vector lanes exactly;
- the KV cache is a static-shape ring buffer updated with dynamic_update_slice
  so decode steps compile once and reuse the executable.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from vtpu.ops import (
    scaled_normal, rms_norm, apply_rope, rope_angles, causal_attention,
    causal_attention_int8kv, flash_attention, paged_causal_attention,
    paged_causal_attention_int8kv,
)
from vtpu.ops import chunk_attn
from vtpu.ops.attention import FLASH_MIN_SEQ
from vtpu.ops.decode_attn import (
    paged_attn_route, paged_decode_attention, paged_decode_attention_int8kv,
)

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    vocab: int = 2048
    d_model: int = 512
    n_heads: int = 4
    n_layers: int = 4
    d_ff: int = 1408
    max_seq: int = 1024
    head_dim: int = 128
    dtype: Any = jnp.bfloat16
    use_pallas: bool = True
    # int8 KV cache with per-token-per-head f32 scales: halves the bytes the
    # bandwidth-bound decode step streams (1 + 4/head_dim bytes/elem vs 2 for
    # bf16) and doubles serving tenant density per HBM GiB. Off by default:
    # training and tests keep exact bf16 KV. The serving engine also accepts
    # "auto": resolved at engine construction via the measured router
    # (serving.engine.choose_kv_int8 — INT8_AB_r05 cells).
    kv_int8: bool | str = False
    # what a family with another attention states (None / True: the dense
    # block's own): key/value heads each shared by n_heads / n_kv_heads
    # query heads, no rotary positions, a softmax scale that is not
    # 1 / sqrt(head_dim), the norms' epsilon
    n_kv_heads: Optional[int] = None
    rotary: bool = True
    attn_scale: Optional[float] = None
    eps: float = 1e-6

    @property
    def qkv_dim(self) -> int:
        return self.n_heads * self.head_dim


def kv_heads(cfg) -> int:
    """Key/value heads of ``cfg``'s attention (its query heads unless it
    states fewer); getattr: MoEConfig shares this trunk."""
    return getattr(cfg, "n_kv_heads", None) or cfg.n_heads


def kv_plane_shape(cfg) -> tuple[int, int]:
    """The two minor axes of a cache plane: (heads, head_dim), or, for
    grouped key/value heads narrower than a row of 128 lanes that fill
    whole rows, (rows, 128) with 128 // head_dim heads a row. The same
    bytes in the same order; the chip would pad a 64-wide minor axis to
    128 lanes, or lay the pool out otherwise and convert it around every
    kernel call, and the paged kernel walks rows of whole lanes."""
    h, dh = kv_heads(cfg), cfg.head_dim
    if h != cfg.n_heads and dh < 128 and 128 % dh == 0 and (h * dh) % 128 == 0:
        return (h * dh // 128, 128)
    return (h, dh)


def init_params(rng: jax.Array, cfg: ModelConfig) -> Params:
    """Scaled-normal init; per-layer tensors stacked on axis 0 for lax.scan."""
    keys = jax.random.split(rng, 8)
    d, f, l, qd = cfg.d_model, cfg.d_ff, cfg.n_layers, cfg.qkv_dim

    def w(key, shape, fan_in):
        return scaled_normal(key, shape, fan_in, cfg.dtype)

    return {
        "embed": w(keys[0], (cfg.vocab, d), d),
        "layers": {
            "wq": w(keys[1], (l, d, qd), d),
            "wk": w(keys[2], (l, d, qd), d),
            "wv": w(keys[3], (l, d, qd), d),
            "wo": w(keys[4], (l, qd, d), qd),
            "w_gate": w(keys[5], (l, d, f), d),
            "w_up": w(keys[6], (l, d, f), d),
            "w_down": w(keys[7], (l, f, d), f),
            "attn_norm": jnp.ones((l, d), cfg.dtype),
            "mlp_norm": jnp.ones((l, d), cfg.dtype),
        },
        "final_norm": jnp.ones((d,), cfg.dtype),
    }


def init_kv_cache(cfg: ModelConfig, batch: int) -> dict[str, jax.Array]:
    shape = (cfg.n_layers, batch, cfg.max_seq) + kv_plane_shape(cfg)
    if kv_quantized(cfg):
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(shape[:-1], jnp.float32),
            "v_scale": jnp.zeros(shape[:-1], jnp.float32),
            "len": jnp.zeros((batch,), jnp.int32),
        }
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
        "len": jnp.zeros((batch,), jnp.int32),
    }


def init_paged_kv_cache(
    cfg: ModelConfig, slots: int, page: int, n_blocks: int
) -> dict[str, jax.Array]:
    """Paged KV pool state: logical sequences decoupled from physical KV.

    One shared block pool per k/v plane, [L, n_blocks, page, H, Dh] (int8
    caches carry [L, n_blocks, page, H] f32 scale pools alongside), plus a
    per-slot page table [slots, max_pages] int32 mapping slot b's logical
    page p to a pool block. All shapes static, so every executable stays
    compile-once exactly like the dense ring. Block 0 is the NULL block —
    the engine's allocator never hands it out; unmapped table entries point
    at it so out-of-window gathers and overflow writes land on one shared,
    always-masked block instead of another slot's memory.

    The payoff over init_kv_cache: a dense pool pins slots * max_seq tokens
    of HBM whether or not any sequence ever grows that long; a paged pool
    sized to EXPECTED live tokens holds more concurrent slots in the same
    bytes (oversubscription, with admission backpressure when the free list
    runs dry) and lets shared prompt prefixes map the same physical blocks
    read-only from many slots' tables.
    """
    if cfg.max_seq % page:
        raise ValueError(f"kv page {page} must divide max_seq {cfg.max_seq}")
    max_pages = cfg.max_seq // page
    shape = (cfg.n_layers, n_blocks, page) + kv_plane_shape(cfg)
    cache: dict[str, jax.Array] = {
        "table": jnp.zeros((slots, max_pages), jnp.int32),
        "len": jnp.zeros((slots,), jnp.int32),
    }
    if kv_quantized(cfg):
        cache.update({
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(shape[:-1], jnp.float32),
            "v_scale": jnp.zeros(shape[:-1], jnp.float32),
        })
    else:
        cache.update({
            "k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype),
        })
    return cache


def kv_bytes_per_token(cfg) -> int:
    """HBM bytes one cached token costs across all layers — the unit the
    paged-vs-dense capacity estimates in ServingEngine.stats() and the
    paged_kv_bench HBM budgets are denominated in."""
    per_plane = kv_heads(cfg) * cfg.head_dim
    if kv_quantized(cfg):
        # int8 values + per-token-per-head f32 scales, two planes
        per_layer = 2 * (per_plane * 1 + kv_heads(cfg) * 4)
    else:
        per_layer = 2 * per_plane * jnp.dtype(cfg.dtype).itemsize
    return cfg.n_layers * per_layer


def kv_quantized(cfg) -> bool:
    # getattr: MoEConfig and other families share this cache machinery
    return bool(getattr(cfg, "kv_int8", False))


def quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """[..., H, Dh] -> (int8 values, [..., H] f32 absmax/127 scales).

    Per-token-per-head symmetric scaling — the standard KV-cache quant: each
    head's token vector is scaled independently, so one outlier head cannot
    crush another's resolution. Scales stay f32 (4/Dh bytes per element —
    noise next to the 2x saved on values)."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-6) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale




@jax.named_scope("sample")
def sample_tokens(
    logits: jax.Array,
    keys: jax.Array,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    return_logprobs: bool = False,
) -> tuple[jax.Array, Optional[jax.Array], jax.Array]:
    """Batched on-device sampling: [B, vocab] f32 logits + [B] PRNG keys ->
    ([B] int32 tokens, [B] f32 logprobs or None, advanced [B] keys).

    All sampling config is STATIC, so a caller that closes over it and jits
    gets the whole chain fused into its decode step — the per-tick
    device->host transfer shrinks from B x vocab x 4 logit bytes to B x 4
    token bytes, which is what makes the serving engine's pipelined tick
    possible (the sampled array feeds the next dispatch device-resident).

    temperature == 0 is greedy (a bare argmax; keys unused and returned
    unchanged). Otherwise: temperature scaling, optional top-k cut (keep the
    k highest logits), optional nucleus cut (keep the smallest set whose
    probability mass reaches top_p; the top-1 token always survives), then
    EXACT categorical sampling over the filtered distribution via the
    Gumbel-max trick — argmax(logits + Gumbel noise) draws from
    softmax(logits) without materializing a CDF, and masked entries at -inf
    can never win. One key per slot: slot b's draw stream is independent of
    its neighbors, so admission order in other slots never perturbs it.
    Keys advance (split) once per call for every row, active or not.

    return_logprobs: also return log p(token) under the FINAL (filtered,
    temperature-scaled) distribution — what a serving API reports per
    streamed token.
    """
    v = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    if temperature <= 0.0:
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        lp = None
        if return_logprobs:
            lp = jnp.take_along_axis(
                jax.nn.log_softmax(logits, axis=-1), tok[:, None], axis=-1
            )[:, 0]
        return tok, lp, keys
    x = logits / temperature
    if top_k and top_k < v:
        kth = jax.lax.top_k(x, top_k)[0][:, -1:]
        x = jnp.where(x < kth, -jnp.inf, x)
    if top_p < 1.0:
        srt = jnp.sort(x, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(srt, axis=-1)
        mass_before = jnp.cumsum(probs, axis=-1) - probs
        # the top-1 column is kept unconditionally: at top_p <= 0 the mass
        # test alone keeps nothing (thresh = inf) and the whole row would
        # collapse to -inf
        keep = (mass_before < top_p).at[:, 0].set(True)
        thresh = jnp.min(
            jnp.where(keep, srt, jnp.inf), axis=-1, keepdims=True
        )
        x = jnp.where(x < thresh, -jnp.inf, x)
    split = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
    gumbel = jax.vmap(lambda k: jax.random.gumbel(k, (v,), jnp.float32))(
        split[:, 0]
    )
    tok = jnp.argmax(x + gumbel, axis=-1).astype(jnp.int32)
    lp = None
    if return_logprobs:
        lp = jnp.take_along_axis(
            jax.nn.log_softmax(x, axis=-1), tok[:, None], axis=-1
        )[:, 0]
    return tok, lp, split[:, 1]


# Sentinel a multi-tick device loop pads frozen slots' token output with:
# sampled ids are always >= 0 (argmax over the vocab), so -1 can never be a
# real token — the host trusts the per-slot counts, the sentinel just keeps
# the [B, k] matrix self-describing in dumps and tests.
LOOP_PAD_TOKEN = -1


def multi_tick_decode(
    decode_fn,
    sample_fn,
    k: int,
    eos_token: int,
    logprobs: bool,
    state,
    tokens: jax.Array,
    active: jax.Array,
    keys: jax.Array,
    cap: jax.Array,
):
    """Run ``k`` decode ticks inside ONE traced loop with on-device token
    feedback: the sampled token of inner tick i feeds inner tick i+1
    without ever visiting the host. This is the loop body the serving
    engine's device-resident decode loop (``ServingConfig.decode_loop_k``)
    compiles — the host tick tax (dispatch, fetch, deliver, bookkeeping)
    is then paid once per k tokens instead of once per token.

    ``decode_fn(state, tokens[B], active[B]) -> (logits[B, vocab], state)``
    is one tick of the family trunk (the caller closes over params /
    kv_bucket / unroll — dense, paged, int8 and MoE layouts all route
    through the same shared trunk, so the loop body IS the existing step).
    ``sample_fn(logits, keys) -> (tok, lp|None, keys)`` is the on-device
    sampler (sample_tokens with the config bound statically).

    Per-slot EARLY EXIT: a slot freezes in place the inner tick after it
    emits its cap'th token (``cap`` [B] int32 — its remaining budget,
    clamped to k by the caller) or an ``eos_token`` — its active lane goes
    False, so subsequent inner ticks mask its KV writes exactly like any
    inactive slot (dense: where-masked; paged: the out-of-range drop
    sentinel routes the write off every mapped block) and its cache length
    stops advancing. Frozen output columns hold LOOP_PAD_TOKEN.

    Under a paged pool the per-tick write address is derived ON DEVICE
    from the advancing length (``table[b, len // page]`` / ``len % page``
    — the PR-9 table-walk discipline), so the page-table row needs no host
    round trip between inner ticks; the host-replicated length mirror
    catches up at flush delivery.

    Returns ``(out [B, k] int32, counts [B] int32, carry [B] int32,
    lps [B, k] f32 | None, state, keys)``: ``out[b, :counts[b]]`` are the
    tokens slot b emits this flush (sentinel-padded above), ``carry`` is
    each slot's final sampled token — the device-resident feed for the
    NEXT flush's dispatch.
    """
    b = tokens.shape[0]
    out0 = jnp.full((b, k), LOOP_PAD_TOKEN, jnp.int32)
    lp0 = jnp.zeros((b, k if logprobs else 0), jnp.float32)
    bud0 = jnp.where(active, jnp.maximum(cap, 0), 0)

    def body(i, carry):
        state, tok, act, keys, bud, out, lps = carry
        logits, state = decode_fn(state, tok, act)
        nxt, lp, keys = sample_fn(logits, keys)
        out = out.at[:, i].set(jnp.where(act, nxt, LOOP_PAD_TOKEN))
        if logprobs:
            lps = lps.at[:, i].set(jnp.where(act, lp, 0.0))
        bud = bud - act.astype(jnp.int32)
        # the emitted token becomes the slot's pending feed; after a
        # freeze the lane is masked, so the stale value is unobservable
        tok = jnp.where(act, nxt, tok)
        act = act & (bud > 0) & (nxt != eos_token)
        return (state, tok, act, keys, bud, out, lps)

    state, tok, _, keys, bud, out, lps = jax.lax.fori_loop(
        0, k, body, (state, tokens, active, keys, bud0, out0, lp0))
    counts = bud0 - bud
    return out, counts, tok, (lps if logprobs else None), state, keys


def ngram_draft(hist: jax.Array, hist_len: jax.Array, k: int,
                max_ngram: int) -> jax.Array:
    """Device-side n-gram draft proposal over a right-aligned token window.

    ``hist`` is [B, W] int32 with each slot's most recent tokens packed at
    the RIGHT edge (``hist[:, W-1]`` is the pending token the next tick
    conditions on) and ``hist_len`` [B] counts how many trailing entries
    are real. For each slot, find the most recent earlier occurrence of
    the longest matching suffix n-gram (n = max_ngram down to 1 — mirror
    of the host-side ``lookup_draft``, including its preference for a
    match with a FULL k-token continuation over a more recent one whose
    continuation runs off the window edge: on a periodic stream the most
    recent match always abuts the suffix and would propose one real token
    plus zeros, capping acceptance at 2/tick) and propose the ``k`` tokens
    that followed it; slots with no match propose zeros (exactly the host
    helper's zero padding — under greedy verification draft CONTENTS only
    move the acceptance rate, never the emitted stream, so the fallback is
    a perf choice, not a correctness one).

    Everything is fixed-shape masked arithmetic over [B, W] — no host, no
    dynamic shapes — so it can live inside a compiled fori_loop body. The
    n-loop is a Python loop over ``max_ngram`` (static, small): longer
    n-grams overwrite shorter ones so the longest match wins, and within
    one n the most recent candidate start wins via a masked max.
    """
    b, w = hist.shape
    draft = jnp.zeros((b, k), jnp.int32)
    for n in range(1, max_ngram + 1):
        m = w - n  # candidate starts 0..m-1 (the suffix itself excluded)
        if m < 1:
            break
        tail = hist[:, w - n:]
        eq = jnp.ones((b, m), bool)
        for j in range(n):
            eq = eq & (hist[:, j:m + j] == tail[:, j:j + 1])
        starts = jnp.arange(m)[None, :]
        # a candidate window is only real if it sits inside the slot's
        # valid tail, and matching the suffix needs >= n+1 real tokens
        first_real = (w - jnp.minimum(hist_len, w))[:, None]
        ok = eq & (starts >= first_real) & (hist_len >= n + 1)[:, None]
        # two-tier pick within this n: the most recent start whose k-token
        # continuation fits inside the window wins; only when no start
        # does, fall back to the most recent partial (zero-padded) match
        full = ok & (starts + n + k <= w)
        wfull = jnp.max(jnp.where(full, starts, -1), axis=1)
        wany = jnp.max(jnp.where(ok, starts, -1), axis=1)
        wstar = jnp.where(wfull >= 0, wfull, wany)
        has = wstar >= 0
        idx = wstar[:, None] + n + jnp.arange(k)[None, :]
        cont = jnp.where(
            idx < w,
            jnp.take_along_axis(hist, jnp.clip(idx, 0, w - 1), axis=1), 0)
        draft = jnp.where(has[:, None], cont, draft)
    return draft


def multi_tick_spec_decode(
    spec_fn,
    k: int,
    spec_tokens: int,
    ngram: int,
    eos_token: int,
    state,
    tokens: jax.Array,
    active: jax.Array,
    cap: jax.Array,
    hist: jax.Array,
    hist_len: jax.Array,
    k_dyn: jax.Array,
):
    """Fused device-side speculation: draft + verify as the body of the
    multi-tick loop, so the host tick tax is paid once per flush while
    each inner tick emits UP TO ``spec_tokens + 1`` tokens instead of one.

    Each inner tick (i) materializes a draft on device — the pending token
    plus an ``ngram_draft`` continuation proposed from the slot's recent
    token window carried IN the loop state — then (ii) runs one greedy
    verify chunk through ``spec_fn(state, draft [B, T], active, budget) ->
    (pred [B, T], count [B], state)`` (the ``batched_spec_step`` trunk:
    T = spec_tokens + 1 positions through ``spec_verify_loop``, accepted
    prefix + bonus counted against the remaining budget, per-slot KV
    scatter with the paged ``t//page``/``t%page`` arithmetic, rejected
    tails and inactive lanes masked off every mapped block). Accepted
    tokens shift into the history window device-side (frozen lanes have
    count 0, so their window is untouched), the last accepted token
    becomes the next tick's pending feed, and a lane freezes — the
    existing early-exit discipline — when its budget hits zero or an
    ACCEPTED position equals ``eos_token``.

    Token-equality is by construction: greedy verification emits the
    model's own argmax at every accepted position and the bonus token is
    the argmax continuation, so the stream equals plain greedy decode for
    ANY draft contents — draft quality moves only the acceptance rate.

    ``k_dyn`` (scalar int32, clamped to [0, k]) is the flush window this
    dispatch actually runs: a TRACED fori_loop bound lowers to while_loop,
    so one compiled executable serves every LoopPolicy-chosen k without a
    per-k recompile. The output buffer stays shaped by the static maximum
    ``k``; un-run inner ticks hold LOOP_PAD_TOKEN / zero counts.

    Returns ``(out [B, k, spec_tokens+1] int32, counts [B, k] int32,
    carry [B] int32, state)``: ``out[b, i, :counts[b, i]]`` are the tokens
    slot b emitted at inner tick i (the host's ONE padded fetch per
    flush), ``carry`` the device-resident pending feed for the next flush.
    """
    b = tokens.shape[0]
    t = spec_tokens + 1
    w = hist.shape[1]
    out0 = jnp.full((b, k, t), LOOP_PAD_TOKEN, jnp.int32)
    cnt0 = jnp.zeros((b, k), jnp.int32)
    bud0 = jnp.where(active, jnp.maximum(cap, 0), 0)

    def body(i, carry):
        state, tok, act, bud, hist, hlen, out, cnts = carry
        cont = ngram_draft(hist, hlen, spec_tokens, ngram)
        draft = jnp.concatenate([tok[:, None], cont], axis=1)
        pred, count, state = spec_fn(state, draft, act, bud)
        accepted = jnp.arange(t)[None, :] < count[:, None]
        out = out.at[:, i].set(jnp.where(accepted, pred, LOOP_PAD_TOKEN))
        cnts = cnts.at[:, i].set(count)
        bud = bud - count
        # eos freezes the lane AFTER the tick that accepted it (the host
        # truncates the delivered tail at the eos, spec-path convention)
        hit = jnp.any(accepted & (pred == eos_token), axis=1)
        # shift the accepted run into the right-aligned window: count is 0
        # on frozen lanes, so their window (and feed) is a no-op shift
        cat = jnp.concatenate([hist, pred], axis=1)
        hist = jnp.take_along_axis(
            cat, count[:, None] + jnp.arange(w)[None, :], axis=1)
        hlen = jnp.minimum(hlen + count, w)
        last = jnp.take_along_axis(
            pred, jnp.clip(count - 1, 0, t - 1)[:, None], axis=1)[:, 0]
        tok = jnp.where(act & (count > 0), last, tok)
        act = act & (bud > 0) & ~hit
        return (state, tok, act, bud, hist, hlen, out, cnts)

    state, tok, _, _, _, _, out, counts = jax.lax.fori_loop(
        0, jnp.clip(k_dyn, 0, k), body,
        (state, tokens, active, bud0, hist, hist_len, out0, cnt0))
    return out, counts, tok, state


PROJECTIONS = ("wq", "wk", "wv")


@functools.partial(jax.jit, static_argnames=("n_heads", "head_dim"))
def _held_projection(w, n_heads: int, head_dim: int):
    """[L, d, H*Dh] -> [L, H, Dh, d]."""
    return jnp.transpose(
        w.reshape(w.shape[:2] + (n_heads, head_dim)), (0, 2, 3, 1))


def hold_projections(layers: dict[str, Any], cfg) -> dict[str, Any]:
    """A new dict of stacked layer leaves with ``wq``, ``wk``, ``wv`` held
    as a serving program's products read them, [L, H, Dh, d]: the head axis
    explicit and the contraction axis minor, which is the layout the v5e
    compiler gives a projection's weights (from the published
    [L, d, H*Dh] every launch copied each whole stack into it first: PR
    31). One jitted relayout a leaf; a shape stands for a leaf that is one
    (a compile-only rehearsal)."""
    out = dict(layers)
    for name in PROJECTIONS:
        leaf = layers[name]
        held = functools.partial(
            _held_projection, head_dim=cfg.head_dim,
            n_heads=cfg.n_heads if name == "wq" else kv_heads(cfg))
        if isinstance(leaf, jax.ShapeDtypeStruct):
            shape = jax.eval_shape(held, leaf)
            out[name] = jax.ShapeDtypeStruct(
                shape.shape, shape.dtype, sharding=leaf.sharding)
        else:
            out[name] = held(leaf)
    return out


@jax.named_scope("qkv")
def _qkv(cfg, lp, x, cos, sin, positions):
    """Project to rotated q/k/v heads: [B, S, H, Dh] each. A layer's
    projections are the published [d, H*Dh] (init_params, training, the
    pipeline) or a serving adapter's held [H, Dh, d] (hold_projections):
    the leaf's rank says which, and either way an output is the same dot
    product over d. k and v have ``kv_heads(cfg)`` heads; a family without
    rotary positions (``cfg.rotary`` False) gets q and k as projected; one
    with ``cfg.qk_norm`` norms each head of q and k first (gains
    ``q_norm`` / ``k_norm`` [Dh] a layer)."""
    normed = rms_norm(x, lp["attn_norm"], getattr(cfg, "eps", 1e-6))
    if lp["wq"].ndim == 3:
        q, k, v = (jnp.einsum("bsd,hed->bshe", normed, lp[name])
                   for name in PROJECTIONS)
    else:
        q, k, v = ((normed @ lp[name]).reshape(
            x.shape[:2] + (-1, cfg.head_dim)) for name in PROJECTIONS)
    if getattr(cfg, "qk_norm", False):
        # an RMS norm a head (gain [Dh]) on queries and keys, before the
        # rotary positions
        eps = getattr(cfg, "eps", 1e-6)
        q, k = rms_norm(q, lp["q_norm"], eps), rms_norm(k, lp["k_norm"], eps)
    if not getattr(cfg, "rotary", True):
        return q, k, v
    return apply_rope(q, cos, sin, positions), apply_rope(k, cos, sin, positions), v


@jax.named_scope("mlp")
def _mlp_block(lp, x):
    normed = rms_norm(x, lp["mlp_norm"])
    gate = jax.nn.silu((normed @ lp["w_gate"]).astype(jnp.float32)).astype(x.dtype)
    return (gate * (normed @ lp["w_up"])) @ lp["w_down"]


@jax.named_scope("o_proj")
def _o_proj(lp, x, attn):
    """The attention output projection and its residual add."""
    return x + attn.reshape(x.shape[:2] + (-1,)) @ lp["wo"]


@jax.named_scope("embed")
def _embed(params, cfg, tokens):
    return params["embed"][tokens].astype(cfg.dtype)


@jax.named_scope("lm_head")
def _lm_head(params, x, logits_at=None):
    """Final norm and the output head, the embedding's transpose unless the
    parameters hold a ``head`` of their own ([V, D]: a model whose
    configuration unties it); ``logits_at`` ([B] positions) gathers one row
    a sequence before the vocabulary projection."""
    x = rms_norm(x, params["final_norm"])
    if logits_at is not None:
        x = x[jnp.arange(x.shape[0]), logits_at]  # [B, D]
    return (x @ params.get("head", params["embed"]).T).astype(jnp.float32)


def transformer_layer(
    cfg: ModelConfig, lp: dict[str, jax.Array], x: jax.Array, cos, sin,
    positions, mesh=None,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """One decoder block over a full sequence. x: [B, S, D] -> (x, (k, v)).

    Shared by the dense prefill scan and the pipelined stage body
    (vtpu/parallel/pipeline.py) so the block exists exactly once. ``mesh``
    (the serving ('tp',) mesh) reaches the flash kernel, which must run
    per head shard under it.
    """
    b, s, _ = x.shape
    q, k, v = _qkv(cfg, lp, x, cos, sin, positions)
    with jax.named_scope("attn"):
        if cfg.use_pallas and s % 128 == 0 and s >= FLASH_MIN_SEQ:
            attn = flash_attention(q, k, v, mesh=mesh)
        else:
            attn = causal_attention(q, k, v)
    x = _o_proj(lp, x, attn)
    x = x + _mlp_block(lp, x)
    return x, (k, v)


def prefill(
    params: Params, cfg: ModelConfig, tokens: jax.Array,
    logits_at: Optional[jax.Array] = None, mesh=None,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Full-sequence forward. tokens: [B, S] int32. Returns (logits, kv_cache).
    ``mesh``: the serving ('tp',) mesh when the params are tensor-parallel.

    ``logits_at`` ([B] int32 positions) gathers the trunk output at one
    position per row BEFORE the vocab projection, returning [B, vocab]
    instead of [B, S, vocab] — admission only consumes each prompt's final
    position, and the full-bucket projection is O(S*D*V) of wasted compute
    (and, batched, an [N, bucket, vocab] f32 intermediate) at every prefill
    dispatch."""
    b, s = tokens.shape
    cos, sin = rope_angles(cfg.max_seq, cfg.head_dim)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x = _embed(params, cfg, tokens)

    def layer(x, lp):
        return transformer_layer(cfg, lp, x, cos, sin, positions, mesh=mesh)

    x, (ks, vs) = jax.lax.scan(layer, x, params["layers"])
    logits = _lm_head(params, x, logits_at)
    return logits, _prefill_cache(cfg, ks, vs)


@jax.named_scope("kv_write")
def _prefill_cache(cfg, ks: jax.Array, vs: jax.Array) -> dict[str, jax.Array]:
    """A fresh cache holding a prefill's [L, B, S, H, Dh] keys and values."""
    b, s = ks.shape[1:3]
    cache = init_kv_cache(cfg, b)
    cache.update(fill_kv_cache(cache, ks, vs))
    cache["len"] = jnp.full((b,), s, jnp.int32)
    return cache


def fill_kv_cache(
    cache: dict[str, jax.Array], ks: jax.Array, vs: jax.Array
) -> dict[str, jax.Array]:
    """Write freshly-computed [L, B, S, H, Dh] KV into a (possibly int8)
    cache's leading positions — the single prefill fill site shared by the
    dense and MoE families."""
    out = {}
    if "k_scale" in cache:
        kq, ksc = quantize_kv(ks)
        vq, vsc = quantize_kv(vs)
        out["k"] = jax.lax.dynamic_update_slice(cache["k"], kq, (0, 0, 0, 0, 0))
        out["v"] = jax.lax.dynamic_update_slice(cache["v"], vq, (0, 0, 0, 0, 0))
        out["k_scale"] = jax.lax.dynamic_update_slice(
            cache["k_scale"], ksc, (0, 0, 0, 0))
        out["v_scale"] = jax.lax.dynamic_update_slice(
            cache["v_scale"], vsc, (0, 0, 0, 0))
        return out
    out["k"] = jax.lax.dynamic_update_slice(
        cache["k"], ks.astype(cache["k"].dtype), (0, 0, 0, 0, 0))
    out["v"] = jax.lax.dynamic_update_slice(
        cache["v"], vs.astype(cache["v"].dtype), (0, 0, 0, 0, 0))
    return out


def decode_step(
    params: Params, cfg: ModelConfig, cache: dict[str, jax.Array], token: jax.Array,
    kv_bucket: int = 0, unroll: bool = False,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """One autoregressive step. token: [B] int32. Static shapes throughout.

    kv_bucket (static; 0 = max_seq) bounds the attention READS to the given
    prefix of the cache — decode is HBM-bandwidth-bound, so callers that know
    their sequences are short pass the smallest bucket covering them (the
    serving engine does this per tick). Writes still land in the full cache.
    unroll: see decode_layer_loop (static layer index fuses the bounded read).
    """
    pos0 = cache["len"][0]  # uniform batch position (benchmark decodes in lockstep)

    def write_kv(l, kv, k, v):
        out = dict(kv)
        if "k_scale" in kv:
            kq, ksc = quantize_kv(k)
            vq, vsc = quantize_kv(v)
            out["k"] = jax.lax.dynamic_update_slice(kv["k"], kq[None], (l, 0, pos0, 0, 0))
            out["v"] = jax.lax.dynamic_update_slice(kv["v"], vq[None], (l, 0, pos0, 0, 0))
            out["k_scale"] = jax.lax.dynamic_update_slice(
                kv["k_scale"], ksc[None], (l, 0, pos0, 0))
            out["v_scale"] = jax.lax.dynamic_update_slice(
                kv["v_scale"], vsc[None], (l, 0, pos0, 0))
            return out
        out["k"] = jax.lax.dynamic_update_slice(kv["k"], k[None], (l, 0, pos0, 0, 0))
        out["v"] = jax.lax.dynamic_update_slice(kv["v"], v[None], (l, 0, pos0, 0, 0))
        return out

    logits, new_kv = decode_layer_loop(
        params, cfg, cache, token, kv_bucket, write_kv, unroll=unroll
    )
    return logits, {**new_kv, "len": cache["len"] + 1}


def decode_layer_loop(
    params: Params,
    cfg: ModelConfig,
    cache: dict[str, jax.Array],
    token: jax.Array,
    kv_bucket: int,
    write_kv,
    ffn_fn=None,
    unroll: bool = False,
    mesh=None,
    paged_attn=None,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Shared decode-step body: a fori_loop carrying the STACKED cache (not a
    scan stacking fresh per-layer outputs), so the cache write — supplied by
    the caller as ``write_kv(l, kv, k, v) -> kv`` (lockstep column update
    here, per-slot scatter in the serving engine) — aliases in place instead
    of copying the whole cache. Decode is bandwidth-bound and that copy
    dominated the step. The read view is bounded to ``kv_bucket`` (static;
    0 = max_seq); int8 caches (k_scale/v_scale present) dequantize the
    bounded window inline, so the attention reads stream half the bytes.
    ``ffn_fn(lp, x)`` swaps the post-attention block (dense MLP here; routed
    experts for the MoE family — both share this attention trunk).
    ``unroll`` trades compile time for a STATIC layer index (see
    spec_verify_loop, which owns the single implementation — one decode
    token is a T=1 verify chunk, so plain-decode and speculative-verify
    numerics can never drift apart). ``mesh`` marks a head-sharded paged
    pool; ``paged_attn`` forces or resolves the kernel-vs-gather paged read
    route (see spec_verify_loop). Returns (logits [B, vocab], new kv)."""
    logits, new_kv = spec_verify_loop(
        params, cfg, cache, token[:, None], kv_bucket, write_kv,
        ffn_fn=ffn_fn, unroll=unroll, mesh=mesh, paged_attn=paged_attn,
    )
    return logits[:, 0], new_kv


def spec_verify_loop(
    params: Params,
    cfg: ModelConfig,
    cache: dict[str, jax.Array],
    draft: jax.Array,
    kv_bucket: int,
    write_kv,
    ffn_fn=None,
    unroll: bool = False,
    mesh=None,
    paged_attn=None,
    attend=None,
    layer_of=None,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Verify pass for speculative decoding: one forward over a [B, T] draft
    chunk whose row-i query sits at cache position len[b] + i.

    The economics: decode is HBM-bandwidth-bound, and the KV window is read
    ONCE here for T candidate positions instead of once per token — so a
    verify tick costs roughly one decode tick in bytes, and every accepted
    draft token is a decode tick never paid. The chunk's own KV is scattered
    first (caller's ``write_kv(l, kv, k, v) -> kv`` handles per-slot offsets
    and bounds), then attention reads the bounded window under the RAGGED
    mask (ops/attention.py kv_len=[B,T]): query i sees k_pos < len + i + 1,
    which is exactly intra-chunk causality because row i IS cache position
    len + i. Rejected positions hold garbage KV above the advanced length;
    the next chunk write (T entries from the new length, which advanced by
    at least 1) overwrites every stale entry before any query can attend to
    it. Returns (logits [B, T, vocab], new kv dict).

    No reference counterpart (HAMi has no model runtime); the TPU-shaped
    twist on standard speculative verification is static chunk shapes +
    scatter-at-offset + ragged masking, so one compiled executable serves
    every acceptance pattern.

    This is THE decode trunk: decode_layer_loop delegates here with T=1, so
    a fix to the attention/write/view logic lands in both paths at once.
    ``unroll`` trades compile time for a STATIC layer index: inside
    fori_loop the bounded read dynamic_index_in_dim(ks, l)[:, :bucket] has
    a loop-carried l, which XLA materializes as a slice copy before
    attention; unrolled, ks[l][:, :bucket] is a static view that fuses into
    the attention reads (the r2 decode-inversion exhibit in mfu_bench).

    ``mesh`` (a ('tp',) Mesh, paged caches only) marks the pool as
    HEAD-SHARDED: the page gathers are pinned chip-local on the head shard
    (ops/attention.py gather_kv_pages) — tables are replicated and every
    chip holds its head slice of every block, so paged reads and writes
    introduce no collectives beyond the per-block all-reduce the dense TP
    path already pays after wo. None (the default) is the single-chip
    path, bit-identical to before the mesh existed.

    ``paged_attn`` (paged caches only) picks the read route: "kernel"
    forces the fused Pallas table-walker (ops.decode_attn
    paged_decode_attention{,_int8kv} — attends over pool blocks IN PLACE,
    no gather, no dense window), "gather" forces the classic
    gather-then-dense chain, and None resolves the measured per-shape
    router (paged_attn_route — the FLASH_MIN_SEQ discipline: the kernel
    engages only where it beat the gather path on this hardware). Both
    routes share the kv_len masking and null-block contracts verbatim, so
    streams stay token-equal across the routing decision.

    ``attend(l, lp, x, kv) -> (attn, kv)`` replaces the attention half
    (None: ``cached_attention`` over this cache, as described above; a
    family whose rows of one slot see each other both ways brings its own:
    vtpu/models/blockdiff.py). ``layer_of(stack, l)`` (unrolled walks only)
    replaces the slice of every stacked leaf as a layer's parameters: a
    family whose kernels read a stack in place hands a view that slices a
    leaf where it is used (``latent.LayerOfStack``).
    """
    ffn = ffn_fn or _mlp_block
    if attend is None:
        attend = cached_attention(
            cfg, cache, draft.shape[1], kv_bucket, write_kv, unroll=unroll,
            mesh=mesh, paged_attn=paged_attn)
    x = _embed(params, cfg, draft)

    def layer(l, carry, lp=None):
        x, kv = carry
        if lp is None:
            lp = jax.tree_util.tree_map(lambda a: a[l], params["layers"])
        attn, kv = attend(l, lp, x, kv)
        x = _o_proj(lp, x, attn)
        x = x + ffn(lp, x)
        return x, kv

    kv0 = {key: cache[key] for key in kv_planes(cache)}
    if unroll:
        carry = (x, kv0)
        for l in range(cfg.n_layers):
            if layer_of is not None:
                lp = layer_of(params["layers"], l)
            else:
                lp = jax.tree_util.tree_map(lambda a: a[l], params["layers"])
            carry = layer(l, carry, lp=lp)
        x, new_kv = carry
    else:
        x, new_kv = jax.lax.fori_loop(0, cfg.n_layers, layer, (x, kv0))
    logits = _lm_head(params, x)
    if "table" in cache:
        # the table is read-only inside the trunk (the engine owns it,
        # updating rows host-side at admission); pass it through so the
        # returned state pytree matches the input and donation can alias
        new_kv = {**new_kv, "table": cache["table"]}
    return logits, new_kv


def kv_planes(cache) -> tuple:
    """The names of a cache's key/value planes (int8 caches carry scales)."""
    return (("k", "v", "k_scale", "v_scale") if "k_scale" in cache
            else ("k", "v"))


def cached_attention(cfg, cache, t: int, kv_bucket: int, write_kv,
                     unroll: bool = False, mesh=None, paged_attn=None):
    """The attention half of a layer over a per-slot KV cache, for a [B, T]
    chunk whose row-i query sits at cache position len[b] + i: what
    ``spec_verify_loop`` documents (the chunk's own KV scattered first by
    the caller's ``write_kv``, the bounded window read under the ragged
    mask, a paged pool read through its table by the kernel or the gather
    route), once, for every family whose layers attend over this cache:
    the dense and expert trunks above walk it every layer, a family of
    several layer kinds (vtpu/models/hybrid.py) at its attention layers,
    with ``l`` the layer's index among those. Returns
    ``attend(l, lp, x, kv) -> (attn [B, T, H, Dh], kv)``."""
    bucket = kv_bucket or cfg.max_seq
    quant = "k_scale" in cache
    scale = getattr(cfg, "attn_scale", None)
    cos, sin = (rope_angles(cfg.max_seq, cfg.head_dim,
                            getattr(cfg, "rope_theta", 10000.0))
                if getattr(cfg, "rotary", True) else (None, None))
    lens = cache["len"]
    # Paged pool ("table" present): reads gather each slot's live pages
    # through its page-table row instead of slicing a per-slot ring. The
    # gathered window is positionally identical to the dense prefix
    # [:, :bucket], so the ragged masks and every numeric below are SHARED
    # verbatim — paged-vs-dense streams stay token-identical. The caller's
    # write_kv owns the paged scatter (block id = table[b, pos // page]).
    table = cache.get("table")
    use_kernel = False
    if table is not None:
        page = cache["k"].shape[2]  # [L, n_blocks, page, H, Dh]
        table_w = table[:, : bucket // page]  # [B, Wp]
        # route resolution is a static per-shape property (window, chunk
        # width, quantization), so the engine's per-tick route counters can
        # mirror it exactly
        use_kernel = paged_attn_route(
            paged_attn, bucket, t=t, quant=quant) == "kernel"
    # clip: a slot near the context wall still computes (static shapes) but
    # its out-of-range rows are never written (write_kv masks) nor emitted
    # (the engine caps acceptance); clipping only keeps the rope gather legal
    positions = jnp.minimum(
        lens[:, None] + jnp.arange(t)[None, :], cfg.max_seq - 1
    )
    ragged_len = jnp.minimum(
        lens[:, None] + 1 + jnp.arange(t)[None, :], cfg.max_seq
    )
    block = getattr(cfg, "attn_block", 0)
    if block:
        # causal between blocks of ``block`` positions, two-sided inside
        # one: a query reads up to the end of its own block (the chunk's
        # rows are in the cache before attention reads it)
        ragged_len = jnp.minimum(
            ((lens[:, None] + jnp.arange(t)[None, :]) // block + 1) * block,
            cfg.max_seq)
    kv_keys = kv_planes(cache)
    plane = cache["k"].shape[-2:]  # kv_plane_shape: may pack heads a row

    def attend(l, lp, x, kv):
        q, k, v = _qkv(cfg, lp, x, cos, sin, positions)
        if k.shape[-2:] != plane:
            k, v = (a.reshape(a.shape[:2] + plane) for a in (k, v))
        with jax.named_scope("kv_write"):
            kv = write_kv(l, kv, k, v)
        # Paged KERNEL route: the fused table-walker takes the WHOLE
        # scatter-updated pool plus the layer index (a scalar-prefetch
        # operand — static under the unrolled serving loop, traced under
        # fori_loop, one executable either way), so no per-layer view and
        # no gathered window ever materialize. This is the re-promotion of
        # the r5 study: the pool operand aliases straight into the
        # pallas_call, killing the copy that routed every trunk cell to
        # XLA back then.
        if use_kernel:
            # the pools go in as stored; pool_relayout (the query's
            # preparation) and paged_attn are named inside the call
            if quant:
                return paged_decode_attention_int8kv(
                    q, kv["k"], kv["k_scale"], kv["v"], kv["v_scale"],
                    table_w, ragged_len, layer=l, mesh=mesh), kv
            return paged_decode_attention(
                q, kv["k"], kv["v"], table_w, ragged_len, layer=l,
                mesh=mesh, scale=scale), kv
        with jax.named_scope("attn" if table is None else "gather_attn"):
            return window_attention(l, kv, q), kv

    def window_attention(l, kv, q):
        # Bounded window reads: with the UNROLLED loop (the serving
        # default) the static index is a contiguous leading-dim slice and
        # the [:, :bucket] view fuses into the attention reads; under
        # fori_loop the loop-carried layer index materializes the slice
        # (correct but slow — benchmarks/mfu_bench.py decode_fori_exhibit).
        if unroll:
            view = {key: kv[key][l] for key in kv_keys}
        else:
            view = {
                key: jax.lax.dynamic_index_in_dim(
                    kv[key], l, 0, keepdims=False)
                for key in kv_keys
            }
        if table is not None:
            if quant:
                return paged_causal_attention_int8kv(
                    q, view["k"], view["k_scale"], view["v"],
                    view["v_scale"], table_w, kv_len=ragged_len, mesh=mesh)
            return paged_causal_attention(
                q, view["k"], view["v"], table_w, kv_len=ragged_len,
                mesh=mesh, scale=scale)
        if quant:
            return causal_attention_int8kv(
                q, view["k"][:, :bucket], view["k_scale"][:, :bucket],
                view["v"][:, :bucket], view["v_scale"][:, :bucket],
                kv_len=ragged_len)
        k, v = view["k"][:, :bucket], view["v"][:, :bucket]
        if t == 1:  # a decode step over a dense cache
            return causal_attention(q, k, v, kv_len=ragged_len, scale=scale)
        return chunk_window_attention(
            q, k, v, ragged_len, scale, mesh, stack=(kv["k"], kv["v"], l))

    return attend


def chunk_window_attention(q, k, v, reach, scale, mesh=None, stack=None):
    """The T queries of a chunk over the dense window they share, query i
    reading its first ``reach[b, i]`` rows: in the chunk kernel where
    ``chunk_attn.takes`` the shapes (a TPU, bfloat16, rows enough a
    key/value head), else ``causal_attention``'s ragged form, which is the
    same attention as XLA code (``chunk_attn.attend_window``: either runs
    under the scope ``chunk_attn``). ``stack`` ``(keys, values, layer)``:
    the layers' stacked planes ``[L, B, S, ...]`` that ``k`` and ``v`` are
    layer ``layer``'s first positions of; the kernel reads them where they
    lie, so no layer is sliced out for its operand."""
    keys, values, layer = stack or (k, v, None)
    return chunk_attn.attend_window(
        q, keys, values, reach,
        1.0 / math.sqrt(q.shape[-1]) if scale is None else scale,
        lambda: causal_attention(q, k, v, kv_len=reach, scale=scale), mesh,
        layer=layer, window=k.shape[1] if stack else None)


def greedy_generate(
    params: Params, cfg: ModelConfig, tokens: jax.Array, steps: int
) -> jax.Array:
    """Prefill + greedy decode; returns [B, steps] generated ids.

    The FIRST generated id is the argmax of the prefill's last-position
    logits — the same token a serving engine streams at admission — followed
    by steps-1 decode steps. (Previously that token was computed to seed the
    decode loop but dropped from the output, so the returned stream was ids
    2..steps+1: self-consistent comparisons never noticed, but any check of
    an engine stream against this reference was off by one.)"""
    logits, cache = prefill(params, cfg, tokens)
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)

    def step(carry, _):
        tok, cache = carry
        logits, cache = decode_step(params, cfg, cache, tok)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (nxt, cache), nxt

    (_, _), out = jax.lax.scan(step, (tok, cache), None,
                               length=max(steps - 1, 0))
    return jnp.concatenate([tok[:, None], out.T], axis=1)[:, :steps]
