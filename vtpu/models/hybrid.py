"""A decoder of two layer kinds with two kinds of per-session state: Mamba-2
layers, whose state is a short convolution window and a recurrent matrix a
head, among grouped-query attention layers over the paged KV pool (the
block of granite-4.0-h / Bamba: ``layer_types`` says which layer is which).

What differs from ``transformer.py``, by mechanism:

- **A session's state is pages and rows.** The attention layers keep keys
  and values in the paged pool exactly as the dense family does
  (``init_paged_kv_cache`` over ``HybridConfig.attention``; the page table,
  both read routes and the scatter of a step are ``transformer
  .cached_attention`` and ``slots.decode_kv_writer``, shared). The Mamba
  layers keep, a slot, ``conv [Lm, slots, K - 1, Dc]`` (the last K - 1 inputs
  of the causal depthwise convolution) and ``h [Lm, slots, H, P, N]`` float32
  (the recurrent state), whatever the session's length. Both live in the one
  engine state and are updated in place.
- **The Mamba-2 mixer** from its equations (``_mamba_mixer``): one input
  projection into gate, convolved channels and step sizes; a width-K causal
  depthwise convolution with bias, then SiLU; ``h_t = exp(dt_t A) h_{t-1} +
  dt_t x_t (outer) B_t``, ``y_t = h_t C_t + D x_t`` a head; a gated RMSNorm
  over all inner channels; the output projection. A decode step is the
  recurrence itself (``_ssd_step``; on a TPU ``ops/ssm_step``'s kernel, one
  visit of a layer's state in the stack); a prefill chunk computes the same in
  the chunked matrix form (``_ssd_chunked``: inside a chunk of
  ``ssd_chunk`` tokens a masked ``C B^T`` product weighted by the
  cumulative decay, between chunks the carried state).
- **Padding leaves the state alone.** Every entry point says how many of a
  row's T tokens are real (``n_valid``): a step of ``dt = 0`` carries ``h``
  through unchanged and the convolution window is taken at the last K - 1
  real inputs, so a padded bucket, the pads of a last chunk and an inactive
  slot of a decode step need no branch and no ``vmap`` a row.
- **Grouped-query attention without rotary positions** under a softmax
  scale of its own, and the multipliers of the Granite family: the embedding
  times ``embedding_multiplier``, every residual branch times
  ``residual_multiplier``, the logits over ``logits_scaling``.

One walk (``_walk``) serves every entry point, as in ``latent.py``: the
attention layers unrolled (a static layer index), each run of Mamba layers
between them a ``fori_loop`` over the stacked leaves and the stacked state
(one compiled body a run, the state's row of a layer updated in place; the
kernel's route hands it the stack and the layer's index).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from vtpu.models import slots as slot_steps
from vtpu.models.transformer import (
    ModelConfig,
    cached_attention,
    init_paged_kv_cache,
    kv_plane_shape,
)
from vtpu.ops import rms_norm, scaled_normal
from vtpu.ops.ssm_step import ssm_state_step

Params = dict[str, Any]
KV_KEYS = ("k", "v")


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    """Toy sizes by default; vbench/sut/hybrid.py gives the published."""

    vocab: int = 256
    d_model: int = 128
    layer_types: tuple = ("mamba", "mamba", "attention", "mamba", "mamba")
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 64
    d_ff: int = 256
    ssm_heads: int = 8        # H
    ssm_head_dim: int = 32    # P; the inner width is H * P
    ssm_state: int = 16       # N
    ssm_groups: int = 1       # groups of B and C (one: shared by every head)
    conv_width: int = 4       # K
    ssd_chunk: int = 8        # the chunked form's chunk (published: 256)
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.015625
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    eps: float = 1e-5
    max_seq: int = 256
    dtype: Any = jnp.bfloat16
    kv_int8: bool = False     # refused by the adapter: stated to be refused

    def __post_init__(self):
        odd = set(self.layer_types) - {"mamba", "attention"}
        if odd:
            raise ValueError(f"layer_types holds unknown kinds {sorted(odd)}")
        if self.ssm_groups != 1:
            raise ValueError(
                f"ssm_groups={self.ssm_groups}: the mixer shares one group "
                "of B and C over its heads")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_ssm_layers(self) -> int:
        return self.layer_types.count("mamba")

    @property
    def n_attn_layers(self) -> int:
        return self.layer_types.count("attention")

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels under the convolution: x, then B and C."""
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def attention(self) -> ModelConfig:
        """The attention stack as the shared cache machinery reads it."""
        return ModelConfig(
            vocab=self.vocab, d_model=self.d_model, n_heads=self.n_heads,
            n_layers=self.n_attn_layers, d_ff=self.d_ff,
            max_seq=self.max_seq, head_dim=self.head_dim, dtype=self.dtype,
            n_kv_heads=self.n_kv_heads, rotary=False,
            attn_scale=self.attention_multiplier, eps=self.eps)

    @property
    def recurrent_bytes_per_slot(self) -> int:
        """Bytes of conv window and recurrent state one slot holds."""
        conv = (self.conv_width - 1) * self.conv_dim * jnp.dtype(
            self.dtype).itemsize
        h = self.ssm_heads * self.ssm_head_dim * self.ssm_state * 4
        return self.n_ssm_layers * (conv + h)


def layer_runs(layer_types: tuple) -> list:
    """[(kind, first, end)]: the model's order as runs of one kind, each
    with its span in that kind's own stack."""
    runs, seen = [], {}
    for kind in layer_types:
        i = seen.get(kind, 0)
        if runs and runs[-1][0] == kind:
            runs[-1] = (kind, runs[-1][1], i + 1)
        else:
            runs.append((kind, i, i + 1))
        seen[kind] = i + 1
    return runs


def init_hybrid_params(rng: jax.Array, cfg: HybridConfig) -> Params:
    """Seeded weights at toy sizes: each kind's leaves stacked [L, ...], the
    projections as published ([d, H * Dh]; a serving adapter holds them)."""
    d, f, di, dc = cfg.d_model, cfg.d_ff, cfg.d_inner, cfg.conv_dim
    h, k = cfg.ssm_heads, cfg.conv_width
    lm, la = cfg.n_ssm_layers, cfg.n_attn_layers
    qd, kvd = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    keys = iter(jax.random.split(rng, 32))

    def w(shape, fan_in, dtype=None):
        return scaled_normal(next(keys), shape, fan_in, dtype or cfg.dtype)

    def mlp(l):
        return {"mlp_norm": jnp.ones((l, d), cfg.dtype),
                "w_gate": w((l, d, f), d), "w_up": w((l, d, f), d),
                "w_down": w((l, f, d), f)}

    steps = jnp.exp(jax.random.uniform(
        next(keys), (lm, h), jnp.float32, jnp.log(0.001), jnp.log(0.1)))
    return {
        "embed": w((cfg.vocab, d), d),
        "final_norm": jnp.ones((d,), cfg.dtype),
        "mamba": {
            "norm": jnp.ones((lm, d), cfg.dtype),
            "in_proj": w((lm, d, di + dc + h), d),
            "conv_w": w((lm, k, dc), k),
            "conv_b": w((lm, dc), 16.0),
            # softplus(dt_bias) is the step at a zero projection
            "dt_bias": steps + jnp.log(-jnp.expm1(-steps)),
            "a_log": jnp.log(jax.random.uniform(
                next(keys), (lm, h), jnp.float32, 1.0, 16.0)),
            "d_skip": jnp.ones((lm, h), jnp.float32),
            "gate_norm": jnp.ones((lm, di), cfg.dtype),
            "out_proj": w((lm, di, d), di),
            **mlp(lm)},
        "attention": {
            "attn_norm": jnp.ones((la, d), cfg.dtype),
            "wq": w((la, d, qd), d), "wk": w((la, d, kvd), d),
            "wv": w((la, d, kvd), d), "wo": w((la, qd, d), qd),
            **mlp(la)},
    }


def init_hybrid_state(cfg: HybridConfig, slots: int, page: int,
                      n_blocks: int) -> dict[str, jax.Array]:
    """The paged pool of the attention layers (``table`` / ``len`` / ``k`` /
    ``v`` as ``init_paged_kv_cache`` lays them) with the Mamba layers'
    slot-indexed rows beside it."""
    state = init_paged_kv_cache(cfg.attention, slots, page, n_blocks)
    state["conv"], state["h"] = _empty_rows(cfg, slots)
    return state


def _empty_rows(cfg: HybridConfig, n: int):
    """(conv [Lm, n, K - 1, Dc], h [Lm, n, H, P, N] float32) of n sequences
    that have read nothing."""
    return (jnp.zeros((cfg.n_ssm_layers, n, cfg.conv_width - 1, cfg.conv_dim),
                      cfg.dtype),
            jnp.zeros((cfg.n_ssm_layers, n, cfg.ssm_heads, cfg.ssm_head_dim,
                       cfg.ssm_state), jnp.float32))


# ------------------------------------------------------------- the mixer


def step_in_kernel(t: int) -> bool:
    """Whether a program of ``t`` tokens a row updates the recurrent state
    in the Pallas kernel (``ops/ssm_step``: one visit of a layer's state,
    over the stack as stored) and not in ``_ssd_step``'s XLA code: a decode
    step on a TPU. Resolved when a program is traced, from what it can
    observe, as ``cached_attention`` asks for the paged kernel; the engine
    counts its ticks by the same call (``stats()["ssm_kernel_ticks"]``)."""
    return t == 1 and jax.default_backend() == "tpu"


def _step_operands(xs, dt, a, bm, cm):
    """A one-token step's operands in float32: (decay [B, H], dt * x
    [B, H, P], B [B, N], C [B, N]) from xs [B, 1, H, P], dt [B, 1, H], a
    [H], bm, cm [B, 1, N]; or, with a row of B and C a head (a linear
    attention's key and query: vtpu/models/sparselinear.py), bm, cm
    [B, 1, H, N] -> B, C [B, H, N]."""
    f32 = jnp.float32
    dt = dt[:, 0]
    return (jnp.exp(dt * a), dt[..., None] * xs[:, 0].astype(f32),
            bm[:, 0].astype(f32), cm[:, 0].astype(f32))


def _ssd_step(xs, dt, a, bm, cm, h):
    """The recurrence, one token a row. xs [B, 1, H, P]; dt [B, 1, H]
    float32 (0: the state passes through); a [H]; bm, cm [B, 1, N] (or
    [B, 1, H, N]: a row a head); h [B, H, P, N] float32 -> (y [B, 1, H, P]
    float32, h)."""
    decay, dx, bm, cm = _step_operands(xs, dt, a, bm, cm)
    if bm.ndim == 3:  # a row a head
        h = h * decay[..., None, None] + dx[..., None] * bm[:, :, None, :]
        return jnp.einsum("bhpn,bhn->bhp", h, cm)[:, None], h
    h = h * decay[..., None, None] + dx[..., None] * bm[:, None, None, :]
    y = jnp.einsum("bhpn,bn->bhp", h, cm)
    return y[:, None], h


def _ssd_chunked(xs, dt, a, bm, cm, h, chunk: int):
    """The same recurrence over T tokens a row in the chunked matrix form
    (state-space duality). Inside a chunk of Q tokens, with ``cum`` the
    running sum of ``dt A``: ``y_i = sum_{j <= i} exp(cum_i - cum_j) (C_i .
    B_j) dt_j x_j + exp(cum_i) C_i . h_in``; a chunk hands on ``h_out =
    exp(cum_Q) h_in + sum_j exp(cum_Q - cum_j) dt_j x_j (outer) B_j``.
    Shapes as ``_ssd_step`` with T in the place of 1 (bm, cm [B, T, N], or
    [B, T, H, N] a row a head); the products take
    their operands in xs's dtype and accumulate in float32, the decays are
    float32 throughout."""
    b, t, nh, p = xs.shape
    n = bm.shape[-1]
    pad = -t % chunk
    if pad:  # dt = 0: a padded token moves nothing
        xs, dt, bm, cm = (jnp.pad(z, ((0, 0), (0, pad)) + ((0, 0),) * (z.ndim - 2))
                          for z in (xs, dt, bm, cm))
    nc, q = (t + pad) // chunk, chunk
    f32 = jnp.float32
    cum = jnp.cumsum((dt * a).reshape(b, nc, q, nh), axis=2)  # [B, C, Q, H]
    cum_h = jnp.swapaxes(cum, 2, 3)  # [B, C, H, Q]
    xdt = (xs.astype(f32) * dt[..., None]).astype(xs.dtype).reshape(
        b, nc, q, nh, p)
    # B and C a row shared by the heads, or (rank 4) a row a head
    shared = bm.ndim == 3
    rows = (b, nc, q, n) if shared else (b, nc, q, nh, n)
    bm, cm = bm.reshape(rows), cm.reshape(rows)
    # inside a chunk: the masked C B^T product under the decay between j, i
    cb = jnp.einsum("bcin,bcjn->bcij" if shared else "bcihn,bcjhn->bchij",
                    cm, bm, preferred_element_type=f32)
    seg = cum_h[..., :, None] - cum_h[..., None, :]  # [B, C, H, Qi, Qj]
    lower = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
    mix = (jnp.exp(jnp.where(lower, seg, -jnp.inf))
           * (cb[:, :, None] if shared else cb)).astype(xs.dtype)
    y = jnp.einsum("bchij,bcjhp->bcihp", mix, xdt, preferred_element_type=f32)
    # what each chunk adds to the state, and the state each one starts from
    to_end = jnp.exp(cum[:, :, -1:, :] - cum)  # [B, C, Q, H]
    added = jnp.einsum(
        "bcjhp,bcjn->bchpn" if shared else "bcjhp,bcjhn->bchpn",
        (xdt.astype(f32) * to_end[..., None]).astype(xs.dtype), bm,
        preferred_element_type=f32)
    whole = jnp.exp(cum[:, :, -1, :])  # [B, C, H]

    def carry(h, xs_):
        decay, add = xs_
        return h * decay[..., None, None] + add, h

    h, starts = jax.lax.scan(
        carry, h, (jnp.swapaxes(whole, 0, 1), jnp.swapaxes(added, 0, 1)))
    starts = jnp.swapaxes(starts, 0, 1)  # [B, C, H, P, N]: h entering a chunk
    y = y + jnp.einsum(
        "bcin,bchpn->bcihp" if shared else "bcihn,bchpn->bcihp",
        cm.astype(f32), starts,
        precision=jax.lax.Precision.HIGHEST) * jnp.exp(cum)[..., None]
    return y.reshape(b, nc * q, nh, p)[:, :t], h


def _residual(cfg: HybridConfig, x, branch):
    return (x.astype(jnp.float32)
            + cfg.residual_multiplier * branch.astype(jnp.float32)
            ).astype(x.dtype)


def _mamba_mixer(cfg: HybridConfig, lp, x, conv, h, n_valid, layer=None):
    """One Mamba-2 mixer over x [B, T, D] from the carried rows ``conv``
    [B, K - 1, Dc] and ``h`` [B, H, P, N]; ``n_valid`` [B] of each row's T
    tokens are real (the first ones). Returns (x + r * mixer(x), conv, h).
    With ``layer`` (a step in the kernel: ``step_in_kernel``), ``h`` is the
    whole stack [Lm, B, H, P, N], taken and returned."""
    b, t, _ = x.shape
    nh, p, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    di, dc, k = cfg.d_inner, cfg.conv_dim, cfg.conv_width
    f32 = jnp.float32
    with jax.named_scope("qkv"):
        proj = rms_norm(x, lp["norm"], cfg.eps) @ lp["in_proj"]
        z, xbc, dt = proj[..., :di], proj[..., di:di + dc], proj[..., di + dc:]
    with jax.named_scope("attn"):  # vbench/scopes.py's name for the three
        with jax.named_scope("ssm_conv"):
            seq = jnp.concatenate([conv, xbc], axis=1)  # [B, K - 1 + T, Dc]
            acc = lp["conv_b"].astype(f32)
            for j in range(k):  # tap j reads the input K - 1 - j tokens back
                acc = acc + seq[:, j:j + t].astype(f32) * lp["conv_w"][j].astype(f32)
            xbc = jax.nn.silu(acc).astype(x.dtype)
            # the window after the row's last real token
            if t == 1:  # one token on, or where it stood: no gather
                conv = jnp.where((n_valid > 0)[:, None, None], seq[:, 1:], conv)
            else:
                at = n_valid[:, None] + jnp.arange(k - 1)[None, :]
                conv = jnp.take_along_axis(seq, at[:, :, None], axis=1)
        with jax.named_scope("ssm_scan"):
            xs = xbc[..., :di].reshape(b, t, nh, p)
            bm, cm = xbc[..., di:di + n], xbc[..., di + n:]
            real = jnp.arange(t)[None, :] < n_valid[:, None]
            dt = jax.nn.softplus(dt.astype(f32) + lp["dt_bias"]) * real[..., None]
            a = -jnp.exp(lp["a_log"].astype(f32))
            if layer is not None:  # the stack, this layer of it in place
                y, h = ssm_state_step(
                    h, layer, *_step_operands(xs, dt, a, bm, cm),
                    interpret=jax.default_backend() != "tpu")
                y = y[:, None]
            elif t == 1:
                y, h = _ssd_step(xs, dt, a, bm, cm, h)
            else:
                y, h = _ssd_chunked(xs, dt, a, bm, cm, h, cfg.ssd_chunk)
            y = y + lp["d_skip"].astype(f32)[:, None] * xs.astype(f32)
        with jax.named_scope("ssm_gate"):
            gated = y.reshape(b, t, di) * jax.nn.silu(z.astype(f32))
            y = rms_norm(gated, lp["gate_norm"], cfg.eps).astype(x.dtype)
    with jax.named_scope("o_proj"):
        return _residual(cfg, x, y @ lp["out_proj"]), conv, h


@jax.named_scope("mlp")
def _mlp(cfg: HybridConfig, lp, x):
    """x + r * SwiGLU(rms_norm(x))."""
    n = rms_norm(x, lp["mlp_norm"], cfg.eps)
    gate = jax.nn.silu((n @ lp["w_gate"]).astype(jnp.float32)).astype(x.dtype)
    return _residual(cfg, x, (gate * (n @ lp["w_up"])) @ lp["w_down"])


@jax.named_scope("embed")
def _embed(params: Params, cfg: HybridConfig, tokens):
    return (params["embed"][tokens].astype(jnp.float32)
            * cfg.embedding_multiplier).astype(cfg.dtype)


@jax.named_scope("lm_head")
def _head(params: Params, cfg: HybridConfig, x):
    """Final norm, the tied output head, the logits' divisor."""
    x = rms_norm(x, params["final_norm"], cfg.eps)
    return (x @ params["embed"].T).astype(jnp.float32) / cfg.logits_scaling


def _walk(params: Params, cfg: HybridConfig, tokens, n_valid, kv, conv, h,
          attend):
    """Every layer in the model's order over tokens [B, T]: the attention
    layers through ``attend`` (``transformer.cached_attention``'s, over
    whatever cache ``kv`` is), the Mamba layers from and into the rows
    ``conv`` [Lm, B, K - 1, Dc] and ``h`` [Lm, B, H, P, N] of these B
    sequences. Returns (hidden [B, T, D], kv, conv, h)."""
    x = _embed(params, cfg, tokens)
    mamba, attention = params["mamba"], params["attention"]

    in_kernel = step_in_kernel(tokens.shape[1])

    def mamba_layer(l, carry):
        x, conv, h = carry
        lp = jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, l, 0, keepdims=False),
            mamba)
        # a layer's rows read from and written to the state: the scan's
        # (the kernel visits its layer of ``h`` in the stack itself)
        with jax.named_scope("attn"), jax.named_scope("ssm_scan"):
            c = jax.lax.dynamic_index_in_dim(conv, l, 0, False)
            s = h if in_kernel else jax.lax.dynamic_index_in_dim(h, l, 0, False)
        x, c, s = _mamba_mixer(
            cfg, lp, x, c, s, n_valid, l if in_kernel else None)
        x = _mlp(cfg, lp, x)
        with jax.named_scope("attn"), jax.named_scope("ssm_scan"):
            return (x, jax.lax.dynamic_update_index_in_dim(conv, c, l, 0),
                    s if in_kernel
                    else jax.lax.dynamic_update_index_in_dim(h, s, l, 0))

    for kind, first, end in layer_runs(cfg.layer_types):
        if kind == "mamba":
            x, conv, h = jax.lax.fori_loop(
                first, end, mamba_layer, (x, conv, h))
            continue
        for l in range(first, end):
            lp = jax.tree_util.tree_map(lambda a: a[l], attention)
            attn, kv = attend(l, lp, x, kv)
            with jax.named_scope("o_proj"):
                x = _residual(
                    cfg, x, attn.reshape(x.shape[:2] + (-1,)) @ lp["wo"])
            x = _mlp(cfg, lp, x)
    return x, kv, conv, h


# -------------------------------------------------------- the entry points


def _fresh_rows(params: Params, cfg: HybridConfig, tokens, true_lens):
    """N right-padded prompts [N, S] from empty state, over a scratch cache
    of their own: (hidden [N, S, D], the layers' keys and values
    [La, N, S, ...] as the pool stores a token, conv, h at each row's
    ``true_len``)."""
    n, s = tokens.shape
    acfg = cfg.attention
    plane = kv_plane_shape(acfg)
    scratch = {key: jnp.zeros((acfg.n_layers, n, s) + plane, cfg.dtype)
               for key in KV_KEYS}
    scratch["len"] = jnp.zeros((n,), jnp.int32)

    def write_kv(l, kv, k, v):
        return {"k": kv["k"].at[l].set(k), "v": kv["v"].at[l].set(v)}

    attend = cached_attention(acfg, scratch, s, s, write_kv, unroll=True)
    return _walk(params, cfg, tokens, true_lens,
                 {key: scratch[key] for key in KV_KEYS},
                 *_empty_rows(cfg, n), attend)


def hybrid_forward(params: Params, cfg: HybridConfig,
                   tokens: jax.Array) -> jax.Array:
    """Full-sequence forward: tokens [B, S] -> logits [B, S, V]."""
    lens = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
    x, _, _, _ = _fresh_rows(params, cfg, tokens, lens)
    return _head(params, cfg, x)


def hybrid_prefill_rows(params: Params, cfg: HybridConfig, state, tokens,
                        slots, true_lens):
    """Whole-prompt admission: N right-padded prompts [N, bucket] computed
    from empty state and installed, pages through the slots' table rows
    (set by the engine's reservation before the dispatch) and the recurrent
    rows at each prompt's ``true_len``. Returns (logits [N, V] at each
    prompt's last token, the state). Whatever the slots held before is
    overwritten whole."""
    n, s = tokens.shape
    x, seq, conv, h = _fresh_rows(params, cfg, tokens, true_lens)
    logits = _head(params, cfg, x[jnp.arange(n), true_lens - 1])
    _, new = slot_steps._scatter_prefill_pages(
        state, seq, logits, slots, true_lens, s)
    with jax.named_scope("kv_write"):
        new["conv"] = state["conv"].at[:, slots].set(conv)
        new["h"] = state["h"].at[:, slots].set(h)
    return logits, new


@jax.named_scope("kv_write")
def carried_rows(state, slot, offset):
    """The rows a chunk at ``offset`` of ``slot`` starts from, [Lm, 1, ...]
    each: what the slot's earlier chunks left, or zeros at offset 0 (the
    slot may hold an ended session's). Looked up on this module when a
    chunk is traced: the benchmark's tests plant a lossy one here and must
    see it served."""
    carried = offset > 0
    return (jnp.where(carried, state["conv"][:, slot], 0)[:, None],
            jnp.where(carried, state["h"][:, slot], 0)[:, None])


def hybrid_prefill_chunk(params: Params, cfg: HybridConfig, state, chunk,
                         slot, offset, new_len, window: int, block_ids):
    """One [1, C] chunk of a prompt at positions offset .. offset + C - 1 of
    ``slot``, the first ``new_len - offset`` of them real: its keys and
    values written into and read through ``block_ids`` (the dense family's
    chunk: ``slots._chunk_window`` / ``_chunk_write_back``), its Mamba
    layers run from the slot's carried rows (zeros at offset 0, so a slot
    given to a new session keeps nothing of the old one) and written back.
    Returns (logits [1, C, V], state)."""
    c = chunk.shape[1]
    view = slot_steps._chunk_window(state, KV_KEYS, window, slot, block_ids,
                                    None)
    view["len"] = jnp.full((1,), offset, jnp.int32)

    def write_kv(l, kv, k, v):
        at = (l, 0, offset, 0, 0)
        return {"k": jax.lax.dynamic_update_slice(kv["k"], k[None], at),
                "v": jax.lax.dynamic_update_slice(kv["v"], v[None], at)}

    attend = cached_attention(
        cfg.attention, view, c, window, write_kv, unroll=True)
    conv, h = carried_rows(state, slot, offset)
    x, new_view, conv, h = _walk(
        params, cfg, chunk, (new_len - offset)[None],
        {key: view[key] for key in KV_KEYS}, conv, h, attend)
    new = slot_steps._chunk_write_back(
        state, new_view, KV_KEYS, window, c, slot, offset, new_len, block_ids)
    with jax.named_scope("kv_write"):
        new["conv"] = state["conv"].at[:, slot].set(conv[:, 0], mode="drop")
        new["h"] = state["h"].at[:, slot].set(h[:, 0], mode="drop")
    return _head(params, cfg, x), new


def hybrid_decode_step(params: Params, cfg: HybridConfig, state, tokens,
                       active, window: int, paged_attn=None):
    """One decode tick for the whole slot pool: tokens [B], active [B] ->
    (logits [B, V], state). An active slot writes its key and value at its
    own length, reads its pages through the first ``window`` positions of
    its table row (kernel or gather: ``paged_attn`` as the dense family's)
    and moves its recurrent rows one token on; an inactive slot writes no
    page and its rows pass through as they stood."""
    acfg = cfg.attention
    lens = state["len"]
    write_kv = slot_steps.decode_kv_writer(acfg, state, active)
    attend = cached_attention(
        acfg, state, 1, window, write_kv, unroll=True, paged_attn=paged_attn)
    x, kv, conv, h = _walk(
        params, cfg, tokens[:, None], active.astype(jnp.int32),
        {key: state[key] for key in KV_KEYS}, state["conv"], state["h"],
        attend)
    new = {**state, **kv, "conv": conv, "h": h,
           "len": jnp.where(active, lens + 1, lens)}
    return _head(params, cfg, x[:, 0]), new
