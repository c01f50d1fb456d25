"""Latent attention, under a learned sparse selection or over all that is
cached, over two stacks of layers: the third model family with a paged
cache (DeepSeek-V3.2's block, and DeepSeek-V2's).

What differs from ``transformer.py`` / ``moe.py``, by mechanism:

- **The cache holds latents, not heads.** A cached token is one row a
  layer of ``kv_rank`` normed values and ``rope_dim`` rotated ones (512 +
  64 published), shared by all heads, in a pool plane ``ckv [L, n_blocks,
  page, kv_rank + rope_dim]`` (rows stored padded to whole lane tiles:
  ``LatentConfig.stored_width``). Beside it a second plane ``ik [L, n_blocks,
  page, index_dim]`` holds the indexer's key of the token. Both are walked
  by the one page table (``table`` / ``len`` as ``init_paged_kv_cache``
  lays them, so the engine's allocator serves them unchanged).
- **Attention reads a selection.** The indexer scores every visible
  position of the read window and the best ``index_topk`` are kept. A
  decode step attends in the latent space (the absorbed form: queries
  taken through the key up-projection, the mix of latents through the
  value up-projection) over those rows alone, gathered through the page
  table; a prefill chunk's many queries share one window, so it takes the
  window's latents through both up-projections once a layer (the expanded
  form) and attends a head 192 and 128 wide under the selection's mask.
  Which form runs is read off the shapes in ``vtpu/ops/latent.py``.
- **A model without an indexer attends all it has cached**
  (``index_heads`` 0: DeepSeek-V2). It has no ``idx_*`` leaf, no ``ik``
  plane and no indexer scope; a decode step walks its slots' live pages
  in the latent plane (``ops/decode_attn.latent_decode_attention``), so
  its attention grows with the context; a chunk masks causally.
- **Two stacks walked in order**: ``params["dense"]`` (the leading layers,
  a SwiGLU each) and ``params["sparse"]`` (a router ``n_experts`` wide, a
  shared expert, and the stacks of the experts *held here*, ``held =
  (first, count)``: this chip's share of a layer that a deployment divides
  over several; what the absent experts would add is left out). The
  router is the model's own (``topk_method``: V3's ``noaux_tc``,
  ``moe.grouped_route``; V2's ``group_limited_greedy``,
  ``moe.group_limited_route``).
- An untied output head, an epsilon that is the configuration's, YaRN's
  frequencies. Two projections are held as a decode step's products read
  them, so that no step lays a weight out anew: ``wq_b [heads * (nope +
  rope), q_rank]`` and ``idx_wq [index_heads * index_dim, q_rank]`` (the
  published matrices transposed) and the published
  ``kv_b`` in its two halves a head, ``w_uk [H, nope, kv_rank]`` and
  ``w_uv [H, kv_rank, v]`` (compiled for a v5e, the step otherwise copied
  133 MB of them a layer).

One walk (``_walk``) serves every entry point: N sequences of T queries
each, writing their rows at given pool addresses and reading through given
table rows. A decode step is T = 1 over the slots, a prefill chunk N = 1,
a whole-prompt admission N prompts of a bucket, the full forward a scratch
pool of its own.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from vtpu.models.moe import (
    group_limited_route, grouped_route, held_experts_ffn)
from vtpu.models.transformer import _embed
from vtpu.ops import rms_norm, scaled_normal, yarn_rope_angles
from vtpu.ops.latent import sparse_latent_attention, write_rows

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LatentConfig:
    """Toy sizes by default; vbench/sut/latent.py gives the published."""

    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4
    n_dense_layers: int = 1
    n_sparse_layers: int = 2
    d_ff: int = 128           # the dense layers' SwiGLU width
    d_ff_expert: int = 32     # a routed expert's
    d_ff_shared: int | None = None  # the shared experts' as one SwiGLU
    #                           (None: one routed expert's, V3.2; V2: 3072)
    q_rank: int = 48
    kv_rank: int = 32
    nope_dim: int = 16        # a head's part without position
    rope_dim: int = 8         # ... and its rotated part (one key head)
    v_dim: int = 16
    index_heads: int = 4      # 0: no indexer, every cached latent attended
    index_dim: int = 16
    index_topk: int = 16
    n_experts: int = 16       # the router's width: the whole layer's experts
    held: tuple = (0, 16)     # (first, count) of the experts held here
    top_k: int = 4
    n_group: int = 4
    topk_group: int = 2
    route_scale: float = 2.5
    topk_method: str = "noaux_tc"  # or "group_limited_greedy" (V2's router)
    max_seq: int = 256
    rope_theta: float = 10000.0
    yarn_factor: float = 40.0
    yarn_original_max: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 1.0
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @property
    def n_layers(self) -> int:
        return self.n_dense_layers + self.n_sparse_layers

    @property
    def latent_width(self) -> int:
        return self.kv_rank + self.rope_dim

    @property
    def has_indexer(self) -> bool:
        """Whether attention reads a selection (an indexer's best
        ``index_topk``) or, with no indexer, all that is cached."""
        return self.index_heads > 0

    def _mscale(self, m: float) -> float:
        return 0.1 * m * math.log(self.yarn_factor) + 1.0 \
            if self.yarn_factor > 1 else 1.0

    @property
    def stored_width(self) -> int:
        """A latent row as the pool stores it: padded to whole 128-lane
        tiles, which is what the chip allocates for a 576-wide row anyway
        (640). Stated, the padding costs nothing more; left to the
        compiler, it lays the plane out blocks-minor to save it and
        converts the whole pool on the way in and out of every step
        (compiled for a v5e: 2 x 1.84 GB of copies a decode step)."""
        return -(-self.latent_width // 128) * 128

    @property
    def attn_scale(self) -> float:
        """The softmax scale: 1 / sqrt(a head's query width), times the
        square of YaRN's magnitude correction for ``mscale_all_dim``."""
        return ((self.nope_dim + self.rope_dim) ** -0.5
                * self._mscale(self.yarn_mscale_all_dim) ** 2)

    @property
    def kv_bytes_per_token(self) -> int:
        """Pool bytes one cached token costs across all layers."""
        row = self.stored_width + (self.index_dim if self.has_indexer else 0)
        return self.n_layers * row * jnp.dtype(self.dtype).itemsize

    def rope_tables(self) -> tuple[jax.Array, jax.Array]:
        return yarn_rope_angles(
            self.max_seq, self.rope_dim, self.rope_theta, self.yarn_factor,
            self.yarn_original_max, self.yarn_beta_fast, self.yarn_beta_slow,
            attn_factor=(self._mscale(self.yarn_mscale)
                         / self._mscale(self.yarn_mscale_all_dim)))


def init_latent_params(rng: jax.Array, cfg: LatentConfig) -> Params:
    """Seeded weights at toy sizes: each stack's leaves stacked [L, ...]."""
    d, h, rq, rkv = cfg.d_model, cfg.n_heads, cfg.q_rank, cfg.kv_rank
    dn, dr, dv = cfg.nope_dim, cfg.rope_dim, cfg.v_dim
    hi, di, f, e = cfg.index_heads, cfg.index_dim, cfg.d_ff_expert, cfg.n_experts
    fs = cfg.d_ff_shared or f
    keys = iter(jax.random.split(rng, 64))

    def w(shape, fan_in, dtype=None):
        return scaled_normal(next(keys), shape, fan_in, dtype or cfg.dtype)

    def attn(l):
        leaves = {
            "attn_norm": jnp.ones((l, d), cfg.dtype),
            "wq_a": w((l, d, rq), d),
            "q_norm": jnp.ones((l, rq), cfg.dtype),
            "wq_b": w((l, h * (dn + dr), rq), rq),
            "wkv_a": w((l, d, rkv + dr), d),
            "kv_norm": jnp.ones((l, rkv), cfg.dtype),
            "w_uk": w((l, h, dn, rkv), rkv),
            "w_uv": w((l, h, rkv, dv), rkv),
            "wo": w((l, h * dv, d), h * dv),
        }
        if cfg.has_indexer:
            leaves.update({
                "idx_wq": w((l, hi * di, rq), rq),
                "idx_wk": w((l, d, di), d),
                "idx_k_gain": jnp.ones((l, di), cfg.dtype),
                "idx_k_bias": w((l, di), 16.0),
                "idx_w": w((l, d, hi), d),
            })
        return {**leaves, "mlp_norm": jnp.ones((l, d), cfg.dtype)}

    ld, ls, held = cfg.n_dense_layers, cfg.n_sparse_layers, cfg.held[1]
    return {
        "embed": w((cfg.vocab, d), d),
        "final_norm": jnp.ones((d,), cfg.dtype),
        "head": w((cfg.vocab, d), d),
        "dense": {**attn(ld),
                  "w_gate": w((ld, d, cfg.d_ff), d),
                  "w_up": w((ld, d, cfg.d_ff), d),
                  "w_down": w((ld, cfg.d_ff, d), cfg.d_ff)},
        "sparse": {**attn(ls),
                   "router": w((ls, d, e), d, jnp.float32),
                   **({"route_bias": w((ls, e), 400.0, jnp.float32)}
                      if cfg.topk_method == "noaux_tc" else {}),
                   "w_gate": w((ls, held, d, f), d),
                   "w_up": w((ls, held, d, f), d),
                   "w_down": w((ls, held, f, d), f),
                   "ws_gate": w((ls, d, fs), d),
                   "ws_up": w((ls, d, fs), d),
                   "ws_down": w((ls, fs, d), fs)},
    }


def init_latent_cache(cfg: LatentConfig, slots: int, page: int,
                      n_blocks: int) -> dict[str, jax.Array]:
    """The paged state: the latent plane, the indexer's key plane beside
    it where the model has an indexer, and ``table`` / ``len`` as
    ``init_paged_kv_cache`` lays them. Block 0 is the null block (never
    handed out; unmapped entries point at it)."""
    if cfg.max_seq % page:
        raise ValueError(f"kv page {page} must divide max_seq {cfg.max_seq}")
    state = {
        "table": jnp.zeros((slots, cfg.max_seq // page), jnp.int32),
        "len": jnp.zeros((slots,), jnp.int32),
        "ckv": jnp.zeros((cfg.n_layers, n_blocks, page, cfg.stored_width),
                         cfg.dtype),
    }
    if cfg.has_indexer:
        state["ik"] = jnp.zeros(
            (cfg.n_layers, n_blocks, page, cfg.index_dim), cfg.dtype)
    return state


# ------------------------------------------------------------- the block


def _rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
          positions: jax.Array) -> jax.Array:
    """Rotate the halves of x's last axis against each other by the
    position's angles; x [N, T, d] or [N, T, H, d], positions [N, T]."""
    c, s = cos[positions], sin[positions]  # [N, T, d/2]
    if x.ndim == 4:
        c, s = c[:, :, None], s[:, :, None]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1).astype(x.dtype)


def _rope_head(x: jax.Array, cos, sin, positions, dr: int) -> jax.Array:
    """Rotary positions on the first ``dr`` of the last axis only."""
    return jnp.concatenate(
        [_rope(x[..., :dr], cos, sin, positions), x[..., dr:]], axis=-1)


def _layer_norm(x: jax.Array, gain, bias, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    out = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (out * gain.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _swiglu(x, w_gate, w_up, w_down):
    gate = jax.nn.silu((x @ w_gate).astype(jnp.float32)).astype(x.dtype)
    return (gate * (x @ w_up)) @ w_down


def _attention(cfg: LatentConfig, lp, l: int, x, ckv, ik, rope, positions,
               tables, wblk, woff, given, lens):
    """One layer's attention half over x [N, T, D]: the latent and indexer
    projections, their rows written into layer ``l`` of the two planes at
    (wblk, woff) (out-of-range block ids drop), the selection, attention
    over the selected rows, the output projection and the residual (a
    model without an indexer: no second plane, no selection, attention
    over all that is cached; ``lens`` is what a decode step's slots read).
    In which form attention runs (absorbed in the latent space, as a
    decode step's; or over the window expanded into a head's keys and
    values, as a chunk's) is ``sparse_latent_attention``'s to choose from
    the shapes:
    it takes the query's parts and both up-projections and returns a
    head's values. Returns (x, ckv, ik, the selection: window indices
    [N, T, K] or a mask [N, T, W])."""
    n_, t_, _ = x.shape
    h, dn, dr, dv = cfg.n_heads, cfg.nope_dim, cfg.rope_dim, cfg.v_dim
    rkv = cfg.kv_rank
    cos, sin = rope
    with jax.named_scope("qkv"):
        n = rms_norm(x, lp["attn_norm"], cfg.eps)
        c_q = rms_norm(n @ lp["wq_a"], lp["q_norm"], cfg.eps)
        q = jnp.einsum("ntr,qr->ntq", c_q, lp["wq_b"]).reshape(
            n_, t_, h, dn + dr)
        q_pe = _rope(q[..., dn:], cos, sin, positions)
        kv = n @ lp["wkv_a"]
        latent = jnp.concatenate(
            [rms_norm(kv[..., :rkv], lp["kv_norm"], cfg.eps),
             _rope(kv[..., rkv:], cos, sin, positions)], axis=-1)
    with jax.named_scope("kv_write"):
        ckv = write_rows(ckv, l, wblk, woff, jnp.pad(
            latent, ((0, 0), (0, 0), (0, cfg.stored_width - cfg.latent_width))))
    with jax.named_scope("attn"):  # vbench/scopes.py's name for all of it
        if cfg.has_indexer:
            with jax.named_scope("indexer"):
                q_i = _rope_head(
                    jnp.einsum("ntr,qr->ntq", c_q, lp["idx_wq"]).reshape(
                        n_, t_, cfg.index_heads, cfg.index_dim),
                    cos, sin, positions, dr)
                k_i = _rope_head(_layer_norm(
                    n @ lp["idx_wk"], lp["idx_k_gain"], lp["idx_k_bias"],
                    cfg.eps), cos, sin, positions, dr)
                w_i = (n @ lp["idx_w"]).astype(jnp.float32) * (
                    cfg.index_heads ** -0.5 * cfg.index_dim ** -0.5)
                ik = write_rows(ik, l, wblk, woff, k_i)
            topk = cfg.index_topk
        else:
            q_i = w_i = topk = None
        attn, idx = sparse_latent_attention(
            ckv, ik, l, tables, positions, q[..., :dn], q_pe, lp["w_uk"],
            lp["w_uv"], q_i, w_i, topk, cfg.attn_scale, given=given,
            lens=lens)
    with jax.named_scope("o_proj"):
        x = x + attn.reshape(n_, t_, h * dv) @ lp["wo"]
    return x, ckv, ik, idx


def _dense_ffn(cfg: LatentConfig, lp, x):
    with jax.named_scope("mlp"):
        n = rms_norm(x, lp["mlp_norm"], cfg.eps)
        return x + _swiglu(n, lp["w_gate"], lp["w_up"], lp["w_down"])


def _sparse_ffn(cfg: LatentConfig, lp, x):
    """The shared expert plus this holder's part of the routed experts."""
    shape = x.shape
    first, count = cfg.held
    with jax.named_scope("route"):
        n = rms_norm(x, lp["mlp_norm"], cfg.eps).reshape(-1, shape[-1])
        if cfg.topk_method == "noaux_tc":
            gates = grouped_route(
                lp["router"], lp["route_bias"], n, cfg.top_k, cfg.n_group,
                cfg.topk_group, cfg.route_scale)
        else:
            gates = group_limited_route(
                lp["router"], n, cfg.top_k, cfg.n_group, cfg.topk_group,
                cfg.route_scale)
        gates = gates[:, first:first + count]
    with jax.named_scope("experts"):
        y = held_experts_ffn(lp, n, gates, cfg.top_k) + _swiglu(
            n, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return x + y.reshape(shape)


class LayerOfStack:
    """Layer ``i`` of a stack of leaves ``[L, ...]``, each leaf sliced where
    it is used: a copy the compiler makes of one (a projection laid out
    for its product) then lies under that part's scope in a trace."""

    def __init__(self, stack: dict, i: int):
        self.stack, self.i = stack, i

    def __getitem__(self, name: str) -> jax.Array:
        return self.stack[name][self.i]

    def stacked(self, name: str) -> tuple[jax.Array, int]:
        """(the leaf whole, this layer's index), for a kernel that reads
        the stack in place."""
        return self.stack[name], self.i


def _walk(params: Params, cfg: LatentConfig, ckv, ik, tokens, positions,
          tables, wblk, woff, given=None, lens=None):
    """Both stacks in order over tokens [N, T]: (hidden [N, T, D], ckv, ik,
    [the selection of each layer]); ``ik`` is None, and stays so, for a
    model without an indexer, whose decode step says with ``lens [N]``
    how many rows each slot's attention reads."""
    x = _embed(params, cfg, tokens)
    with jax.named_scope("qkv"):
        rope = cfg.rope_tables()
    selected, l = [], 0
    for kind, ffn in (("dense", _dense_ffn), ("sparse", _sparse_ffn)):
        stack = params[kind]
        for i in range(jax.tree_util.tree_leaves(stack)[0].shape[0]):
            lp = LayerOfStack(stack, i)
            x, ckv, ik, idx = _attention(
                cfg, lp, l, x, ckv, ik, rope, positions, tables, wblk, woff,
                None if given is None else given[l], lens)
            x = ffn(cfg, lp, x)
            selected.append(idx)
            l += 1
    return x, ckv, ik, selected


@jax.named_scope("lm_head")
def _head(params: Params, cfg: LatentConfig, x: jax.Array) -> jax.Array:
    """Final norm and the untied output head over the rows given."""
    x = rms_norm(x, params["final_norm"], cfg.eps)
    return (x @ params["head"].T).astype(jnp.float32)


# -------------------------------------------------------- the entry points


def _planes(ckv, ik) -> dict:
    """The pool planes of a state: the latent plane, and the indexer's
    keys where there are any."""
    return {"ckv": ckv} if ik is None else {"ckv": ckv, "ik": ik}


def latent_forward(params: Params, cfg: LatentConfig, tokens: jax.Array,
                   given=None, page: int = 8):
    """Full-sequence forward over a scratch pool of its own: tokens [B, S]
    -> (logits [B, S, V], the layers' selections). ``given`` (a list of
    [B, S, K] position indices, one a layer) takes the selections' place."""
    b, s = tokens.shape
    pages = -(-s // page)
    cache = init_latent_cache(
        dataclasses.replace(cfg, max_seq=pages * page), 1, page,
        1 + b * pages)
    tables = 1 + jnp.arange(b * pages, dtype=jnp.int32).reshape(b, pages)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    wblk = jnp.take_along_axis(tables, positions // page, axis=1)
    x, _, _, selected = _walk(
        params, cfg, cache["ckv"], cache.get("ik"), tokens, positions,
        tables, wblk, positions % page, given)
    return _head(params, cfg, x), selected


def latent_prefill_rows(params: Params, cfg: LatentConfig, state, tokens,
                        slots, true_lens):
    """Whole-prompt admission: N right-padded prompts [N, bucket] written
    through their slots' table rows (set by the engine's reservation before
    the dispatch). Returns (logits [N, V] at each prompt's last position,
    the state with those slots' lengths set). Pads write junk above the
    true length: masked by length now, overwritten before any query may
    see it; a pad past the reservation lands on the null block."""
    n, s = tokens.shape
    page = state["ckv"].shape[2]
    tables = state["table"][slots, :-(-s // page)]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (n, s))
    wblk = jnp.take_along_axis(tables, positions // page, axis=1)
    x, ckv, ik, _ = _walk(params, cfg, state["ckv"], state.get("ik"), tokens,
                          positions, tables, wblk, positions % page)
    last = x[jnp.arange(n), true_lens - 1]
    new = {**state, **_planes(ckv, ik),
           "len": state["len"].at[slots].set(true_lens)}
    return _head(params, cfg, last), new


def latent_prefill_chunk(params: Params, cfg: LatentConfig, state, chunk,
                         slot, offset, new_len, window: int, block_ids,
                         given=None):
    """One [1, C] chunk of a prompt at positions offset .. offset + C - 1,
    written into and read through ``block_ids`` ([window // page] pool
    blocks, padded with the null block 0): no slot and no table row is
    needed, so the engine's slot-less prefix build passes through here too
    (``slot`` out of range: the length write drops). Returns (logits
    [1, C, V], state)."""
    c = chunk.shape[1]
    page = state["ckv"].shape[2]
    n_blocks = state["ckv"].shape[1]
    positions = (offset + jnp.arange(c, dtype=jnp.int32))[None]
    # a position past the window has no block: its write drops
    wblk = jnp.take(block_ids, positions // page, mode="fill",
                    fill_value=n_blocks)
    x, ckv, ik, _ = _walk(
        params, cfg, state["ckv"], state.get("ik"), chunk, positions,
        block_ids[None, :window // page], wblk, positions % page, given)
    new = {**state, **_planes(ckv, ik),
           "len": state["len"].at[slot].set(new_len, mode="drop")}
    return _head(params, cfg, x), new


def latent_decode_step(params: Params, cfg: LatentConfig, state, tokens,
                       active, window: int, given=None):
    """One decode tick for the whole slot pool: tokens [B], active [B] ->
    (logits [B, V], state). Each slot writes its new rows at its own
    length and reads the first ``window`` positions of its table row: the
    indexer's keys of the whole window, the latents of the selected only;
    or, where nothing selects, its own ``len + 1`` rows page by page. An
    inactive slot (its table row may be stale) writes nowhere: its block
    id is out of range and the scatter drops it; nor does it read, where
    the walk is told lengths."""
    page, n_blocks = state["ckv"].shape[2], state["ckv"].shape[1]
    lens = state["len"]
    rows = jnp.arange(tokens.shape[0])
    here = jnp.minimum(lens // page, state["table"].shape[1] - 1)
    wblk = jnp.where(active & (lens < cfg.max_seq),
                     state["table"][rows, here], n_blocks)
    x, ckv, ik, _ = _walk(
        params, cfg, state["ckv"], state.get("ik"), tokens[:, None],
        lens[:, None], state["table"][:, :window // page], wblk[:, None],
        (lens % page)[:, None], given,
        None if cfg.has_indexer else jnp.where(active, lens + 1, 0))
    new = {**state, **_planes(ckv, ik),
           "len": jnp.where(active, lens + 1, lens)}
    return _head(params, cfg, x[:, 0]), new
