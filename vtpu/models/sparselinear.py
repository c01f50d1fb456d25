"""A decoder of two mixers with three kinds of per-session state: block-sparse
attention layers, which select whole pages of a head-cached pool by a score
over compressed keys, among linear-attention layers whose recurrent state is
a matrix a head driven by a key a head (the block of MiniCPM-SALA:
InfLLM-v2 beside Lightning Attention; ``layer_types`` says which layer is
which).

What differs from ``hybrid.py``, whose walk, residual, ``n_valid`` rule and
run-of-layers ``fori_loop`` this module shares (``hybrid.layer_runs``,
``_ssd_step`` / ``_ssd_chunked`` / ``ops.ssm_step`` with a ``B`` / ``C``
row a head), by mechanism:

- **A session's state is pages, compressed keys and rows.** A sparse layer
  keeps keys and values in the paged pool, one plane a (layer, key/value
  head) pair (``[Ls * Hk, n_blocks, page, 1, D]``: the shared cache
  machinery sees Ls * Hk layers of one head, so a selected page of one head
  is one contiguous block and the selection's table a head walks it), and
  beside them ``ck [Ls * Hk, n_blocks, page // stride, D]``, the compressed
  keys (``ops/blocksparse.py``), walked by the same page table. A linear
  layer keeps ``s [Ll, slots, H, D, D]`` float32 whatever the session's
  length. All live in the one engine state.
- **The sparse mixer**: QK-norm, no rotary, an output gate; a query that
  sees more than ``dense_len`` tokens attends the ``topk`` blocks its
  selection keeps (forced ones among them), else everything it sees. A
  decode step turns the selection into a page table a key/value head and
  walks it with the pool's own routes (the grouped kernel on a TPU, the
  gather route elsewhere: ``paged_attn`` as the dense family's); a chunk
  attends its gathered window under the selection's mask.
- **The linear mixer**: QK-norm, rotary on the whole head, ``S_t = lambda
  S_{t-1} + k_t v_t^T``, ``o_t = q_t^T S_t / sqrt(D)``, an output norm a
  head, an output gate: the state-space form with ``dt = 1``, ``A = log
  lambda``, ``B = k``, ``C = q``, ``x = v``, ``D = 0``. ``lambda`` is a
  head's and a layer's (``decay_log``): a later layer of the published
  model forgets more slowly, so ``layer_index`` says where in it each of
  this stage's layers lies.
- **The muP scalars**: the embedding times ``scale_emb``, every residual
  branch times ``scale_depth / sqrt(depth)`` (``depth`` the published
  number of layers, whatever this stage holds), the logits over ``d_model
  / dim_model_base``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from vtpu.models import slots as slot_steps
from vtpu.models.hybrid import (
    _ssd_chunked,
    _ssd_step,
    _step_operands,
    layer_runs,
    step_in_kernel,
)
from vtpu.models.transformer import (
    PROJECTIONS,
    ModelConfig,
    chunk_window_attention,
    hold_projections as _hold_projections,
    init_paged_kv_cache,
    kv_plane_shape,
)
from vtpu.ops import (
    apply_rope,
    paged_attn_route,
    paged_causal_attention,
    paged_decode_attention,
    rms_norm,
    rope_angles,
    scaled_normal,
)
from vtpu.ops import blocksparse, chunk_attn
from vtpu.ops.ssm_step import ssm_state_step

Params = dict[str, Any]
KV_KEYS = ("k", "v")
KINDS = ("sparse", "linear")


@dataclasses.dataclass(frozen=True)
class SparseLinearConfig:
    """Toy sizes by default; vbench/sut/sparselinear.py gives the published."""

    vocab: int = 256
    d_model: int = 128
    layer_types: tuple = ("sparse", "linear", "linear", "sparse", "linear")
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 32
    d_ff: int = 256
    lin_heads: int = 4
    lin_head_dim: int = 32     # a head's keys and values alike
    ssd_chunk: int = 8         # the chunked form's chunk
    # the selection (MiniCPM4's sparse_config; kernel = 2 * stride)
    kernel_stride: int = 2
    block_size: int = 8        # = the pool's page
    window_size: int = 16
    init_blocks: int = 1
    topk: int = 4
    dense_len: int = 48
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    depth: int = 32            # the published number of layers
    layer_index: tuple = ()    # each layer's place among them; () = 0, 1, ..
    dim_model_base: int = 32
    rope_theta: float = 10000.0
    eps: float = 1e-6
    max_seq: int = 256
    dtype: Any = jnp.bfloat16
    kv_int8: bool = False      # refused by the adapter: stated to be refused

    def __post_init__(self):
        odd = set(self.layer_types) - set(KINDS)
        if odd:
            raise ValueError(f"layer_types holds unknown kinds {sorted(odd)}")
        if self.block_size % self.kernel_stride or (
                self.window_size % self.block_size):
            raise ValueError("whole strides a block, whole blocks a window")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("whole groups of query heads")
        at = tuple(self.layer_index) or tuple(range(self.n_layers))
        if len(at) != self.n_layers or not all(
                0 <= i < self.depth for i in at):
            raise ValueError(
                f"layer_index {at} places {self.n_layers} layers in a "
                f"model of {self.depth}")
        object.__setattr__(self, "layer_index", at)

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_sparse_layers(self) -> int:
        return self.layer_types.count("sparse")

    @property
    def n_linear_layers(self) -> int:
        return self.layer_types.count("linear")

    @property
    def group(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def windows_per_block(self) -> int:
        return self.block_size // self.kernel_stride

    @property
    def n_sel(self) -> int:
        """Pages a decode step's selected table holds: the ``topk`` blocks,
        or every block of a session that still attends whole."""
        return max(self.topk, -(-self.dense_len // self.block_size))

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.depth)

    @property
    def attention(self) -> ModelConfig:
        """The sparse layers' cache as the shared machinery reads it: a
        plane a (layer, key/value head) pair, one head of ``head_dim``."""
        return ModelConfig(
            vocab=self.vocab, d_model=self.d_model, n_heads=self.group,
            n_layers=self.n_sparse_layers * self.n_kv_heads, d_ff=self.d_ff,
            max_seq=self.max_seq, head_dim=self.head_dim, dtype=self.dtype,
            n_kv_heads=1, rotary=False, eps=self.eps)

    @property
    def kv_bytes_per_token(self) -> int:
        """Keys, values and the compressed keys' share, all sparse layers."""
        el = jnp.dtype(self.dtype).itemsize
        row = self.n_kv_heads * self.head_dim * el
        return self.n_sparse_layers * (2 * row + row // self.kernel_stride)

    @property
    def recurrent_bytes_per_slot(self) -> int:
        return self.n_linear_layers * self.lin_heads * self.lin_head_dim ** 2 * 4


def decay_log(cfg: SparseLinearConfig) -> jax.Array:
    """log lambda of every linear layer and head, [Ll, H] float32: ``-2^(-8
    (h + 1) / H) (1 - l / (depth - 1) + 1e-5)``, h = 0 .. H - 1, ``l`` the
    layer's place in the published model of ``depth`` layers."""
    heads = np.arange(1, cfg.lin_heads + 1)
    at = np.asarray([i for i, kind in zip(cfg.layer_index, cfg.layer_types)
                     if kind == "linear"])
    rate = 1.0 - at / max(cfg.depth - 1, 1) + 1e-5
    return jnp.asarray(
        -rate[:, None] * np.exp2(-8.0 * heads / cfg.lin_heads)[None, :],
        jnp.float32)


def init_sparselinear_params(rng: jax.Array, cfg: SparseLinearConfig,
                             q_gain: float = 2.0) -> Params:
    """Seeded weights at toy sizes, each kind's leaves stacked [L, ...];
    ``q_gain`` spreads the sparse layers' scores (a normed head scores a
    mean of keys within rounding of every other at a gain of one)."""
    d, f = cfg.d_model, cfg.d_ff
    qd, kvd = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    ld = cfg.lin_heads * cfg.lin_head_dim
    ls, ll = cfg.n_sparse_layers, cfg.n_linear_layers
    keys = iter(jax.random.split(rng, 32))

    def w(shape, fan_in):
        return scaled_normal(next(keys), shape, fan_in, cfg.dtype)

    def ones(*shape):
        return jnp.ones(shape, cfg.dtype)

    def mlp(l):
        return {"mlp_norm": ones(l, d), "w_gate": w((l, d, f), d),
                "w_up": w((l, d, f), d), "w_down": w((l, f, d), f)}

    return {
        "embed": w((cfg.vocab, d), d * (cfg.scale_emb / 1.5) ** 2),
        "head": w((cfg.vocab, d), d),
        "final_norm": ones(d),
        "sparse": {
            "attn_norm": ones(ls, d),
            "wq": w((ls, d, qd), d), "wk": w((ls, d, kvd), d),
            "wv": w((ls, d, kvd), d),
            "q_norm": ones(ls, cfg.head_dim) * q_gain,
            "k_norm": ones(ls, cfg.head_dim),
            "wg": w((ls, d, qd), d), "wo": w((ls, qd, d), qd), **mlp(ls)},
        "linear": {
            "attn_norm": ones(ll, d),
            "wq": w((ll, d, ld), d), "wk": w((ll, d, ld), d),
            "wv": w((ll, d, ld), d),
            "q_norm": ones(ll, cfg.lin_head_dim),
            "k_norm": ones(ll, cfg.lin_head_dim),
            "o_norm": ones(ll, cfg.lin_head_dim),
            "wg": w((ll, d, ld), d), "wo": w((ll, ld, d), ld), **mlp(ll)},
    }


def init_sparselinear_state(cfg: SparseLinearConfig, slots: int, page: int,
                            n_blocks: int) -> dict[str, jax.Array]:
    """The paged pool of the sparse layers (``table`` / ``len`` / ``k`` /
    ``v`` as ``init_paged_kv_cache`` lays them, a plane a layer and head),
    the compressed keys' plane and the linear layers' slot-indexed rows."""
    if page != cfg.block_size:
        raise ValueError(
            f"kv_page {page} must be the selection's block_size "
            f"{cfg.block_size}: a selected block is a page")
    state = init_paged_kv_cache(cfg.attention, slots, page, n_blocks)
    state["ck"] = jnp.zeros(
        (cfg.n_sparse_layers * cfg.n_kv_heads, n_blocks,
         cfg.windows_per_block, cfg.head_dim), cfg.dtype)
    state["s"] = _empty_rows(cfg, slots)
    return state


def _empty_rows(cfg: SparseLinearConfig, n: int) -> jax.Array:
    """s [Ll, n, H, D, D] float32 of n sequences that have read nothing."""
    return jnp.zeros((cfg.n_linear_layers, n, cfg.lin_heads,
                      cfg.lin_head_dim, cfg.lin_head_dim), jnp.float32)


# ------------------------------------------------------------ the mixers


def _residual(cfg: SparseLinearConfig, x, branch):
    return (x.astype(jnp.float32)
            + cfg.residual_scale * branch.astype(jnp.float32)).astype(x.dtype)


def _gated(x, gate):
    return (x.astype(jnp.float32)
            * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(gate.dtype)


def _heads_of(n, w, head_dim: int):
    """n [B, T, d] projected to heads [B, T, H, Dh]: by a layer's published
    ``w [d, H * Dh]`` or by a serving adapter's held ``[H, Dh, d]``
    (``hold_projections``); the leaf's rank says which, as in
    ``transformer._qkv``, and either way an output is the same dot product
    over d."""
    if w.ndim == 3:
        return jnp.einsum("btd,hed->bthe", n, w)
    return (n @ w).reshape(n.shape[:2] + (-1, head_dim))


def hold_projections(params: Params, cfg: SparseLinearConfig) -> Params:
    """The parameters with both stacks' ``wq``, ``wk``, ``wv`` held as a
    serving program's products read them, ``[L, H, Dh, d]``
    (``transformer.hold_projections``: from the published ``[L, d, H * Dh]``
    the v5e's compiler copied each whole stack into that layout at every
    launch; three stacks of 201 MB a decode step here)."""
    linear = ModelConfig(n_heads=cfg.lin_heads, head_dim=cfg.lin_head_dim)
    sparse = ModelConfig(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                         head_dim=cfg.head_dim)
    return {**params,
            "linear": _hold_projections(params["linear"], linear),
            "sparse": _hold_projections(params["sparse"], sparse)}


def _linear_mixer(cfg: SparseLinearConfig, lp, x, s, a, n_valid, positions,
                  rope, layer=None):
    """One linear-attention mixer over x [B, T, D] from the carried rows
    ``s`` [B, H, D, D] under the layer's ``a`` = log lambda [H]; ``n_valid``
    [B] of each row's T tokens are real (the first ones); positions [B, T].
    Returns (x + r * mixer(x), s). With ``layer`` (a step in the kernel),
    ``s`` is the whole stack [Ll, B, H, D, D], taken and returned."""
    b, t, _ = x.shape
    nh, dh = cfg.lin_heads, cfg.lin_head_dim
    f32 = jnp.float32
    with jax.named_scope("qkv"):
        n = rms_norm(x, lp["attn_norm"], cfg.eps)
        q, k, v = (_heads_of(n, lp[name], dh) for name in PROJECTIONS)
        gate = n @ lp["wg"]
        q = apply_rope(rms_norm(q, lp["q_norm"], cfg.eps), *rope, positions)
        k = apply_rope(rms_norm(k, lp["k_norm"], cfg.eps), *rope, positions)
    with jax.named_scope("attn"):  # vbench/scopes.py's name for the two
        with jax.named_scope("ssm_scan"):
            real = jnp.arange(t)[None, :] < n_valid[:, None]
            dt = jnp.broadcast_to(real[..., None], (b, t, nh)).astype(f32)
            if layer is not None:  # the stack, this layer of it in place
                y, s = ssm_state_step(
                    s, layer, *_step_operands(v, dt, a, k, q),
                    # a test that patches the rule reaches here on the CPU
                    interpret=jax.default_backend() != "tpu")
                y = y[:, None]
            elif t == 1:
                y, s = _ssd_step(v, dt, a, k, q, s)
            else:
                y, s = _ssd_chunked(v, dt, a, k, q, s, cfg.ssd_chunk)
        with jax.named_scope("ssm_gate"):
            y = rms_norm(y * (1.0 / math.sqrt(dh)), lp["o_norm"], cfg.eps)
            y = _gated(y.reshape(b, t, nh * dh), gate)
    with jax.named_scope("o_proj"):
        return _residual(cfg, x, y.astype(x.dtype) @ lp["wo"]), s


def _sparse_qkv(cfg: SparseLinearConfig, lp, x):
    """(q [B, T, Hk, G, D], k, v [B, T, Hk, D], the gate [B, T, Hq * D])."""
    b, t, _ = x.shape
    hk, g, dh = cfg.n_kv_heads, cfg.group, cfg.head_dim
    with jax.named_scope("qkv"):
        n = rms_norm(x, lp["attn_norm"], cfg.eps)
        q, k, v = (_heads_of(n, lp[name], dh) for name in PROJECTIONS)
        q = rms_norm(q.reshape(b, t, hk, g, dh), lp["q_norm"], cfg.eps)
        k = rms_norm(k, lp["k_norm"], cfg.eps)
        return q, k, v, n @ lp["wg"]


@jax.named_scope("mlp")
def _mlp(cfg: SparseLinearConfig, lp, x):
    """x + r * SwiGLU(rms_norm(x))."""
    n = rms_norm(x, lp["mlp_norm"], cfg.eps)
    gate = jax.nn.silu((n @ lp["w_gate"]).astype(jnp.float32)).astype(x.dtype)
    return _residual(cfg, x, (gate * (n @ lp["w_up"])) @ lp["w_down"])


@jax.named_scope("embed")
def _embed(params: Params, cfg: SparseLinearConfig, tokens):
    return (params["embed"][tokens].astype(jnp.float32)
            * cfg.scale_emb).astype(cfg.dtype)


@jax.named_scope("lm_head")
def _head(params: Params, cfg: SparseLinearConfig, x):
    """Final norm, the untied output head, the logits' divisor."""
    x = rms_norm(x, params["final_norm"], cfg.eps)
    return (x @ params["head"].T).astype(jnp.float32) / (
        cfg.d_model / cfg.dim_model_base)


def _walk(params: Params, cfg: SparseLinearConfig, tokens, n_valid,
          positions, kv, s, attend):
    """Every layer in the model's order over tokens [B, T]: the sparse
    layers through ``attend(l, lp, x, kv) -> (x, kv)`` (over whatever cache
    ``kv`` is), each run of linear layers a ``fori_loop`` from and into the
    rows ``s`` [Ll, B, H, D, D] of these B sequences. Returns (hidden
    [B, T, D], kv, s)."""
    x = _embed(params, cfg, tokens)
    linear, sparse = params["linear"], params["sparse"]
    in_kernel = step_in_kernel(tokens.shape[1])
    rope = rope_angles(cfg.max_seq, cfg.lin_head_dim, cfg.rope_theta)
    decays = decay_log(cfg)

    def linear_layer(l, carry):
        x, s = carry
        lp = jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, l, 0, keepdims=False),
            linear)
        with jax.named_scope("attn"), jax.named_scope("ssm_scan"):
            row = s if in_kernel else jax.lax.dynamic_index_in_dim(
                s, l, 0, False)
        x, row = _linear_mixer(
            cfg, lp, x, row, jax.lax.dynamic_index_in_dim(decays, l, 0, False),
            n_valid, positions, rope, l if in_kernel else None)
        x = _mlp(cfg, lp, x)
        with jax.named_scope("attn"), jax.named_scope("ssm_scan"):
            return x, (row if in_kernel
                       else jax.lax.dynamic_update_index_in_dim(s, row, l, 0))

    for kind, first, end in layer_runs(cfg.layer_types):
        if kind == "linear":
            x, s = jax.lax.fori_loop(first, end, linear_layer, (x, s))
            continue
        for l in range(first, end):
            lp = jax.tree_util.tree_map(lambda a: a[l], sparse)
            x, kv = attend(l, lp, x, kv)
            x = _mlp(cfg, lp, x)
    return x, kv, s


def _close(cfg: SparseLinearConfig, lp, x, attn, gate):
    """The output gate, the projection and the residual of a sparse layer:
    attn [B, T, Hk, G, D]."""
    with jax.named_scope("o_proj"):
        o = _gated(attn.reshape(x.shape[:2] + (-1,)), gate)
        return _residual(cfg, x, o @ lp["wo"])


# ---------------------------------------------- a decode step's attention


def _completed_window(cfg: SparseLinearConfig, kv, l: int, tables, lens,
                      active):
    """The compressed key that the token a decode step wrote at ``lens``
    completes, written into ``ck``: window ``j`` ends at ``stride j + kernel
    - 1``; a step at another position, or of an idle slot, writes nothing.
    Its ``kernel`` keys are read back from the pool (the new one is in)."""
    stride, page = cfg.kernel_stride, cfg.block_size
    kernel, hk = 2 * stride, cfg.n_kv_heads
    nb = kv["k"].shape[1]
    first = lens - (kernel - 1)
    done = active & (first >= 0) & (first % stride == 0)
    at = jnp.maximum(first, 0)[:, None] + jnp.arange(kernel)[None, :]
    rows = jnp.take_along_axis(tables, at // page, axis=1) * page + at % page
    j = jnp.maximum(first, 0) // stride
    per = cfg.windows_per_block
    blk = jnp.where(done, jnp.take_along_axis(
        tables, (j // per)[:, None], axis=1)[:, 0], nb)
    ck = kv["ck"]
    flat = kv["k"].reshape(kv["k"].shape[0], nb * page, cfg.head_dim)
    for h in range(hk):  # a gather from the stack: no layer is sliced out
        mean = flat[l * hk + h, rows].astype(jnp.float32).mean(axis=1)
        ck = ck.at[l * hk + h, blk, j % per].set(
            mean.astype(ck.dtype), mode="drop")
    return ck


def decode_attend(cfg: SparseLinearConfig, state, active, window: int,
                  paged_attn=None):
    """``attend(l, lp, x, kv) -> (x, kv)`` of a decode step over the slot
    pool: the new key and value written at each slot's own length, the
    compressed key a step completes, the selection over the windows a slot
    sees, and the walk of the pages it keeps (kernel or gather route)."""
    acfg = cfg.attention
    lens = state["len"]
    page, hk = cfg.block_size, cfg.n_kv_heads
    write_kv = slot_steps.decode_kv_writer(acfg, state, active)
    tables = state["table"][:, :window // page]
    nb_w = tables.shape[1]
    per = cfg.windows_per_block
    scale = 1.0 / math.sqrt(cfg.head_dim)
    n_sel = min(cfg.n_sel, nb_w)
    in_kernel = paged_attn_route(paged_attn, window, t=1) == "kernel"
    positions = jnp.minimum(lens, cfg.max_seq - 1)

    def attend(l, lp, x, kv):
        q, k, v, gate = _sparse_qkv(cfg, lp, x)
        b = x.shape[0]
        with jax.named_scope("kv_write"):
            for h in range(hk):
                kv = {**kv, **write_kv(l * hk + h, kv, k[:, :, h:h + 1],
                                       v[:, :, h:h + 1])}
            kv["ck"] = _completed_window(cfg, kv, l, tables, lens, active)
        with jax.named_scope("attn"):
            with jax.named_scope("indexer"):
                comp = kv["ck"][  # [Hk, B, Wp, per, D], from the stack
                    (l * hk + jnp.arange(hk))[:, None, None], tables[None]]
                comp = comp.reshape(hk, b, nb_w * per, cfg.head_dim)
                wins = blocksparse.window_scores(
                    q, comp, positions[:, None], cfg.kernel_stride, scale)
            with jax.named_scope("select"):
                score = blocksparse.block_scores(
                    wins, positions[:, None], cfg, nb_w)[:, 0]
                pages, sel_lens = blocksparse.selected_pages(
                    score, positions, tables, cfg, n_sel)
            out = []
            for h in range(hk):
                if in_kernel:
                    out.append(paged_decode_attention(
                        q[:, :, h], kv["k"], kv["v"], pages[:, h],
                        sel_lens[:, h, None], layer=l * hk + h, scale=scale))
                else:
                    with jax.named_scope("gather_attn"):
                        out.append(paged_causal_attention(
                            q[:, :, h], kv["k"][l * hk + h],
                            kv["v"][l * hk + h], pages[:, h],
                            kv_len=sel_lens[:, h, None], scale=scale))
            attn = jnp.stack(out, axis=2)
        return _close(cfg, lp, x, attn, gate), kv

    return attend


# ------------------------------------------ a chunk's attention (a window)


def _masked_head(q, keys, values, keep, positions, page, scale, h):
    """``blocksparse.masked_attention`` for key/value head ``h`` alone:
    [N, T, G, D]."""
    one = slice(h, h + 1)
    return blocksparse.masked_attention(
        q[:, :, one], keys[one], values[one], keep[:, :, one], positions,
        page, scale)[:, :, 0]


def window_attend(cfg: SparseLinearConfig, offset, n_valid, window: int):
    """``attend(l, lp, x, kv) -> (x, kv)`` of T queries a sequence over a
    dense window view ``kv`` (``k``, ``v`` [Ls * Hk, N, W, 1, D], ``ck``
    [Ls * Hk, N, W // stride, D]): the chunk's keys and values written at
    ``offset`` .. ``offset + T - 1`` (every sequence's alike: a chunk's one,
    0 for whole prompts), the compressed keys whose last token is among a
    sequence's first ``n_valid`` [N], then each query's selection and its
    attention under the mask; a window of at most ``dense_len`` positions
    holds no query that selects and is attended as the dense family's
    chunk is."""
    hk, dh, stride = cfg.n_kv_heads, cfg.head_dim, cfg.kernel_stride
    page, per = cfg.block_size, cfg.windows_per_block
    nb_w = window // page
    scale = 1.0 / math.sqrt(dh)

    def attend(l, lp, x, kv):
        q, k, v, gate = _sparse_qkv(cfg, lp, x)
        n, t = x.shape[:2]
        positions = jnp.broadcast_to(offset + jnp.arange(t), (n, t))
        first = l * hk
        planes = slice(first, first + hk)
        with jax.named_scope("kv_write"):
            kv = dict(kv)
            for key, new in (("k", k), ("v", v)):  # [N, T, Hk, D] into place
                kv[key] = jax.lax.dynamic_update_slice(
                    kv[key], jnp.moveaxis(new, 2, 0)[:, :, :, None],
                    (first, 0, offset, 0, 0))
            # windows whose last token the chunk's real tokens hold: from
            # the first stride-aligned window that can end in the chunk
            nj = min(t // stride + 1, window // stride - 1)
            j0 = jnp.clip(-(-(offset - 2 * stride + 1) // stride), 0,
                          window // stride - nj - 1)
            halves = blocksparse.half_sums(jax.lax.dynamic_slice(
                kv["k"], (first, 0, j0 * stride, 0, 0),
                (hk, n, (nj + 1) * stride, 1, dh))[:, :, :, 0], stride)
            comp = ((halves[:, :, :-1] + halves[:, :, 1:])
                    / (2 * stride)).astype(kv["ck"].dtype)  # [Hk, N, nj, D]
            last = (j0 + jnp.arange(nj)) * stride + 2 * stride - 1
            new = (last >= offset)[None, :] & (
                last[None, :] < (offset + n_valid)[:, None])    # [N, nj]
            old = jax.lax.dynamic_slice(
                kv["ck"], (first, 0, j0, 0), (hk, n, nj, dh))
            kv["ck"] = jax.lax.dynamic_update_slice(
                kv["ck"], jnp.where(new[None, :, :, None], comp, old),
                (first, 0, j0, 0))
        with jax.named_scope("attn"):
            keys, values = kv["k"][planes, :, :, 0], kv["v"][planes, :, :, 0]
            if window <= cfg.dense_len:
                reach = jnp.minimum(positions + 1, window)
                attn = jnp.stack([chunk_window_attention(
                    q[:, :, h], keys[h][:, :, None], values[h][:, :, None],
                    reach, scale) for h in range(hk)], axis=2)
            else:
                with jax.named_scope("indexer"):
                    wins = blocksparse.window_scores(
                        q, kv["ck"][planes], positions, stride, scale)
                with jax.named_scope("select"):
                    keep = blocksparse.kept_mask(
                        blocksparse.block_scores(wins, positions, cfg, nb_w),
                        positions, cfg)
                with jax.named_scope("gather_attn"):
                    # a key/value head's G query heads over its own plane
                    # of the stack, read where it lies: the chunk kernel
                    # under the selection's mask where it takes the shapes,
                    # else the same attention as XLA code
                    flat = {key: kv[key].reshape(kv[key].shape[:3] + (dh,))
                            for key in KV_KEYS}
                    reach = jnp.minimum(positions + 1, window)
                    attn = jnp.stack([chunk_attn.attend_window(
                        q[:, :, h], flat["k"], flat["v"], reach, scale,
                        functools.partial(
                            _masked_head, q, keys, values, keep, positions,
                            page, scale, h),
                        layer=l * hk + h, keep=keep[:, :, h:h + 1])
                        for h in range(hk)], axis=2)
        return _close(cfg, lp, x, attn, gate), kv

    return attend


# -------------------------------------------------------- the entry points


def _fresh_rows(params: Params, cfg: SparseLinearConfig, tokens, true_lens):
    """N right-padded prompts [N, S] from empty state, over a scratch window
    of their own: (hidden [N, S, D], the window's planes, s at each row's
    ``true_len``)."""
    n, s = tokens.shape
    acfg = cfg.attention
    scratch = {key: jnp.zeros((acfg.n_layers, n, s) + kv_plane_shape(acfg),
                              cfg.dtype) for key in KV_KEYS}
    scratch["ck"] = jnp.zeros(
        (acfg.n_layers, n, s // cfg.kernel_stride, cfg.head_dim), cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (n, s))
    return _walk(params, cfg, tokens, true_lens, positions, scratch,
                 _empty_rows(cfg, n),
                 window_attend(cfg, jnp.int32(0), true_lens, s))


def sparselinear_forward(params: Params, cfg: SparseLinearConfig,
                         tokens: jax.Array) -> jax.Array:
    """Full-sequence forward: tokens [B, S] -> logits [B, S, V]."""
    lens = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
    x, _, _ = _fresh_rows(params, cfg, tokens, lens)
    return _head(params, cfg, x)


def sparselinear_prefill_rows(params: Params, cfg: SparseLinearConfig, state,
                              tokens, slots, true_lens):
    """Whole-prompt admission: N right-padded prompts [N, bucket] computed
    from empty state and installed, pages and compressed keys through the
    slots' table rows (set by the engine's reservation before the dispatch)
    and the recurrent rows at each prompt's ``true_len``. Returns (logits
    [N, V] at each prompt's last token, the state)."""
    n, s = tokens.shape
    x, seq, rows = _fresh_rows(params, cfg, tokens, true_lens)
    logits = _head(params, cfg, x[jnp.arange(n), true_lens - 1])
    _, new = slot_steps._scatter_prefill_pages(
        state, seq, logits, slots, true_lens, s)
    with jax.named_scope("kv_write"):
        page = cfg.block_size
        blk = state["table"][slots, :s // page]
        new["ck"] = state["ck"].at[:, blk].set(seq["ck"].reshape(
            seq["ck"].shape[0], n, s // page, cfg.windows_per_block,
            cfg.head_dim))
        new["s"] = state["s"].at[:, slots].set(rows)
    return logits, new


def sparselinear_prefill_chunk(params: Params, cfg: SparseLinearConfig, state,
                               chunk, slot, offset, new_len, window: int,
                               block_ids):
    """One [1, C] chunk of a prompt at positions offset .. offset + C - 1 of
    ``slot``, the first ``new_len - offset`` of them real: its keys, values
    and completed compressed keys written into and read through
    ``block_ids``, its linear layers run from the slot's carried rows (zeros
    at offset 0) and written back. Returns (logits [1, C, V], state)."""
    c = chunk.shape[1]
    page = cfg.block_size
    view = slot_steps._chunk_window(state, KV_KEYS, window, slot, block_ids,
                                    None)
    with jax.named_scope("gather_attn"):
        ck = state["ck"][:, block_ids]  # [Ls * Hk, Wp, per, D]
        view["ck"] = ck.reshape(ck.shape[0], 1, -1, cfg.head_dim)
    n_valid = jnp.reshape(new_len - offset, (1,)).astype(jnp.int32)
    positions = jnp.minimum(offset + jnp.arange(c)[None, :], cfg.max_seq - 1)
    with jax.named_scope("kv_write"):
        # what the slot's earlier chunks left, or zeros at offset 0 (the
        # slot may hold an ended session's)
        carried = jnp.where(offset > 0, state["s"][:, slot], 0)[:, None]
    x, new_view, rows = _walk(
        params, cfg, chunk, n_valid, positions, view, carried,
        window_attend(cfg, offset, n_valid, window))
    new = slot_steps._chunk_write_back(
        state, new_view, KV_KEYS, window, c, slot, offset, new_len, block_ids)
    with jax.named_scope("kv_write"):
        # the pages the chunk's windows can lie in: its own and the one
        # before (a window is stored where its first token is)
        wp = window // page
        span = min(-(-c // page) + 2, wp)
        p0 = jnp.clip(offset // page - 1, 0, wp - span)
        ids = jax.lax.dynamic_slice(block_ids, (p0,), (span,))
        pages = new_view["ck"].reshape(
            new_view["ck"].shape[0], wp, cfg.windows_per_block, cfg.head_dim)
        written = jax.lax.dynamic_slice(
            pages, (0, p0, 0, 0), (pages.shape[0], span) + pages.shape[2:])
        new["ck"] = state["ck"].at[:, ids].set(written)
        # in place, a slot's rows alone: a scatter that may drop its row is
        # lowered to a pass over the whole stack (1.2 GB at the cell's
        # sizes), and no chunk of this family runs without a slot (a shared
        # prefix and a slot-less prefill are refused)
        new["s"] = jax.lax.dynamic_update_slice(
            state["s"], rows, (0, slot, 0, 0, 0))
    return _head(params, cfg, x), new


def sparselinear_decode_step(params: Params, cfg: SparseLinearConfig, state,
                             tokens, active, window: int, paged_attn=None):
    """One decode tick for the whole slot pool: tokens [B], active [B] ->
    (logits [B, V], state). An active slot writes its key, value and (where
    the token completes a window) compressed key, selects and attends its
    kept pages, and moves its recurrent rows one token on; an inactive slot
    writes nothing and its rows pass through as they stood."""
    lens = state["len"]
    positions = jnp.minimum(lens, cfg.max_seq - 1)[:, None]
    attend = decode_attend(cfg, state, active, window, paged_attn)
    kv = {key: state[key] for key in KV_KEYS + ("ck",)}
    x, kv, rows = _walk(params, cfg, tokens[:, None],
                        active.astype(jnp.int32), positions, kv, state["s"],
                        attend)
    new = {**state, **kv, "s": rows,
           "len": jnp.where(active, lens + 1, lens)}
    return _head(params, cfg, x[:, 0]), new
