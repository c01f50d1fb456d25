"""A decoder whose attention layers are of two kinds with two kinds of
per-session cache (MiMo-V2's block; ``layer_types`` says which layer is
which): **full** layers that read all that is cached, and **window** layers
that read the last ``window`` positions under a softmax with a learned sink.

What differs from ``transformer.py``, by mechanism:

- **A session's cache is pages and a ring.** The full layers keep a token's
  key and value in a paged pool walked by the one page table (``k [Lf,
  n_blocks, page, Hk * Dk]``, ``v [Lf, n_blocks, page, Hk * Dv]``); pages
  are charged for them alone. The window layers keep, a slot, a ring of
  ``window`` rows (``wk [Lw, slots, window, Hkw * Dk]``, ``wv`` likewise),
  position p at row ``p % window``: bytes that do not grow with a session.
  A ring is never cleared: a row that holds no position its reader may see
  is masked by the reader's own position, so a slot given to a new session
  reads nothing of the last one (``ops/window_attn.py``).
- **Heads wider for keys than for values**, stored side by side in rows of
  whole 128-lane tiles whatever a head's width (4 x 192 = 768 and 4 x 128 =
  512 columns a token a full layer, 2560 B in bfloat16; 8 heads, 5120 B a
  ring row), and fewer key/value heads in a full layer than in a window
  layer. The value is scaled as it is projected (``value_scale``).
- **Rotary positions on the first ``rope_dim`` columns of a head**, halves
  rotated against each other, the rest unrotated; a base a layer kind.
- **A leading dense SwiGLU, then expert layers** under the sigmoid router
  of ``moe.grouped_route`` (one group: nothing is limited), of which this
  holder computes its own experts' part (``moe.held_experts_ffn``).

One walk (``_walk``) serves every entry point, every layer unrolled (a
static index into its kind's stack): layers alike in every leaf (the kind of
attention and the kind of block after it) are stacked together, as
``layer_kinds`` names them.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from vtpu.models.latent import LayerOfStack, _rope_head, _swiglu
from vtpu.models.moe import grouped_route, held_experts_ffn
from vtpu.models.transformer import _held_projection
from vtpu.ops import chunk_attn, rms_norm, rope_angles, scaled_normal
from vtpu.ops.decode_attn import paged_attn_route, wide_decode_attention
from vtpu.ops.latent import window_rows, write_rows
from vtpu.ops.window_attn import (
    full_attention,
    own_values,
    ring_after,
    ring_step_attention,
    spread_queries,
    window_attention,
)

Params = dict[str, Any]
POOL_KEYS = ("k", "v")
RING_KEYS = ("wk", "wv")


@dataclasses.dataclass(frozen=True)
class SwaConfig:
    """Toy sizes by default; vbench/sut/swa.py gives the published."""

    vocab: int = 256
    d_model: int = 64
    n_heads: int = 8
    head_dim: int = 24        # Dk: a head's width for queries and keys
    v_head_dim: int = 16      # Dv: ... and for values
    rope_dim: int = 8         # the first columns of a head that are rotated
    layer_types: tuple = ("full", "window", "window", "full", "window")
    ffn_types: tuple = ("dense", "moe", "moe", "moe", "moe")
    n_kv_heads: int = 2       # a full layer's key/value heads
    n_kv_heads_window: int = 4
    window: int = 8           # positions a window layer reads, its own one
    rope_theta: float = 1e7
    rope_theta_window: float = 1e4
    value_scale: float = 0.707
    d_ff: int = 128           # the dense layers' SwiGLU width
    d_ff_expert: int = 32
    n_experts: int = 16       # the router's width: the whole layer's experts
    held: tuple = (0, 16)     # (first, count) of the experts held here
    top_k: int = 4
    eps: float = 1e-5
    max_seq: int = 256
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        odd = (set(self.layer_types) - {"full", "window"}) | (
            set(self.ffn_types) - {"dense", "moe"})
        if odd or len(self.layer_types) != len(self.ffn_types):
            raise ValueError(
                f"layer_types {self.layer_types} and ffn_types "
                f"{self.ffn_types} must name full/window and dense/moe, a "
                "layer each")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def layer_kinds(self) -> tuple:
        """A layer's kind: its attention and the block after it. Layers of
        one kind are alike in every leaf and stacked together."""
        return tuple(f"{a}_{f}" for a, f in
                     zip(self.layer_types, self.ffn_types))

    def kv_heads(self, attn: str) -> int:
        return self.n_kv_heads if attn == "full" else self.n_kv_heads_window

    @property
    def attn_scale(self) -> float:
        return self.head_dim ** -0.5

    @property
    def kv_bytes_per_token(self) -> int:
        """Pool bytes one cached token costs: the full layers alone."""
        return (self.layer_types.count("full") * self.n_kv_heads
                * (self.head_dim + self.v_head_dim)
                * jnp.dtype(self.dtype).itemsize)

    @property
    def ring_bytes_per_position(self) -> int:
        """Bytes one position costs in every window layer's ring (what a
        cached token would cost there were those layers paged)."""
        return (self.layer_types.count("window") * self.n_kv_heads_window
                * (self.head_dim + self.v_head_dim)
                * jnp.dtype(self.dtype).itemsize)

    @property
    def ring_bytes_per_slot(self) -> int:
        return self.window * self.ring_bytes_per_position


def init_swa_params(rng: jax.Array, cfg: SwaConfig) -> Params:
    """Seeded weights at toy sizes: a kind's leaves stacked [L, ...], the
    projections as published ([d, H * Dh]; a serving adapter holds them,
    ``hold_projections``)."""
    d, hq, dk, dv = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.v_head_dim
    held = cfg.held[1]
    keys = iter(jax.random.split(rng, 64))

    def w(shape, fan_in, dtype=None):
        return scaled_normal(next(keys), shape, fan_in, dtype or cfg.dtype)

    def stack(kind: str, n: int) -> dict:
        attn, ffn = kind.split("_")
        hk = cfg.kv_heads(attn)
        out = {"attn_norm": jnp.ones((n, d), cfg.dtype),
               "mlp_norm": jnp.ones((n, d), cfg.dtype),
               "wq": w((n, d, hq * dk), d), "wk": w((n, d, hk * dk), d),
               "wv": w((n, d, hk * dv), d), "wo": w((n, hq * dv, d), hq * dv)}
        if attn == "window":
            out["sink"] = jax.random.uniform(
                next(keys), (n, hq), jnp.float32, -1.5, 1.5)
        if ffn == "dense":
            out.update(w_gate=w((n, d, cfg.d_ff), d),
                       w_up=w((n, d, cfg.d_ff), d),
                       w_down=w((n, cfg.d_ff, d), cfg.d_ff))
        else:
            f = cfg.d_ff_expert
            out.update(
                router=w((n, d, cfg.n_experts), d, jnp.float32),
                route_bias=jax.random.uniform(
                    next(keys), (n, cfg.n_experts), jnp.float32, -0.05, 0.05),
                w_gate=w((n, held, d, f), d), w_up=w((n, held, d, f), d),
                w_down=w((n, held, f, d), f))
        return out

    kinds = cfg.layer_kinds
    return {"embed": w((cfg.vocab, d), d),
            "final_norm": jnp.ones((d,), cfg.dtype),
            "head": w((cfg.vocab, d), d),
            "layers": {kind: stack(kind, kinds.count(kind))
                       for kind in dict.fromkeys(kinds)}}


def hold_projections(layers: dict, cfg: SwaConfig) -> dict:
    """The kinds' stacks with ``wq``, ``wk``, ``wv`` held as a serving
    program's products read them, [L, H, Dh, d] (``transformer
    .hold_projections`` says why); a shape stands for a leaf that is one."""
    out = {}
    for kind, stack in layers.items():
        hk = cfg.kv_heads(kind.split("_")[0])
        held = dict(stack)
        for name, h, dh in (("wq", cfg.n_heads, cfg.head_dim),
                            ("wk", hk, cfg.head_dim),
                            ("wv", hk, cfg.v_head_dim)):
            leaf = stack[name]
            if isinstance(leaf, jax.ShapeDtypeStruct):
                held[name] = jax.ShapeDtypeStruct(
                    (leaf.shape[0], h, dh, leaf.shape[1]), leaf.dtype,
                    sharding=leaf.sharding)
            else:
                held[name] = _held_projection(leaf, n_heads=h, head_dim=dh)
        out[kind] = held
    return out


def init_swa_state(cfg: SwaConfig, slots: int, page: int,
                   n_blocks: int) -> dict[str, jax.Array]:
    """The engine state: ``table`` / ``len`` as ``init_paged_kv_cache`` lays
    them, the full layers' pool planes, the window layers' rings beside
    them. Block 0 is the null block."""
    if cfg.max_seq % page:
        raise ValueError(f"kv page {page} must divide max_seq {cfg.max_seq}")
    lf, lw = cfg.layer_types.count("full"), cfg.layer_types.count("window")
    dk, dv = cfg.head_dim, cfg.v_head_dim
    return {
        "table": jnp.zeros((slots, cfg.max_seq // page), jnp.int32),
        "len": jnp.zeros((slots,), jnp.int32),
        "k": jnp.zeros((lf, n_blocks, page, cfg.n_kv_heads * dk), cfg.dtype),
        "v": jnp.zeros((lf, n_blocks, page, cfg.n_kv_heads * dv), cfg.dtype),
        "wk": jnp.zeros((lw, slots, cfg.window, cfg.n_kv_heads_window * dk),
                        cfg.dtype),
        "wv": jnp.zeros((lw, slots, cfg.window, cfg.n_kv_heads_window * dv),
                        cfg.dtype),
    }


# ------------------------------------------------------------- the block


def _qkv(cfg: SwaConfig, lp, x, attn: str, rope, positions):
    """q [N, T, Hq, Dk], k [N, T, Hk, Dk] (both rotated in their first
    ``rope_dim`` columns), v [N, T, Hk, Dv] (scaled). The leaf's rank says
    whether a projection is the published [d, H * Dh] or held [H, Dh, d]."""
    n_, t_, _ = x.shape
    n = rms_norm(x, lp["attn_norm"], cfg.eps)
    if lp["wq"].ndim == 3:
        q, k, v = (jnp.einsum("ntd,hed->nthe", n, lp[name])
                   for name in ("wq", "wk", "wv"))
    else:
        hk = cfg.kv_heads(attn)
        q = (n @ lp["wq"]).reshape(n_, t_, cfg.n_heads, cfg.head_dim)
        k = (n @ lp["wk"]).reshape(n_, t_, hk, cfg.head_dim)
        v = (n @ lp["wv"]).reshape(n_, t_, hk, cfg.v_head_dim)
    cos, sin = rope[attn]
    q = _rope_head(q, cos, sin, positions, cfg.rope_dim)
    k = _rope_head(k, cos, sin, positions, cfg.rope_dim)
    v = (v.astype(jnp.float32) * cfg.value_scale).astype(v.dtype)
    return q, k, v


def _flat(x: jax.Array) -> jax.Array:
    """[N, T, H, D] -> [N, T, H * D]: a token's heads side by side."""
    return x.reshape(x.shape[:2] + (-1,))


def _gathered_attention(cfg: SwaConfig, q, keys, values, positions):
    """A full layer's attention over its gathered window, rows of heads
    side by side ``[N, W, Hk * D]``: a decode step's single query in
    ``full_attention``; a chunk's or a prompt's queries in the chunk
    kernel over the rows as they lie where ``chunk_attn.takes`` the
    shapes, else in ``full_attention`` too (``chunk_attn.attend_window``)."""
    n, hk = q.shape[0], cfg.n_kv_heads

    def in_xla():
        return full_attention(
            q, keys.reshape(n, -1, hk, cfg.head_dim),
            values.reshape(n, -1, hk, cfg.v_head_dim), positions,
            cfg.attn_scale)

    if q.shape[1] == 1:
        return in_xla()
    return chunk_attn.attend_window(
        q, keys, values, positions + 1, cfg.attn_scale, in_xla)


def _full_layer(cfg: SwaConfig, lp, l: int, x, pool, rope, positions, at,
                route):
    """A full layer's attention half over x [N, T, D]: the token's key and
    value rows written into layer ``l`` of the pool planes at ``at.wblk,
    at.woff`` (an out-of-range block id drops), then attention over the
    read window through ``at.tables``: a decode step on the kernel's route
    walks the slots' live pages in place (``at.lens`` rows each), every
    other program gathers its window."""
    with jax.named_scope("qkv"):
        q, k, v = _qkv(cfg, lp, x, "full", rope, positions)
    hk = cfg.n_kv_heads
    with jax.named_scope("kv_write"):
        pool = {"k": write_rows(pool["k"], l, at["wblk"], at["woff"], _flat(k)),
                "v": write_rows(pool["v"], l, at["wblk"], at["woff"], _flat(v))}
    if route == "kernel":
        with jax.named_scope("pool_relayout"):
            spread = spread_queries(q[:, 0], hk)
        with jax.named_scope("paged_attn"):
            mixed = wide_decode_attention(
                spread, pool["k"], pool["v"], at["tables"], at["lens"], l,
                cfg.attn_scale)
        with jax.named_scope("pool_relayout"):
            attn = own_values(mixed, hk)[:, None]
    else:
        with jax.named_scope("gather_attn"):
            keys = window_rows(pool["k"], l, at["tables"])
            values = window_rows(pool["v"], l, at["tables"])
            attn = _gathered_attention(cfg, q, keys, values, positions)
    return attn, pool


def _window_layer(cfg: SwaConfig, lp, l: int, x, rings, rope, positions, at):
    """A window layer's attention half over x [N, T, D] from and into the
    rings of layer ``l``. A decode step (T = 1 over every slot) writes each
    dispatched slot's row in place and reads the rings as they lie; a chunk
    or a whole prompt reads its sequences' rings (``at.slots``) beside its
    own keys under the band mask and writes back the rows its real tokens
    (``at.new_len``) now own."""
    with jax.named_scope("qkv"):
        q, k, v = _qkv(cfg, lp, x, "window", rope, positions)
    hk, window = cfg.n_kv_heads_window, cfg.window
    wk, wv = rings["wk"], rings["wv"]
    with jax.named_scope("attn"):
        if "active" in at:  # a decode step: one token a slot
            active, lens = at["active"], positions[:, 0]
            rows = jnp.arange(x.shape[0])
            with jax.named_scope("ring_write"):
                row = jnp.where(active, lens % window, window)
                wk = wk.at[l, rows, row].set(_flat(k)[:, 0], mode="drop")
                wv = wv.at[l, rows, row].set(_flat(v)[:, 0], mode="drop")
            with jax.named_scope("window_attn"):
                seen = jnp.where(active, jnp.minimum(lens + 1, window), 0)
                attn = ring_step_attention(
                    q[:, 0], wk[l], wv[l], seen, lp["sink"], hk,
                    cfg.attn_scale)[:, None]
        else:
            slots, offset = at["slots"], positions[:, 0]
            with jax.named_scope("window_attn"):
                ring_k = jnp.take(wk[l], slots, axis=0, mode="clip")
                ring_v = jnp.take(wv[l], slots, axis=0, mode="clip")
                attn = window_attention(
                    q, k, v, ring_k, ring_v, offset, lp["sink"], window,
                    cfg.attn_scale)
            with jax.named_scope("ring_write"):
                wk = wk.at[l, slots].set(
                    ring_after(ring_k, _flat(k), offset, at["new_len"]),
                    mode="drop")
                wv = wv.at[l, slots].set(
                    ring_after(ring_v, _flat(v), offset, at["new_len"]),
                    mode="drop")
    return attn, {"wk": wk, "wv": wv}


def _dense_ffn(cfg: SwaConfig, lp, x):
    with jax.named_scope("mlp"):
        n = rms_norm(x, lp["mlp_norm"], cfg.eps)
        return x + _swiglu(n, lp["w_gate"], lp["w_up"], lp["w_down"])


def _expert_ffn(cfg: SwaConfig, lp, x):
    """This holder's part of the routed experts (there is no shared one)."""
    shape = x.shape
    first, count = cfg.held
    with jax.named_scope("route"):
        n = rms_norm(x, lp["mlp_norm"], cfg.eps).reshape(-1, shape[-1])
        gates = grouped_route(lp["router"], lp["route_bias"], n, cfg.top_k,
                              1, 1, 1.0)[:, first:first + count]
    with jax.named_scope("experts"):
        y = held_experts_ffn(lp, n, gates, cfg.top_k)
    return x + y.reshape(shape)


def _walk(params: Params, cfg: SwaConfig, state, tokens, positions, at,
          route: str = "gather"):
    """Every layer in the model's order over tokens [N, T] at ``positions``
    [N, T]: (hidden [N, T, D], the pool planes, the rings). ``at`` says
    where the sequences' caches are: ``tables [N, Wp]``, ``wblk`` / ``woff``
    [N, T] for the pool; for the rings ``slots`` [N] and ``new_len`` [N], or
    a decode step's ``active`` [slots] (and ``lens``, what its walk
    reads)."""
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(cfg.dtype)
    with jax.named_scope("qkv"):
        clipped = jnp.minimum(positions, cfg.max_seq - 1)
        rope = {"full": rope_angles(cfg.max_seq, cfg.rope_dim, cfg.rope_theta),
                "window": rope_angles(cfg.max_seq, cfg.rope_dim,
                                      cfg.rope_theta_window)}
    pool = {key: state[key] for key in POOL_KEYS}
    rings = {key: state[key] for key in RING_KEYS}
    seen = {"full": 0, "window": 0}
    of_kind: dict = {}
    for attn_kind, ffn_kind, kind in zip(cfg.layer_types, cfg.ffn_types,
                                         cfg.layer_kinds):
        lp = LayerOfStack(params["layers"][kind], of_kind.get(kind, 0))
        of_kind[kind] = of_kind.get(kind, 0) + 1
        l = seen[attn_kind]
        seen[attn_kind] = l + 1
        if attn_kind == "full":
            attn, pool = _full_layer(
                cfg, lp, l, x, pool, rope, clipped, at, route)
        else:
            attn, rings = _window_layer(
                cfg, lp, l, x, rings, rope, clipped, at)
        with jax.named_scope("o_proj"):
            x = x + _flat(attn) @ lp["wo"]
        x = (_dense_ffn if ffn_kind == "dense" else _expert_ffn)(cfg, lp, x)
    return x, pool, rings


@jax.named_scope("lm_head")
def _head(params: Params, cfg: SwaConfig, x: jax.Array) -> jax.Array:
    """Final norm and the untied output head over the rows given."""
    x = rms_norm(x, params["final_norm"], cfg.eps)
    return (x @ params["head"].T).astype(jnp.float32)


# -------------------------------------------------------- the entry points


def _pool_places(state, tables, positions, n_blocks: int):
    """(wblk, woff) [N, T]: where positions fall in the pool through
    ``tables [N, Wp]``; a position past the table's width has no block and
    its write drops."""
    page = state["k"].shape[2]
    wblk = jnp.take_along_axis(
        tables, jnp.minimum(positions // page, tables.shape[1] - 1), axis=1)
    wblk = jnp.where(positions // page < tables.shape[1], wblk, n_blocks)
    return wblk, positions % page


def swa_forward(params: Params, cfg: SwaConfig, tokens: jax.Array,
                page: int = 8) -> jax.Array:
    """Full-sequence forward over a scratch state of its own: tokens [B, S]
    -> logits [B, S, V]."""
    b, s = tokens.shape
    pages = -(-s // page)
    state = init_swa_state(
        dataclasses.replace(cfg, max_seq=pages * page), b, page,
        1 + b * pages)
    tables = 1 + jnp.arange(b * pages, dtype=jnp.int32).reshape(b, pages)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    wblk, woff = _pool_places(state, tables, positions, 1 + b * pages)
    x, _, _ = _walk(params, cfg, state, tokens, positions, dict(
        tables=tables, wblk=wblk, woff=woff, slots=jnp.arange(b),
        new_len=jnp.full((b,), s, jnp.int32)))
    return _head(params, cfg, x)


def swa_prefill_rows(params: Params, cfg: SwaConfig, state, tokens, slots,
                     true_lens):
    """Whole-prompt admission: N right-padded prompts [N, bucket] from
    empty caches, the full layers' rows written through the slots' table
    rows (set by the engine's reservation before the dispatch), the window
    layers' rings left holding each prompt's last ``window`` positions up
    to its ``true_len``. Returns (logits [N, V] at each prompt's last
    token, the state). Pads write junk into the pool above the true length:
    masked by length now, overwritten before any query may see it; they
    enter no ring."""
    n, s = tokens.shape
    page, n_blocks = state["k"].shape[2], state["k"].shape[1]
    tables = state["table"][slots, :-(-s // page)]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (n, s))
    wblk, woff = _pool_places(state, tables, positions, n_blocks)
    x, pool, rings = _walk(params, cfg, state, tokens, positions, dict(
        tables=tables, wblk=wblk, woff=woff, slots=slots, new_len=true_lens))
    last = x[jnp.arange(n), true_lens - 1]
    new = {**state, **pool, **rings,
           "len": state["len"].at[slots].set(true_lens)}
    return _head(params, cfg, last), new


def swa_prefill_chunk(params: Params, cfg: SwaConfig, state, chunk, slot,
                      offset, new_len, window: int, block_ids):
    """One [1, C] chunk of a prompt at positions offset .. offset + C - 1 of
    ``slot``, the first ``new_len - offset`` of them real: its full layers'
    rows written into and read through ``block_ids`` ([window // page] pool
    blocks, padded with the null block 0), its window layers run from the
    slot's ring as the earlier chunks left it (at offset 0 every row is
    masked, so a slot given to a new session reads nothing of the old one)
    and written back. Returns (logits [1, C, V], state)."""
    c = chunk.shape[1]
    n_blocks = state["k"].shape[1]
    page = state["k"].shape[2]
    tables = block_ids[None, :window // page]
    positions = (offset + jnp.arange(c, dtype=jnp.int32))[None]
    wblk, woff = _pool_places(state, tables, positions, n_blocks)
    x, pool, rings = _walk(params, cfg, state, chunk, positions, dict(
        tables=tables, wblk=wblk, woff=woff,
        slots=jnp.reshape(slot, (1,)), new_len=jnp.reshape(new_len, (1,))))
    new = {**state, **pool, **rings,
           "len": state["len"].at[slot].set(new_len, mode="drop")}
    return _head(params, cfg, x), new


def swa_decode_step(params: Params, cfg: SwaConfig, state, tokens, active,
                    window: int, paged_attn=None):
    """One decode tick for the whole slot pool: tokens [B], active [B] ->
    (logits [B, V], state). A dispatched slot writes its full layers' rows
    at its own length and reads its ``len + 1`` rows through the first
    ``window`` positions of its table row (the kernel's walk or the
    gathered window: ``paged_attn`` as the other families'), writes its
    window layers' rows at ``len % window`` and reads its ring; an inactive
    slot (its table row may be stale) writes nowhere and reads nothing."""
    page, n_blocks = state["k"].shape[2], state["k"].shape[1]
    lens = state["len"]
    rows = jnp.arange(tokens.shape[0])
    here = jnp.minimum(lens // page, state["table"].shape[1] - 1)
    wblk = jnp.where(active & (lens < cfg.max_seq),
                     state["table"][rows, here], n_blocks)
    route = paged_attn_route(paged_attn, window, t=1)
    x, pool, rings = _walk(
        params, cfg, state, tokens[:, None], lens[:, None], dict(
            tables=state["table"][:, :window // page], wblk=wblk[:, None],
            woff=(lens % page)[:, None], active=active,
            lens=jnp.where(active, lens + 1, 0)), route)
    new = {**state, **pool, **rings,
           "len": jnp.where(active, lens + 1, lens)}
    return _head(params, cfg, x[:, 0]), new
