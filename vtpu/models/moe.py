"""Mixture-of-Experts transformer: the second model family of the data plane.

GShard-style top-k routing with STATIC shapes end to end -- the TPU contract:
- expert capacity is a compile-time constant (ceil(k*T/E * capacity_factor)),
  so dispatch/combine are dense one-hot einsums the MXU eats whole; no
  dynamic gather/scatter, no data-dependent shapes under jit;
- per-layer expert weights are stacked [L, E, D, F] and the layer loop is one
  `lax.scan`, same as the dense flagship (vtpu/models/transformer.py);
- expert parallelism shards the E axis over an 'ep' mesh axis -- either via
  NamedSharding annotations (XLA inserts the all-to-alls; used by the train
  step) or the explicit `shard_map` path in vtpu/parallel/expert.py.

The reference middleware has no model code (SURVEY.md §2.6); this family
exists so the benchmark/dryrun exercise a real EP workload under vTPU limits.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from vtpu.models.transformer import (
    _embed, _lm_head, _o_proj, _prefill_cache, _qkv, kv_heads,
)
from vtpu.ops import scaled_normal, rms_norm, rope_angles, causal_attention
from vtpu.ops.grouped_ffn import grouped_experts_ffn, takes

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    vocab: int = 2048
    d_model: int = 512
    n_heads: int = 4
    n_layers: int = 4
    d_ff: int = 1024          # per-expert hidden width
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 2.0
    max_seq: int = 1024
    head_dim: int = 128
    dtype: Any = jnp.bfloat16
    kv_int8: bool = False  # int8 KV cache (see ModelConfig.kv_int8)
    # What a published block may state otherwise; the defaults are what
    # OLMoE's cell runs (vbench/configs/olmoe-1b-7b-8l.json records its
    # QK-norm and its untied head as departures: each is one field here).
    # The shared trunk reads them (transformer._qkv, _lm_head,
    # cached_attention).
    n_kv_heads: Optional[int] = None  # grouped key/value heads (None: n_heads)
    qk_norm: bool = False      # an RMS norm a head on q and k before rope
    tied_head: bool = True     # False: params["head"] [V, D] of its own
    rope_theta: float = 10000.0
    eps: float = 1e-6
    # (first, count) of the experts whose stacks this holder has, of the
    # n_experts the router scores (None: all of them; ``held_moe_ffn``)
    held: Optional[tuple] = None

    @property
    def qkv_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def d_ff_expert(self) -> int:
        return self.d_ff

    @property
    def n_held(self) -> int:
        return self.held[1] if self.held else self.n_experts

    def capacity(self, tokens: int) -> int:
        """Static per-expert slot count for a `tokens`-token batch."""
        return max(1, math.ceil(self.top_k * tokens / self.n_experts * self.capacity_factor))


def init_moe_params(rng: jax.Array, cfg: MoEConfig) -> Params:
    """Stacked [L, ...] tensors; experts stacked on their own axis [L, E, ...]."""
    keys = jax.random.split(rng, 9)
    d, f, l, e, qd = cfg.d_model, cfg.d_ff, cfg.n_layers, cfg.n_experts, cfg.qkv_dim
    kvd = kv_heads(cfg) * cfg.head_dim
    h = cfg.n_held  # expert stacks held here; the router scores all e

    def w(key, shape, fan_in):
        return scaled_normal(key, shape, fan_in, cfg.dtype)

    params = {
        "embed": w(keys[0], (cfg.vocab, d), d),
        "layers": {
            "wq": w(keys[1], (l, d, qd), d),
            "wk": w(keys[2], (l, d, kvd), d),
            "wv": w(keys[3], (l, d, kvd), d),
            "wo": w(keys[4], (l, qd, d), qd),
            # router stays f32: tiny matmul, and softmax over experts is
            # numerically load-bearing for balanced routing
            "router": (jax.random.normal(keys[5], (l, d, e), jnp.float32) / math.sqrt(d)),
            "w_gate": w(keys[6], (l, h, d, f), d),
            "w_up": w(keys[7], (l, h, d, f), d),
            "w_down": w(keys[8], (l, h, f, d), f),
            "attn_norm": jnp.ones((l, d), cfg.dtype),
            "mlp_norm": jnp.ones((l, d), cfg.dtype),
        },
        "final_norm": jnp.ones((d,), cfg.dtype),
    }
    if cfg.qk_norm:
        params["layers"]["q_norm"] = jnp.ones((l, cfg.head_dim), cfg.dtype)
        params["layers"]["k_norm"] = jnp.ones((l, cfg.head_dim), cfg.dtype)
    if not cfg.tied_head:
        params["head"] = w(jax.random.fold_in(rng, 9), (cfg.vocab, d), d)
    return params


def route(
    router_w: jax.Array, x: jax.Array, cfg: MoEConfig, capacity: int,
    pad_mask: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k routing over flat tokens x: [T, D].

    Returns (dispatch [T, E, C] one-hot, combine [T, E, C] gate weights,
    aux load-balancing loss scalar). Tokens beyond an expert's capacity are
    dropped (their combine row is zero -> residual passes them through),
    matching GShard semantics with k-th-choice priority ordering.
    ``pad_mask`` ([T] bool, True = real token) excludes pads from routing
    entirely: they claim no capacity slot, so real tokens' slot positions
    depend only on other real tokens — right padding cannot change them.
    """
    t, e = x.shape[0], cfg.n_experts
    logits = x.astype(jnp.float32) @ router_w  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, cfg.top_k)  # [T, k]
    gate_vals = gate_vals / jnp.maximum(jnp.sum(gate_vals, -1, keepdims=True), 1e-9)

    dispatch = jnp.zeros((t, e, capacity), jnp.float32)
    combine = jnp.zeros((t, e, capacity), jnp.float32)
    prev_counts = jnp.zeros((e,), jnp.int32)
    for j in range(cfg.top_k):  # static unroll, one pass a choice
        onehot = jax.nn.one_hot(gate_idx[:, j], e, dtype=jnp.int32)  # [T, E]
        if pad_mask is not None:
            onehot = onehot * pad_mask.astype(jnp.int32)[:, None]
        pos_all = jnp.cumsum(onehot, axis=0) - onehot + prev_counts[None, :]
        pos = jnp.sum(pos_all * onehot, axis=-1)  # [T] slot within chosen expert
        keep = pos < capacity
        prev_counts = prev_counts + jnp.sum(onehot, axis=0)
        slot = jax.nn.one_hot(pos, capacity, dtype=jnp.float32) * keep[:, None]  # [T, C]
        hot = onehot.astype(jnp.float32)[:, :, None] * slot[:, None, :]  # [T, E, C]
        dispatch = dispatch + hot
        combine = combine + gate_vals[:, j][:, None, None] * hot

    # load-balancing auxiliary (Switch/GShard): E * mean(frac_tokens * mean_prob)
    frac = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], e, dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(frac * jnp.mean(probs, axis=0))
    return dispatch, combine, aux


def grouped_route(
    router_w: jax.Array, bias: jax.Array, x: jax.Array, top_k: int,
    n_group: int, topk_group: int, scale: float,
) -> jax.Array:
    """Group-limited routing with sigmoid scores (DeepSeek-V3's router)
    over flat tokens x: [T, D], router_w [D, E] and bias [E] in float32.

    A token's affinity to an expert is ``g = sigmoid(x . w)``. The choice
    is made on ``g + bias`` (the bias balances load and weighs nothing):
    the experts lie in ``n_group`` equal groups, a group scores the sum of
    its two best choice scores, the ``topk_group`` best groups are kept and
    the ``top_k`` best choice scores among the kept groups are chosen. The
    weights are ``g`` at the chosen experts, divided by their sum, times
    ``scale``. Returns them as gates [T, E] float32, zero at every expert
    not chosen: a column is what that expert's output is weighed by, so
    the holder of a share of the experts reads its own columns and needs
    nothing else of the routing. Nothing is dropped."""
    t, e = x.shape[0], router_w.shape[1]
    g = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), router_w, precision=jax.lax.Precision.HIGHEST))
    choice = g + bias
    groups = choice.reshape(t, n_group, e // n_group)
    group_score = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)  # [T, G]
    _, kept = jax.lax.top_k(group_score, topk_group)
    keep = jnp.sum(jax.nn.one_hot(kept, n_group, dtype=jnp.float32), axis=1)
    choice = jnp.where(keep[:, :, None] > 0, groups, -jnp.inf).reshape(t, e)
    _, chosen = jax.lax.top_k(choice, top_k)  # [T, k]
    hot = jnp.sum(jax.nn.one_hot(chosen, e, dtype=jnp.float32), axis=1)
    weights = g * hot
    return weights / jnp.sum(weights, axis=-1, keepdims=True) * scale


def group_limited_route(
    router_w: jax.Array, x: jax.Array, top_k: int, n_group: int,
    topk_group: int, scale: float,
) -> jax.Array:
    """Group-limited greedy routing with softmax scores (DeepSeek-V2's
    router, ``topk_method`` ``group_limited_greedy``) over flat tokens x:
    [T, D], router_w [D, E] in float32.

    A token's affinity to an expert is ``s = softmax(x . w)`` over all E.
    The experts lie in ``n_group`` equal groups (the devices that hold
    them), a group scores its **best** ``s`` (V3's ``grouped_route`` sums
    the two best of ``sigmoid + bias``), the ``topk_group`` best groups are
    kept and the ``top_k`` largest ``s`` among the kept groups are chosen.
    A chosen expert weighs ``scale * s``: no bias, and no division by the
    chosen scores' sum (``norm_topk_prob`` false). Returns gates [T, E]
    float32 in ``grouped_route``'s form, zero at every expert not chosen,
    so ``held_experts_ffn`` reads its own columns. Nothing is dropped."""
    t, e = x.shape[0], router_w.shape[1]
    s = jax.nn.softmax(jnp.matmul(
        x.astype(jnp.float32), router_w,
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    groups = s.reshape(t, n_group, e // n_group)
    _, kept = jax.lax.top_k(jnp.max(groups, axis=-1), topk_group)  # [T, g]
    keep = jnp.sum(jax.nn.one_hot(kept, n_group, dtype=jnp.float32), axis=1)
    choice = jnp.where(keep[:, :, None] > 0, groups, -jnp.inf).reshape(t, e)
    _, chosen = jax.lax.top_k(choice, top_k)  # [T, k]
    hot = jnp.sum(jax.nn.one_hot(chosen, e, dtype=jnp.float32), axis=1)
    return s * hot * scale


def topk_softmax_gates(router_w: jax.Array, x: jax.Array,
                       top_k: int) -> jax.Array:
    """``route``'s arithmetic (softmax over all E experts, the ``top_k``
    largest a token, their weights divided by their sum) over flat tokens
    x: [T, D], router_w [D, E] in float32, as gates [T, E] float32 in
    ``grouped_route``'s form: zero at every expert not chosen, so the
    holder of a share of the experts reads its own columns
    (``held_experts_ffn``). No capacity: nothing is dropped."""
    gates = group_limited_route(router_w, x, top_k, 1, 1, 1.0)
    return gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)


def held_moe_ffn(cfg: MoEConfig):
    """The post-attention block, for the shared decode trunk
    (``transformer.spec_verify_loop``'s ``ffn_fn``), of a holder of
    ``cfg.held`` of the layer's experts: the router scores all
    ``n_experts``, chooses ``top_k`` of them a row (softmax, renormalised),
    and the held experts add their part (``held_experts_ffn``: the grouped
    kernels on a TPU); what the absent ones would add is left out. Exact
    and dropless at any number of rows."""
    first, count = cfg.held or (0, cfg.n_experts)

    def ffn(lp, x):
        shape = x.shape
        with jax.named_scope("route"):  # the norm rides with the router
            n = rms_norm(x, lp["mlp_norm"], cfg.eps).reshape(-1, shape[-1])
            gates = topk_softmax_gates(
                lp["router"], n, cfg.top_k)[:, first:first + count]
        with jax.named_scope("experts"):
            y = held_experts_ffn(lp, n, gates, cfg.top_k)
        return y.reshape(shape)

    return ffn


def experts_grouped(t: int, d: int, f: int) -> bool:
    """Whether ``held_experts_ffn`` runs a launch of ``t`` rows ``d`` wide
    through experts ``f`` wide in the grouped kernels: the shapes they
    take (``ops.grouped_ffn.takes``: up to the most rows they were compiled
    and timed at, and what fits the chip's VMEM), on a TPU. At every
    number of rows the cells' programs have (a step's 16 and 96, the
    admission bucket's 256, a chunk's 512) the kernels are the faster on a
    v5e: PERF.md section 3 has the rule, section 6 the table. Resolved
    when a program is traced, from what it can observe, as ``ops.latent
    .attends_in_kernel``; the engine counts its launches' rows by the same
    call (``stats()["expert_rows_grouped"]``)."""
    return takes(t, d, f) and jax.default_backend() == "tpu"


def held_experts_all_rows(lp_e: dict[str, jax.Array], x: jax.Array,
                          gates: jax.Array) -> jax.Array:
    """``held_experts_ffn`` with every held expert computing over all T
    rows, the gate zeroing the rows not routed to it: H times the rows'
    products (32 times what a chunk's routing needs; PERF.md section 6,
    PR 41). What a launch the kernels do not take runs, the CPU's route,
    and the kernels' reference: the same arithmetic a pair."""
    gate = jnp.einsum("td,hdf->htf", x, lp_e["w_gate"])
    up = jnp.einsum("td,hdf->htf", x, lp_e["w_up"])
    act = jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
    act = (act * gates.T[:, :, None]).astype(x.dtype)  # weighed, then summed
    return jnp.einsum("htf,hfd->td", act, lp_e["w_down"])


def _stacked(lp_e, name: str) -> tuple[jax.Array, Any]:
    """(a leaf with its layers' axis, this layer's index): the stack as
    stored where ``lp_e`` is a layer of one (``latent.LayerOfStack.stacked``), a
    dict's leaf under a leading axis of one."""
    if isinstance(lp_e, dict):
        return lp_e[name][None], 0
    return lp_e.stacked(name)


def held_experts_ffn(lp_e: dict[str, jax.Array], x: jax.Array,
                     gates: jax.Array, top_k: int | None = None) -> jax.Array:
    """This holder's part of an expert layer's result: the experts whose
    stacks it is given ([H, D, F] / [H, F, D]: a share of the layer's,
    told which by the ``gates [T, H]`` it is handed, the router's columns
    for exactly these experts), each a SwiGLU over x [T, D], weighed by its
    gate and summed. Exact and dropless on static shapes.

    On the launches ``experts_grouped`` names each held expert multiplies
    only the rows with a gate other than zero (``ops.grouped_ffn``: the
    pairs laid out expert by expert for the worst routing, the work done
    following the routed rows, an expert without a row not read), and a
    row's pairs are summed in float32; every other launch runs
    ``held_experts_all_rows``. ``top_k`` is the most gates other than zero
    a row can have, the router's (None: one for every expert held); it
    sizes the worst routing's buffers and chooses nothing."""
    w_down, layer = _stacked(lp_e, "w_down")
    if not experts_grouped(*x.shape, w_down.shape[-2]):
        return held_experts_all_rows(lp_e, x, gates)
    return grouped_experts_ffn(
        x, gates, _stacked(lp_e, "w_gate")[0], _stacked(lp_e, "w_up")[0],
        w_down, layer, top_k or gates.shape[1]).astype(x.dtype)


def expert_ffn(lp_e: dict[str, jax.Array], slots: jax.Array) -> jax.Array:
    """SwiGLU over dispatched slots [E, C, D] with per-expert weights [E, D, F]."""
    gate = jnp.einsum("ecd,edf->ecf", slots, lp_e["w_gate"])
    up = jnp.einsum("ecd,edf->ecf", slots, lp_e["w_up"])
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(slots.dtype) * up
    return jnp.einsum("ecf,efd->ecd", act, lp_e["w_down"])


def moe_ffn(lp: dict[str, jax.Array], x: jax.Array, cfg: MoEConfig,
            capacity: int | None = None,
            pad_mask: jax.Array | None = None) -> tuple[jax.Array, jax.Array]:
    """Single-device (or annotation-sharded) MoE block. x: [B, S, D].

    With `w_gate`/`w_up`/`w_down` sharded P('ep') on the expert axis, XLA turns
    the dispatch/combine einsums into all-to-alls over 'ep' by itself -- the
    pjit path. ``capacity`` overrides the config formula (serving decode
    passes the full token count so routing can never drop a token).
    ``pad_mask`` ([B, S] bool, True = real) keeps pads out of routing.
    Returns (out [B, S, D], aux_loss).
    """
    b, s, d = x.shape
    flat = x.reshape(b * s, d)
    cap = capacity or cfg.capacity(b * s)
    with jax.named_scope("route"):
        dispatch, combine, aux = route(
            lp["router"], flat, cfg, cap,
            pad_mask=None if pad_mask is None else pad_mask.reshape(b * s))
    with jax.named_scope("experts"):
        slots = jnp.einsum(
            "tec,td->ecd", dispatch.astype(x.dtype), flat)  # [E, C, D]
        out_slots = expert_ffn(lp, slots)
        out = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), out_slots)
    return out.reshape(b, s, d), aux


def _moe_layer(cfg: MoEConfig, lp, x, cos, sin, positions, ffn):
    """One MoE decoder block over a full sequence: the SINGLE copy of the
    attention trunk shared by the training forward (moe_forward) and the
    serving prefill (moe_prefill). Returns (out, aux, (k, v))."""
    q, k, v = _qkv(cfg, lp, x, cos, sin, positions)
    with jax.named_scope("attn"):
        attn = causal_attention(q, k, v)
    x = _o_proj(lp, x, attn)
    with jax.named_scope("route"):  # the norm rides with the router
        normed = rms_norm(x, lp["mlp_norm"])
    moe_out, aux = ffn(lp, normed, cfg)
    return x + moe_out, aux, (k, v)


def moe_forward(
    params: Params, cfg: MoEConfig, tokens: jax.Array, ffn=moe_ffn
) -> tuple[jax.Array, jax.Array]:
    """Full-sequence forward. tokens: [B, S] -> (logits [B, S, V], aux loss).

    `ffn` is injectable so vtpu/parallel/expert.py can swap in the shard_map
    expert-parallel block without duplicating the trunk.
    """
    b, s = tokens.shape
    cos, sin = rope_angles(cfg.max_seq, cfg.head_dim)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x = _embed(params, cfg, tokens)

    def layer(carry, lp):
        x, aux = carry
        out, layer_aux, _kv = _moe_layer(cfg, lp, x, cos, sin, positions, ffn)
        return (out, aux + layer_aux), None

    (x, aux), _ = jax.lax.scan(layer, (x, jnp.float32(0.0)), params["layers"])
    return _lm_head(params, x), aux / cfg.n_layers


def moe_loss(params: Params, cfg: MoEConfig, tokens: jax.Array, ffn=moe_ffn) -> jax.Array:
    """Next-token cross-entropy + 0.01 * load-balancing aux."""
    from vtpu.ops.loss import next_token_ce

    logits, aux = moe_forward(params, cfg, tokens, ffn=ffn)
    return next_token_ce(logits, tokens) + 0.01 * aux


# ------------------------------------------------------------------ serving


def moe_decode_ffn(cfg: MoEConfig):
    """The post-attention block for the shared decode trunk
    (transformer.decode_layer_loop): routed experts instead of the dense
    MLP; the aux load-balancing term is a training loss, dropped here."""

    def ffn(lp, x):
        # capacity = the full token count: decode routes every slot's token
        # jointly (including retired slots' stale ones), and a capacity
        # drop triggered by garbage would zero a LIVE slot's expert output —
        # with capacity >= tokens, routing can never drop anyone. x is
        # [B, T, D]: T=1 for plain decode, K+1 for a speculative verify
        # chunk (the same trunk serves both).
        with jax.named_scope("route"):  # the norm rides with the router
            normed = rms_norm(x, lp["mlp_norm"])
        out, _aux = moe_ffn(lp, normed, cfg,
                            capacity=x.shape[0] * x.shape[1])
        return out

    return ffn


def moe_prefill(
    params: Params, cfg: MoEConfig, tokens: jax.Array,
    true_len: jax.Array | None = None,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Full-sequence forward that also fills a KV cache — the serving-side
    sibling of moe_forward (same trunk, same expert routing; the aux term is
    dropped). tokens: [B, S] -> (logits [B, S, V], cache).

    ``true_len`` (scalar or [B] int32) marks where the right padding starts.
    When given, pads are masked OUT of expert routing — they claim no
    capacity slot, so a pad can never evict a real token — and capacity
    uses the config's capacity-factor formula over the bucket instead of
    the full token count, bounding dispatch/combine memory at the largest
    prefill buckets (ADVICE r3). Note the formula capacity carries GShard
    drop semantics, exactly like training: under extreme routing imbalance
    a real token's overflow choice past capacity drops to the residual
    path (and since capacity scales with the bucket, the drop threshold
    does too). Without true_len, capacity = full token count: no token
    (real or pad) can ever drop — exact, but O(E/cf) more dispatch memory.
    """
    b, s = tokens.shape
    cos, sin = rope_angles(cfg.max_seq, cfg.head_dim)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x = _embed(params, cfg, tokens)

    pad_mask = None
    if true_len is not None:
        lens = jnp.reshape(jnp.asarray(true_len, jnp.int32), (-1, 1))  # [B|1, 1]
        pad_mask = positions < lens  # [B, S]

    def serving_ffn(lp, normed, cfg_):
        # The serving engine prefills RIGHT-PADDED [1, bucket] prompts, and
        # under the raw training formula a pad token's first choice could
        # exhaust an expert before a real token's second choice claims its
        # slot — padding would change a real token's output. Two exact-safe
        # modes: with true_len, pads are masked out of routing so real
        # tokens compete only with each other and the cf formula bounds
        # capacity; without it, capacity >= T means nobody can drop.
        if pad_mask is not None:
            return moe_ffn(lp, normed, cfg_, pad_mask=pad_mask)
        return moe_ffn(lp, normed, cfg_, capacity=normed.shape[0] * normed.shape[1])

    def layer(x, lp):
        out, _aux, kv = _moe_layer(cfg, lp, x, cos, sin, positions, serving_ffn)
        return out, kv

    x, (ks, vs) = jax.lax.scan(layer, x, params["layers"])
    return _lm_head(params, x), _prefill_cache(cfg, ks, vs)
