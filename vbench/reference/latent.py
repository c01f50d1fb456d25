"""Plain reference of the latent-attention decoder under a learned sparse
selection (DeepSeek-V3.2's block), as the configuration's file cuts it: a
leading dense layer, then sparse layers holding a share of the routed
experts. Float32, ``highest``, no cache: every projection over the whole
sequence, keys and values expanded from the latents for every head, scores
over all cached tokens with the selection as a mask, queries in blocks so
that a 25 k sequence fits.

Per token t, cached tokens s <= t, n = rms_norm(x):
  c_q = rms_norm(W_qa n); q = W_qb c_q -> H heads of [q_nope | q_pe], rotary
  on q_pe. [c_kv | k_pe] = W_kva n; c_kv = rms_norm(c_kv); rotary on k_pe.
  [k_nope | v] per head = W_kvb c_kv. Indexer: q_i = W_iq c_q (Hi heads),
  k_i = layer_norm(W_ik n), rotary on the first rope_dim of each; w = W_iw n
  * Hi^-0.5 * Di^-0.5; I(t, s) = sum_h w[t, h] relu(q_i[t, h] . k_i[s]); S_t =
  the index_topk visible s with the largest I. Attention over S_t with
  scale (dn + dr)^-0.5 * m^2, m = 0.1 * mscale_all_dim * ln(factor) + 1.
  Dense layer: SwiGLU. Sparse layer: sigmoid router over all experts, the
  choice on scores + bias within the best groups, weights renormalised and
  scaled; shared expert + the held experts' part (the rest left out).
Rotary positions are YaRN's, halves rotated against each other (the
pairing is the configuration's ``assumed``: a permutation of seeded
columns). The indexer computes in the stated precision without the
published Hadamard rotation (orthogonal: it cancels in the product).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from vbench.reference import common

_HI = jax.lax.Precision.HIGHEST
_QUERY_BLOCK = 128


def layer_kinds(cfg: dict) -> list[str]:
    k = cfg["first_k_dense_replace"]
    return ["dense"] * k + ["sparse"] * (cfg["num_hidden_layers"] - k)


def _dims(cfg: dict) -> dict:
    return dict(
        d=cfg["hidden_size"], h=cfg["num_attention_heads"],
        rq=cfg["q_lora_rank"], rkv=cfg["kv_lora_rank"],
        dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
        dv=cfg["v_head_dim"], hi=cfg["index_n_heads"],
        di=cfg["index_head_dim"], topk=cfg["index_topk"],
        f=cfg["intermediate_size"], fe=cfg["moe_intermediate_size"],
        e=cfg["n_routed_experts_published"], held=cfg["n_routed_experts"],
        first=cfg["held_experts_first"], k=cfg["num_experts_per_tok"],
        shared=cfg["n_shared_experts"])


def weight_specs(cfg: dict) -> list[dict]:
    m, t, v = _dims(cfg), cfg["dtype"], cfg["vocab_size"]
    d, h, rq, rkv, dn, dr, dv = (m[x] for x in
                                 ("d", "h", "rq", "rkv", "dn", "dr", "dv"))

    def leaf(name, shape, fan_in, layered=True, dtype=t, kind=None):
        spec = {"name": name, "shape": list(shape), "fan_in": fan_in,
                "dtype": dtype, "layered": layered}
        if kind is not None:
            spec["kind"] = kind
        return spec

    fs = m["fe"] * m["shared"]
    return [
        leaf("embed", [v, d], d, layered=False),
        leaf("final_norm", [d], None, layered=False),
        leaf("lm_head", [v, d], d, layered=False),
        # attention and the indexer: every layer
        leaf("attn_norm", [d], None),
        leaf("wq_a", [d, rq], d),
        leaf("q_norm", [rq], None),
        leaf("wq_b", [rq, h * (dn + dr)], rq),
        leaf("wkv_a", [d, rkv + dr], d),
        leaf("kv_norm", [rkv], None),
        leaf("wkv_b", [rkv, h * (dn + dv)], rkv),
        leaf("wo", [h * dv, d], h * dv),
        leaf("idx_wq", [rq, m["hi"] * m["di"]], rq),
        leaf("idx_wk", [d, m["di"]], d),
        leaf("idx_k_gain", [m["di"]], None),
        leaf("idx_k_bias", [m["di"]], 300),      # a small range: +-0.1
        leaf("idx_w", [d, m["hi"]], d),
        leaf("mlp_norm", [d], None),
        # the leading dense layers
        leaf("w_gate", [d, m["f"]], d, kind="dense"),
        leaf("w_up", [d, m["f"]], d, kind="dense"),
        leaf("w_down", [m["f"], d], m["f"], kind="dense"),
        # the sparse layers: router as wide as published, the held stacks
        leaf("router", [d, m["e"]], d, dtype="float32", kind="sparse"),
        leaf("route_bias", [m["e"]], 1200, dtype="float32", kind="sparse"),
        leaf("e_gate", [m["held"], d, m["fe"]], d, kind="sparse"),
        leaf("e_up", [m["held"], d, m["fe"]], d, kind="sparse"),
        leaf("e_down", [m["held"], m["fe"], d], m["fe"], kind="sparse"),
        leaf("s_gate", [d, fs], d, kind="sparse"),
        leaf("s_up", [d, fs], d, kind="sparse"),
        leaf("s_down", [fs, d], fs, kind="sparse"),
    ]


# ------------------------------------------------------------ positions


def yarn_inv_freq(cfg: dict) -> jax.Array:
    """[dr / 2] inverse frequencies: as trained where a pair turns more
    than beta_fast times over the original context, divided by ``factor``
    where fewer than beta_slow, a linear blend between."""
    dim, base, rs = cfg["qk_rope_head_dim"], cfg["rope_theta"], cfg["rope_scaling"]
    half = dim // 2
    plain = 1.0 / (base ** (jnp.arange(half, dtype=jnp.float32) / half))

    def pair(rotations):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(pair(rs["beta_fast"])), 0)
    high = min(math.ceil(pair(rs["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return plain / rs["factor"] * ramp + plain * (1.0 - ramp)


def _mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(cfg: dict) -> float:
    rs = cfg["rope_scaling"]
    return ((cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
            * _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2)


def rope(cfg: dict, x: jax.Array, positions=None) -> jax.Array:
    """Rotary positions (0..S-1 unless given) on the last axis of x
    [S, ..., dr], halves rotated against each other."""
    rs = cfg["rope_scaling"]
    if positions is None:
        positions = jnp.arange(x.shape[0])
    ang = positions.astype(jnp.float32)[:, None] * yarn_inv_freq(cfg)[None, :]
    mag = _mscale(rs["factor"], rs["mscale"]) / _mscale(
        rs["factor"], rs["mscale_all_dim"])
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (ang.shape[1],)
    cos, sin = (jnp.cos(ang) * mag).reshape(shape), (jnp.sin(ang) * mag).reshape(shape)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _rope_first(cfg: dict, x: jax.Array) -> jax.Array:
    dr = cfg["qk_rope_head_dim"]
    return jnp.concatenate([rope(cfg, x[..., :dr]), x[..., dr:]], -1)


def _layer_norm(x, gain, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps) * gain.astype(jnp.float32)
            + bias.astype(jnp.float32))


# ------------------------------------------------------------- the layer


def indexer_parts(cfg, w, n, c_q, precision):
    """What the indexer's score is made of: queries q_i [S, Hi, Di], keys
    k_i [S, Di] and head weights w_i [S, Hi] (the score of all pairs is
    only ever made a block of queries at a time)."""
    m = _dims(cfg)
    s = n.shape[0]
    q_i = _rope_first(cfg, common.mm(c_q, w["idx_wq"], precision).reshape(
        s, m["hi"], m["di"]))
    k_i = _rope_first(cfg, _layer_norm(
        common.mm(n, w["idx_wk"], precision), w["idx_k_gain"],
        w["idx_k_bias"], cfg["rms_norm_eps"]))
    w_i = common.mm(n, w["idx_w"], precision) * (m["hi"] ** -0.5
                                                  * m["di"] ** -0.5)
    return q_i, k_i, w_i


def selection_mask(scores: jax.Array, qpos: jax.Array, topk: int) -> jax.Array:
    """[Q, S] bool: the ``topk`` visible positions (s <= qpos) of each row
    with the largest score, all of the visible while fewer than that."""
    s = scores.shape[-1]
    visible = jnp.arange(s)[None, :] <= qpos[:, None]
    if s <= topk:
        return visible
    _, idx = jax.lax.top_k(jnp.where(visible, scores, -jnp.inf), topk)
    chosen = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None], idx].set(True)
    return chosen & visible


def attention_block(cfg: dict, w: dict, x: jax.Array, precision: str,
                    selected=None) -> jax.Array:
    """x + W_o . attention over each query's selection; x [S, D].
    ``selected`` ([S, S] bool, tests only) takes the selection's place."""
    m = _dims(cfg)
    s = x.shape[0]
    h, dn, dr, dv, rkv = m["h"], m["dn"], m["dr"], m["dv"], m["rkv"]
    eps = cfg["rms_norm_eps"]
    n = common.rms_norm(x, w["attn_norm"], eps)
    c_q = common.rms_norm(common.mm(n, w["wq_a"], precision), w["q_norm"], eps)
    kv = common.mm(n, w["wkv_a"], precision)
    c_kv = common.rms_norm(kv[:, :rkv], w["kv_norm"], eps)
    k_pe = rope(cfg, kv[:, rkv:])                                 # [S, dr]
    kvb = common.mm(c_kv, w["wkv_b"], precision).reshape(s, h, dn + dv)
    k_nope, v = kvb[..., :dn], kvb[..., dn:]
    q_i, k_i, w_i = indexer_parts(cfg, w, n, c_q, precision)
    scale = softmax_scale(cfg)
    block = min(_QUERY_BLOCK, s)
    if s % block:
        raise ValueError(f"sequence {s} is no multiple of {block}")

    def one_block(start):
        rows = start + jnp.arange(block)
        q = common.mm(c_q[rows], w["wq_b"], precision).reshape(
            block, h, dn + dr)
        q_nope, q_pe = q[..., :dn], rope(cfg, q[..., dn:], rows)
        if selected is None:
            per_head = jnp.einsum("qhd,sd->qhs", q_i[rows], k_i, precision=_HI)
            score = jnp.sum(jax.nn.relu(per_head) * w_i[rows][:, :, None], 1)
            keep = selection_mask(score, rows, m["topk"])
        else:
            keep = selected[rows]
        logits = (jnp.einsum("qhd,shd->hqs", q_nope, k_nope, precision=_HI)
                  + jnp.einsum("qhd,sd->hqs", q_pe, k_pe, precision=_HI))
        probs = jax.nn.softmax(
            jnp.where(keep[None], logits * scale, -jnp.inf), axis=-1)
        return jnp.einsum("hqs,shd->qhd", probs, v, precision=_HI)

    out = jax.lax.map(one_block, jnp.arange(0, s, block))
    return x + common.mm(out.reshape(s, h * dv), w["wo"], precision)


def route_gates(cfg: dict, w: dict, n: jax.Array, precision: str) -> jax.Array:
    """[S, E] float32: the renormalised, scaled weight of each chosen
    expert, 0 elsewhere."""
    e, k = w["router"].shape[1], cfg["num_experts_per_tok"]
    groups, kept = cfg["n_group"], cfg["topk_group"]
    g = jax.nn.sigmoid(common.mm(n, w["router"], precision))
    choice = (g + w["route_bias"]).reshape(-1, groups, e // groups)
    group_score = jnp.sum(jax.lax.top_k(choice, 2)[0], axis=-1)
    _, best = jax.lax.top_k(group_score, kept)
    in_kept = jnp.zeros(group_score.shape, bool).at[
        jnp.arange(n.shape[0])[:, None], best].set(True)
    choice = jnp.where(in_kept[:, :, None], choice, -jnp.inf).reshape(-1, e)
    _, chosen = jax.lax.top_k(choice, k)
    picked = jnp.take_along_axis(g, chosen, axis=1)
    weights = picked / jnp.sum(picked, -1, keepdims=True) \
        * cfg["routed_scaling_factor"]
    return jnp.zeros_like(g).at[
        jnp.arange(n.shape[0])[:, None], chosen].set(weights)


def selection(cfg: dict, w: dict, x: jax.Array, precision: str = "f32"
              ) -> jax.Array:
    """[S, S] bool: what each position of x [S, D] selects in this layer
    (whole, so for short sequences: the tests print the two sides' overlap
    with it and hold both to one selection)."""
    eps = cfg["rms_norm_eps"]
    n = common.rms_norm(x, w["attn_norm"], eps)
    c_q = common.rms_norm(common.mm(n, w["wq_a"], precision), w["q_norm"], eps)
    q_i, k_i, w_i = indexer_parts(cfg, w, n, c_q, precision)
    per_head = jnp.einsum("qhd,sd->qhs", q_i, k_i, precision=_HI)
    score = jnp.sum(jax.nn.relu(per_head) * w_i[:, :, None], axis=1)
    return selection_mask(score, jnp.arange(x.shape[0]), cfg["index_topk"])


def layer(cfg: dict, w: dict, x: jax.Array, precision: str,
          kind: str, selected=None) -> jax.Array:
    """One block over a whole sequence x [S, D] (float32)."""
    x = attention_block(cfg, w, x, precision, selected)
    n = common.rms_norm(x, w["mlp_norm"], cfg["rms_norm_eps"])
    if kind == "dense":
        return x + common.swiglu(n, w["w_gate"], w["w_up"], w["w_down"],
                                 precision)
    first, held = cfg["held_experts_first"], cfg["n_routed_experts"]
    gates = route_gates(cfg, w, n, precision)[:, first:first + held]

    def one_expert(acc, xs):
        wg, wu, wd, g = xs
        return acc + g[:, None] * common.swiglu(n, wg, wu, wd, precision), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(n),
        (w["e_gate"], w["e_up"], w["e_down"], gates.T))
    shared = common.swiglu(n, w["s_gate"], w["s_up"], w["s_down"], precision)
    return x + shared + routed


# -------------------------------------------------- operations and bytes


def _attn_params(cfg: dict) -> tuple:
    """(latent attention's parameters a layer, the indexer's)."""
    m = _dims(cfg)
    latent = (m["d"] * m["rq"] + m["rq"] * m["h"] * (m["dn"] + m["dr"])
              + m["d"] * (m["rkv"] + m["dr"])
              + m["rkv"] * m["h"] * (m["dn"] + m["dv"])
              + m["h"] * m["dv"] * m["d"])
    indexer = m["rq"] * m["hi"] * m["di"] + m["d"] * m["di"] + m["d"] * m["hi"]
    return latent, indexer


def sparse_attn_step_cost(cfg: dict, batch: int, live_tokens: int) -> tuple:
    """(FLOPs, bytes) of one decode step's indexer, selection and latent
    attention alone (the scopes ``indexer``, ``select``, ``latent_attn``),
    all layers: the indexer's three projections (weights read once), its
    key written, its scores against every live token's key (each read
    once); the selection needs nothing of the memory; attention in the
    latent space over min(index_topk, length) rows a stream, each read
    once."""
    m, el = _dims(cfg), 2
    _, indexer = _attn_params(cfg)
    chosen = min(live_tokens, batch * m["topk"])
    row = m["rkv"] + m["dr"]
    flops = (batch * 2 * indexer + 2 * m["hi"] * m["di"] * live_tokens
             + 2 * m["h"] * (row + m["rkv"]) * chosen)
    byts = (indexer * el + (live_tokens + batch) * m["di"] * el
            + chosen * row * el)
    return cfg["num_hidden_layers"] * flops, cfg["num_hidden_layers"] * byts


def decode_step_cost(cfg: dict, batch: int, live_tokens: int) -> tuple:
    """(FLOPs, bytes) the algorithm needs for one decode step: every
    weight outside the routed experts read once; of the held experts those
    a token of the batch chose, taken as min(held, expected choices) a
    layer, and k * held / E of them computed a token; the live tokens'
    indexer keys and index_topk latents a stream read once; the new
    token's rows written."""
    m, el = _dims(cfg), 2
    d, layers = m["d"], cfg["num_hidden_layers"]
    dense = cfg["first_k_dense_replace"]
    latent, _ = _attn_params(cfg)
    sf, sb = sparse_attn_step_cost(cfg, batch, live_tokens)
    hf, hb = common.head_step_cost(cfg, batch)
    flops = sf + layers * batch * 2 * latent + hf
    byts = sb + layers * (latent * el + batch * (m["rkv"] + m["dr"]) * el) + hb
    flops += dense * batch * 2 * 3 * d * m["f"]
    byts += dense * 3 * d * m["f"] * el
    here = m["k"] * m["held"] / m["e"]          # chosen and held, a token
    expert = 3 * d * m["fe"]
    flops += (layers - dense) * batch * (
        2 * d * m["e"] + 2 * expert * (m["shared"] + here))
    byts += (layers - dense) * (
        d * m["e"] * 4 + expert * el * (m["shared"]
                                        + min(m["held"], batch * here)))
    return flops, byts
