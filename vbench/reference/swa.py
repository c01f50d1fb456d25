"""Plain reference of a decoder of window and full attention layers with
heads wider for keys than for values (MiMo-V2.5's language model), as the
configuration's file cuts it: the first ``num_hidden_layers`` layers of the
published ``hybrid_layer_pattern`` (0 full, 1 window) and ``moe_layer_freq``
(0 dense, 1 experts), holding a share of the routed experts. Float32,
``highest``, no cache: every projection over the whole sequence, a full
layer's scores over all earlier tokens, a window layer's over the band,
queries in blocks so that a 25 k sequence fits.

A layer on hidden x, n = rms_norm(x) (epsilon ``layernorm_epsilon``),
pre-norm residual blocks, a final norm:
  q = W_q n as H heads of Dk = ``head_dim`` (192); k = W_k n as Hk heads of
  Dk; v = ``attention_value_scale`` * W_v n as Hk heads of Dv =
  ``v_head_dim`` (128). Hk = ``num_key_value_heads`` (4) in a full layer,
  ``swa_num_key_value_heads`` (8) in a window layer; query head h reads
  key/value head h // (H / Hk). Rotary positions on the first
  int(Dk * ``partial_rotary_factor``) = 64 columns of a head, halves rotated
  against each other, the other columns unrotated; base ``rope_theta`` in a
  full layer, ``swa_rope_theta`` in a window layer.
  score_h(t, s) = Dk^-0.5 * q_h,t . k_g,s.
  Full layer: p = softmax over all s <= t.
  Window layer: over t - ``sliding_window`` < s <= t (128 positions with the
  query's own), with a learned scalar b_h a query head as one more logit
  that has no value: p_h(t, s) = exp(score) / (sum_s' exp(score') +
  exp(b_h)) (``add_swa_attention_sink_bias``; the full layers have none).
  out = W_o concat_h(sum_s p_h(t, s) v_g,s), W_o: H * Dv -> hidden.
  Dense layer (``moe_layer_freq`` 0): SwiGLU ``intermediate_size`` wide.
  Expert layer: g = sigmoid(W_r n) over all ``n_routed_experts_published``
  in float32; the ``num_experts_per_tok`` largest of g + bias are chosen
  (``n_group`` 1, ``topk_group`` 1: no group is left out); a chosen expert
  weighs g_e / sum_chosen g (``norm_topk_prob``; ``routed_scaling_factor``
  null: 1); y = sum_e w_e SwiGLU_e(n), experts ``moe_intermediate_size``
  wide, no shared expert, nothing dropped; of the routed experts only the
  held ones add (the rest left out, here and in the program alike).
``attention_chunk_size``, ``hybrid_block_size`` and
``attention_projection_layout`` take no part (the file's ``assumed``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from vbench.reference import common

_HI = jax.lax.Precision.HIGHEST
_QUERY_BLOCK = 128
KINDS = {(0, 0): "full_dense", (0, 1): "full_moe",
         (1, 0): "window_dense", (1, 1): "window_moe"}


def layer_kinds(cfg: dict) -> list[str]:
    n = cfg["num_hidden_layers"]
    return [KINDS[(w, m)] for w, m in zip(cfg["hybrid_layer_pattern"][:n],
                                          cfg["moe_layer_freq"][:n])]


def _dims(cfg: dict) -> dict:
    dk = cfg["head_dim"]
    return dict(
        d=cfg["hidden_size"], h=cfg["num_attention_heads"], dk=dk,
        dv=cfg["v_head_dim"], dr=int(dk * cfg["partial_rotary_factor"]),
        hk_full=cfg["num_key_value_heads"],
        hk_window=cfg["swa_num_key_value_heads"],
        window=cfg["sliding_window"], f=cfg["intermediate_size"],
        fe=cfg["moe_intermediate_size"],
        e=cfg["n_routed_experts_published"], held=cfg["n_routed_experts"],
        k=cfg["num_experts_per_tok"])


def _kv_heads(m: dict, kind: str) -> int:
    return m["hk_window"] if kind.startswith("window") else m["hk_full"]


def weight_specs(cfg: dict) -> list[dict]:
    """A leaf that two kinds own in one shape still stands once a kind
    (a spec names one owner): the leaves are drawn by their index in this
    list and the layer's index, so both sides make layer 3 alike."""
    m, t, v = _dims(cfg), cfg["dtype"], cfg["vocab_size"]
    d, h, dk, dv = m["d"], m["h"], m["dk"], m["dv"]

    def leaf(name, shape, fan_in, layered=True, dtype=t, kind=None):
        spec = {"name": name, "shape": list(shape), "fan_in": fan_in,
                "dtype": dtype, "layered": layered}
        if kind is not None:
            spec["kind"] = kind
        return spec

    specs = [
        leaf("embed", [v, d], d, layered=False),
        leaf("final_norm", [d], None, layered=False),
        leaf("lm_head", [v, d], d, layered=False),
        # every layer
        leaf("attn_norm", [d], None),
        leaf("wq", [d, h * dk], d),
        leaf("wo", [h * dv, d], h * dv),
        leaf("mlp_norm", [d], None),
    ]
    for kind in dict.fromkeys(layer_kinds(cfg)):
        hk = _kv_heads(m, kind)
        specs += [leaf("wk", [d, hk * dk], d, kind=kind),
                  leaf("wv", [d, hk * dv], d, kind=kind)]
        if kind.startswith("window"):
            # the sink's logit a query head: uniform on +-sqrt(3)
            specs.append(leaf("sink", [h], 1.0, dtype="float32", kind=kind))
        if kind.endswith("dense"):
            specs += [leaf("w_gate", [d, m["f"]], d, kind=kind),
                      leaf("w_up", [d, m["f"]], d, kind=kind),
                      leaf("w_down", [m["f"], d], m["f"], kind=kind)]
        else:  # router as wide as published, the held experts' stacks
            specs += [
                leaf("router", [d, m["e"]], d, dtype="float32", kind=kind),
                leaf("route_bias", [m["e"]], 1200.0, dtype="float32",
                     kind=kind),  # +-0.05
                leaf("e_gate", [m["held"], d, m["fe"]], d, kind=kind),
                leaf("e_up", [m["held"], d, m["fe"]], d, kind=kind),
                leaf("e_down", [m["held"], m["fe"], d], m["fe"], kind=kind)]
    return specs


# ------------------------------------------------------------- the layer


def _rope(cfg: dict, x: jax.Array, theta: float) -> jax.Array:
    """Rotary positions 0..S-1 on the first columns of x [S, H, Dk]."""
    dr = _dims(cfg)["dr"]
    return jnp.concatenate(
        [common.rope(x[..., :dr], theta), x[..., dr:]], axis=-1)


def attention_block(cfg: dict, w: dict, x: jax.Array, precision: str,
                    kind: str) -> jax.Array:
    """x + W_o . attention; x [S, D]. A window layer's block of queries
    reads the slice of keys its band can reach (the keys padded in front by
    a window's worth), a full layer's all of them."""
    m = _dims(cfg)
    s = x.shape[0]
    h, dk, dv = m["h"], m["dk"], m["dv"]
    windowed = kind.startswith("window")
    hk = _kv_heads(m, kind)
    g = h // hk
    theta = cfg["swa_rope_theta"] if windowed else cfg["rope_theta"]
    n = common.rms_norm(x, w["attn_norm"], cfg["layernorm_epsilon"])
    q = _rope(cfg, common.mm(n, w["wq"], precision).reshape(s, h, dk), theta)
    k = _rope(cfg, common.mm(n, w["wk"], precision).reshape(s, hk, dk), theta)
    v = cfg["attention_value_scale"] * common.mm(
        n, w["wv"], precision).reshape(s, hk, dv)
    block = min(_QUERY_BLOCK, s)
    if s % block:
        raise ValueError(f"sequence {s} is no multiple of {block}")
    window = m["window"]
    if windowed:  # keys a block can reach: ``window`` before it, and itself
        k = jnp.pad(k, ((window, 0), (0, 0), (0, 0)))
        v = jnp.pad(v, ((window, 0), (0, 0), (0, 0)))
        sink = w["sink"].astype(jnp.float32).reshape(hk, g, 1, 1)
    scale = dk ** -0.5

    def one_block(start):
        rows = start + jnp.arange(block)
        qb = q[rows].reshape(block, hk, g, dk)
        if windowed:
            kb = jax.lax.dynamic_slice_in_dim(k, start, block + window)
            vb = jax.lax.dynamic_slice_in_dim(v, start, block + window)
            kpos = start - window + jnp.arange(block + window)
            seen = ((kpos[None, :] <= rows[:, None])
                    & (kpos[None, :] > rows[:, None] - window)
                    & (kpos[None, :] >= 0))
        else:
            kb, vb = k, v
            seen = jnp.arange(s)[None, :] <= rows[:, None]
        logits = jnp.einsum("qkgd,skd->kgqs", qb, kb, precision=_HI) * scale
        logits = jnp.where(seen[None, None], logits, -jnp.inf)
        top = jnp.max(logits, axis=-1, keepdims=True)
        if windowed:
            top = jnp.maximum(top, sink)
        e = jnp.exp(logits - top)
        total = jnp.sum(e, axis=-1, keepdims=True)
        if windowed:
            total = total + jnp.exp(sink - top)
        out = jnp.einsum("kgqs,skv->qkgv", e / total, vb, precision=_HI)
        return out.reshape(block, h * dv)

    out = jax.lax.map(one_block, jnp.arange(0, s, block))
    return x + common.mm(out.reshape(s, h * dv), w["wo"], precision)


def route_gates(cfg: dict, w: dict, n: jax.Array, precision: str) -> jax.Array:
    """[S, E] float32: a chosen expert's sigmoid score over the sum of the
    chosen ones', 0 elsewhere; the choice is of score + bias."""
    k = cfg["num_experts_per_tok"]
    if (cfg["scoring_func"], cfg["topk_method"], cfg["n_group"],
            cfg["topk_group"], cfg["norm_topk_prob"]) != (
                "sigmoid", "noaux_tc", 1, 1, True):
        raise ValueError("this reference routes by sigmoid scores, noaux_tc, "
                         "one group, the chosen weights renormalised")
    scale = cfg["routed_scaling_factor"] or 1.0
    g = jax.nn.sigmoid(common.mm(n, w["router"], precision))
    _, chosen = jax.lax.top_k(g + w["route_bias"], k)
    picked = jnp.take_along_axis(g, chosen, axis=1)
    picked = picked / jnp.sum(picked, axis=-1, keepdims=True) * scale
    return jnp.zeros_like(g).at[
        jnp.arange(n.shape[0])[:, None], chosen].set(picked)


def expert_block(cfg: dict, w: dict, n: jax.Array,
                 precision: str) -> jax.Array:
    """The held experts' part of the layer's result over n [S, D]: the
    experts whose stacks ``w`` holds are ``held_experts_first ..`` of the
    router's columns, ``n_routed_experts`` of them."""
    first, held = cfg["held_experts_first"], cfg["n_routed_experts"]
    gates = route_gates(cfg, w, n, precision)[:, first:first + held]

    def one_expert(acc, xs):
        wg, wu, wd, g = xs
        return acc + g[:, None] * common.swiglu(n, wg, wu, wd, precision), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(n),
        (w["e_gate"], w["e_up"], w["e_down"], gates.T))
    return routed


def layer(cfg: dict, w: dict, x: jax.Array, precision: str,
          kind: str) -> jax.Array:
    """One block over a whole sequence x [S, D] (float32)."""
    x = attention_block(cfg, w, x, precision, kind)
    n = common.rms_norm(x, w["mlp_norm"], cfg["layernorm_epsilon"])
    if kind.endswith("dense"):
        return x + common.swiglu(n, w["w_gate"], w["w_up"], w["w_down"],
                                 precision)
    return x + expert_block(cfg, w, n, precision)


# -------------------------------------------------- operations and bytes


def _counts(cfg: dict) -> tuple:
    """(full layers, window layers, dense layers, expert layers) held."""
    kinds = layer_kinds(cfg)
    full = sum(k.startswith("full") for k in kinds)
    dense = sum(k.endswith("dense") for k in kinds)
    return full, len(kinds) - full, dense, len(kinds) - dense


def full_attn_step_cost(cfg: dict, batch: int, live_tokens: int) -> tuple:
    """(FLOPs, bytes) of one decode step's attention over the cache in the
    full layers alone (the scope ``paged_attn``), for streams holding
    ``live_tokens`` cached tokens: every query head against every live
    token's key (Dk wide) and the probabilities against its value (Dv), a
    token's row of key/value heads read once a layer (2560 B published).
    ``batch`` does not enter (a stream's queries and output are a page's
    worth)."""
    del batch
    m, el = _dims(cfg), 2
    full = _counts(cfg)[0]
    flops = 2 * m["h"] * (m["dk"] + m["dv"]) * live_tokens
    byts = m["hk_full"] * (m["dk"] + m["dv"]) * el * live_tokens
    return full * flops, full * byts


def window_attn_step_cost(cfg: dict, batch: int, live_tokens: int) -> tuple:
    """(FLOPs, bytes) of one decode step's attention in the window layers
    (the scopes ``window_attn`` and ``ring_write``) for ``batch`` streams,
    each taken to have filled its ring: ``sliding_window`` rows of key and
    value read once a layer, the new row written; the cached tokens beyond
    the ring do not enter."""
    del live_tokens
    m, el = _dims(cfg), 2
    rows = batch * m["window"]
    flops = 2 * m["h"] * (m["dk"] + m["dv"]) * rows
    byts = m["hk_window"] * (m["dk"] + m["dv"]) * el * (rows + batch)
    window = _counts(cfg)[1]
    return window * flops, window * byts


def decode_step_cost(cfg: dict, batch: int, live_tokens: int) -> tuple:
    """(FLOPs, bytes) the algorithm needs for one decode step: every weight
    outside the routed experts read once; of the held experts those a token
    of the batch chose, taken as min(held, expected choices) a layer, and
    k * held / E of them computed a token; the full layers' live rows and
    the window layers' rings read once; the new token's rows written."""
    m, el = _dims(cfg), 2
    d = m["d"]
    full, window, dense, moe = _counts(cfg)
    ff, fb = full_attn_step_cost(cfg, batch, live_tokens)
    wf, wb = window_attn_step_cost(cfg, batch, live_tokens)
    hf, hb = common.head_step_cost(cfg, batch)
    shared = d * m["h"] * (m["dk"] + m["dv"])          # W_q and W_o
    proj = (full + window) * shared + d * (m["dk"] + m["dv"]) * (
        full * m["hk_full"] + window * m["hk_window"])
    flops = ff + wf + hf + batch * 2 * proj
    byts = fb + wb + hb + proj * el + full * batch * m["hk_full"] * (
        m["dk"] + m["dv"]) * el
    flops += dense * batch * 2 * 3 * d * m["f"]
    byts += dense * 3 * d * m["f"] * el
    here = m["k"] * m["held"] / m["e"]          # chosen and held, a token
    expert = 3 * d * m["fe"]
    flops += moe * batch * (2 * d * m["e"] + 2 * expert * here)
    byts += moe * (d * m["e"] * 4 + expert * el * min(m["held"], batch * here))
    return flops, byts
