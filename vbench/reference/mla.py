"""Plain reference of the dense latent-attention decoder (DeepSeek-V2's
block), as the configuration's file cuts it: a leading dense layer, then
sparse layers holding one group of the routed experts. Float32,
``highest``, no cache: every projection over the whole sequence, keys and
values expanded from the latents for every head, scores over **all**
cached tokens under the causal mask (there is no indexer and no
selection), queries in blocks so that a 25 k sequence fits.

Per token t, cached tokens s <= t, n = rms_norm(x):
  c_q = rms_norm(W_qa n); q = W_qb c_q -> H heads of [q_nope | q_pe], rotary
  on q_pe. [c_kv | k_pe] = W_kva n; c_kv = rms_norm(c_kv); rotary on k_pe
  (one rotated key for all heads). [k_nope | v] per head = W_kvb c_kv.
  score_h(t, s) = scale * (q_nope_h,t . k_nope_h,s + q_pe_h,t . k_pe_s),
  scale = (dn + dr)^-0.5 * m^2, m = 0.1 * mscale_all_dim * ln(factor) + 1
  (published: 192^-0.5 * 1.5896); out = W_o concat_h(softmax(score_h) v_h).
  Dense layer: SwiGLU. Sparse layer: s = softmax(W_r n) over all experts in
  float32; the experts lie in n_group equal groups; a group scores its
  largest s; the topk_group best groups are kept; the
  num_experts_per_tok largest s among them are chosen; a chosen expert
  weighs routed_scaling_factor * s (not renormalised);
  y = sum_e w_e SwiGLU_e(n) + SwiGLU_shared(n), the n_shared_experts shared
  experts being one SwiGLU n_shared_experts * moe_intermediate_size wide
  (their sum, exactly); of the routed experts only the held ones add (the
  rest left out, here and in the program alike).
Rotary positions are YaRN's, halves rotated against each other (the
pairing is the configuration's ``assumed``: a permutation of seeded
columns), as ``vbench/reference/latent.py`` makes them; with mscale =
mscale_all_dim the rotary tables carry no magnitude.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from vbench.reference import common
from vbench.reference.latent import rope, softmax_scale  # YaRN's positions
# and the softmax scale are the latent family's, pairing and all: one copy

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
        dv=cfg["v_head_dim"], f=cfg["intermediate_size"],
        fe=cfg["moe_intermediate_size"],
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
        # attention: every layer
        leaf("attn_norm", [d], None),
        leaf("wq_a", [d, rq], d),
        leaf("q_norm", [rq], None),
        leaf("wq_b", [rq, h * (dn + dr)], rq),
        leaf("wkv_a", [d, rkv + dr], d),
        leaf("kv_norm", [rkv], None),
        leaf("wkv_b", [rkv, h * (dn + dv)], rkv),
        leaf("wo", [h * dv, d], h * dv),
        leaf("mlp_norm", [d], None),
        # the leading dense layers
        leaf("w_gate", [d, m["f"]], d, kind="dense"),
        leaf("w_up", [d, m["f"]], d, kind="dense"),
        leaf("w_down", [m["f"], d], m["f"], kind="dense"),
        # the sparse layers: router as wide as published, the held stacks,
        # the shared experts as the one SwiGLU that is their sum
        leaf("router", [d, m["e"]], d, dtype="float32", kind="sparse"),
        leaf("e_gate", [m["held"], d, m["fe"]], d, kind="sparse"),
        leaf("e_up", [m["held"], d, m["fe"]], d, kind="sparse"),
        leaf("e_down", [m["held"], m["fe"], d], m["fe"], kind="sparse"),
        leaf("s_gate", [d, fs], d, kind="sparse"),
        leaf("s_up", [d, fs], d, kind="sparse"),
        leaf("s_down", [fs, d], fs, kind="sparse"),
    ]


# ------------------------------------------------------------- the layer


def attention_block(cfg: dict, w: dict, x: jax.Array,
                    precision: str) -> jax.Array:
    """x + W_o . attention of every query over every token up to its own;
    x [S, D]."""
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
    scale = softmax_scale(cfg)
    block = min(_QUERY_BLOCK, s)
    if s % block:
        raise ValueError(f"sequence {s} is no multiple of {block}")

    def one_block(start):
        rows = start + jnp.arange(block)
        q = common.mm(c_q[rows], w["wq_b"], precision).reshape(
            block, h, dn + dr)
        q_nope, q_pe = q[..., :dn], rope(cfg, q[..., dn:], rows)
        logits = (jnp.einsum("qhd,shd->hqs", q_nope, k_nope, precision=_HI)
                  + jnp.einsum("qhd,sd->hqs", q_pe, k_pe, precision=_HI))
        seen = jnp.arange(s)[None, :] <= rows[:, None]
        probs = jax.nn.softmax(
            jnp.where(seen[None], logits * scale, -jnp.inf), axis=-1)
        return jnp.einsum("hqs,shd->qhd", probs, v, precision=_HI)

    out = jax.lax.map(one_block, jnp.arange(0, s, block))
    return x + common.mm(out.reshape(s, h * dv), w["wo"], precision)


def route_gates(cfg: dict, w: dict, n: jax.Array, precision: str) -> jax.Array:
    """[S, E] float32: routed_scaling_factor times the softmax score of
    each chosen expert, 0 elsewhere."""
    e, k = w["router"].shape[1], cfg["num_experts_per_tok"]
    groups, kept = cfg["n_group"], cfg["topk_group"]
    s = jax.nn.softmax(common.mm(n, w["router"], precision), axis=-1)
    grouped = s.reshape(-1, groups, e // groups)
    _, best = jax.lax.top_k(jnp.max(grouped, axis=-1), kept)
    in_kept = jnp.zeros(grouped.shape[:2], bool).at[
        jnp.arange(n.shape[0])[:, None], best].set(True)
    choice = jnp.where(in_kept[:, :, None], grouped, -jnp.inf).reshape(-1, e)
    _, chosen = jax.lax.top_k(choice, k)
    picked = jnp.take_along_axis(s, chosen, axis=1)
    return jnp.zeros_like(s).at[
        jnp.arange(n.shape[0])[:, None], chosen].set(
            picked * cfg["routed_scaling_factor"])


def layer(cfg: dict, w: dict, x: jax.Array, precision: str,
          kind: str) -> jax.Array:
    """One block over a whole sequence x [S, D] (float32)."""
    x = attention_block(cfg, w, x, precision)
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


def _attn_params(cfg: dict) -> int:
    """Latent attention's parameters a layer."""
    m = _dims(cfg)
    return (m["d"] * m["rq"] + m["rq"] * m["h"] * (m["dn"] + m["dr"])
            + m["d"] * (m["rkv"] + m["dr"])
            + m["rkv"] * m["h"] * (m["dn"] + m["dv"])
            + m["h"] * m["dv"] * m["d"])


def latent_attn_step_cost(cfg: dict, batch: int, live_tokens: int) -> tuple:
    """(FLOPs, bytes) of one decode step's attention over the cache alone
    (the scope ``latent_attn``), all layers, for ``batch`` streams holding
    ``live_tokens`` cached tokens: every head's absorbed query against
    every live token's row (kv_lora_rank + rope wide) and the
    probabilities against its latent (kv_lora_rank wide), the row read
    once for both. The row counts at its published width whatever the
    pool stores and whatever implements the walk; ``batch`` does not
    enter (a stream's own queries and output are a page's worth)."""
    del batch
    m, el = _dims(cfg), 2
    row = m["rkv"] + m["dr"]
    flops = 2 * m["h"] * (row + m["rkv"]) * live_tokens
    byts = row * el * live_tokens
    return cfg["num_hidden_layers"] * flops, cfg["num_hidden_layers"] * byts


def decode_step_cost(cfg: dict, batch: int, live_tokens: int) -> tuple:
    """(FLOPs, bytes) the algorithm needs for one decode step: every
    weight outside the routed experts read once; of the held experts those
    a token of the batch chose, taken as min(held, expected choices) a
    layer, and k * held / E of them computed a token; every live token's
    latent row read once and attended by every head; the new token's rows
    written."""
    m, el = _dims(cfg), 2
    d, layers = m["d"], cfg["num_hidden_layers"]
    dense = cfg["first_k_dense_replace"]
    latent = _attn_params(cfg)
    af, ab = latent_attn_step_cost(cfg, batch, live_tokens)
    hf, hb = common.head_step_cost(cfg, batch)
    flops = af + layers * batch * 2 * latent + hf
    byts = ab + layers * (latent * el + batch * (m["rkv"] + m["dr"]) * el) + hb
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
