"""Plain reference of the sparse-expert decoder (OLMoE block): pre-norm
attention with rotary positions, then a router over all experts, the top-k
of them a token, each a SwiGLU, mixed by the renormalised router weights.
No capacity and no dropping: every token gets its k experts. Departures
from the published model are in the configuration's file."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from vbench.reference import common


def weight_specs(cfg: dict) -> list[dict]:
    d, f, e, t = (cfg["hidden_size"], cfg["intermediate_size"],
                  cfg["num_experts"], cfg["dtype"])
    return common.attn_specs(cfg) + [
        {"name": "router", "shape": [d, e], "fan_in": d, "dtype": "float32",
         "layered": True},
        {"name": "w_gate", "shape": [e, d, f], "fan_in": d, "dtype": t,
         "layered": True},
        {"name": "w_up", "shape": [e, d, f], "fan_in": d, "dtype": t,
         "layered": True},
        {"name": "w_down", "shape": [e, f, d], "fan_in": f, "dtype": t,
         "layered": True},
    ]


def layer(cfg: dict, w: dict, x: jax.Array, precision: str) -> jax.Array:
    """One block over a whole sequence x [S, D] (float32)."""
    x = common.attention_block(cfg, w, x, precision)
    n = common.rms_norm(x, w["mlp_norm"], cfg["rms_norm_eps"])
    e, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(common.mm(n, w["router"], precision), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    # gates [S, E]: the renormalised weight of a chosen expert, else 0
    gates = jnp.zeros_like(probs).at[
        jnp.arange(n.shape[0])[:, None], top_i].set(top_p)

    def one_expert(acc, xs):
        wg, wu, wd, g = xs
        return acc + g[:, None] * common.swiglu(n, wg, wu, wd, precision), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(n),
        (w["w_gate"], w["w_up"], w["w_down"], gates.T), length=e)
    return x + out


def decode_step_cost(cfg: dict, batch: int, live_tokens: int) -> tuple:
    """(FLOPs, bytes) the algorithm needs for one decode step: k experts a
    token computed; an expert's weights read once if any token chose it,
    taken as min(E, batch * k) experts touched a layer."""
    d, f, layers = (cfg["hidden_size"], cfg["intermediate_size"],
                    cfg["num_hidden_layers"])
    e, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    af, ab = common.attn_step_cost(cfg, batch, live_tokens)
    hf, hb = common.head_step_cost(cfg, batch)
    touched = min(e, batch * k)
    flops = layers * (af + batch * (2 * d * e + k * 2 * 3 * d * f)) + hf
    byts = layers * (ab + d * e * 4 + touched * 3 * d * f * 2) + hb
    return flops, byts
