"""Plain reference of the dense decoder (DeepSeek-LLM / LLaMA block):
pre-norm attention with rotary positions, pre-norm SwiGLU, final norm,
output head. Departures from the published model are the configuration
file's ``assumed`` and ``departures`` lists (tied output head)."""

from __future__ import annotations

import jax

from vbench.reference import common


def weight_specs(cfg: dict) -> list[dict]:
    d, f, t = cfg["hidden_size"], cfg["intermediate_size"], cfg["dtype"]
    return common.attn_specs(cfg) + [
        {"name": "w_gate", "shape": [d, f], "fan_in": d, "dtype": t,
         "layered": True},
        {"name": "w_up", "shape": [d, f], "fan_in": d, "dtype": t,
         "layered": True},
        {"name": "w_down", "shape": [f, d], "fan_in": f, "dtype": t,
         "layered": True},
    ]


def layer(cfg: dict, w: dict, x: jax.Array, precision: str) -> jax.Array:
    """One block over a whole sequence x [S, D] (float32)."""
    x = common.attention_block(cfg, w, x, precision)
    n = common.rms_norm(x, w["mlp_norm"], cfg["rms_norm_eps"])
    return x + common.swiglu(n, w["w_gate"], w["w_up"], w["w_down"],
                             precision)


def decode_step_cost(cfg: dict, batch: int, live_tokens: int) -> tuple:
    """(FLOPs, bytes) the algorithm needs for one decode step: every weight
    read once, the live cache read once, one new token a stream."""
    d, f, layers = (cfg["hidden_size"], cfg["intermediate_size"],
                    cfg["num_hidden_layers"])
    af, ab = common.attn_step_cost(cfg, batch, live_tokens)
    hf, hb = common.head_step_cost(cfg, batch)
    flops = layers * (af + batch * 2 * 3 * d * f) + hf
    byts = layers * (ab + 3 * d * f * 2) + hb
    return flops, byts
