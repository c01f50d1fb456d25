"""``WindowSlotModel`` over its paged pool and its rings, for ``family:
swa`` (MiMo-V2.5's block: window layers among full layers, heads wider for
keys than for values): the benchmark's leaves handed to the program's
stacks. The reference's kinds of layer (``full_dense``, ``window_moe``,
``full_moe``: vbench/reference/swa.py ``layer_kinds``) are the program's
own, a kind's layers stacked in the model's order, so no leaf is copied or
joined: the held experts' stacks go under the names the program's expert
layer reads, and the adapter lays out the three projections itself.
``serving.read_windows`` is the slot model's, not a ``ServingConfig`` field:
it goes to the adapter."""

from __future__ import annotations

import jax.numpy as jnp

from vbench.reference.swa import layer_kinds
from vbench.sut import common
from vtpu.models.swa import SwaConfig  # noqa: F401  a program without it
# cannot run this family: fail here, at once, before any weight is made

_EXPERT_NAMES = {"e_gate": "w_gate", "e_up": "w_up", "e_down": "w_down"}


def model_config(cfg: dict, dtype=None):
    """The configuration's keys as the program's ``SwaConfig`` (computing
    in the configuration's ``dtype`` unless told another)."""
    dtype = dtype or {"bfloat16": jnp.bfloat16,
                      "float32": jnp.float32}[cfg["dtype"]]
    if (cfg["scoring_func"], cfg["topk_method"], cfg["n_group"],
            cfg["topk_group"], cfg["norm_topk_prob"],
            cfg["routed_scaling_factor"], cfg["n_shared_experts"]) != (
                "sigmoid", "noaux_tc", 1, 1, True, None, None):
        raise ValueError("the program's router for this family is sigmoid, "
                         "noaux_tc, one group, renormalised, unscaled, with "
                         "no shared expert")
    if (not cfg["add_swa_attention_sink_bias"]
            or cfg["add_full_attention_sink_bias"]):
        raise ValueError("the program's window layers have a sink and its "
                         "full layers have none")
    if (cfg["swa_head_dim"], cfg["swa_v_head_dim"],
            cfg["swa_num_attention_heads"]) != (
                cfg["head_dim"], cfg["v_head_dim"],
                cfg["num_attention_heads"]):
        raise ValueError("the program's window and full layers share their "
                         "query heads and head widths")
    kinds = layer_kinds(cfg)
    return SwaConfig(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
        v_head_dim=cfg["v_head_dim"],
        rope_dim=int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
        layer_types=tuple(k.split("_")[0] for k in kinds),
        ffn_types=tuple(k.split("_")[1] for k in kinds),
        n_kv_heads=cfg["num_key_value_heads"],
        n_kv_heads_window=cfg["swa_num_key_value_heads"],
        window=cfg["sliding_window"], rope_theta=cfg["rope_theta"],
        rope_theta_window=cfg["swa_rope_theta"],
        value_scale=cfg["attention_value_scale"],
        d_ff=cfg["intermediate_size"],
        d_ff_expert=cfg["moe_intermediate_size"],
        n_experts=cfg["n_routed_experts_published"],
        held=(cfg["held_experts_first"], cfg["n_routed_experts"]),
        top_k=cfg["num_experts_per_tok"], eps=cfg["layernorm_epsilon"],
        max_seq=cfg["max_position_embeddings"], dtype=dtype)


def params_of(cfg: dict, weights: dict) -> dict:
    """The benchmark's leaves under the program's names."""
    return {"embed": weights["embed"], "final_norm": weights["final_norm"],
            "head": weights[cfg["output_head"]],
            "layers": {kind: {_EXPERT_NAMES.get(k, k): v
                              for k, v in leaves.items()}
                       for kind, leaves in weights["layers"].items()}}


def build(cfg: dict, weights: dict):
    from vtpu.serving.adapters import WindowSlotModel

    sizes = dict(cfg["serving"])
    windows = sizes.pop("read_windows", None)
    serving = common.serving_config(sizes)
    model = WindowSlotModel(
        params_of(cfg, weights), model_config(cfg),
        kv_page=serving.kv_page, kv_pool_blocks=serving.kv_pool_blocks,
        read_windows=tuple(windows) if windows else None,
        paged_attn=serving.paged_attn)
    return common.engine(model, serving)
