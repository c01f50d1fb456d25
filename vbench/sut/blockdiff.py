"""``BlockDiffSlotModel`` over the paged pool, for ``family: blockdiff``
(SDAR's language model: a sparse-expert decoder that generates by diffusion
over blocks): the benchmark's leaves handed to the program's stacks under the
names its expert layer and its trunk read. The adapter lays out the three
projections itself. ``serving.read_windows``, ``denoising_steps`` and
``confidence_threshold`` are no ``ServingConfig`` fields: the first goes to
the adapter, the other two, a deployment's rule of commits, to the model's
configuration."""

from __future__ import annotations

import jax.numpy as jnp

from vbench.sut import common
from vtpu.models.blockdiff import BlockDiffConfig  # noqa: F401  a program
# without it cannot run this family: fail here, at once, before any weight
# is made

_NAMES = {"e_gate": "w_gate", "e_up": "w_up", "e_down": "w_down"}
_RULE = ("denoising_steps", "confidence_threshold")


def model_config(cfg: dict, dtype=None):
    """The configuration's keys as the program's ``BlockDiffConfig``
    (computing in the configuration's ``dtype`` unless told another)."""
    dtype = dtype or {"bfloat16": jnp.bfloat16,
                      "float32": jnp.float32}[cfg["dtype"]]
    if (not cfg["norm_topk_prob"] or cfg["decoder_sparse_step"] != 1
            or cfg["mlp_only_layers"] or cfg["tie_word_embeddings"]
            or cfg["attention_bias"] or cfg["rope_scaling"]
            or cfg["use_sliding_window"]):
        raise ValueError(
            "the program's block for this family is sparse in every layer, "
            "renormalises the chosen experts' weights, has a head of its "
            "own, no bias, no rotary scaling and no sliding window")
    serving = cfg["serving"]
    return BlockDiffConfig(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        n_layers=cfg["num_hidden_layers"], d_ff=cfg["moe_intermediate_size"],
        n_experts=cfg["num_experts_published"],
        held=(cfg["held_experts_first"], cfg["num_experts"]),
        top_k=cfg["num_experts_per_tok"], eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]),
        max_seq=cfg["max_position_embeddings"], dtype=dtype,
        block_length=cfg["block_length"],
        mask_token_id=cfg["mask_token_id"],
        denoising_steps=serving["denoising_steps"],
        confidence_threshold=serving["confidence_threshold"])


def params_of(cfg: dict, weights: dict) -> dict:
    """The benchmark's leaves under the program's names."""
    return {"embed": weights["embed"], "final_norm": weights["final_norm"],
            "head": weights[cfg["output_head"]],
            "layers": {_NAMES.get(k, k): v
                       for k, v in weights["layers"].items()}}


def build(cfg: dict, weights: dict):
    from vtpu.serving.adapters import BlockDiffSlotModel

    sizes = {k: v for k, v in cfg["serving"].items() if k not in _RULE}
    windows = sizes.pop("read_windows", None)
    serving = common.serving_config(sizes)
    model = BlockDiffSlotModel(
        params_of(cfg, weights), model_config(cfg),
        kv_page=serving.kv_page, kv_pool_blocks=serving.kv_pool_blocks,
        read_windows=tuple(windows) if windows else None,
        paged_attn=serving.paged_attn)
    return common.engine(model, serving)
