"""``TransformerSlotModel`` over the paged pool, for ``family: dense``."""

from __future__ import annotations

import jax.numpy as jnp

from vbench.sut import common


def build(cfg: dict, weights: dict):
    from vtpu.models.transformer import ModelConfig
    from vtpu.serving.adapters import TransformerSlotModel

    serving = common.serving_config(cfg["serving"])
    mcfg = ModelConfig(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_layers=cfg["num_hidden_layers"],
        d_ff=cfg["intermediate_size"], max_seq=cfg["max_position_embeddings"],
        head_dim=cfg["head_dim"], dtype=jnp.bfloat16)
    params = {"embed": weights["embed"], "final_norm": weights["final_norm"],
              "layers": weights["layers"]}
    model = TransformerSlotModel(
        params, mcfg, kv_page=serving.kv_page,
        kv_pool_blocks=serving.kv_pool_blocks, paged_attn=serving.paged_attn)
    return common.engine(model, serving)
