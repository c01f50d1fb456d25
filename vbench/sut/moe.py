"""``MoeSlotModel`` over the paged pool, for ``family: moe``. ``top_k`` is
the published experts per token and ``capacity_factor = E / k``, so that
``MoEConfig.capacity()`` is the token count and prefill drops nobody, as
decode already does (every expert then computes over all rows: the code's
price as it stands)."""

from __future__ import annotations

import jax.numpy as jnp

from vbench.sut import common


def build(cfg: dict, weights: dict):
    from vtpu.models.moe import MoEConfig
    from vtpu.serving.adapters import MoeSlotModel

    serving = common.serving_config(cfg["serving"])
    e, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    mcfg = MoEConfig(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_layers=cfg["num_hidden_layers"],
        d_ff=cfg["intermediate_size"], n_experts=e, top_k=k,
        capacity_factor=e / k, max_seq=cfg["max_position_embeddings"],
        head_dim=cfg["head_dim"], dtype=jnp.bfloat16)
    params = {"embed": weights["embed"], "final_norm": weights["final_norm"],
              "layers": weights["layers"]}
    model = MoeSlotModel(
        params, mcfg, kv_page=serving.kv_page,
        kv_pool_blocks=serving.kv_pool_blocks, paged_attn=serving.paged_attn)
    return common.engine(model, serving)
