"""``LatentSlotModel`` over its one paged plane, for ``family: mla``
(DeepSeek-V2's block: latent attention over all that is cached, no
indexer): the benchmark's leaves handed to the program's two stacks
(``dense`` and ``sparse``, as ``layer_kinds`` names them), the held
experts' stacks under the names the program's expert layer reads. The
leaves the program holds in another layout (``wq_b`` transposed, ``kv_b``
in its key and value halves a head) are laid out once, here, and the
benchmark's own copy of each is dropped as it goes, so that the build's
peak is what serving holds. ``serving.read_windows`` is the slot model's,
not a ``ServingConfig`` field: it goes to the adapter."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from vbench.sut import common
from vtpu.models.latent import LatentConfig
from vtpu.models.moe import group_limited_route  # noqa: F401  a program
# without this router cannot run this family: fail here, at once, before
# any weight is made

_EXPERT_NAMES = {"e_gate": "w_gate", "e_up": "w_up", "e_down": "w_down",
                 "s_gate": "ws_gate", "s_up": "ws_up", "s_down": "ws_down"}


def model_config(cfg: dict, dtype=None):
    """The configuration's keys as the program's ``LatentConfig`` (computing
    in the configuration's ``dtype`` unless told another)."""
    dtype = dtype or {"bfloat16": jnp.bfloat16,
                      "float32": jnp.float32}[cfg["dtype"]]
    if (cfg["scoring_func"], cfg["topk_method"], cfg["norm_topk_prob"]) != (
            "softmax", "group_limited_greedy", False):
        raise ValueError("the program's router for this family is softmax, "
                         "group_limited_greedy, weights not renormalised")
    rs = cfg["rope_scaling"]
    dense = cfg["first_k_dense_replace"]
    return LatentConfig(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_dense_layers=dense,
        n_sparse_layers=cfg["num_hidden_layers"] - dense,
        d_ff=cfg["intermediate_size"],
        d_ff_expert=cfg["moe_intermediate_size"],
        d_ff_shared=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
        nope_dim=cfg["qk_nope_head_dim"], rope_dim=cfg["qk_rope_head_dim"],
        v_dim=cfg["v_head_dim"], index_heads=0, index_dim=0, index_topk=0,
        n_experts=cfg["n_routed_experts_published"],
        held=(cfg["held_experts_first"], cfg["n_routed_experts"]),
        top_k=cfg["num_experts_per_tok"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"],
        route_scale=cfg["routed_scaling_factor"],
        topk_method=cfg["topk_method"],
        max_seq=cfg["max_position_embeddings"],
        rope_theta=cfg["rope_theta"], yarn_factor=rs["factor"],
        yarn_original_max=rs["original_max_position_embeddings"],
        yarn_beta_fast=rs["beta_fast"], yarn_beta_slow=rs["beta_slow"],
        yarn_mscale=rs["mscale"], yarn_mscale_all_dim=rs["mscale_all_dim"],
        eps=cfg["rms_norm_eps"], dtype=dtype)


def params_of(cfg: dict, weights: dict, consume: bool = False) -> dict:
    """The benchmark's leaves under the program's names. ``consume`` frees
    a leaf of the benchmark's once the program's layout of it is made
    (``build`` may: the weights were made for it and vbench/run.py reads
    them no more)."""
    h, dn = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]

    def laid_out(fn, leaf):
        if isinstance(leaf, jax.ShapeDtypeStruct):  # a rehearsal's shapes
            return jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=leaf.sharding),
                jax.eval_shape(fn, leaf))
        out = jax.block_until_ready(jax.jit(fn)(leaf))
        if consume:
            leaf.delete()
        return out

    def halves(kv_b):  # [L, rkv, H * (dn + dv)] -> w_uk, w_uv a head
        kv_b = kv_b.reshape(kv_b.shape[0], kv_b.shape[1], h, -1)
        return (jnp.transpose(kv_b[..., :dn], (0, 2, 3, 1)),
                jnp.transpose(kv_b[..., dn:], (0, 2, 1, 3)))

    def stack(leaves: dict) -> dict:
        out = {_EXPERT_NAMES.get(k, k): v for k, v in leaves.items()
               if k not in ("wq_b", "wkv_b")}
        out["wq_b"] = laid_out(lambda a: jnp.swapaxes(a, 1, 2),
                               leaves["wq_b"])
        out["w_uk"], out["w_uv"] = laid_out(halves, leaves["wkv_b"])
        return out

    layers = weights["layers"]
    return {"embed": weights["embed"], "final_norm": weights["final_norm"],
            "head": weights[cfg["output_head"]],
            "dense": stack(layers["dense"]),
            "sparse": stack(layers["sparse"])}


def build(cfg: dict, weights: dict):
    from vtpu.serving.adapters import LatentSlotModel

    sizes = dict(cfg["serving"])
    windows = sizes.pop("read_windows", None)
    serving = common.serving_config(sizes)
    model = LatentSlotModel(
        params_of(cfg, weights, consume=True), model_config(cfg),
        kv_page=serving.kv_page, kv_pool_blocks=serving.kv_pool_blocks,
        read_windows=tuple(windows) if windows else None)
    return common.engine(model, serving)
