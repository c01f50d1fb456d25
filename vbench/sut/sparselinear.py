"""``SparseLinearSlotModel`` over its paged pool, its compressed keys and its
recurrent rows, for ``family: sparselinear``: the benchmark's leaves handed
to the program's two stacks. The reference names a kind for layer 0
(``sparse_in``, which carries the embedding's scale) and one for every
linear layer (``linear.<l>``: its decay depends on its place in the published
model: vbench/reference/sparselinear.py); the program has two stacks,
multiplies in its embedding and takes the places as ``layer_index``, so each
stack is its kinds' rows joined in the model's order.
``q_norm`` goes through the reference's ``map_leaves``, the one copy of that
map. ``serving.read_windows`` is the slot model's, not a ``ServingConfig``
field: it goes to the adapter."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from vbench.reference.sparselinear import (
    KINDS,
    layer_kinds,
    map_leaves,
    sparse_config,
)
from vbench.sut import common
from vtpu.models.sparselinear import SparseLinearConfig  # noqa: F401  a
# program without it cannot run this family: fail here, at once, before any
# weight is made


def model_config(cfg: dict, dtype=None):
    """The configuration's keys as the program's ``SparseLinearConfig``
    (computing in the configuration's ``dtype`` unless told another)."""
    dtype = dtype or {"bfloat16": jnp.bfloat16,
                      "float32": jnp.float32}[cfg["dtype"]]
    if cfg["attn_use_rope"] or not cfg["lightning_use_rope"]:
        raise ValueError("the program's sparse layers take no rotary "
                         "positions and its linear layers do")
    if not (cfg["qk_norm"] and cfg["use_output_gate"]
            and cfg["use_output_norm"] and cfg["attn_use_output_gate"]):
        raise ValueError("the program's mixers norm q and k and gate (and, "
                         "linear, norm) their output")
    sp = sparse_config(cfg)
    return SparseLinearConfig(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        layer_types=tuple(KINDS[m] for m in cfg["mixer_types"]),
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], lin_heads=cfg["lightning_nh"],
        lin_head_dim=cfg["lightning_head_dim"],
        ssd_chunk=cfg["lightning_chunk"],
        kernel_stride=sp["kernel_stride"], block_size=sp["block_size"],
        window_size=sp["window_size"], init_blocks=sp["init_blocks"],
        topk=sp["topk"], dense_len=sp["dense_len"],
        scale_emb=cfg["scale_emb"], scale_depth=cfg["scale_depth"],
        depth=cfg["residual_depth"], layer_index=tuple(cfg["layer_indices"]),
        dim_model_base=cfg["dim_model_base"],
        rope_theta=cfg["rope_theta"], eps=cfg["rms_norm_eps"],
        max_seq=cfg["max_position_embeddings"], dtype=dtype)


def params_of(cfg: dict, weights: dict, consume: bool = False) -> dict:
    """The benchmark's leaves under the program's names. ``consume`` frees
    each pair of sparse leaves once they are joined (``build`` may: the
    weights were made for it and vbench/run.py reads them no more)."""
    if cfg["tie_word_embeddings"] or cfg["output_head"] != "head":
        raise ValueError("the program's head is its own leaf, untied")
    layers = weights["layers"]
    kinds = list(dict.fromkeys(layer_kinds(cfg)))  # each stack's rows are
    # stacked in the model's order, and so are the kinds of one mixer

    def joined(parts):
        if len(parts) == 1:
            return parts[0]
        first = parts[0]
        if isinstance(first, jax.ShapeDtypeStruct):  # a rehearsal's shapes
            return jax.ShapeDtypeStruct(
                (sum(p.shape[0] for p in parts),) + first.shape[1:],
                first.dtype, sharding=first.sharding)
        whole = jax.block_until_ready(jnp.concatenate(parts))
        if consume:
            for p in parts:
                p.delete()
        return whole

    def stack(mixer):
        mine = [k for k in kinds if k.startswith(mixer)]
        return {name: joined([layers[k][name] for k in mine])
                for name in layers[mine[0]]}

    sparse = stack("sparse")
    if not isinstance(sparse["q_norm"], jax.ShapeDtypeStruct):
        sparse = map_leaves(sparse)
    return {"embed": weights["embed"], "head": weights["head"],
            "final_norm": weights["final_norm"], "sparse": sparse,
            "linear": stack("linear")}


def build(cfg: dict, weights: dict):
    from vtpu.serving.adapters import SparseLinearSlotModel

    sizes = dict(cfg["serving"])
    windows = sizes.pop("read_windows", None)
    serving = common.serving_config(sizes)
    model = SparseLinearSlotModel(
        params_of(cfg, weights, consume=True), model_config(cfg),
        kv_page=serving.kv_page,
        kv_pool_blocks=serving.kv_pool_blocks,
        read_windows=tuple(windows) if windows else None,
        paged_attn=serving.paged_attn)
    return common.engine(model, serving)
