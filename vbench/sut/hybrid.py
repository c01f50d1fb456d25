"""``HybridSlotModel`` over its paged pool and its recurrent rows, for
``family: hybrid``: the benchmark's leaves handed to the program's two
stacks. The reference names three kinds of layer (``mamba_in`` is layer 0,
which carries the embedding's multiplier: vbench/reference/hybrid.py); the
program has two and multiplies in its embedding, so ``mamba_in``'s one row
goes first in the Mamba stack. ``dt_bias`` and ``a_log`` go through the
reference's ``map_leaves``, the one copy of that map.
``serving.read_windows`` is the slot model's, not a ``ServingConfig``
field: it goes to the adapter."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from vbench.reference.hybrid import map_leaves
from vbench.sut import common
from vtpu.models.hybrid import HybridConfig  # noqa: F401  a program without it
# cannot run this family: fail here, at once, before any weight is made


def model_config(cfg: dict, dtype=None):
    """The configuration's keys as the program's ``HybridConfig`` (computing
    in the configuration's ``dtype`` unless told another)."""
    dtype = dtype or {"bfloat16": jnp.bfloat16,
                      "float32": jnp.float32}[cfg["dtype"]]
    if cfg["position_embedding_type"] != "nope":
        raise ValueError("the program's hybrid attention has no rotary form")
    return HybridConfig(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        layer_types=tuple(cfg["layer_types"]),
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["shared_intermediate_size"],
        ssm_heads=cfg["mamba_n_heads"], ssm_head_dim=cfg["mamba_d_head"],
        ssm_state=cfg["mamba_d_state"], ssm_groups=cfg["mamba_n_groups"],
        conv_width=cfg["mamba_d_conv"], ssd_chunk=cfg["mamba_chunk_size"],
        embedding_multiplier=cfg["embedding_multiplier"],
        attention_multiplier=cfg["attention_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        logits_scaling=cfg["logits_scaling"], eps=cfg["rms_norm_eps"],
        max_seq=cfg["max_position_embeddings"], dtype=dtype)


def params_of(cfg: dict, weights: dict, consume: bool = False) -> dict:
    """The benchmark's leaves under the program's names. ``consume`` frees
    each pair of Mamba leaves once they are joined: the joined stack is a
    second copy of 5.5 GB of the 6.4, and weights, copy and state together
    do not fit the chip. ``build`` may: the weights were made for it and
    vbench/run.py reads them no more."""
    if not cfg["tie_word_embeddings"] or cfg["output_head"] != "embed":
        raise ValueError("the program's hybrid head is the tied embedding")
    layers = weights["layers"]

    def first_then_rest(name):
        first, rest = layers["mamba_in"][name], layers["mamba"][name]
        if isinstance(first, jax.ShapeDtypeStruct):  # a rehearsal's shapes
            return jax.ShapeDtypeStruct(
                (first.shape[0] + rest.shape[0],) + first.shape[1:],
                first.dtype, sharding=first.sharding)
        joined = jax.block_until_ready(jnp.concatenate([first, rest]))
        if consume:
            first.delete()
            rest.delete()
        return joined

    mamba = {name: first_then_rest(name) for name in layers["mamba"]}
    if not isinstance(mamba["dt_bias"], jax.ShapeDtypeStruct):
        mamba = map_leaves(mamba)
    return {"embed": weights["embed"], "final_norm": weights["final_norm"],
            "mamba": mamba, "attention": layers["attention"]}


def build(cfg: dict, weights: dict):
    from vtpu.serving.adapters import HybridSlotModel

    sizes = dict(cfg["serving"])
    windows = sizes.pop("read_windows", None)
    serving = common.serving_config(sizes)
    model = HybridSlotModel(
        params_of(cfg, weights, consume=True), model_config(cfg),
        kv_page=serving.kv_page,
        kv_pool_blocks=serving.kv_pool_blocks,
        read_windows=tuple(windows) if windows else None,
        paged_attn=serving.paged_attn)
    return common.engine(model, serving)
