"""NamedSharding rules for the transformer parameters and batches.

Megatron-style tensor parallelism: q/k/v/gate/up are column-sharded over 'tp'
(heads split across chips), o/down are row-sharded, so each layer needs exactly
one all-reduce per block -- XLA inserts it from these annotations; we never
write a collective by hand on this path (scaling-book recipe: annotate, let
the compiler place psums on ICI).
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def param_shardings(mesh: Mesh) -> dict[str, Any]:
    """PartitionSpec pytree matching vtpu.models.transformer.init_params."""
    return {
        # vocab-sharded embedding: logits matmul reduces over 'tp'
        "embed": NamedSharding(mesh, P(None, "tp")),
        "layers": {
            # [L, d_model, heads*head_dim]: shard the head (output) dim
            "wq": NamedSharding(mesh, P(None, None, "tp")),
            "wk": NamedSharding(mesh, P(None, None, "tp")),
            "wv": NamedSharding(mesh, P(None, None, "tp")),
            # [L, heads*head_dim, d_model]: shard the head (input) dim
            "wo": NamedSharding(mesh, P(None, "tp", None)),
            "w_gate": NamedSharding(mesh, P(None, None, "tp")),
            "w_up": NamedSharding(mesh, P(None, None, "tp")),
            "w_down": NamedSharding(mesh, P(None, "tp", None)),
            "attn_norm": NamedSharding(mesh, P(None, None)),
            "mlp_norm": NamedSharding(mesh, P(None, None)),
        },
        "final_norm": NamedSharding(mesh, P(None)),
    }


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Tokens [B, S]: batch over 'dp' (and 'slice' on a multislice mesh so
    the gradient reduction is hierarchical: ICI within the slice, one DCN
    hop across slices), sequence replicated."""
    if "slice" in mesh.axis_names:
        return NamedSharding(mesh, P(("slice", "dp"), None))
    return NamedSharding(mesh, P("dp", None))


def _follow_projection_ranks(specs: dict[str, Any], params: Any) -> dict[str, Any]:
    """*specs* with the q/k/v rules following each leaf's rank: the head
    axis over 'tp' is the last of the published [L, d, H*Dh] and the second
    of a serving adapter's held [L, H, Dh, d]
    (models.transformer.hold_projections)."""
    layers = dict(specs["layers"])
    for name in ("wq", "wk", "wv"):
        if params["layers"][name].ndim == 4:
            layers[name] = head_sharding(layers[name].mesh, 4, 1)
    return {**specs, "layers": layers}


def shard_params(params: Any, mesh: Mesh) -> Any:
    """Place a host pytree of params onto the mesh per param_shardings."""
    specs = _follow_projection_ranks(param_shardings(mesh), params)
    return jax.tree.map(jax.device_put, params, specs)


def kv_cache_shardings(mesh: Mesh, quantized: bool = False) -> dict[str, NamedSharding]:
    """KV cache [L, B, S, H, Dh]: heads over 'tp' (matching the q/k/v column
    shards), lengths replicated. ``quantized`` adds the int8 cache's
    per-token-per-head scale planes [L, B, S, H], head-sharded alongside
    their values. Serving is tp-only — see shard_kv_cache."""
    kv = NamedSharding(mesh, P(None, None, None, "tp", None))
    out = {"k": kv, "v": kv, "len": NamedSharding(mesh, P())}
    if quantized:
        sc = NamedSharding(mesh, P(None, None, None, "tp"))
        out["k_scale"] = sc
        out["v_scale"] = sc
    return out


def shard_kv_cache(cache: dict[str, jax.Array], mesh: Mesh) -> dict[str, jax.Array]:
    """Place (or re-place) a KV cache per kv_cache_shardings."""
    return jax.tree.map(
        jax.device_put, cache,
        kv_cache_shardings(mesh, quantized="k_scale" in cache))


# Head-axis position per paged-cache plane, counted from the END so the same
# rule covers the pool layout ([L, n_blocks, page, H, Dh] / scale
# [L, n_blocks, page, H]) and every derived view (gathered window
# [B, W, H, Dh], single-slot chunk view [L, 1, S, H, Dh], ...): KV value
# planes carry a trailing Dh, scale planes end at H.
_PAGED_HEAD_AXIS = {"k": -2, "v": -2, "k_scale": -1, "v_scale": -1}


def head_sharding(mesh: Mesh, ndim: int, head_axis: int) -> NamedSharding:
    """NamedSharding putting one axis (negative indices allowed) on 'tp' and
    replicating the rest — the single rule every paged-KV plane follows."""
    spec = [None] * ndim
    spec[head_axis] = "tp"
    return NamedSharding(mesh, P(*spec))


def paged_kv_shardings(mesh: Mesh, quantized: bool = False) -> dict[str, NamedSharding]:
    """Paged KV pool [L, n_blocks, page, H, Dh]: heads over 'tp' (matching
    the q/k/v column shards, exactly like the dense cache), block/page axes
    replicated — every chip holds its head slice of EVERY block, so a page
    table lookup never implies cross-chip traffic. The per-slot page table
    and lengths are replicated: they are host-authored control state, tiny
    next to the pools, and both the gather and the scatter consume them on
    every chip. ``quantized`` adds the int8 scale pools [L, n_blocks, page,
    H], head-sharded alongside their values."""
    out = {
        "k": head_sharding(mesh, 5, -2),
        "v": head_sharding(mesh, 5, -2),
        "table": NamedSharding(mesh, P()),
        "len": NamedSharding(mesh, P()),
    }
    if quantized:
        out["k_scale"] = head_sharding(mesh, 4, -1)
        out["v_scale"] = head_sharding(mesh, 4, -1)
    return out


def constrain_paged_kv(state: dict[str, jax.Array], mesh: Mesh) -> dict[str, jax.Array]:
    """Pin a paged cache pytree (pool OR any single-slot/window view of it)
    to its head shards inside a jitted step: k/v planes shard the head axis
    (ndim-2), scale planes theirs (ndim-1), table/len replicated. Applied at
    every step boundary by the serving adapters so the compiler can never
    drift a donated pool through an unsharded (single-chip-OOM) layout."""
    out = {}
    for key, arr in state.items():
        ax = _PAGED_HEAD_AXIS.get(key)
        if ax is None:
            sh = NamedSharding(mesh, P())
        else:
            sh = head_sharding(mesh, arr.ndim, ax)
        out[key] = jax.lax.with_sharding_constraint(arr, sh)
    return out


def moe_tp_param_shardings(mesh: Mesh, n_experts: int) -> dict[str, Any]:
    """PartitionSpec pytree for vtpu.models.moe.init_moe_params under a
    tp-only serving mesh: the attention trunk shards exactly like the dense
    transformer (heads column-sharded, wo row-sharded — one all-reduce per
    block), the router stays replicated (tiny, numerically load-bearing),
    and the expert stacks shard their E axis over 'tp' when it divides
    (expert parallelism riding the serving mesh; the combine einsum's
    expert contraction becomes the block's all-reduce) — replicated
    otherwise, trading memory for zero routing collectives."""
    ep = "tp" if n_experts % mesh.shape["tp"] == 0 else None
    expert = NamedSharding(mesh, P(None, ep, None, None))
    return {
        "embed": NamedSharding(mesh, P(None, "tp")),
        "layers": {
            "wq": NamedSharding(mesh, P(None, None, "tp")),
            "wk": NamedSharding(mesh, P(None, None, "tp")),
            "wv": NamedSharding(mesh, P(None, None, "tp")),
            "wo": NamedSharding(mesh, P(None, "tp", None)),
            "router": NamedSharding(mesh, P(None, None, None)),
            "w_gate": expert,
            "w_up": expert,
            "w_down": expert,
            "attn_norm": NamedSharding(mesh, P(None, None)),
            "mlp_norm": NamedSharding(mesh, P(None, None)),
        },
        "final_norm": NamedSharding(mesh, P(None)),
    }


def shard_moe_params(params: Any, mesh: Mesh, n_experts: int) -> Any:
    """Place a host pytree of MoE params onto the mesh per
    moe_tp_param_shardings."""
    specs = _follow_projection_ranks(
        moe_tp_param_shardings(mesh, n_experts), params)
    return jax.tree.map(jax.device_put, params, specs)
