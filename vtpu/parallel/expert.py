"""Expert parallelism: MoE expert axis sharded over an 'ep' mesh axis.

Two TPU-native paths over the same model (vtpu/models/moe.py):

1. `moe_param_shardings(mesh)` -- pjit/annotation path. Expert weights are
   NamedSharding'd P(None, 'ep', ...) and XLA lowers the dispatch/combine
   einsums into all-to-alls over ICI by itself (scaling-book recipe). Used by
   the MoE train step in the dryrun.
2. `make_ep_ffn(mesh)` -- explicit `shard_map` path: tokens are routed
   locally, dispatched to the expert-owning devices with two tiled
   `lax.all_to_all`s (the classic GShard exchange), experts run on their
   local shard, and gates combine the returned slots. Deterministic comms
   placement for serving, where the all-to-all must overlap decode compute.

No NCCL/MPI analog exists in the reference (SURVEY.md §2.6) -- this is the
data-plane capability the middleware schedules, built on XLA collectives.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from vtpu.models.moe import MoEConfig, expert_ffn, route


def moe_param_shardings(mesh: Mesh, ep_axis: str = "ep") -> dict:
    """PartitionSpec pytree for vtpu.models.moe.init_moe_params.

    Expert-stacked tensors [L, E, D, F] shard the E axis over `ep_axis`;
    attention + router replicate (router must see every expert's logit).
    """
    e = NamedSharding(mesh, P(None, ep_axis, None, None))
    r = lambda *spec: NamedSharding(mesh, P(*spec))  # noqa: E731
    return {
        "embed": r(None, None),
        "layers": {
            "wq": r(None, None, None),
            "wk": r(None, None, None),
            "wv": r(None, None, None),
            "wo": r(None, None, None),
            "router": r(None, None, None),
            "w_gate": e,
            "w_up": e,
            "w_down": e,
            "attn_norm": r(None, None),
            "mlp_norm": r(None, None),
        },
        "final_norm": r(None),
    }


def _ep_body(router, wg, wu, wd, x, *, cfg: MoEConfig, axis: str):
    """Per-device MoE block. x: [B_loc, S, D]; wg/wu/wd: [E_loc, D, F]-shaped."""
    b, s, d = x.shape
    flat = x.reshape(b * s, d)
    cap = cfg.capacity(b * s)  # static: local token count is a trace constant
    dispatch, combine, aux = route(router, flat, cfg, cap)

    # [T_loc, E, C] x [T_loc, D] -> [E, C, D]: slots for EVERY expert, grouped
    # so that split_axis=0 all_to_all hands each device its experts' tokens.
    slots = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), flat)
    recv = jax.lax.all_to_all(slots, axis, split_axis=0, concat_axis=1, tiled=True)
    out_loc = expert_ffn({"w_gate": wg, "w_up": wu, "w_down": wd}, recv)
    back = jax.lax.all_to_all(out_loc, axis, split_axis=1, concat_axis=0, tiled=True)
    out = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), back)
    return out.reshape(b, s, d), jax.lax.pmean(aux, axis)


def make_ep_ffn(mesh: Mesh, axis: str = "ep"):
    """Build an `ffn(lp, x, cfg)` drop-in for vtpu.models.moe.moe_forward.

    Batch is sharded over `axis` (every device routes its own tokens); expert
    weights are sharded on their leading E axis.
    """

    def ffn(lp, x, cfg: MoEConfig):
        import functools

        n = mesh.shape[axis]
        if cfg.n_experts % n:
            raise ValueError(
                f"expert parallelism needs n_experts % mesh['{axis}'] == 0, "
                f"got {cfg.n_experts} experts over {n} devices"
            )
        if x.shape[0] % n:
            raise ValueError(
                f"batch {x.shape[0]} not divisible by '{axis}' mesh size {n}"
            )
        body = shard_map(
            functools.partial(_ep_body, cfg=cfg, axis=axis),
            mesh=mesh,
            in_specs=(
                P(),                      # router: replicated
                P(axis, None, None),      # w_gate [E, D, F] sharded on E
                P(axis, None, None),      # w_up
                P(axis, None, None),      # w_down [E, F, D]
                P(axis, None, None),      # x [B, S, D] sharded on batch
            ),
            out_specs=(P(axis, None, None), P()),
        )
        return body(lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"], x)

    return ffn


def ep_moe_forward(params, cfg: MoEConfig, tokens: jax.Array, mesh: Mesh, axis: str = "ep"):
    """Expert-parallel full-sequence forward: (logits, aux)."""
    from vtpu.models.moe import moe_forward

    return moe_forward(params, cfg, tokens, ffn=make_ep_ffn(mesh, axis))
