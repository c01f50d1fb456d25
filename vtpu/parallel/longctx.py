"""Long-context sequence-parallel prefill: the flagship model over a ring.

Context past one chip's HBM is first-class: the WHOLE transformer forward
runs with the sequence sharded over an 'sp' axis — every elementwise op,
norm, matmul and RoPE is local to a sequence chunk, and only attention
communicates, via the ring schedule (vtpu/parallel/ring.py: k/v blocks
ppermute around the ICI ring into an online-softmax accumulator). Activation
memory per chip scales as S/n, so n chips prefill an n-times-longer context
with zero approximation (verified exactly against the dense path in tests).

Built with shard_map (not sharding annotations): causal attention across
sequence shards would otherwise tempt XLA into an all-gather of K/V, which
is exactly the materialization this path exists to avoid.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from vtpu.models.transformer import ModelConfig, Params, _mlp_block, _qkv
from vtpu.ops import rms_norm, rope_angles
from vtpu.parallel.ring import _local_ring


def _param_specs(params: Params):
    return jax.tree.map(lambda _: P(), params)


def sp_prefill(
    params: Params, cfg: ModelConfig, tokens: jax.Array, mesh: Mesh, axis: str = "sp"
) -> jax.Array:
    """Sequence-parallel full forward. tokens: [B, S] with S % n == 0.

    Returns logits [B, S, V] (f32), sequence-sharded over *axis*. Parameters
    are replicated across the ring (pair with 'tp' separately if weights
    must also shard).
    """
    b, s = tokens.shape
    n = mesh.shape[axis]
    if s % n:
        raise ValueError(f"seq len {s} not divisible by {axis}={n}")
    cos, sin = rope_angles(cfg.max_seq, cfg.head_dim)

    def local_fn(params, tokens_loc, cos, sin):
        s_loc = tokens_loc.shape[1]
        idx = jax.lax.axis_index(axis)
        # global positions of this chunk: RoPE and the causal mask both key
        # off absolute sequence position, not the local index
        positions = jnp.broadcast_to(
            idx * s_loc + jnp.arange(s_loc, dtype=jnp.int32), (b, s_loc)
        )
        x = params["embed"][tokens_loc].astype(cfg.dtype)

        def layer(x, lp):
            q, k, v = _qkv(cfg, lp, x, cos, sin, positions)
            attn = _local_ring(q, k, v, axis=axis)
            x = x + attn.reshape(b, s_loc, cfg.qkv_dim) @ lp["wo"]
            x = x + _mlp_block(lp, x)
            return x, None

        x, _ = jax.lax.scan(layer, x, params["layers"])
        x = rms_norm(x, params["final_norm"])
        return (x @ params["embed"].T).astype(jnp.float32)

    fn = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(_param_specs(params), P(None, axis), P(), P()),
        out_specs=P(None, axis, None),
    )
    return fn(params, tokens, cos, sin)


def sp_loss(
    params: Params, cfg: ModelConfig, tokens: jax.Array, mesh: Mesh, axis: str = "sp"
) -> jax.Array:
    """Next-token CE over the sequence-parallel forward (long-context
    training path; gradients flow back through the ring ppermutes)."""
    from vtpu.ops.loss import next_token_ce

    return next_token_ce(sp_prefill(params, cfg, tokens, mesh, axis), tokens)


def place_sp_tokens(tokens: jax.Array, mesh: Mesh, axis: str = "sp") -> jax.Array:
    """Shard [B, S] tokens over the sequence axis."""
    return jax.device_put(tokens, NamedSharding(mesh, P(None, axis)))

