"""Ulysses-style sequence parallelism: all-to-all head/sequence exchange.

The complement to ring attention (`vtpu/parallel/ring.py`) for long-context
work: where the ring rotates k/v blocks with `ppermute` (P-1 hops, O(S/P)
memory, any head count), Ulysses pays two `all_to_all` collectives to
re-shard [B, S/P, H, Dh] -> [B, S, H/P, Dh], runs ordinary full-sequence
attention on each device's head slice, and re-shards back. On a TPU ICI
mesh the all-to-alls ride bisection bandwidth, so Ulysses wins when
H >= mesh size and the per-hop latency of the ring dominates (short-ish
sequences, many heads); the ring wins on very long sequences or when heads
cannot be split. Both compose with dp/tp over a 2D mesh.

Constraint: the head count must divide by the sequence-parallel mesh size.
"""

from __future__ import annotations

import functools

import jax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from vtpu.ops.attention import causal_attention


def _local_ulysses(q, k, v, *, axis: str):
    """Per-shard body. q/k/v: [B, S_loc, H, Dh] (this device's seq chunk)."""
    # seq-sharded -> head-sharded: split heads (axis 2) across devices,
    # gather the full sequence (axis 1). tiled=True keeps array rank.
    def to_heads(x):
        return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1, tiled=True)

    def to_seq(x):
        return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2, tiled=True)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)  # [B, S, H/P, Dh]
    out = causal_attention(qh, kh, vh)
    return to_seq(out)  # [B, S_loc, H, Dh]


def ulysses_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh, axis: str = "sp"
) -> jax.Array:
    """Causal attention over sequence-sharded q/k/v [B, S, H, Dh]."""
    n = mesh.shape[axis]
    heads = q.shape[2]
    if heads % n != 0:
        raise ValueError(
            f"ulysses needs heads % mesh == 0, got {heads} heads over {n} devices "
            "(use ring_attention instead)"
        )
    spec = P(None, axis, None, None)
    fn = shard_map(
        functools.partial(_local_ulysses, axis=axis),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
