"""Ring attention: causal attention with the sequence sharded over an 'sp' axis.

Each device holds one sequence chunk of q/k/v; k/v blocks rotate around the
ring with `ppermute` while an online-softmax accumulator (o, m, l) folds each
block in. Communication overlaps compute around the ICI ring and no device
ever materializes the full [S, S] score matrix -- this is how the benchmark
workload scales context past one chip's HBM.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from vtpu.parallel.collectives import pvary

_NEG = -1e30


def _local_ring(q, k, v, *, axis: str):
    """Per-shard body. q/k/v: [B, S_loc, H, Dh] (this device's chunk)."""
    n = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    b, s_loc, h, dh = q.shape
    scale = 1.0 / math.sqrt(dh)

    qf = q.astype(jnp.float32)
    q_pos = idx * s_loc + jnp.arange(s_loc)  # global positions of local queries

    # mark the zero-init accumulators as varying over the ring axis, else the
    # fori_loop carry types disagree under shard_map's varying-axis tracking
    o0 = pvary(jnp.zeros((b, h, s_loc, dh), jnp.float32), axis)
    m0 = pvary(jnp.full((b, h, s_loc), _NEG, jnp.float32), axis)
    l0 = pvary(jnp.zeros((b, h, s_loc), jnp.float32), axis)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(t, carry):
        o, m, l, k_blk, v_blk = carry
        src = (idx - t) % n  # which global chunk this k/v block is
        scores = jnp.einsum("bqhd,bkhd->bhqk", qf, k_blk.astype(jnp.float32)) * scale
        k_pos = src * s_loc + jnp.arange(s_loc)
        mask = k_pos[None, :] <= q_pos[:, None]  # [S_loc_q, S_loc_k] causal
        scores = jnp.where(mask[None, None], scores, _NEG)
        blk_max = jnp.max(scores, axis=-1)
        new_m = jnp.maximum(m, blk_max)
        corr = jnp.exp(m - new_m)
        p = jnp.where(mask[None, None], jnp.exp(scores - new_m[..., None]), 0.0)
        l = l * corr + jnp.sum(p, axis=-1)
        o = o * corr[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32))
        k_blk = jax.lax.ppermute(k_blk, axis, perm)
        v_blk = jax.lax.ppermute(v_blk, axis, perm)
        return o, new_m, l, k_blk, v_blk

    o, m, l, _, _ = jax.lax.fori_loop(0, n, body, (o0, m0, l0, k, v))
    out = o / l[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # [B, S_loc, H, Dh]


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh, axis: str = "sp") -> jax.Array:
    """Causal attention over sequence-sharded q/k/v [B, S, H, Dh]."""
    spec = P(None, axis, None, None)
    fn = shard_map(
        functools.partial(_local_ring, axis=axis),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
