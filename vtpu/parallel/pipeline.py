"""Pipeline parallelism: transformer layers staged over a 'pp' mesh axis.

GPipe-style microbatch schedule, written the TPU way:
- the layer stack [L, ...] is sharded on L over 'pp' (each device owns L/pp
  contiguous layers and scans them locally -- one compiled stage body);
- the schedule is ONE `lax.scan` over M + pp - 1 ticks; activations hop to
  the next stage with `ppermute` each tick, so the transfer rides a single
  ICI hop and overlaps the next tick's compute;
- everything is static-shape and differentiable (scan + ppermute + psum all
  have transposes), so the same function sits inside a pjit train step.

The reference middleware has no parallelism strategies (SURVEY.md §2.6);
this is data-plane capability for the workloads it schedules.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from vtpu.parallel.collectives import pvary


def microbatch(x: jax.Array, n_micro: int) -> jax.Array:
    """[B, ...] -> [M, B/M, ...] microbatches for the pipeline schedule."""
    if x.shape[0] % n_micro:
        raise ValueError(f"batch {x.shape[0]} not divisible by n_micro={n_micro}")
    return x.reshape(n_micro, x.shape[0] // n_micro, *x.shape[1:])


def _pp_body(local_layers, xs, *, stage_fn, axis: str):
    """Per-stage schedule. local_layers: [L/pp, ...] pytree; xs: [M, ...]."""
    n = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    m = xs.shape[0]

    def run_stage(x):
        y, _ = jax.lax.scan(lambda h, lp: (stage_fn(lp, h), None), x, local_layers)
        return y

    # zero-init carries marked varying over 'pp' so scan carry types agree
    recv0 = pvary(jnp.zeros_like(xs[0]), axis)
    out0 = pvary(jnp.zeros_like(xs), axis)
    perm = [(i, i + 1) for i in range(n - 1)]  # stage i -> i+1; stage 0 gets zeros

    def tick(carry, t):
        recv, out = carry
        # stage 0 feeds microbatch t (clipped replay past M never reaches the
        # last stage before the schedule ends); others consume the ppermute'd
        # activation from the previous tick
        x0 = jax.lax.dynamic_index_in_dim(xs, jnp.clip(t, 0, m - 1), 0, keepdims=False)
        inp = jnp.where(idx == 0, x0, recv)
        y = run_stage(inp)
        mb = t - (n - 1)  # which microbatch the LAST stage just finished
        upd = jax.lax.dynamic_update_index_in_dim(out, y, jnp.clip(mb, 0, m - 1), 0)
        out = jnp.where(jnp.logical_and(idx == n - 1, mb >= 0), upd, out)
        recv = jax.lax.ppermute(y, axis, perm)
        return (recv, out), None

    (_, out), _ = jax.lax.scan(tick, (recv0, out0), jnp.arange(m + n - 1))
    # only the last stage wrote real outputs; psum replicates them to all
    return jax.lax.psum(out, axis)


def pipeline_apply(
    layer_params: Any,
    xs: jax.Array,
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    mesh: Mesh,
    axis: str = "pp",
) -> jax.Array:
    """Run stacked layers over microbatches through the pipeline.

    layer_params: pytree with leading layer axis L (L % mesh['pp'] == 0);
    xs: [M, ...] microbatched activations (replicated input);
    stage_fn(lp, x) -> x applies ONE layer. Returns [M, ...] outputs.
    """
    n = mesh.shape[axis]
    n_layers = jax.tree.leaves(layer_params)[0].shape[0]
    if n_layers % n:
        raise ValueError(f"n_layers={n_layers} not divisible by '{axis}' mesh size {n}")
    if xs.shape[0] < n:
        raise ValueError(f"need >= {n} microbatches to fill the pipeline, got {xs.shape[0]}")
    layer_specs = jax.tree.map(lambda l: P(axis, *([None] * (l.ndim - 1))), layer_params)
    body = shard_map(
        functools.partial(_pp_body, stage_fn=stage_fn, axis=axis),
        mesh=mesh,
        in_specs=(layer_specs, P()),
        out_specs=P(),
    )
    return body(layer_params, xs)


def pp_transformer_forward(params, cfg, tokens: jax.Array, mesh: Mesh, n_micro: int | None = None):
    """Pipelined forward of the flagship transformer: logits [B, S, V].

    Embedding and the LM head run replicated on every stage (they are tiny
    next to the layer stack); the stack itself is pipelined over 'pp'.
    """
    from vtpu.models.transformer import transformer_layer
    from vtpu.ops import rms_norm, rope_angles

    n = mesh.shape["pp"]
    if n_micro is None:
        n_micro = max(n, 2)
    b, s = tokens.shape
    cos, sin = rope_angles(cfg.max_seq, cfg.head_dim)

    def layer(lp, x):
        mb = x.shape[0]
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (mb, s))
        y, _kv = transformer_layer(cfg, lp, x, cos, sin, positions)
        return y

    x = params["embed"][tokens].astype(cfg.dtype)
    xs = microbatch(x, n_micro)
    ys = pipeline_apply(params["layers"], xs, layer, mesh)
    y = ys.reshape(b, s, cfg.d_model)
    y = rms_norm(y, params["final_norm"])
    return (y @ params["embed"].T).astype(jnp.float32)


def pp_loss(params, cfg, tokens: jax.Array, mesh: Mesh, n_micro: int | None = None) -> jax.Array:
    """Next-token cross-entropy through the pipeline (differentiable)."""
    from vtpu.ops.loss import next_token_ce

    logits = pp_transformer_forward(params, cfg, tokens, mesh, n_micro)
    return next_token_ce(logits, tokens)
