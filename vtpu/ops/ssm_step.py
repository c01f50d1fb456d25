"""A decode step's update of one Mamba-2 layer's recurrent state and its
readout, in one visit of the state (a Pallas kernel).

A layer ``l`` of a step, all float32::

    h[l, b, hd] <- h[l, b, hd] * decay[b, hd] + dx[b, hd, :] (outer) B[b, :]   # [P, N]
    y[b, hd, :] <- h[l, b, hd] . C[b, :]      # from the tile just written

(``B`` and ``C`` one row the heads of a slot share, Mamba-2's single group,
or a row a head, ``B[b, hd, :]``: a linear-attention layer's key and query,
whose state is the same stack with P the value's width and N the key's.)

The kernel takes the whole stacked state ``h [Lm, B, H, P, N]`` as the
engine stores it, aliased input to output, with the layer index a
scalar-prefetch argument that the block specs' index maps read: a loop over
layers hands it the carry and ``l`` and nothing slices a layer out or puts
it back. One grid step holds ``_TILE_HEADS`` heads of one slot; the pipeline
double-buffers the tiles, so the state crosses the memory once each way.

Orientation: a state tile has P along the sublanes and N along the lanes;
``dx`` and ``y`` are ``[B, H, P]`` with P along the lanes. A head's row of
``dx`` becomes a column by ``decode_attn._sublane_column``'s diagonal (the
row laid under itself P times, all but the diagonal masked, summed along
the lanes: exact); a head's readout is a column already (the lane
reduction over N), the tile's columns are gathered as lanes of one
``[P, heads]`` and turned once on the MXU (a product with the identity at
``HIGHEST``: exact). The decays are scalars (scalar prefetch). Slicing a
lane out of a transposed ``dx`` instead costs four times the arithmetic
(PERF.md section 6, PR 36), and with either the kernel is bounded by its
copies: the chip's memory gives a read and a write in flight together 642
GB/s (hack/hbm_copy_probe.py).

``vtpu/models/hybrid.py`` routes a decode step here on a TPU; its
``_ssd_step`` is the same update as XLA code, the CPU route and this
kernel's reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# heads of one slot a grid step: [64, 64, 128] float32 is 2 MB in and the
# same out, 8 MB of VMEM double-buffered; under 16 heads a step's overhead
# shows (benchmarks/ssm_state_bench.py sweeps it; PERF.md section 6, PR 36)
_TILE_HEADS = 64


def _eye(n):
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))


def _kernel(lay_ref, decay_ref, dx_ref, b_ref, c_ref, h_ref, y_ref, ho_ref):
    """One slot's tile of heads. decay_ref [B * H] (SMEM); dx_ref, y_ref
    [1, heads, P]; b_ref, c_ref [1, 1, N] (a row the heads share) or
    [1, heads, N] (a row a head); h_ref, ho_ref [1, 1, heads, P, N]."""
    del lay_ref  # the index maps read it
    heads, p = dx_ref.shape[1:]
    first = (pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)) * heads
    per_head = b_ref.shape[1] != 1
    b_row, c_row = b_ref[0], c_ref[0]  # [1, N]: the shared row
    diagonal = _eye(p)
    lane = jax.lax.broadcasted_iota(jnp.int32, (p, heads), 1)
    y_t = jnp.zeros((p, heads), jnp.float32)
    for j in range(heads):
        dx_col = jnp.sum(jnp.where(diagonal, dx_ref[0, j:j + 1, :], 0.0),
                         axis=-1, keepdims=True)  # [P, 1]
        if per_head:
            b_row, c_row = b_ref[0, j:j + 1, :], c_ref[0, j:j + 1, :]
        h = h_ref[0, 0, j] * decay_ref[first + j] + dx_col * b_row
        ho_ref[0, 0, j] = h
        y_t = jnp.where(
            lane == j, jnp.sum(h * c_row, axis=-1, keepdims=True), y_t)
    y_ref[0] = jax.lax.dot_general(  # y_t.T
        _eye(heads).astype(jnp.float32), y_t, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def ssm_state_step(h, layer, decay, dx, bm, cm, interpret: bool = False):
    """h [Lm, B, H, P, N] float32 (the stack, donated); layer: an int or a
    traced int32 scalar; decay [B, H], dx [B, H, P], bm, cm [B, N] (a row
    of B and C the heads share: Mamba-2's one group) or [B, H, N] (a row a
    head: a linear attention's key and query), float32 -> (y [B, H, P]
    float32, h with layer ``layer`` moved one token on and every other
    layer as it stood)."""
    _, b, nh, p, n = h.shape
    heads = _TILE_HEADS if nh % _TILE_HEADS == 0 else nh
    tile = (1, 1, heads, p, n)
    per_head = bm.ndim == 3
    if not per_head:
        bm, cm = bm[:, None], cm[:, None]

    def state_at(i, g, lay, dec):
        return lay[0], i, g, 0, 0

    def slot_heads(i, g, lay, dec):
        return i, g, 0

    def slot(i, g, lay, dec):
        return i, 0, 0

    y, h = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, nh // heads),
            in_specs=[pl.BlockSpec((1, heads, p), slot_heads),
                      *[pl.BlockSpec((1, heads, n), slot_heads) if per_head
                        else pl.BlockSpec((1, 1, n), slot)] * 2,
                      pl.BlockSpec(tile, state_at)],
            out_specs=[pl.BlockSpec((1, heads, p), slot_heads),
                       pl.BlockSpec(tile, state_at)]),
        out_shape=[jax.ShapeDtypeStruct((b, nh, p), jnp.float32),
                   jax.ShapeDtypeStruct(h.shape, h.dtype)],
        input_output_aliases={5: 1},  # the state, counted past the scalars
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=16 * heads * p * n + (16 << 20)),
        interpret=interpret, name="ssm_state_step",
    )(jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), decay.reshape(-1),
      dx, bm, cm, h)
    return y, h
