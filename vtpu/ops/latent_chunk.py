"""A prefill chunk's attention over its expanded window, in one visit of the
scores (a Pallas kernel).

What ``vtpu.ops.latent._expanded`` computes, for the T queries of a chunk
that share one window of W cached latents: a head's keys ``latents . w_uk``
beside the rotated key the heads share, its values ``latents . w_uv``,
scores one product ``dn + Dr`` wide under the scale and the mask, softmax
in float32, the exponentials against the values in the window's dtype, the
quotient on the values' side. XLA cannot hold a block's scores between two
products, so that form makes them twice and sends the exponentials through
the chip's memory once each way. Here a grid step holds ``_HEADS`` heads
of one sequence against one block of ``_KEYS`` window positions: the
block's latents are expanded into those heads' keys and values in VMEM, a
head's scores ``[T, block]`` live there from the score product to the value
product, and the row maximum, the row sum and the accumulator run on
(float32) from a block to the next. All of a chunk's queries are one block:
a head's products have only its own queries for rows.

**It stops at the chunk's own end.** ``ends [N]`` (scalar prefetch) is one
past a sequence's last query position. A key block that starts at or past
it computes nothing, and the index maps hand it the last live block again,
which the pipeline does not copy a second time: a window read for its
bucket (4 / 8 / 16 / 24 / 32 k) costs what the positions up to the chunk's
end cost, rounded up to a block.

**The mask is an input.** With a selection it arrives as ``keep [N, T, W]``
in int8, a block ``[T, block]`` a grid step, shared by the step's heads;
without one the kernel makes the causal mask from ``positions`` and takes
no mask array. Either way a position past ``ends`` is masked (the
selection keeps visible positions only), so the last live block needs no
bound of its own.

``vtpu/ops/latent.py`` routes a chunk here on a TPU (``attends_in_kernel``);
its ``_expanded`` is the same attention as XLA code, the CPU route and this
kernel's reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# window positions a grid step, and heads a grid step, which share the
# block's latents and mask. The rescaling of the accumulator and a step's
# overhead are paid once a block of a head ([512, 1024] float32 scores: 2 MB
# of VMEM); a chunk attends its end rounded up to a block, half a block too
# many on average. On a v5e at the 24 k window (a layer, ms, whole window /
# the chunk at 18 k; PERF.md section 6, PR 38): 512 x 4 14.1 / 10.9,
# 1024 x 2 13.0 / 10.1, 1024 x 4 12.7 / 9.8, 1024 x 8 12.5 / 9.7,
# 2048 x 4 12.3 / 9.5 at twice the rounding (XLA's form: 17.5 either way)
_KEYS = 1024
_HEADS = 8
_VMEM_BYTES = 96 << 20
_LOW = -1e30  # a running maximum's start: finite, so no row is ever nan
_NT = (((1,), (1,)), ((), ()))  # a [M, K] . b [N, K] -> [M, N]


def key_block(w: int) -> int:
    """Window positions a grid step of the kernel takes of a window of
    ``w``: ``_KEYS`` halved while it does not divide ``w`` and is over
    128, else the whole window."""
    bk = min(_KEYS, w)
    while bk > 128 and w % bk:
        bk //= 2
    return bk if w % bk == 0 else w


def keys_attended(end: int, w: int) -> int:
    """Window positions the kernel multiplies for a chunk whose last query
    is at ``end - 1`` in a window of ``w``: ``end`` rounded up to a block."""
    bk = key_block(w)
    return min(w, -(-end // bk) * bk)


def _kernel(ends_ref, *refs, scale, rank, heads, causal):
    """One sequence's ``heads`` heads against one block of the window.
    q_ref [1, heads, T, dn + Dr]; pos_ref [1, T, 1] (causal) or keep_ref
    [1, T, bk] int8; win_ref [1, bk, rank + Dr]; uk_ref [heads, dn, rank];
    uv_ref [heads, rank, dv]; o_ref [1, T, heads * dv]; scratch m_ref,
    l_ref [heads, T, 1], acc_ref [heads, T, dv], float32."""
    q_ref, mask_ref, win_ref, uk_ref, uv_ref, o_ref, m_ref, l_ref, acc_ref = refs
    kb = pl.program_id(2)
    bk = win_ref.shape[1]
    dv = uv_ref.shape[2]
    dtype = win_ref.dtype

    @pl.when(kb == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _LOW, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(kb * bk < ends_ref[pl.program_id(0)])
    def _():
        latents, k_pe = win_ref[0, :, :rank], win_ref[0, :, rank:]
        if causal:
            at = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            keep = at <= mask_ref[0]
        else:
            keep = mask_ref[0].astype(jnp.int32) != 0
        for h in range(heads):
            k = jax.lax.dot_general(
                latents, uk_ref[h], _NT,
                preferred_element_type=jnp.float32).astype(dtype)
            v = jnp.dot(latents, uv_ref[h],
                        preferred_element_type=jnp.float32).astype(dtype)
            s = jax.lax.dot_general(
                q_ref[0, h], jnp.concatenate([k, k_pe], axis=1), _NT,
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(keep, s, -jnp.inf)
            m_old = m_ref[h]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
            e = jnp.exp(s - m_new)
            grown = jnp.exp(m_old - m_new)
            m_ref[h] = m_new
            l_ref[h] = grown * l_ref[h] + jnp.sum(e, axis=1, keepdims=True)
            acc_ref[h] = grown * acc_ref[h] + jnp.dot(
                e.astype(dtype), v, preferred_element_type=jnp.float32)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _():
        for h in range(heads):
            o_ref[0, :, h * dv:(h + 1) * dv] = (
                acc_ref[h] / l_ref[h]).astype(o_ref.dtype)


def chunk_attention(q_nope: jax.Array, q_pe: jax.Array, window: jax.Array,
                    keep, positions: jax.Array, w_uk: jax.Array,
                    w_uv: jax.Array, scale: float,
                    interpret: bool = False) -> jax.Array:
    """q_nope ``[N, T, H, dn]``, q_pe ``[N, T, H, Dr]``: a head's query in
    its two parts; window ``[N, W, rank + Dr]``: the latents and rotated
    keys all T queries of a sequence share; keep ``[N, T, W]`` bool: the
    positions a query attends, each at or before its own, or None: every
    position at or before ``positions [N, T]``; w_uk ``[H, dn, rank]``,
    w_uv ``[H, rank, dv]`` -> a head's values ``[N, T, H, dv]`` in the
    window's dtype. The kernel is ``latent_chunk`` in a trace, under the
    caller's scope."""
    n, t, nh, dn = q_nope.shape
    w, rank, dv = window.shape[1], w_uk.shape[-1], w_uv.shape[-1]
    bk, heads = key_block(w), _HEADS
    while nh % heads:
        heads //= 2
    ends = jnp.max(positions, axis=1).astype(jnp.int32) + 1
    q = jnp.moveaxis(jnp.concatenate([q_nope, q_pe], axis=-1), 2, 1)

    def block(i, g, kb, ends):  # the last live block again past the end
        return i, jnp.minimum(kb, (ends[i] - 1) // bk)

    if keep is None:
        mask = positions.astype(jnp.int32)[..., None]
        mask_spec = pl.BlockSpec((1, t, 1), lambda i, g, kb, ends: (i, 0, 0))
    else:
        mask = keep.astype(jnp.int8)
        mask_spec = pl.BlockSpec(
            (1, t, bk), lambda i, g, kb, ends: (i, 0, block(i, g, kb, ends)[1]))
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, rank=rank, heads=heads,
                          causal=keep is None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n, nh // heads, w // bk),
            in_specs=[
                pl.BlockSpec((1, heads, t, q.shape[-1]),
                             lambda i, g, kb, ends: (i, g, 0, 0)),
                mask_spec,
                pl.BlockSpec((1, bk, window.shape[-1]),
                             lambda i, g, kb, ends: (*block(i, g, kb, ends), 0)),
                pl.BlockSpec((heads, dn, rank),
                             lambda i, g, kb, ends: (g, 0, 0)),
                pl.BlockSpec((heads, rank, dv),
                             lambda i, g, kb, ends: (g, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, t, heads * dv),
                                   lambda i, g, kb, ends: (i, 0, g)),
            scratch_shapes=[
                pltpu.VMEM((heads, t, 1), jnp.float32),   # maximum
                pltpu.VMEM((heads, t, 1), jnp.float32),   # denominator
                pltpu.VMEM((heads, t, dv), jnp.float32),  # accumulator
            ]),
        out_shape=jax.ShapeDtypeStruct((n, t, nh * dv), window.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret, name="latent_chunk",
    )(ends, q, mask, window, w_uk, w_uv)
    return out.reshape(n, t, nh, dv)
