"""Causal attention: XLA reference path + a Pallas flash-style TPU kernel.

The Pallas kernel keeps the q-block resident in VMEM and streams K/V for one
(batch, head) per grid program -- MXU does the two matmuls, the softmax rides
the VPU in f32. For the sequence lengths the benchmark workload uses
(<= 2048 x head_dim 128, bf16) K and V fit comfortably in VMEM, so a single
K-pass per q-block is the fastest schedule (no online-softmax rescan needed).
``interpret=None`` resolves from the backend: compiled (Mosaic) on a TPU,
the Pallas interpreter elsewhere. The interpreter is how the CPU tests check
the kernel's numerics and nothing more; that the compiled path is what a
chip run executed is proved by chip_smoke.py (a ``tpu_custom_call`` in the
warmed executable) and tests/test_tpu_compile.py, never assumed.

The fused Pallas DECODE kernels live in vtpu/ops/decode_attn.py: the dense-
cache study (parked after r5 full-trunk measurement routed every serving
cell to the XLA op chain — the cache-view materialization a pallas operand
forces cost more than the kernel saved) and the shipped PAGED product path,
``paged_decode_attention{,_int8kv}``, which walks the page table over the
block pool in place — the serving trunk routes between it and the
``paged_causal_attention`` gather path below per measured shape
(decode_attn.paged_attn_route).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

_NEG_INF = -1e30


def _causal_mask(sq: int, sk: int, kv_len: jax.Array | None) -> jax.Array:
    """Broadcastable [*, *, Sq, Sk] attention mask shared by the bf16 and
    int8 paths. kv_len None: plain causal (prefill). [B]: causal suffix +
    per-row validity (lockstep decode). [B, Sq]: ragged per-query validity
    (speculative verify) — the ONLY mask, since the chunk's scatter offsets
    make k_pos < kv_len[b, q] exactly intra-chunk causality."""
    k_pos = jnp.arange(sk)[None, :]
    if kv_len is not None and kv_len.ndim == 2:
        return (jnp.arange(sk)[None, None, :] < kv_len[:, :, None])[:, None, :, :]
    q_pos = jnp.arange(sq)[:, None] + (sk - sq)
    mask = k_pos <= q_pos  # [Sq, Sk] causal
    if kv_len is not None:
        valid = k_pos < kv_len[:, None]  # [B, Sk]
        return (mask[None, :, :] & valid[:, None, :])[:, None, :, :]
    return mask[None, None, :, :]


def _fold_query_groups(q: jax.Array, k: jax.Array, v: jax.Array,
                       kv_len: jax.Array | None):
    """Grouped queries against fewer key/value heads, as the one einsum
    below reads them: q [B, Sq, Hk * G, Dh] (query head h reads key/value
    head h // G) becomes [B, Sq * G, Hk, Dh], a group's G heads laid along
    the query axis under the same ragged length, and k, v stored with
    several heads a row (``kv_plane_shape``: [B, Sk, rows, lanes]) are
    read as [B, Sk, Hk, Dh], which is the same bytes. Returns (q, k, v,
    kv_len, G); G == 1 and nothing changed where the head counts agree."""
    b, sq, hq, dh = q.shape
    if k.shape[2:] == (hq, dh):
        return q, k, v, kv_len, 1
    if kv_len is None or kv_len.ndim != 2:
        raise ValueError("grouped queries need the ragged [B, Sq] kv_len")
    k = k.reshape(k.shape[:2] + (-1, dh))
    v = v.reshape(k.shape)
    hk = k.shape[2]
    g = hq // hk
    q = q.reshape(b, sq, hk, g, dh).transpose(0, 1, 3, 2, 4).reshape(
        b, sq * g, hk, dh)
    return q, k, v, jnp.repeat(kv_len, g, axis=1), g


def _unfold_query_groups(out: jax.Array, g: int) -> jax.Array:
    """[B, Sq * G, Hk, Dh] back to [B, Sq, Hk * G, Dh]."""
    if g == 1:
        return out
    b, sqg, hk, dh = out.shape
    return out.reshape(b, sqg // g, g, hk, dh).transpose(0, 1, 3, 2, 4).reshape(
        b, sqg // g, hk * g, dh)


def causal_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_len: jax.Array | None = None,
    scale: float | None = None,
) -> jax.Array:
    """Reference causal attention.

    q: [B, Sq, H, Dh]; k, v: [B, Sk, H, Dh] with Sk >= Sq (decode passes the
    full static cache and masks with kv_len, keeping shapes static under jit).
    kv_len: optional valid-entry count per cache row. [B] int32 places the
    queries at the cache SUFFIX (lockstep decode). [B, Sq] int32 is the
    ragged form (speculative verify): query i of row b may read k_pos <
    kv_len[b, i], which alone encodes intra-chunk causality when the chunk
    was scattered at per-row offsets (kv_len[b, i] = len[b] + i + 1) — no
    suffix-position mask applies because the chunk does not sit at the
    window's end.

    ``scale`` is the softmax scale (None: 1 / sqrt(Dh)). Fewer key/value
    heads than query heads (q [B, Sq, Hk * G, Dh]; k, v of Hk heads, as
    [.., Hk, Dh] or several heads a stored row) take the ragged kv_len:
    ``_fold_query_groups``.
    """
    q, k, v, kv_len, group = _fold_query_groups(q, k, v, kv_len)
    b, sq, h, dh = q.shape
    sk = k.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(dh)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    scores = jnp.where(_causal_mask(sq, sk, kv_len), scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return _unfold_query_groups(out.astype(q.dtype), group)


def causal_attention_int8kv(
    q: jax.Array,
    kq: jax.Array,
    k_scale: jax.Array,
    vq: jax.Array,
    v_scale: jax.Array,
    kv_len: jax.Array | None = None,
) -> jax.Array:
    """Causal attention directly over an int8-quantized KV window.

    The per-token-per-head scales are EXACT to apply after the matmuls
    instead of to the operands: scores(q, k*s_k) = scores(q, k) * s_k and
    sum_k p_k * (v_k * s_vk) = sum_k (p_k * s_vk) * v_k — so the int8 values
    feed the MXU through a bare convert (which XLA fuses into the dot) and
    the scales ride the [B,H,Sq,Sk] score tensor that exists anyway. A
    dequantize-then-attend formulation measured SLOWER than bf16 on r4
    hardware: XLA materialized the full dequantized window, paying the bf16
    bytes the quantization was supposed to save.

    q: [B,Sq,H,Dh]; kq, vq: [B,Sk,H,Dh] int8; k_scale, v_scale: [B,Sk,H]
    f32 (absmax/127 per token per head); kv_len as in causal_attention
    (including the ragged [B, Sq] form for speculative verify).
    """
    b, sq, h, dh = q.shape
    sk = kq.shape[1]
    scale = 1.0 / math.sqrt(dh)
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, kq.astype(q.dtype),
        preferred_element_type=jnp.float32) * scale
    scores = scores * k_scale.transpose(0, 2, 1)[:, :, None, :]  # [B,H,1,Sk]
    scores = jnp.where(_causal_mask(sq, sk, kv_len), scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = probs * v_scale.transpose(0, 2, 1)[:, :, None, :]
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", probs.astype(q.dtype), vq.astype(q.dtype))
    return out.astype(q.dtype)


def gather_kv_pages(pool: jax.Array, table: jax.Array,
                    mesh=None) -> jax.Array:
    """Materialize a slot-pooled read window from a paged block pool.

    pool: one layer's plane, [n_blocks, page, ...] (KV values [.., H, Dh] or
    int8 scales [.., H]); table: [B, Wp] int32 block ids, entry p of row b
    naming the block holding slot b's logical page p. Returns
    [B, Wp*page, ...] — positionally IDENTICAL to the dense cache slice
    [:, :Wp*page], which is what keeps every downstream mask, ragged length,
    and numeric exactly shared with the dense path: a paged read is a gather
    plus reshape in front of the same attention.

    Window entries past a slot's live pages carry block id 0 (the engine's
    reserved null block), so a short slot's padding reads dedupe onto one
    HBM-resident block instead of streaming distinct dead lines — the
    per-slot analogue of "pad to the smallest bucket covering THIS slot's
    length" that a single static-shape dispatch could not otherwise express.
    Null-block values are garbage by design; every consumer masks reads at
    kv_len, so they are never observable.

    ``mesh`` (a ('tp',) Mesh) marks a HEAD-SHARDED pool: every chip holds
    its head slice of every block, the table is replicated, so the gather
    is chip-local by construction — the sharding constraint pins the
    gathered window to the pool's own head shard (H sits at axis 2 of the
    window for value planes and scale planes alike) so the partitioner can
    never "help" by all-gathering the pool first.
    """
    b, wp = table.shape
    g = pool[table]  # [B, Wp, page, ...]
    out = g.reshape((b, wp * pool.shape[1]) + pool.shape[2:])
    if mesh is not None:
        from vtpu.parallel.sharding import head_sharding

        out = jax.lax.with_sharding_constraint(
            out, head_sharding(mesh, out.ndim, 2))
    return out


def paged_causal_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    table: jax.Array,
    kv_len: jax.Array | None = None,
    mesh=None,
    scale: float | None = None,
) -> jax.Array:
    """Causal attention over a paged KV window: gather each slot's live
    pages from the shared block pool, then the reference attention
    (``scale`` and grouped queries as ``causal_attention`` takes them).

    q: [B, Sq, H, Dh]; k_pool, v_pool: [n_blocks, page, H, Dh] (ONE layer's
    plane of the pool); table: [B, Wp] block ids with Wp*page >= the read
    window. kv_len exactly as in causal_attention — the gathered window is
    positionally identical to a dense cache prefix, so the masking contract
    is unchanged. ``mesh`` marks head-sharded pools (tensor-parallel
    serving): the gathers stay chip-local on the head shard and the
    attention runs on each chip's heads, exactly like the dense TP path."""
    k = gather_kv_pages(k_pool, table, mesh=mesh)
    v = gather_kv_pages(v_pool, table, mesh=mesh)
    return causal_attention(q, k, v, kv_len=kv_len, scale=scale)


def paged_causal_attention_int8kv(
    q: jax.Array,
    kq_pool: jax.Array,
    k_scale_pool: jax.Array,
    vq_pool: jax.Array,
    v_scale_pool: jax.Array,
    table: jax.Array,
    kv_len: jax.Array | None = None,
    mesh=None,
) -> jax.Array:
    """Paged variant of causal_attention_int8kv: int8 value pools
    [n_blocks, page, H, Dh] plus f32 scale pools [n_blocks, page, H],
    gathered per slot through the same page table, then the shared
    int8-window attention (scales applied post-matmul, exactly as dense).
    ``mesh`` as in paged_causal_attention — the scale pools shard their
    head axis alongside their values, so all four gathers are chip-local."""
    kq = gather_kv_pages(kq_pool, table, mesh=mesh)
    vq = gather_kv_pages(vq_pool, table, mesh=mesh)
    k_scale = gather_kv_pages(k_scale_pool, table, mesh=mesh)
    v_scale = gather_kv_pages(v_scale_pool, table, mesh=mesh)
    return causal_attention_int8kv(q, kq, k_scale, vq, v_scale, kv_len=kv_len)


# Below this sequence length the kernel is maintenance without payoff.
# Measured in round 5 on a v5e (flash 1.6x XLA at [16,1024], 2.75x at
# [16,2048], 7.5x at [4,2048], ~98x at [1,8192]); record removed with the
# rig; re-measure (ROADMAP Speed #3-#5).
FLASH_MIN_SEQ = 1024


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, block_q: int, scale: float):
    """Single-K-pass schedule, deliberately NOT the blocked online-softmax
    loop: K/V for one (batch, head) are VMEM-resident at every supported
    shape, so the whole-S score matmul runs as one MXU op. An r4 experiment
    with a causal k-block skip (dynamic-trip fori_loop, online softmax)
    measured SLOWER everywhere — 19.0 ms vs 15.8 at [16,2048], 18.3 vs 15.2
    at [1,8192] — the loop's 128-wide matmuls and VPU rescaling cost more
    than the upper-triangle waste it avoided."""
    j = pl.program_id(1)
    q = q_ref[0]  # (block_q, Dh)
    k = k_ref[0]  # (S, Dh)
    v = v_ref[0]
    s = k.shape[0]
    scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    q_pos = j * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, s), 0)
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (block_q, s), 1)
    scores = jnp.where(k_pos <= q_pos, scores, _NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32) / denom
    o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "interpret", "mesh"))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    block_q: int = 128,
    interpret: bool | None = None,
    mesh=None,
) -> jax.Array:
    """Pallas blocked causal attention for prefill. q, k, v: [B, S, H, Dh].

    S must be a multiple of block_q (the model pads prompts to the block).
    ``interpret=None``: compiled on a TPU, interpreted elsewhere (the CPU
    tests' numerics rig — see the module docstring). ``mesh`` (a ('tp',)
    Mesh) wraps the call in shard_map over the head axis: a compiled Mosaic
    kernel cannot be partitioned by the SPMD pass, so under a tensor-
    parallel jit each chip must run the kernel on its own head shard
    (heads are independent — zero collectives).
    """
    b, s, h, dh = q.shape
    if s % block_q:
        raise ValueError(f"seq len {s} not a multiple of block_q {block_q}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if mesh is not None:
        heads = P(None, None, "tp", None)
        return shard_map(
            functools.partial(flash_attention, block_q=block_q,
                              interpret=interpret),
            mesh=mesh, in_specs=(heads, heads, heads), out_specs=heads,
            check_vma=False)(q, k, v)
    scale = 1.0 / math.sqrt(dh)
    # [B, S, H, Dh] -> [B*H, S, Dh]: one grid row per (batch, head)
    qh = q.transpose(0, 2, 1, 3).reshape(b * h, s, dh)
    kh = k.transpose(0, 2, 1, 3).reshape(b * h, s, dh)
    vh = v.transpose(0, 2, 1, 3).reshape(b * h, s, dh)
    grid = (b * h, s // block_q)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, block_q=block_q, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, s, dh), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, s, dh), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, dh), q.dtype),
        interpret=interpret,
    )(qh, kh, vh)
    return out.reshape(b, h, s, dh).transpose(0, 2, 1, 3)
