"""Fused decode-attention kernels: the dense-cache study and the PAGED
product path that attends over pool blocks in place.

History. The dense kernel below started as an in-trunk route (r5): standalone
it beat XLA at the T=1 long-window cells (bf16 1.1-1.6x from window 1024,
int8 1.9x at 2048; int8@1024 and T=4 chunks lost), but in the trunk it lost
everywhere: a pallas operand must be materialized while the serving cache is
being
scatter-updated, so XLA copied the layer view it would otherwise fuse windowed
reads from — the copy cost more than the kernel saved. r6 parked it as a
standalone study under benchmarks/decode_attn_kernel.py, whose verdict named
what re-promotion needed: a shard_map wrapper for ('tp',) meshes, and
input/output aliasing so the cache feeds the kernel without materialization.

The PAGED pool is what finally delivers both. ``paged_decode_attention``
takes the WHOLE donated block pool ``[L, n_blocks, page, H, Dh]`` as its
operand — no per-layer slice, no gathered window, no reshape: the kernel's
BlockSpec is a ``(1, 1, page, H, Dh)`` block of the buffer the kv_write
scatter just updated in place, so the pool aliases straight into the
pallas_call. (Until PR 26 the call merged H and Dh first, which under TPU
tiling copied every plane once a layer: 82 % of a decode step.) The page
table rides in as a SCALAR-PREFETCH operand, so the kernel's BlockSpec
index map walks the table itself: grid step (b, j) DMAs pool block
``table[b, j]`` into VMEM and the online softmax runs across window pages —
the O(window) gather (`ops.attention.gather_kv_pages`) that every paged
decode tick used to pay simply never exists. Under a ('tp',) mesh the call
wraps in shard_map: every chip walks its own head shard of the pool with the
replicated table, zero collectives and zero gathers (asserted on compiled
HLO by tests/test_paged_attn_kernel.py; that nothing else of a plane's size
is computed either, by tests/test_tpu_compile.py).

int8 is the kernel's NATIVE layout: the quantized planes stream as int8
bytes and convert to the compute dtype in VMEM — the halving the cache
quantization promises — with the per-token-per-head scales applied
post-matmul exactly as ``causal_attention_int8kv`` (k_scale on the score
tile before max/exp; v_scale on the probabilities only in the output
accumulation, never in the softmax denominator).

Both kernels equal their XLA references on the same operands
(tests/test_ops.py drives the dense study; tests/test_paged_attn_kernel.py
drives the paged path against paged_causal_attention{,_int8kv}).
"""

from __future__ import annotations

import functools
import math
import re
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

_NEG_INF = -1e30


# --------------------------------------------------------------------------
# Measured shape routing (the FLASH_MIN_SEQ discipline applied to the paged
# decode path). Basis: the standalone DENSE-kernel study DECODE_ATTN_r05.json,
# measured in round 5 on a v5e through a rig since removed; re-measure
# (ROADMAP Speed #7) — PR 21's chip_smoke.py timed the IN-TRUNK paged
# kernel at 18.5 ms a tick against the gather route's 2.2 ms at window 1024
# x 4 slots (bf16; 14.0 vs 2.4 int8), so these floors do not describe it.
# Most of that was the call's copy of the pool, gone since PR 26: read the
# two routes again before moving a floor (Speed #7c).
# The study, read cell by cell:
#   bf16 T=1: pallas/XLA 1.64 (b8 w1024), 1.43 (b8 w2048), 1.10 (b32
#     w1024), 1.23 (b32 w2048) — the kernel wins every measured bf16
#     decode cell from window 1024 up.
#   int8 T=1: 0.65/0.90 at window 1024, 1.90/1.01 at 2048 — int8 wins only
#     from 2048 (XLA's int8 chain is already cheap at 1024; the kernel's
#     dequantize-in-VMEM payoff needs a longer window's byte traffic).
#   T=4 verify chunks: 0.28-0.59 at EVERY cell — XLA amortizes the window
#     across the chunk's queries better than this schedule, so auto never
#     routes T > 1 to the kernel (spec verify rides the gather path unless
#     the override forces otherwise; the kernel stays token-equal there, it
#     just measured slower).
# Windows below 1024 were never measured, so the auto floor sits AT the
# smallest measured winning cell, never below it. The in-trunk paged
# variant shares the dense study's inner schedule but hasn't been swept on
# chip yet — ROADMAP holds the follow-up: re-measure through the in-trunk
# kernel and tighten (or move) these floors per cell. Non-TPU backends
# always route gather on auto: pallas runs as interpreted emulation
# off-chip, which is a correctness rig, never a win (the bench's kernel arm
# forces the route explicitly to prove the contracts).
PAGED_ATTN_MIN_WINDOW = 1024       # bf16, T=1
PAGED_ATTN_MIN_WINDOW_INT8 = 2048  # int8, T=1 (1024 measured 0.65-0.90x)

# Per-T auto-routing floors: (t, quant) -> the smallest window (tokens) at
# which the kernel engages for that chunk depth. A MISSING row means "never
# on auto" — the measured T=4 verify cells all lost to XLA's gather, so no
# T>1 row ships by default and the fused-speculation verify chunks (T=K+1)
# ride gather off-chip exactly as before. The table exists so on-chip
# sweeps of the IN-TRUNK kernel (`paged_kv_bench --attn-kernel
# --spec-chunk T`) can add/tighten rows per measured cell without touching
# the resolver; the T=1 rows alias the constants above so the historical
# knobs keep working.
PAGED_ATTN_T_FLOORS: dict = {
    (1, False): PAGED_ATTN_MIN_WINDOW,
    (1, True): PAGED_ATTN_MIN_WINDOW_INT8,
}

# ServingConfig.paged_attn / adapter ``paged_attn=`` override values.
PAGED_ATTN_ROUTES = ("kernel", "gather")


def paged_attn_route(override: Optional[str], window: int,
                     backend: Optional[str] = None,
                     t: int = 1, quant: bool = False) -> str:
    """Resolve the paged decode-attention route for one dispatch shape.

    ``override`` forces "kernel" or "gather" outright (the ServingConfig
    escape hatch — benches and regressions-in-waiting both need it); None is
    the measured auto route above, keyed on the full shape the study
    measured: ``window`` (the read window in tokens — the engine's
    kv_bucket, or max_seq unbounded), ``t`` (queries per dispatch: 1 for a
    decode tick, K+1 for a spec verify chunk) and ``quant`` (int8 KV pools
    carry a higher floor) through the PAGED_ATTN_T_FLOORS table — a chunk
    shape with no table row never routes kernel on auto (every measured
    T>1 cell lost; on-chip sweeps may add rows back per measured cell).
    The resolution is a STATIC per-shape property — the
    engine counts it per dispatched tick
    (stats()['paged_attn_kernel_ticks'/'paged_attn_gather_ticks']) and the
    trunk resolves it at trace time, so the two can never disagree."""
    if override is not None:
        if override not in PAGED_ATTN_ROUTES:
            raise ValueError(
                f"paged_attn must be one of {PAGED_ATTN_ROUTES} or None "
                f"(auto), got {override!r}")
        return override
    if (backend or jax.default_backend()) != "tpu":
        return "gather"
    floor = PAGED_ATTN_T_FLOORS.get((t, bool(quant)))
    return "kernel" if floor is not None and window >= floor else "gather"


# --------------------------------------------------------------------------
# Shared per-head online-softmax update (flash-style accumulation across
# KV tiles), used by the dense study kernel and the paged table-walker —
# the numerics exist exactly once.


def _attend_head(q, k, v, valid, scale, h, m_ref, d_ref, acc_ref,
                 k_scale_vec=None, v_scale_vec=None):
    """One head's contribution of one KV tile to the running softmax.

    q: (T, Dh); k, v: (S_blk, Dh) already in compute dtype; valid: (T, S_blk)
    mask; k_scale_vec/v_scale_vec: (S_blk,) f32 int8 scales or None. The
    scale placement mirrors causal_attention_int8kv exactly: k_scale on the
    score tile BEFORE max/exp, v_scale on the probabilities only in the
    output accumulation (the softmax denominator sees unscaled p)."""
    scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if k_scale_vec is not None:
        scores = scores * k_scale_vec[None, :]
    scores = jnp.where(valid, scores, _NEG_INF)
    m_prev = m_ref[h, :, :1]  # (T, 1) f32 (lane-replicated store)
    d_prev = d_ref[h, :, :1]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)  # (T, S_blk) f32
    d_ref[h] = jnp.broadcast_to(
        d_prev * alpha + jnp.sum(p, axis=-1, keepdims=True), d_ref[h].shape)
    m_ref[h] = jnp.broadcast_to(m_new, m_ref[h].shape)
    if v_scale_vec is not None:
        p = p * v_scale_vec[None, :]
    pv = jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    acc_ref[h] = acc_ref[h] * alpha + pv


def _emit_heads(o_ref, acc_ref, d_ref, nheads: int, dh: int) -> None:
    for h in range(nheads):
        out = acc_ref[h] / d_ref[h, :, :1]
        o_ref[0, :, h * dh:(h + 1) * dh] = out.astype(o_ref.dtype)


def _init_accumulators(m_ref, d_ref, acc_ref) -> None:
    m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, m_ref.dtype)
    d_ref[...] = jnp.zeros(d_ref.shape, d_ref.dtype)
    acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)


def _softmax_scratch(nheads: int, t: int, dh: int) -> list:
    return [
        pltpu.VMEM((nheads, t, dh), jnp.float32),   # acc
        pltpu.VMEM((nheads, t, 128), jnp.float32),  # m (lane-replicated)
        pltpu.VMEM((nheads, t, 128), jnp.float32),  # d (lane-replicated)
    ]


# --------------------------------------------------------------------------
# Dense-cache decode kernel (the r5 study, kept runnable: equals
# causal_attention / causal_attention_int8kv on the same operands, and
# hack/decode_attn_bench.py re-checks its standalone two-chain numbers).


def _decode_kernel(q_ref, k_ref, v_ref, lens_ref, o_ref,
                   acc_ref, m_ref, d_ref, *,
                   scale: float, nheads: int, dh: int, s_blk: int,
                   n_blocks: int, ks_ref=None, vs_ref=None):
    """One batch row x one KV S-block, all heads unrolled in-kernel.

    Decode attention on the XLA path is dispatch-bound, not byte-bound
    (M=1 batched matmuls, a materialized [B,H,T,S] mask/score tensor,
    separate softmax ops). Here the whole
    attention for a batch row is one kernel: K/V stream through VMEM as
    contiguous (S_blk, H*Dh) tiles read straight from the cache's native
    [B, S, H*Dh] view (a [B,H,S,Dh] relayout would copy the entire window
    every tick, costing the bytes the kernel exists to save), heads are a
    static unroll, and the softmax runs ONLINE across S-blocks (flash
    style) so VMEM holds one tile + (T, Dh) f32 accumulators per head."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _init_accumulators(m_ref, d_ref, acc_ref)

    lens = lens_ref[0, 0, :]  # (T,) int32: query i may read k_pos < lens[i]
    t = lens.shape[0]
    base = j * s_blk
    k_pos = base + jax.lax.broadcasted_iota(jnp.int32, (t, s_blk), 1)
    valid = k_pos < lens[:, None]
    for h in range(nheads):
        q = q_ref[0, :, h * dh:(h + 1) * dh]  # (T, Dh)
        k = k_ref[0, :, h * dh:(h + 1) * dh].astype(q.dtype)
        v = v_ref[0, :, h * dh:(h + 1) * dh].astype(q.dtype)
        _attend_head(
            q, k, v, valid, scale, h, m_ref, d_ref, acc_ref,
            k_scale_vec=None if ks_ref is None else ks_ref[0, h, :],
            v_scale_vec=None if vs_ref is None else vs_ref[0, h, :])

    @pl.when(j == n_blocks - 1)
    def _emit():
        _emit_heads(o_ref, acc_ref, d_ref, nheads, dh)


def _decode_s_block(s: int) -> int:
    for cand in (512, 256, 128):
        if s % cand == 0:
            return min(cand, s)
    return s


@functools.partial(jax.jit, static_argnames=("bucket", "interpret"))
def decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_len: jax.Array,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    bucket: int = 0,
    interpret: bool | None = None,
) -> jax.Array:
    """Pallas decode/verify attention over the serving cache's native
    layout. q: [B, T, H, Dh] (T = 1 decode tick or k+1 verify chunk);
    k, v: [B, S, H, Dh] bf16, or int8 with k_scale/v_scale [B, S, H] f32;
    kv_len: ragged [B, T] (query i of row b reads k_pos < kv_len[b, i]) or
    [B] (T must be 1; the suffix-decode mask k_pos < len is identical).

    ``bucket`` (static; 0 = S) bounds the attention READS via the GRID —
    blocks past the bucket are simply never scheduled. Callers pass the
    cache's FULL per-layer view (a contiguous leading-dim slice, zero
    copy) instead of a ``[:, :bucket]`` slice: a pallas operand must be
    materialized, so the sliced form forced XLA to copy the whole window
    every tick, erasing the kernel's standalone win.

    Single-chip DENSE-cache kernel — the shipped serving route is the paged
    ``paged_decode_attention`` below, which resolves both of the study's
    re-promotion requirements (whole-pool operand aliasing + a shard_map
    tp wrapper); this entry point stays as the standalone study surface
    hack/decode_attn_bench.py measures. ``interpret=None``: compiled on a
    TPU, interpreted elsewhere (see paged_decode_attention).
    """
    b, t, h, dh = q.shape
    s = k.shape[1]
    bucket = bucket or s
    if bucket > s:
        raise ValueError(f"bucket {bucket} exceeds cache length {s}")
    if kv_len.ndim == 1:
        if t != 1:
            raise ValueError("[B] kv_len requires T=1 (ragged [B,T] otherwise)")
        kv_len = kv_len[:, None]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    scale = 1.0 / math.sqrt(dh)
    s_blk = _decode_s_block(bucket)
    n_blocks = bucket // s_blk
    # native [B, S, H, Dh] -> [B, S, H*Dh] is a free reshape (contiguous);
    # per-head tiles are static minor-dim slices in-kernel
    kf = k.reshape(b, s, h * dh)
    vf = v.reshape(b, s, h * dh)
    qf = q.reshape(b, t, h * dh)
    lens3 = kv_len[:, None, :]  # [B, 1, T]: rank-3 so block dims satisfy tiling
    grid = (b, n_blocks)
    q_spec = pl.BlockSpec((1, t, h * dh), lambda i, j: (i, 0, 0))
    kv_spec = pl.BlockSpec((1, s_blk, h * dh), lambda i, j: (i, j, 0))
    len_spec = pl.BlockSpec((1, 1, t), lambda i, j: (i, 0, 0))
    out_shape = jax.ShapeDtypeStruct((b, t, h * dh), q.dtype)
    scratch = _softmax_scratch(h, t, dh)
    kern = functools.partial(
        _decode_kernel, scale=scale, nheads=h, dh=dh, s_blk=s_blk,
        n_blocks=n_blocks)
    if k_scale is None:
        out = pl.pallas_call(
            kern,
            grid=grid,
            in_specs=[q_spec, kv_spec, kv_spec, len_spec],
            out_specs=q_spec,
            out_shape=out_shape,
            scratch_shapes=scratch,
            interpret=interpret,
            name="decode_attn",
        )(qf, kf, vf, lens3)
        return out.reshape(b, t, h, dh)

    def kern8(q_ref, k_ref, ks_ref, v_ref, vs_ref, lens_ref, o_ref,
              acc_ref, m_ref, d_ref):
        _decode_kernel(q_ref, k_ref, v_ref, lens_ref, o_ref,
                       acc_ref, m_ref, d_ref,
                       scale=scale, nheads=h, dh=dh, s_blk=s_blk,
                       n_blocks=n_blocks, ks_ref=ks_ref, vs_ref=vs_ref)

    # scales sliced to the bucket THEN pre-transposed to [B, H, bucket]:
    # contiguous (H, S_blk) tiles (the cache-native [B, S, H] would DMA
    # 4-byte strided runs). Slicing first keeps the materialization
    # proportional to the window actually read — a full-S transpose on a
    # long cache with a small bucket would cost a significant fraction of
    # the int8 bytes the grid-bounding saves.
    ks_t = k_scale[:, :bucket].transpose(0, 2, 1)
    vs_t = v_scale[:, :bucket].transpose(0, 2, 1)
    scale_spec = pl.BlockSpec((1, h, s_blk), lambda i, j: (i, 0, j))
    out = pl.pallas_call(
        kern8,
        grid=grid,
        in_specs=[q_spec, kv_spec, scale_spec, kv_spec, scale_spec, len_spec],
        out_specs=q_spec,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        name="decode_attn",
    )(qf, kf, ks_t, vf, vs_t, lens3)
    return out.reshape(b, t, h, dh)


# --------------------------------------------------------------------------
# Paged table-walking decode kernel (the product serving route).


def _paged_kernel(lay_ref, tbl_ref, q_ref, k_ref, v_ref, lens_ref, o_ref,
                  acc_ref, m_ref, d_ref, *,
                  scale: float, nheads: int, dh: int, page: int, n_wp: int,
                  ks_ref=None, vs_ref=None):
    """One slot x one WINDOW PAGE, all heads unrolled in-kernel.

    The grid walks (batch row, window page); the BlockSpec index maps read
    the scalar-prefetched page table, so grid step (b, j) DMAs pool block
    ``table[b, j]`` — this kernel IS the gather, fused into the attention.
    Window entries past a slot's live pages carry the reserved null block 0
    (the engine's padding contract): consecutive revisits of an unchanged
    block index skip the DMA, and the kv_len mask below keeps null-block
    garbage unobservable — exactly the gather path's masking contract, so
    the two routes stay token-equal. lay_ref/tbl_ref are the scalar-prefetch
    operands ([1] layer index, [B, Wp] table); the index maps consumed them
    before this body runs."""
    del lay_ref, tbl_ref  # consumed by the BlockSpec index maps
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _init_accumulators(m_ref, d_ref, acc_ref)

    lens = lens_ref[0, 0, :]  # (T,) int32: query i may read k_pos < lens[i]
    t = lens.shape[0]
    k_pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (t, page), 1)
    valid = k_pos < lens[:, None]
    # The block arrives as the pool stores it, (page, H, Dh). It is loaded
    # whole and its heads merged in VMEM, so that a head is a lane-aligned
    # slice: reading k_ref[0, 0, :, h, :] a head at a time gathers 16 rows
    # from 16 tiles and measured 7-10 % slower at 32 heads (PERF.md, PR 26).
    kb = k_ref[0, 0].reshape(page, nheads * dh)
    vb = v_ref[0, 0].reshape(page, nheads * dh)
    for h in range(nheads):
        q = q_ref[0, :, h * dh:(h + 1) * dh]  # (T, Dh)
        k = kb[:, h * dh:(h + 1) * dh].astype(q.dtype)
        v = vb[:, h * dh:(h + 1) * dh].astype(q.dtype)
        _attend_head(
            q, k, v, valid, scale, h, m_ref, d_ref, acc_ref,
            k_scale_vec=None if ks_ref is None else ks_ref[0, 0, :, h],
            v_scale_vec=None if vs_ref is None else vs_ref[0, 0, :, h])

    @pl.when(j == n_wp - 1)
    def _emit():
        _emit_heads(o_ref, acc_ref, d_ref, nheads, dh)


def _norm_kv_len(kv_len: jax.Array, t: int) -> jax.Array:
    if kv_len.ndim == 1:
        if t != 1:
            raise ValueError("[B] kv_len requires T=1 (ragged [B,T] otherwise)")
        kv_len = kv_len[:, None]
    return kv_len


def _layer_arr(layer) -> jax.Array:
    # works for a static python int (unrolled serving loop) AND a traced
    # int32 scalar (the fori_loop layer carry) — the kernel takes it as a
    # [1] scalar-prefetch operand either way
    return jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))


def _paged_call(q, k_pool, v_pool, k_scale_pool, v_scale_pool, table,
                kv_len, lay, interpret: bool):
    """Single-chip pallas_call over (possibly head-LOCAL) pool planes."""
    b, t, h, dh = q.shape
    page = k_pool.shape[2]
    wp = table.shape[1]
    scale = 1.0 / math.sqrt(dh)
    # The pools are the kernel's operands in the layout they are stored in:
    # a block is (page, H, Dh) of the 5-d buffer the kv_write scatter just
    # updated in place. Merging H and Dh out here is a copy of the whole
    # pool under TPU tiling, once a layer a plane (162 ms of a 197 ms step
    # at 3.5 GB x 15 layers until PR 26), so nothing of a pool's size may
    # run under this scope: it holds what preparation is left, the query
    # and the lengths, and ``pool_relayout_ms_per_step`` is the guard.
    with jax.named_scope("pool_relayout"):
        qf = q.reshape(b, t, h * dh)
        lens3 = kv_len[:, None, :]  # [B, 1, T]: rank-3 so block dims tile
    q_spec = pl.BlockSpec((1, t, h * dh), lambda i, j, *_: (i, 0, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, page, h, dh),
        lambda i, j, lay_ref, tbl_ref: (lay_ref[0], tbl_ref[i, j], 0, 0, 0))
    len_spec = pl.BlockSpec((1, 1, t), lambda i, j, *_: (i, 0, 0))
    kern = functools.partial(
        _paged_kernel, scale=scale, nheads=h, dh=dh, page=page, n_wp=wp)
    in_specs = [q_spec, kv_spec, kv_spec, len_spec]
    operands = [qf, k_pool, v_pool, lens3]
    if k_scale_pool is not None:
        # scale pools [L, nb, page, H] walk the same table; the (page, H)
        # tile is tiny next to the value blocks, so the cache-native layout
        # streams as-is (no per-call transpose materialization — the exact
        # trap the dense study's bucket-sliced transpose documents)
        scale_spec = pl.BlockSpec(
            (1, 1, page, h),
            lambda i, j, lay_ref, tbl_ref: (lay_ref[0], tbl_ref[i, j], 0, 0))

        def kern8(lay_ref, tbl_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref,
                  lens_ref, o_ref, acc_ref, m_ref, d_ref):
            _paged_kernel(lay_ref, tbl_ref, q_ref, k_ref, v_ref, lens_ref,
                          o_ref, acc_ref, m_ref, d_ref,
                          scale=scale, nheads=h, dh=dh, page=page, n_wp=wp,
                          ks_ref=ks_ref, vs_ref=vs_ref)

        kern = kern8
        in_specs = [q_spec, kv_spec, scale_spec, kv_spec, scale_spec,
                    len_spec]
        operands = [qf, k_pool, k_scale_pool, v_pool, v_scale_pool, lens3]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # layer index + page table
        grid=(b, wp),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=_softmax_scratch(h, t, dh),
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, t, h * dh), q.dtype),
        interpret=interpret,
        name="paged_attn",  # the kernel's name, and its scope in a trace
    )(lay, table, *operands)
    return out.reshape(b, t, h, dh)


def paged_decode_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    table: jax.Array,
    kv_len: jax.Array,
    layer=0,
    mesh=None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused paged decode/verify attention: walk the page table IN PLACE
    over the block pool — no gather_kv_pages, no dense window.

    q: [B, T, H, Dh] (T = 1 decode tick or k+1 verify chunk); k_pool,
    v_pool: the WHOLE pool [L, n_blocks, page, H, Dh] (pass the full
    scatter-updated buffer, never a per-layer slice — a pallas operand must
    be materialized, and the sliced form is exactly the copy that killed
    the r5 in-trunk route); ``layer`` selects the plane via a [1]
    scalar-prefetch operand (static int under the unrolled serving loop, a
    traced scalar under fori_loop — both compile once). table: [B, Wp]
    block ids, pre-sliced to the read window (Wp = bucket // page), padded
    with the reserved null block 0; kv_len exactly as causal_attention's
    ragged form ([B, T], or [B] with T=1) — the masking contract is shared
    verbatim with the gather path, so the routes are token-equal.

    ``mesh`` (a ('tp',) Mesh) wraps the call in shard_map: each chip walks
    its OWN head shard of the pool (q arrives head-sharded from the column-
    split projections, tables/lengths replicate), so the kernel adds zero
    collectives — compiled-HLO collective parity with the gather route is
    asserted in tests. Routing between this kernel and the gather path is
    measured per shape (paged_attn_route); the engine's ServingConfig
    ``paged_attn`` forces either route.

    ``interpret=None`` resolves from the backend: compiled (Mosaic) on a
    TPU, the Pallas interpreter elsewhere — the CPU tests' numerics rig.
    That a chip run executed the COMPILED kernel is proved, not assumed:
    chip_smoke.py requires a ``tpu_custom_call`` in the engine's decode
    step, tests/test_tpu_compile.py compiles it with interpret=False."""
    t = q.shape[1]
    kv_len = _norm_kv_len(kv_len, t)
    _check_pool(q, k_pool, table)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    lay = _layer_arr(layer)
    if mesh is None:
        return _paged_call(q, k_pool, v_pool, None, None, table, kv_len,
                           lay, interpret)
    fn = shard_map(
        functools.partial(_shard_body, interpret=interpret, quant=False),
        mesh=mesh,
        in_specs=(P(None, None, "tp", None),       # q: head-sharded
                  P(None, None, None, "tp", None),  # pools: head-sharded
                  P(None, None, None, "tp", None),
                  P(None, None), P(None, None), P(None)),  # table/lens/layer
        out_specs=P(None, None, "tp", None),
        check_vma=False,
    )
    return fn(q, k_pool, v_pool, table, kv_len, lay)


def paged_decode_attention_int8kv(
    q: jax.Array,
    kq_pool: jax.Array,
    k_scale_pool: jax.Array,
    vq_pool: jax.Array,
    v_scale_pool: jax.Array,
    table: jax.Array,
    kv_len: jax.Array,
    layer=0,
    mesh=None,
    interpret: bool | None = None,
) -> jax.Array:
    """int8-native paged kernel: int8 value pools [L, n_blocks, page, H, Dh]
    stream as int8 BYTES and dequantize in VMEM; f32 scale pools
    [L, n_blocks, page, H] walk the same table and apply post-matmul exactly
    as causal_attention_int8kv (k_scale on scores before max/exp, v_scale on
    the probabilities only in the output accumulation). Same table/kv_len/
    layer/mesh contract as paged_decode_attention."""
    t = q.shape[1]
    kv_len = _norm_kv_len(kv_len, t)
    _check_pool(q, kq_pool, table)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    lay = _layer_arr(layer)
    if mesh is None:
        return _paged_call(q, kq_pool, vq_pool, k_scale_pool, v_scale_pool,
                           table, kv_len, lay, interpret)
    fn = shard_map(
        functools.partial(_shard_body, interpret=interpret, quant=True),
        mesh=mesh,
        in_specs=(P(None, None, "tp", None),
                  P(None, None, None, "tp", None),
                  P(None, None, None, "tp"),       # scale pools: head-sharded
                  P(None, None, None, "tp", None),
                  P(None, None, None, "tp"),
                  P(None, None), P(None, None), P(None)),
        out_specs=P(None, None, "tp", None),
        check_vma=False,
    )
    return fn(q, kq_pool, k_scale_pool, vq_pool, v_scale_pool, table,
              kv_len, lay)


def _shard_body(*args, interpret: bool, quant: bool):
    """Per-chip body under the ('tp',) shard_map: operands arrive head-LOCAL
    (H/tp heads), the kernel runs exactly as on one chip."""
    if quant:
        q, kq, ks, vq, vs, table, kv_len, lay = args
        return _paged_call(q, kq, vq, ks, vs, table, kv_len, lay, interpret)
    q, k, v, table, kv_len, lay = args
    return _paged_call(q, k, v, None, None, table, kv_len, lay, interpret)


def _check_pool(q: jax.Array, pool: jax.Array, table: jax.Array) -> None:
    if pool.ndim != 5:
        raise ValueError(
            f"expected the WHOLE pool [L, n_blocks, page, H, Dh], got rank "
            f"{pool.ndim} — pass the full buffer, not a per-layer slice "
            "(the slice is the materialization this kernel exists to kill)")
    if table.ndim != 2 or table.shape[0] != q.shape[0]:
        raise ValueError(
            f"table must be [B, Wp] with B={q.shape[0]}, got {table.shape}")


# --------------------------------------------------------------------------
# HLO audits: prove the pool gather, and every other pool-sized result,
# disappeared from a compiled step.


_HLO_RESULT = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = [a-z0-9]+\[([0-9,]*)\]\S* ([\w\-]+)\(", re.M)


def count_pool_sized_ops(hlo_text: str, min_elements: int) -> dict:
    """{opcode: count} of the HLO instructions whose array RESULT holds at
    least ``min_elements`` elements. At one pool plane's size a compiled
    kernel-route step may hold the planes themselves (``parameter``,
    ``bitcast``), the kv_write scatters and the fusions that wrap them one
    for one, and nothing else: a ``copy``, ``reshape``, ``transpose`` or
    ``slice`` of that size is the pool being moved
    (tests/test_tpu_compile.py)."""
    ops: dict = {}
    for m in _HLO_RESULT.finditer(hlo_text):
        elems = math.prod(int(d) for d in m.group(1).split(",") if d)
        if elems >= min_elements:
            ops[m.group(2)] = ops.get(m.group(2), 0) + 1
    return ops


def count_pool_gathers(hlo_text: str, min_elements: int) -> int:
    """Count HLO gather instructions whose RESULT holds at least
    ``min_elements`` elements — at the paged window-gather size
    (B * window * H * Dh per value plane) this isolates the pool gathers
    from the small embedding/table lookups that legitimately remain.
    The bench and tests pass the exact k-plane window size and assert 0 on
    the kernel route, > 0 on the gather route."""
    return count_pool_sized_ops(hlo_text, min_elements).get("gather", 0)
