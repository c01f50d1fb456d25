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
operand — no per-layer slice, no gathered window, no reshape (a merge of H
and Dh under TPU tiling copies every plane once a layer): the pool the
kv_write scatter just updated in place stays in HBM and the kernel copies
the blocks it needs out of it. The page table, the lengths and the layer
index ride in as SCALAR-PREFETCH operands, and the kernel walks the table
itself: one grid step a slot, and in it a loop over the slot's LIVE pages,
``cdiv(kv_len, page)`` of them and not the read window's, a group of pages
a wait, double-buffered, the next group (or the next slot's first) in
flight while this one is computed (``_paged_kernel``). The O(window) gather
(`ops.attention.gather_kv_pages`) that every paged decode tick used to pay
never exists, and a page past a slot's last token is neither copied nor
computed. Under a ('tp',) mesh the call wraps in shard_map: every chip
walks its own head shard of the pool with the replicated table, zero
collectives and zero gathers (asserted on compiled HLO by
tests/test_paged_attn_kernel.py; that nothing else of a plane's size is
computed either, by tests/test_tpu_compile.py).

The arithmetic at T = 1 is a row against a matrix a head, and the vector
unit does it in the layout the pool stores (``_attend_page``). Measured on
a v5e at the benchmark's shapes and lengths and rejected (PERF.md, PR 29;
ms a step of 15 / 15 / 8 layers, dense 16 x 1024 / dense 16 x 4096 / OLMoE
64 x 4096, shipped form 4.72 / 6.16 / 4.09, the copies alone 4.41 / 5.76 /
3.10, a grid step a window page with every head's product on the MXU 23.1 /
88.9 / 144.1): the MXU a head over a group's block merged to (tokens, H*Dh)
6.13 / 7.98 / 4.14; the same reading a head at a time from the stored block
9.27 / 12.23 / 8.35; all heads as one product K (page*H, Dh) x q^T with the
diagonal kept 7.79 / 10.39 / 6.64.

What Mosaic's copies cannot cut out of a plane in HBM walks the same table
through BlockSpec windows instead, one slot x one window page a grid step,
the steps past a slot's live pages copying and computing nothing
(``_paged_window_kernel``): a head count a chip that is no whole tile
(``_copies_cut``: 6, 12, int8's 2), and every int8 pool, because the scale
pools ``[L, n_blocks, page, H]`` have rows narrower than 128 lanes. int8 is
that kernel's NATIVE layout: the quantized planes stream as int8 bytes and
convert in VMEM — the halving the cache quantization promises — and the
scale pools walk the same table, a (page, H) block a page, applied exactly
as ``causal_attention_int8kv`` (k_scale on the scores before max/exp;
v_scale on the probabilities only in the output accumulation, never in the
softmax denominator).

Grouped queries (fewer key/value heads than query heads, the heads stored
several a row of whole lanes: ``transformer.kv_plane_shape``) make the same
walk with their arithmetic on the MXU (``_grouped_kernel``): a slot's
queries of a pool row are a tile of 8 (the hybrid cell's) or 32 (a block
pass's four rows of 8 heads) against a group's tokens, where the
vector unit's form paid a vreg a token a query (PERF.md, PR 32: 22.7 ms
for four layers of the hybrid cell at any page size, its copies not the
bound). A pool row is key/value heads of its own, so the kernel makes a
product a pool row over that row's tokens, read out of the group's buffer
with a stride (``pool_row`` says how, and why not plainly), and a softmax
a pool row over scores that are all wanted. What a group costs is the
latency of its chain (wait, product, softmax, product, carry) more than
its work, so this walk's groups are ``_GROUPED_GROUP_TOKENS`` long where
the others' are 128 (PERF.md, PR 44, has the timings, and those of the
forms not taken).

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
# Per-head online-softmax update (flash-style accumulation across KV
# tiles) of the dense study kernel; the paged walks have ``_attend_page``.


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

# A group is the pages one wait brings into VMEM: as many as fit the budget
# below in both buffer slots of every plane, at most _GROUP_TOKENS tokens,
# never more than the read window has.
_GROUP_TOKENS = 128
_GROUP_VMEM_BYTES = 8 << 20
# the grouped walk's (``_grouped_kernel``). A group there is one dependent
# chain (wait, product, softmax, product) and its time is the chain's
# latency more than its work: a block pass's 24 layers read 27.4 / 22.4 /
# 17.3 / 16.0 / 16.5 ms at 128 / 256 / 512 / 1024 / 2048 tokens, the hybrid
# step's four 6.2 / 6.2 / 5.0 / 4.5 / 4.3 (PERF.md, PR 44)
_GROUPED_GROUP_TOKENS = 1024


def _pages_per_group(page: int, h: int, dh: int, itemsize: int,
                     wp: int, tokens: int) -> int:
    per_page = 2 * page * h * dh * itemsize  # K and V
    fit = _GROUP_VMEM_BYTES // (2 * per_page)
    return max(1, min(fit, tokens // page, wp))


def _copies_cut(h: int, itemsize: int) -> bool:
    """Whether Mosaic cuts a (page, H, Dh) block out of a plane of ``h``
    heads: it tiles the (H, Dh) rows by eight (by the next power of two for
    fewer heads, never under a 32-bit word of rows) and slices a plane in
    HBM only at whole tiles: 2 (bf16), 4, 8, 16, 24.. heads a chip, not 6,
    12 or int8's 2 ("Slice shape along dimension 3 must be aligned to
    tiling (8), but is 12")."""
    return h % max(4 // itemsize, min(8, 1 << (h - 1).bit_length())) == 0


def _live_pages(len_ref, row, t: int, page: int):
    """The pages slot ``row`` visits: those up to its longest query's last
    token, at least one (a slot with nothing live); never more than the
    window has, because ``_paged_call`` clamps the lengths to its end."""
    last = len_ref[row, 0]
    for i in range(1, t):
        last = jnp.maximum(last, len_ref[row, i])
    return jnp.maximum(pl.cdiv(last, page), 1)


def _sublane_column(x: jax.Array) -> jax.Array:
    """(page, H) with H along the lanes -> (page, H, 1), H along the
    sublanes as the score column has it: the diagonal of the row laid
    under itself H times."""
    page, h = x.shape
    eye = (jax.lax.broadcasted_iota(jnp.int32, (page, h, h), 1)
           == jax.lax.broadcasted_iota(jnp.int32, (page, h, h), 2))
    return jnp.sum(jnp.where(eye, x[:, None, :], 0.0), axis=-1, keepdims=True)


def _attend_page(q, lens, first, k, v, k_scale, v_scale, carry):
    """One page of one slot into the running softmax of each of its queries.

    The vector unit's arithmetic, in the layout the pool stores: at T = 1 a
    head's product is one row against a matrix, so scores are ``sum_d q * k``
    (a lane reduction a token a head) and the output ``sum_tok p * v``.
    q: (T, H, Dh) float32, scaled; k, v: (page, H, Dh) float32; ``first``:
    the position of the page's first token; lens: the T lengths (query i
    reads positions under lens[i], exactly as the gather route masks them);
    k_scale, v_scale: (page, H, 1) int8 scales or None, applied where the
    gather route applies them (k_scale on the scores before the maximum,
    v_scale on the probabilities in the accumulation only). carry: a
    (maximum (H, 1), denominator (H, 1), accumulator (H, Dh)) a query."""
    page, h, _ = k.shape
    pos = first + jax.lax.broadcasted_iota(jnp.int32, (page, h, 1), 0)
    out = []
    for ti, (m_prev, d_prev, acc) in enumerate(carry):
        s = jnp.sum(k * q[ti], axis=-1, keepdims=True)  # (page, H, 1)
        if k_scale is not None:
            s = s * k_scale
        s = jnp.where(pos < lens[ti], s, _NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))  # (H, 1)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        d_new = d_prev * alpha + jnp.sum(p, axis=0)
        if v_scale is not None:
            p = p * v_scale
        out.append((m_new, d_new, acc * alpha + jnp.sum(p * v, axis=0)))
    return tuple(out)


def _paged_kernel(lay_ref, tbl_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
                  k_buf, v_buf, sems, turn_ref, *,
                  scale: float, page: int, group: int):
    """One slot a grid step; the slot's LIVE pages, a group at a time.

    The pools stay in HBM. A slot visits ``_live_pages`` of them, read from
    the scalar-prefetched lengths, not the read window's: pages past a
    slot's last token are neither copied nor computed, whatever the table
    names there. Pages come a group at a time (``_pages_per_group``) into
    one of two VMEM slots, one copy a page a plane from
    ``pool[layer, table[b, j]]``; while a group is computed the slot's next
    group, or the next slot's first, is in flight (``turn_ref`` carries the
    buffer's turn from one grid step to the next). The running maximum,
    denominator and accumulator live in the loops' carries and touch no
    scratch (``_attend_page``). PERF.md (PR 29) has the forms measured
    against this one."""
    planes = ((k_hbm, k_buf), (v_hbm, v_buf))
    b, nb = pl.program_id(0), pl.num_programs(0)
    t, h, dh = q_ref.shape[1:]
    lay = lay_ref[0]
    live = functools.partial(_live_pages, len_ref, t=t, page=page)

    def copy_group(row, g, slot, wait: bool):
        """Start (or wait for) the copies of slot ``row``'s group ``g``."""
        n = jnp.minimum(live(row) - g * group, group)

        def one(i, _):
            blk = tbl_ref[row, g * group + i]
            for p, (pool, buf) in enumerate(planes):
                dma = pltpu.make_async_copy(
                    pool.at[lay, blk], buf.at[slot, i], sems.at[p, slot])
                if wait:
                    dma.wait()
                else:
                    dma.start()
            return _

        jax.lax.fori_loop(0, n, one, 0)
        return n

    @pl.when(b == 0)
    def _first():
        turn_ref[0] = 0
        copy_group(0, 0, 0, wait=False)

    n_groups = pl.cdiv(live(b), group)
    turn = turn_ref[0]
    q = q_ref[0].astype(jnp.float32) * scale  # (T, H, Dh)
    lens = [len_ref[b, i] for i in range(t)]

    def attend_group(g, carry):
        slot = (turn + g) % 2
        last = g + 1 == n_groups

        @pl.when(jnp.logical_not(last) | (b + 1 < nb))
        def _prefetch():
            copy_group(jnp.where(last, jnp.minimum(b + 1, nb - 1), b),
                       jnp.where(last, 0, g + 1), 1 - slot, wait=False)

        n = copy_group(b, g, slot, wait=True)
        return jax.lax.fori_loop(
            0, n, lambda i, c: _attend_page(
                q, lens, (g * group + i) * page,
                k_buf[slot, i].astype(jnp.float32),
                v_buf[slot, i].astype(jnp.float32), None, None, c), carry)

    init = tuple((jnp.full((h, 1), _NEG_INF, jnp.float32),
                  jnp.zeros((h, 1), jnp.float32),
                  jnp.zeros((h, dh), jnp.float32)) for _ in range(t))
    state = jax.lax.fori_loop(0, n_groups, attend_group, init)
    turn_ref[0] = (turn + n_groups) % 2
    for ti, (_, d, acc) in enumerate(state):
        o_ref[0, ti] = (acc / d).astype(o_ref.dtype)


def _grouped_kernel(lay_ref, tbl_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
                    k_buf, v_buf, sems, turn_ref, *,
                    scale: float, page: int, group: int, rows: int,
                    lse_ref=None):
    """``_paged_kernel``'s walk for grouped queries, with both products on
    the MXU (``_grouped_call`` has the operands' layout). ``lse_ref``
    (``_grouped_lse_kernel``) also takes each query row's log of its
    softmax's denominator, maximum included, along every lane: what a
    caller needs to join this walk's result with keys that are not in the
    pool.

    A slot's nq queries (``_pack_queries``; a multiple of 8) come as a
    block (rows * nq, lanes), pool row r of query j at r * nq + j, and a
    group of pages as (tokens * rows, lanes), row r of a token at
    token * rows + r: the pool's bytes as they are stored. A pool row is
    key/value heads of its own, so query row (r, j) attends over the
    cached rows r alone: ``pool_row`` reads those out of the group's
    buffer, every ``rows``-th row from the r-th, and a pool row is a
    running softmax of its own, (nq, lanes) x (lanes, tokens) scores in
    float32 and the probabilities against the same rows of the values.
    At ``rows`` 1 nothing is strided and the kernel is the plain walk of
    a (nq, lanes) tile.

    The buffers are zeroed once: a group's unfilled pages are masked, and
    what is masked must still be finite."""
    planes = ((k_hbm, k_buf), (v_hbm, v_buf))
    b, nb = pl.program_id(0), pl.num_programs(0)
    nq = q_ref.shape[1] // rows
    pr = page * rows                    # pool rows a page
    tokens = group * page
    per_word = 4 // k_buf.dtype.itemsize   # pool rows a 32-bit word
    lay = lay_ref[0]
    live = functools.partial(_live_pages, len_ref, t=nq, page=page)
    live_b = live(b)

    def copy_group(row, g, slot, n_live, wait: bool):
        n = jnp.minimum(n_live - g * group, group)

        def one(i, _):
            blk = tbl_ref[row, g * group + i]
            at = pl.ds(pl.multiple_of(i * pr, pr), pr)
            for p, (pool, buf) in enumerate(planes):
                dma = pltpu.make_async_copy(
                    pool.at[lay, blk], buf.at[slot, at], sems.at[p, slot])
                if wait:
                    dma.wait()
                else:
                    dma.start()
            return _

        jax.lax.fori_loop(0, n, one, 0)

    @pl.when(b == 0)
    def _first():
        turn_ref[0] = 0
        k_buf[...] = jnp.zeros(k_buf.shape, k_buf.dtype)
        v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)
        copy_group(0, 0, 0, live_b, wait=False)

    n_groups = pl.cdiv(live_b, group)
    turn = turn_ref[0]
    q = q_ref[0]                                         # (rows * nq, lanes)
    sub = jax.lax.broadcasted_iota(jnp.int32, (nq, 1), 0)
    lens = jnp.zeros((nq, 1), jnp.int32)
    for j in range(nq):
        lens = jnp.where(sub == j, len_ref[b, j], lens)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, tokens), 1)

    def pool_row(buf, slot, r):
        """Pool row r of the group's tokens, (tokens, lanes). Mosaic
        reads with a stride only 32-bit data (of a bfloat16 buffer,
        ``buf[slot, pl.ds(r, tokens, stride=rows)]``: "not implemented:
        Strided load with non 32-bit data"), so a 16-bit buffer is read
        through its 32-bit view, a word holding pool rows 2i (its low
        half) and 2i + 1, and the half wanted is shifted or masked into a
        float32 that is the 16-bit value exactly."""
        if rows == 1:
            return buf[slot]
        if per_word == 1:
            return buf[slot, pl.ds(r, tokens, stride=rows)]
        words = buf.bitcast(jnp.uint32)[
            slot, pl.ds(r // 2, tokens, stride=rows // 2)]
        bits = (words << 16) if r % 2 == 0 else (
            words & jnp.uint32(0xFFFF0000))
        return pltpu.bitcast(bits, jnp.float32).astype(buf.dtype)

    def attend_group(g, carry):
        slot = (turn + g) % 2
        last = g + 1 == n_groups

        @pl.when(jnp.logical_not(last))
        def _prefetch():
            copy_group(b, g + 1, 1 - slot, live_b, wait=False)

        @pl.when(last & (b + 1 < nb))
        def _prefetch_next_slot():
            copy_group(b + 1, 0, 1 - slot, live(b + 1), wait=False)

        copy_group(b, g, slot, live_b, wait=True)
        out = []
        for r, (m_prev, d_prev, acc) in enumerate(carry):
            s = jax.lax.dot_general(
                q[r * nq:(r + 1) * nq], pool_row(k_buf, slot, r),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # (nq, tokens)
            s = jnp.where(g * tokens + lane < lens, s, _NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # a row with nothing to read yet (an idle slot, a padded
            # query) weighs every token by exp(0): finite, and nobody
            # reads it
            p = jnp.exp(s - m_new)
            out.append((m_new,
                        d_prev * alpha + jnp.sum(p, axis=1, keepdims=True),
                        acc * alpha + jnp.dot(
                            p.astype(v_buf.dtype), pool_row(v_buf, slot, r),
                            preferred_element_type=jnp.float32)))
        return tuple(out)

    init = tuple((jnp.full((nq, 1), _NEG_INF, jnp.float32),
                  jnp.zeros((nq, 1), jnp.float32),
                  jnp.zeros((nq, q.shape[1]), jnp.float32))
                 for _ in range(rows))
    state = jax.lax.fori_loop(0, n_groups, attend_group, init)
    turn_ref[0] = (turn + n_groups) % 2
    for r, (m, d, acc) in enumerate(state):
        at = pl.ds(r * nq, nq)
        o_ref[0, at] = (acc / d).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[0, at] = jnp.broadcast_to(
                m + jnp.log(d), (nq, lse_ref.shape[2]))


def _grouped_lse_kernel(lay_ref, tbl_ref, len_ref, q_ref, k_hbm, v_hbm,
                        o_ref, lse_ref, k_buf, v_buf, sems, turn_ref, **kw):
    """``_grouped_kernel`` with its second output."""
    _grouped_kernel(lay_ref, tbl_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
                    k_buf, v_buf, sems, turn_ref, lse_ref=lse_ref, **kw)


def _window_block(i, j, lay_ref, tbl_ref, len_ref, *, t: int, page: int):
    """(layer, block) of grid step (slot i, window page j) of the window
    walk: the table's entry for a live page, the slot's last live page
    again past it (an unchanged block index is not copied a second time)."""
    live = _live_pages(len_ref, i, t, page)
    return lay_ref[0], tbl_ref[i, jnp.minimum(j, live - 1)]


def _paged_window_kernel(lay_ref, tbl_ref, len_ref, q_ref, k_ref, v_ref,
                         *refs, scale: float, page: int, quant: bool):
    """The same walk where the kernel's own copies cannot make it: one slot
    x one window page a grid step, the page brought by a BlockSpec whose
    index map reads the table (``_window_block``).

    Mosaic cuts no copy out of a plane of a head count that is no whole
    tile (``_copies_cut``), nor out of the int8 scale pools
    ``[L, nb, page, H]``, whose rows are narrower than 128 lanes; a
    BlockSpec's window takes both. So int8 pools and such head counts walk
    a static grid: a step past the slot's live pages copies nothing (its
    index map names the last live block again) and computes nothing. The
    softmax state crosses grid steps in VMEM scratch; the arithmetic is
    ``_attend_page``'s."""
    if quant:
        ks_ref, vs_ref, *refs = refs
    o_ref, m_ref, d_ref, acc_ref = refs
    b, j = pl.program_id(0), pl.program_id(1)
    t = q_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        _init_accumulators(m_ref, d_ref, acc_ref)

    @pl.when(j < _live_pages(len_ref, b, t, page))
    def _attend():
        state = _attend_page(
            q_ref[0].astype(jnp.float32) * scale,
            [len_ref[b, i] for i in range(t)], j * page,
            k_ref[0, 0].astype(jnp.float32), v_ref[0, 0].astype(jnp.float32),
            _sublane_column(ks_ref[0, 0]) if quant else None,
            _sublane_column(vs_ref[0, 0]) if quant else None,
            tuple((m_ref[i], d_ref[i], acc_ref[i]) for i in range(t)))
        for i, (m, d, acc) in enumerate(state):
            m_ref[i], d_ref[i], acc_ref[i] = m, d, acc

    @pl.when(j == pl.num_programs(1) - 1)
    def _emit():
        o_ref[0] = (acc_ref[...] / d_ref[...]).astype(o_ref.dtype)


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
                kv_len, lay, interpret: bool, scale: Optional[float] = None):
    """Single-chip pallas_call over (possibly head-LOCAL) pool planes;
    ``scale`` is the softmax scale (None: 1 / sqrt(Dh))."""
    b, t, h, dh = q.shape
    page = k_pool.shape[2]
    quant = k_scale_pool is not None
    wp = table.shape[1]
    # The pools are the kernel's operands in the layout they are stored in
    # and nothing of a pool's size may run ahead of it: a merge of H and Dh
    # out here is a copy of the whole pool. This scope holds what
    # preparation is left, the lengths clamped to the window's end (the
    # bound of the kernel's walk; the mask is the same with or without),
    # and ``pool_relayout_ms_per_step`` is the guard.
    with jax.named_scope("pool_relayout"):
        lens = jnp.minimum(kv_len.astype(jnp.int32), wp * page)  # [B, T]
    q_spec = pl.BlockSpec((1, t, h, dh), lambda i, *_: (i, 0, 0, 0))
    if quant or not _copies_cut(h, k_pool.dtype.itemsize):
        block = functools.partial(_window_block, t=t, page=page)
        kv_spec = pl.BlockSpec(
            (1, 1, page, h, dh), lambda *a: (*block(*a), 0, 0, 0))
        scale_spec = pl.BlockSpec(
            (1, 1, page, h), lambda *a: (*block(*a), 0, 0))
        operands, in_specs = [q, k_pool, v_pool], [q_spec, kv_spec, kv_spec]
        if quant:  # the scale pools walk the same table
            operands += [k_scale_pool, v_scale_pool]
            in_specs += [scale_spec, scale_spec]
        kernel = functools.partial(_paged_window_kernel, quant=quant)
        grid = (b, wp)
        scratch = [pltpu.VMEM((t, h, 1), jnp.float32),   # maximum
                   pltpu.VMEM((t, h, 1), jnp.float32),   # denominator
                   pltpu.VMEM((t, h, dh), jnp.float32)]  # accumulator
        params = pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"))
    else:
        group = _pages_per_group(page, h, dh, k_pool.dtype.itemsize, wp,
                                 _GROUP_TOKENS)
        hbm = pl.BlockSpec(memory_space=pl.ANY)
        operands, in_specs = [q, k_pool, v_pool], [q_spec, hbm, hbm]
        kernel = functools.partial(_paged_kernel, group=group)
        grid = (b,)
        scratch = [
            pltpu.VMEM((2, group) + k_pool.shape[2:], k_pool.dtype),
            pltpu.VMEM((2, group) + v_pool.shape[2:], v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),  # plane x buffer slot
            pltpu.SMEM((1,), jnp.int32),  # which buffer slot is next
        ]
        # a slot hands its buffer turn and its prefetch to the next; beside
        # the groups VMEM holds the query and output blocks and a page's
        # float32 temporaries
        params = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_GROUP_VMEM_BYTES + (24 << 20))
    return pl.pallas_call(
        functools.partial(
            kernel, scale=1.0 / math.sqrt(dh) if scale is None else scale,
            page=page),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # layer index, table, lengths
            grid=grid, in_specs=in_specs, out_specs=q_spec,
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=params,
        interpret=interpret,
        name="paged_attn",  # the kernel's name, and its scope in a trace
    )(lay, table, lens, *operands)


def _grouped_call(packed, k_pool, v_pool, table, kv_len, lay,
                  interpret: bool, scale: float, lse: bool = False):
    """The walk for grouped queries (``_grouped_kernel``). packed [B, nq,
    rows, lanes] (``_pack_queries``), kv_len [B, nq]; the pools go in
    viewed [L, n_blocks, page * rows, lanes], a token's rows under one
    another, which is the bytes as stored (no plane is laid out anew:
    tests/test_tpu_compile.py holds it), so a group of pages is one
    matrix of whole tiles. ``lse``: also return [B, nq, rows] float32,
    each query row's log-sum-exp over what it read (about -1e30 where it
    read nothing)."""
    b, nq, rows, lanes = packed.shape
    n_layers, n_blocks, page = k_pool.shape[:3]
    wp = table.shape[1]
    if rows > 1 and (k_pool.dtype.itemsize not in (2, 4)
                     or rows % (4 // k_pool.dtype.itemsize)):
        raise ValueError(
            f"the grouped walk reads a pool row's tokens with a stride, "
            f"32-bit words of one or two rows only: {rows} rows of "
            f"{k_pool.dtype} a token do not fill whole words")
    pad = -nq % 8  # whole sublane tiles of queries; a padded one reads nothing
    with jax.named_scope("pool_relayout"):
        lens = jnp.pad(jnp.minimum(kv_len.astype(jnp.int32), wp * page),
                       ((0, 0), (0, pad)))
        q = jnp.pad(packed, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q = q.transpose(0, 2, 1, 3).reshape(b, rows * (nq + pad), lanes)
    merged = (n_layers, n_blocks, page * rows, lanes)
    group = _pages_per_group(page, rows, lanes, k_pool.dtype.itemsize, wp,
                             _GROUPED_GROUP_TOKENS)
    q_spec = pl.BlockSpec((1,) + q.shape[1:], lambda i, *_: (i, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out_specs, out_shape = q_spec, jax.ShapeDtypeStruct(q.shape, q.dtype)
    if lse:  # a second output, a row's number along a whole tile of lanes
        wide = q.shape[:2] + (128,)
        out_specs = [q_spec, pl.BlockSpec((1,) + wide[1:],
                                          lambda i, *_: (i, 0, 0))]
        out_shape = [out_shape, jax.ShapeDtypeStruct(wide, jnp.float32)]
    out = pl.pallas_call(
        functools.partial(_grouped_lse_kernel if lse else _grouped_kernel,
                          scale=scale, page=page, group=group, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # layer index, table, lengths
            grid=(b,), in_specs=[q_spec, hbm, hbm], out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((2, group * page * rows, lanes), k_pool.dtype),
                pltpu.VMEM((2, group * page * rows, lanes), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),  # plane x buffer slot
                pltpu.SMEM((1,), jnp.int32),  # which buffer slot is next
            ]),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_GROUP_VMEM_BYTES + (24 << 20)),
        interpret=interpret,
        name="paged_attn",  # the kernel's name, and its scope in a trace
    )(lay, table, lens, q, k_pool.reshape(merged), v_pool.reshape(merged))
    if lse:
        out, sums = out
        sums = sums[..., 0].reshape(b, rows, nq + pad).transpose(
            0, 2, 1)[:, :nq]
    out = out.reshape(b, rows, nq + pad, lanes).transpose(
        0, 2, 1, 3)[:, :nq]
    return (out, sums) if lse else out


def paged_decode_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    table: jax.Array,
    kv_len: jax.Array,
    layer=0,
    mesh=None,
    interpret: bool | None = None,
    scale: Optional[float] = None,
    lse: bool = False,
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
    step, tests/test_tpu_compile.py compiles it with interpret=False.

    ``scale`` is the softmax scale (None: 1 / sqrt(Dh)). Fewer key/value
    heads than query heads, stored several a row of whole lanes
    (``transformer.kv_plane_shape``), walk the same table as more queries
    a slot (``_pack_queries``) with both products on the MXU
    (``_grouped_kernel``); one chip only. For grouped queries alone:
    ``lse`` also returns each query head's log-sum-exp over what it read
    ([B, T, H] float32, about -1e30 where that was nothing), with which a
    caller joins this result to keys that are not in the pool (a pass of a
    block's rows that see each other: vtpu/models/blockdiff.py)."""
    t = q.shape[1]
    kv_len = _norm_kv_len(kv_len, t)
    _check_pool(q, k_pool, table)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    lay = _layer_arr(layer)
    if q.shape[2:] != k_pool.shape[3:]:
        if mesh is not None:
            raise ValueError("grouped queries have no head-sharded walk")
        if scale is None:
            scale = 1.0 / math.sqrt(q.shape[3])
        packed, unpack = _pack_queries(q, k_pool.shape[3], k_pool.shape[4])
        lens = jnp.repeat(kv_len, packed.shape[1] // t, axis=1)
        out = _grouped_call(packed, k_pool, v_pool, table, lens, lay,
                            interpret, scale, lse=lse)
        if not lse:
            return unpack(out)
        out, sums = out
        b, rows = q.shape[0], k_pool.shape[3]
        # query (t, p, g) of pool row r is head (r * pack + p) * G + g
        sums = sums.reshape(b, t, -1, rows).transpose(0, 1, 3, 2)
        return unpack(out), sums.reshape(b, t, q.shape[2])
    if lse:
        raise ValueError("only the grouped walk returns its log-sum-exp")
    if mesh is None:
        return _paged_call(q, k_pool, v_pool, None, None, table, kv_len,
                           lay, interpret, scale)
    if scale is not None:
        raise ValueError("a softmax scale has no head-sharded walk")
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


def _pack_queries(q: jax.Array, rows: int, lanes: int):
    """Grouped queries as the kernel's pool rows read them. The pool stores
    ``pack = lanes // Dh`` key/value heads a row (head ``r * pack + p`` in
    lanes ``p * Dh ..`` of row ``r``); query head ``h`` reads key/value head
    ``h // G``. A query of the kernel is a [rows, lanes] block whose row
    ``r`` is multiplied lane by lane with a cached token's row ``r``, so a
    slot's T x Hk x G query heads go in as T * pack * G queries: query
    (t, p, g) holds head ``(r * pack + p) * G + g`` in lanes ``p * Dh ..``
    of row ``r`` and zeros in the other lanes, whose products add nothing
    to its scores. Its output holds that head's mix in the same lanes (the
    others mix a neighbour's values under this head's weights and are
    dropped). Returns (packed [B, T * pack * G, rows, lanes], unpack)."""
    b, t, hq, dh = q.shape
    pack = lanes // dh
    g = hq // (rows * pack)
    if pack * dh != lanes or g * rows * pack != hq:
        raise ValueError(
            f"{hq} query heads of {dh} do not group over pool rows "
            f"[{rows}, {lanes}]")
    eye = jnp.eye(pack, dtype=q.dtype)
    packed = jnp.einsum(
        "btrpgd,pq->btpgrqd", q.reshape(b, t, rows, pack, g, dh), eye
    ).reshape(b, t * pack * g, rows, lanes)

    def unpack(out):
        out = out.reshape(b, t, pack, g, rows, pack, dh)
        return jnp.einsum("btpgrqd,pq->btrpgd", out, eye).reshape(
            b, t, hq, dh)

    return packed, unpack


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
# The walk over a latent plane (DeepSeek-V2's decode step: every cached
# latent attended, no selection). A cached token is ONE row of the plane
# ``[L, n_blocks, page, stored]``, shared by all heads: key (its first
# ``width`` columns, the normed latent and the rotated part) and value (its
# first ``rank`` columns) at once. The walk is ``_grouped_kernel``'s: one
# grid step a slot, the slot's live pages copied out of the plane in HBM a
# group at a time, double-buffered; a slot's H absorbed queries are one
# (H, stored) tile, so a group's rows are read once for both products, both
# on the MXU. Nothing of a window's or the plane's size is made around it.

_LATENT_GROUP_TOKENS = 1024


def _latent_kernel(lay_ref, tbl_ref, len_ref, q_ref, pool_hbm, o_ref,
                   buf, sems, turn_ref, m_ref, d_ref, acc_ref, *,
                   scale: float, page: int, group: int, rank: int):
    """One slot a grid step: its H queries (H, stored) against its live
    pages' rows, ``group`` pages a wait into one of two VMEM buffers
    (group * page, stored), the next group (or the next slot's first) in
    flight meanwhile. Scores (H, group * page) in float32 under the running
    maximum and sum; the probabilities against the rows' first ``rank``
    columns. The buffers are zeroed once: a group's unfilled pages are
    masked, and what is masked must still be finite. A slot with nothing
    to read (length 0: idle, or still admitting) visits one page and
    gives a finite row that nobody reads."""
    b, nb = pl.program_id(0), pl.num_programs(0)
    width = group * page
    lay = lay_ref[0]
    live = functools.partial(_live_pages, len_ref, t=1, page=page)

    def copy_group(row, g, slot, wait: bool):
        n = jnp.minimum(live(row) - g * group, group)

        def one(i, _):
            blk = tbl_ref[row, g * group + i]
            at = pl.ds(pl.multiple_of(i * page, page), page)
            dma = pltpu.make_async_copy(
                pool_hbm.at[lay, blk], buf.at[slot, at], sems.at[slot])
            if wait:
                dma.wait()
            else:
                dma.start()
            return _

        jax.lax.fori_loop(0, n, one, 0)

    @pl.when(b == 0)
    def _first():
        turn_ref[0] = 0
        buf[...] = jnp.zeros(buf.shape, buf.dtype)
        copy_group(0, 0, 0, wait=False)

    m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, m_ref.dtype)
    d_ref[...] = jnp.zeros(d_ref.shape, d_ref.dtype)
    acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)
    n_groups = pl.cdiv(live(b), group)
    turn = turn_ref[0]
    q = q_ref[0]                                          # (H, stored)
    length = len_ref[b, 0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)

    def attend_group(g, _):
        slot = (turn + g) % 2
        last = g + 1 == n_groups

        @pl.when(jnp.logical_not(last) | (b + 1 < nb))
        def _prefetch():
            copy_group(jnp.where(last, jnp.minimum(b + 1, nb - 1), b),
                       jnp.where(last, 0, g + 1), 1 - slot, wait=False)

        copy_group(b, g, slot, wait=True)
        s = jax.lax.dot_general(
            q, buf[slot], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # (H, width)
        s = jnp.where(g * width + lane < length, s, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        d_ref[...] = d_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(buf.dtype), buf[slot, :, :rank],
            preferred_element_type=jnp.float32)
        return _

    jax.lax.fori_loop(0, n_groups, attend_group, 0)
    turn_ref[0] = (turn + n_groups) % 2
    o_ref[0] = (acc_ref[...] / d_ref[...]).astype(o_ref.dtype)


def latent_decode_attention(q: jax.Array, pool: jax.Array, table: jax.Array,
                            lens: jax.Array, layer, rank: int, scale: float,
                            interpret: bool | None = None) -> jax.Array:
    """A decode step's attention over a latent plane, walked in place.

    q ``[B, H, stored]``: a slot's absorbed queries, a head's query through
    its key up-projection beside its rotated part, zeros in the columns the
    plane's rows are padded with. pool: the WHOLE plane ``[L, n_blocks,
    page, stored]``, never a layer's slice. table ``[B, Wp]`` and ``layer``
    as ``paged_decode_attention`` takes them; lens ``[B]``: the rows a slot
    reads (its first ``lens`` positions; 0 reads nothing). Returns the
    probabilities' mix of the rows' first ``rank`` columns, ``[B, H,
    rank]``, for the caller's value up-projection. The kernel is
    ``latent_walk`` in a trace, under the caller's scope."""
    b, h, stored = q.shape
    n_layers, n_blocks, page, _ = pool.shape
    if pool.shape[3] != stored or table.shape[0] != b:
        raise ValueError(
            f"queries {q.shape} and table {table.shape} do not fit the "
            f"plane {pool.shape}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    wp = table.shape[1]
    lens = jnp.minimum(lens.astype(jnp.int32), wp * page)[:, None]
    group = max(1, min(_LATENT_GROUP_TOKENS // page, wp))
    q_spec = pl.BlockSpec((1, h, stored), lambda i, *_: (i, 0, 0))
    return pl.pallas_call(
        functools.partial(_latent_kernel, scale=scale, page=page,
                          group=group, rank=rank),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # layer index, table, lengths
            grid=(b,),
            in_specs=[q_spec, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, h, rank), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, group * page, stored), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),  # a buffer slot each
                pltpu.SMEM((1,), jnp.int32),    # which buffer slot is next
                pltpu.VMEM((h, 1), jnp.float32),     # maximum
                pltpu.VMEM((h, 1), jnp.float32),     # denominator
                pltpu.VMEM((h, rank), jnp.float32),  # accumulator
            ]),
        out_shape=jax.ShapeDtypeStruct((b, h, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_GROUP_VMEM_BYTES + (24 << 20)),
        interpret=interpret,
        name="latent_walk",
    )(_layer_arr(layer), table, lens, q, pool)


# --------------------------------------------------------------------------
# The walk over keys wider than values (a decoder whose heads are 192 wide
# for keys and 128 for values, few key/value heads under many query heads).
# A cached token is one row of each of two planes, ``k [L, n_blocks, page,
# Hk * Dk]`` and ``v [L, n_blocks, page, Hk * Dv]``: its key/value heads side
# by side, whole rows of 128 lanes whatever a head's width, so a page is
# copied as it is stored. The walk is ``_latent_kernel``'s with the values in
# a plane of their own: a slot's Hq queries come spread over the key row
# (``vtpu.ops.window_attn.spread_queries``: a query head's Dk columns at its
# key/value head's place, zeros elsewhere), one product against a group's key
# rows scores every query head against its own key head, and the
# probabilities against the value rows give every head's mix in its own
# key/value head's columns, which the caller keeps.


def _wide_kernel(lay_ref, tbl_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
                 k_buf, v_buf, sems, turn_ref, m_ref, d_ref, acc_ref, *,
                 scale: float, page: int, group: int):
    """One slot a grid step: its Hq spread queries (Hq, Hk * Dk) against
    its live pages' key rows, ``group`` pages a wait into one of two VMEM
    buffers a plane, the next group (or the next slot's first) in flight
    meanwhile; scores (Hq, group * page) in float32 under the running
    maximum and sum, the probabilities against the value rows. The buffers
    are zeroed once (what is masked must still be finite); a slot with
    nothing to read visits one page and gives a finite row nobody reads."""
    planes = ((k_hbm, k_buf), (v_hbm, v_buf))
    b, nb = pl.program_id(0), pl.num_programs(0)
    width = group * page
    lay = lay_ref[0]
    live = functools.partial(_live_pages, len_ref, t=1, page=page)

    def copy_group(row, g, slot, wait: bool):
        n = jnp.minimum(live(row) - g * group, group)

        def one(i, _):
            blk = tbl_ref[row, g * group + i]
            at = pl.ds(pl.multiple_of(i * page, page), page)
            for p, (pool, buf) in enumerate(planes):
                dma = pltpu.make_async_copy(
                    pool.at[lay, blk], buf.at[slot, at], sems.at[p, slot])
                if wait:
                    dma.wait()
                else:
                    dma.start()
            return _

        jax.lax.fori_loop(0, n, one, 0)

    @pl.when(b == 0)
    def _first():
        turn_ref[0] = 0
        k_buf[...] = jnp.zeros(k_buf.shape, k_buf.dtype)
        v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)
        copy_group(0, 0, 0, wait=False)

    _init_accumulators(m_ref, d_ref, acc_ref)
    n_groups = pl.cdiv(live(b), group)
    turn = turn_ref[0]
    q = q_ref[0]                                          # (Hq, Hk * Dk)
    length = len_ref[b, 0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)

    def attend_group(g, _):
        slot = (turn + g) % 2
        last = g + 1 == n_groups

        @pl.when(jnp.logical_not(last) | (b + 1 < nb))
        def _prefetch():
            copy_group(jnp.where(last, jnp.minimum(b + 1, nb - 1), b),
                       jnp.where(last, 0, g + 1), 1 - slot, wait=False)

        copy_group(b, g, slot, wait=True)
        s = jax.lax.dot_general(
            q, k_buf[slot], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # (Hq, width)
        s = jnp.where(g * width + lane < length, s, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        d_ref[...] = d_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v_buf.dtype), v_buf[slot],
            preferred_element_type=jnp.float32)
        return _

    jax.lax.fori_loop(0, n_groups, attend_group, 0)
    turn_ref[0] = (turn + n_groups) % 2
    o_ref[0] = (acc_ref[...] / d_ref[...]).astype(o_ref.dtype)


def wide_decode_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                          table: jax.Array, lens: jax.Array, layer,
                          scale: float,
                          interpret: bool | None = None) -> jax.Array:
    """A decode step's attention over key and value planes of unequal
    width, walked in place.

    q ``[B, Hq, Hk * Dk]``: a slot's query heads spread over the key row.
    k_pool ``[L, n_blocks, page, Hk * Dk]`` and v_pool ``[L, n_blocks,
    page, Hk * Dv]``: the WHOLE planes, never a layer's slice. table
    ``[B, Wp]`` and ``layer`` as ``paged_decode_attention`` takes them;
    lens ``[B]``: the rows a slot reads (its first ``lens`` positions; 0
    reads nothing). Returns ``[B, Hq, Hk * Dv]``: each query head's mix
    under its own weights of every key/value head's values, of which its
    own head's columns are the attention's result. The kernel is
    ``wide_walk`` in a trace, under the caller's scope."""
    b, hq, ck = q.shape
    page, cv = k_pool.shape[2], v_pool.shape[3]
    if (k_pool.shape[3] != ck or v_pool.shape[:3] != k_pool.shape[:3]
            or table.shape[0] != b):
        raise ValueError(
            f"queries {q.shape} and table {table.shape} do not fit the "
            f"planes {k_pool.shape}, {v_pool.shape}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    wp = table.shape[1]
    lens = jnp.minimum(lens.astype(jnp.int32), wp * page)[:, None]
    group = max(1, min(_LATENT_GROUP_TOKENS // page, wp))
    q_spec = pl.BlockSpec((1, hq, ck), lambda i, *_: (i, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_wide_kernel, scale=scale, page=page, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # layer index, table, lengths
            grid=(b,),
            in_specs=[q_spec, hbm, hbm],
            out_specs=pl.BlockSpec((1, hq, cv), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, group * page, ck), k_pool.dtype),
                pltpu.VMEM((2, group * page, cv), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),  # plane x buffer slot
                pltpu.SMEM((1,), jnp.int32),      # which buffer slot is next
                pltpu.VMEM((hq, 1), jnp.float32),   # maximum
                pltpu.VMEM((hq, 1), jnp.float32),   # denominator
                pltpu.VMEM((hq, cv), jnp.float32),  # accumulator
            ]),
        out_shape=jax.ShapeDtypeStruct((b, hq, cv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_GROUP_VMEM_BYTES + (24 << 20)),
        interpret=interpret,
        name="wide_walk",
    )(_layer_arr(layer), table, lens, q, k_pool, v_pool)


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
