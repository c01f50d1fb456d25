"""A prefill chunk's attention over the window it has gathered, in one visit
of the scores (a Pallas kernel), for the families that cache heads.

What ``vtpu.ops.attention.causal_attention`` computes under the ragged
``kv_len [N, T]`` (and ``window_attn.full_attention``, its twin for heads
wider for keys than for values): the T queries of a chunk, each head's
scores against its key/value head's window from bfloat16 operands
accumulated in float32, under the scale and the mask, softmax in float32,
the exponentials in the values' dtype against the values, the quotient in
float32 on the values' side. XLA writes the float32 scores ``[heads, T x G,
window]`` to the chip's memory, reads them back for the softmax and again
for the value product, and multiplies the whole read window although no
query of the chunk sees past the chunk's end. Here a grid step holds one
sequence's tile of query rows against one block of ``_KEYS`` window
positions, every key/value head in turn: a head's scores ``[rows, block]``
live in VMEM from the score product to the value product, and the row
maximum, the row sum and the accumulator run on (float32) from a block to
the next. A key/value head's G query heads are folded into its rows
(``[T x G, Dk]``, as ``attention._fold_query_groups`` folds them), tiled by
``_ROWS``.

**The mask is ``reach [N, T]``** (and, for a family that selects blocks
of its cache, ``keep [N, T, Hk, Nb]`` beside it: ``chunk_attention``): how
many window rows a query reads, from row 0. ``len + i + 1`` for a causal chunk, the end of the query's own
block under ``cfg.attn_block``, ``position + 1`` for
``window_attn.full_attention``.

**It stops at the chunk's own end.** ``ends [N]`` (scalar prefetch) is a
sequence's largest ``reach``. A key block that starts at or past it computes
nothing, and the index maps hand it the last live block again, which the
pipeline does not copy a second time: a window read for its bucket costs
what the positions up to the chunk's end cost, rounded up to a block. A
block that every query of the sequence sees whole (it ends at or before
``lows [N]``, the smallest ``reach``) is not masked at all.

**The window goes in as it lies**; no transposed or re-laid copy is made
ahead of the kernel. Two forms are stored in this repo:

- *heads under one another*, ``[N, W, R, L]``: a token's R rows of L lanes,
  a row one head (``L == Dk``) or several narrower than 128 lanes side by
  side (``transformer.kv_plane_shape``: the hybrid's two 64-wide heads a
  row). Read as ``[N, W x R, L]``, the same bytes, a head's row of a block
  is every R-th row from its own: ``_row_of_block`` reads it with a stride,
  16-bit data through its 32-bit view (two rows a word, as PR 44's grouped
  walk does), and a head narrower than its row is that row's lanes.
- *heads side by side*, ``[N, W, Hk x D]`` (``models/swa.py``'s rows, keys
  192 wide beside values 128 wide): a head is a block's lanes.

``takes`` is the rule that says which shapes come here on a TPU;
``causal_attention`` is the same attention as XLA code, the route off the
chip and this kernel's reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# window positions a grid step and query rows a grid step: a head's scores
# are [_ROWS, _KEYS] float32 (2 MB of VMEM), the rescaling of the
# accumulator and a step's overhead paid once a block of a head. PERF.md
# section 6 (PR 45) has the sweep on a v5e
_KEYS = 1024
_ROWS = 512
_VMEM_BYTES = 100 << 20
_LOW = -1e30  # a running maximum's start: finite, so no row is ever nan
_NT = (((1,), (1,)), ((), ()))  # a [M, K] . b [N, K] -> [M, N]
# query rows a key/value head must bring, in whole tiles of as many: below
# it the products are matrix-vector ones (a decode step on the gather
# route, a verify chunk of a few rows), which XLA's code does as well
MIN_ROWS = 128
# window positions from which the kernel reads under XLA's code: a shorter
# window's float32 scores stay in the chip's fast memory and XLA's form is
# as fast or faster (the dense family's 1024 window, traced in its cell: 1.12
# ms a chunk of 15 layers against the kernel's 1.46; at 4096 29.9 against
# 3.0; PERF.md section 6, PR 45)
MIN_WINDOW = 2048
# key/value heads side by side are walked by an unrolled loop
_MAX_FLAT_HEADS = 8


def key_block(w: int, keys: int | None = None) -> int:
    """Window positions a grid step of the kernel takes of a window of
    ``w``: ``keys`` (``_KEYS`` unless given) halved while it does not
    divide ``w`` and is over 128, else the whole window."""
    bk = min(keys or _KEYS, w)
    while bk > 128 and w % bk:
        bk //= 2
    return bk if w % bk == 0 else w


def row_tile(rows: int, tile: int | None = None) -> int:
    """Query rows a grid step takes of a head's ``rows``: the largest
    multiple of ``MIN_ROWS`` up to ``tile`` (``_ROWS`` unless given) that
    divides them (``takes`` lets only whole multiples of it in)."""
    rt = min(tile or _ROWS, rows)
    while rows % rt:
        rt -= MIN_ROWS
    return rt


def keys_attended(end: int, w: int) -> int:
    """Window positions the kernel multiplies for a chunk whose largest
    ``reach`` is ``end`` in a window of ``w``: ``end`` rounded up to a
    block."""
    bk = key_block(w)
    return min(w, -(-end // bk) * bk)


def _heads(q_shape, keys_shape, values_shape):
    """(Hk, G, Dk, Dv, R): key/value heads, query heads to each, a head's
    widths, and the rows a token is stored in (0: heads side by side), of
    q ``[N, T, Hq, Dk]`` against a window in either stored form; None where
    the shapes are neither."""
    hq, dk = q_shape[2:]
    if len(keys_shape) == 4:
        rows, lanes = keys_shape[2:]
        if lanes % dk or values_shape[2] != rows:
            return None
        per_row = lanes // dk
        hk, dv = rows * per_row, values_shape[3] // per_row
        if dv * per_row != values_shape[3]:
            return None
    else:
        rows, hk = 0, keys_shape[2] // dk
        if not hk or hk * dk != keys_shape[2] or values_shape[2] % hk:
            return None
        dv = values_shape[2] // hk
    if hq % hk:
        return None
    return hk, hq // hk, dk, dv, rows


def _vmem_bytes(hk, rt, bk, dk, dv, itemsize) -> int:
    """What a grid step keeps in VMEM: the window's two blocks and the
    queries' and outputs' twice each (the pipeline's two buffers), the
    running softmax of every head, and a head's scores with their
    exponentials."""
    window = 2 * bk * hk * (dk + dv) * itemsize
    rows_io = 2 * hk * rt * (dk + dv) * itemsize + 2 * rt * 512
    softmax = hk * rt * (2 * 512 + 4 * max(dv, 128))
    scores = rt * bk * (4 + 4 + itemsize)
    return window + rows_io + softmax + scores


def takes(q, keys, values, reach, mesh=None) -> bool:
    """Whether ``causal_attention(q, keys, values, kv_len=reach)`` (or
    ``full_attention``) over these shapes runs in ``chunk_attention``: read
    off what the traced program can observe, set by nobody. ``q``,
    ``keys``, ``values`` are arrays or anything with their ``shape`` and
    ``dtype``; ``reach`` the ragged ``[N, T]`` lengths or None.

    A TPU backend (elsewhere XLA's code is the route); one chip's pool (a
    head-sharded one, ``mesh``, keeps XLA's code until a cell runs one);
    bfloat16 windows (int8 pools have ``causal_attention_int8kv``); the
    ragged ``reach`` (a bucket admission, ``kv_len`` None, is the flash
    kernel's or XLA's); at least ``MIN_ROWS`` query rows a key/value head
    in whole tiles of 128 (a decode step on the gather route, short
    windows' single tokens and a verify chunk of a few rows are
    matrix-vector work, which XLA's code does as well); a window of
    ``MIN_WINDOW`` positions or more (under it XLA's scores stay on the
    chip: a whole-prompt admission's own bucket, the dense family's 1024
    window); widths the kernel
    was compiled for a v5e and timed at (heads of 64, two a stored row, of
    128, and 192 for keys beside 128 for values; PERF.md section 6, PR 45)
    and the VMEM they take."""
    if jax.default_backend() != "tpu" or mesh is not None or reach is None:
        return False
    if len(reach.shape) != 2 or len(q.shape) != 4:
        return False
    if not (q.dtype == keys.dtype == values.dtype == jnp.bfloat16):
        return False
    if keys.shape[1] < MIN_WINDOW:
        return False
    return fits(q.shape, keys.shape, values.shape, 2)


def fits(q_shape, keys_shape, values_shape, itemsize: int) -> bool:
    """``takes``'s part that is shapes alone: what the kernel can tile."""
    if len(keys_shape) != len(values_shape) or len(keys_shape) not in (3, 4):
        return False
    heads = _heads(q_shape, keys_shape, values_shape)
    if heads is None:
        return False
    hk, g, dk, dv, rows = heads
    t, w = q_shape[1], keys_shape[1]
    if rows:
        per_word = 4 // itemsize
        if rows % per_word or keys_shape[3] % 128 or values_shape[3] % 128:
            return False
    elif hk > _MAX_FLAT_HEADS:
        return False
    if dk % 64 or dv % 64 or max(dk, dv) > 256:
        return False
    if (t * g) % MIN_ROWS or w % 8:
        return False
    return _vmem_bytes(hk, row_tile(t * g), key_block(w), dk, dv,
                       itemsize) <= _VMEM_BYTES - (8 << 20)


def takes_mask(keep_shape, w: int) -> bool:
    """Whether the kernel reads a selection's mask of these blocks over a
    window of ``w``: whole mask blocks a key block, at least eight of them
    (a row's flags are a matrix operand)."""
    bk = key_block(w)
    nb = keep_shape[-1]
    return w % nb == 0 and bk % (w // nb) == 0 and bk // (w // nb) >= 8


def attend_window(q, keys, values, reach, scale: float, in_xla, mesh=None,
                  layer=None, window: int | None = None, keep=None):
    """The one place a chunk's attention over its gathered window is
    routed: under the scope ``chunk_attn`` (inside the caller's ``attn`` or
    ``gather_attn``), ``chunk_attention`` where ``takes`` the shapes, else
    ``in_xla()``, the caller's XLA code for the same attention. ``layer``
    and ``window`` as ``chunk_attention`` takes them: the rule reads one
    layer's window of the stack. ``keep``: a selection's mask by blocks
    (``chunk_attention``), for a family whose queries read the blocks they
    keep (vtpu/models/sparselinear.py)."""

    def a_window(planes):
        shape = planes.shape if layer is None else planes.shape[1:]
        if window:
            shape = shape[:1] + (window,) + shape[2:]
        return jax.ShapeDtypeStruct(shape, planes.dtype)

    with jax.named_scope("chunk_attn"):
        if takes(q, a_window(keys), a_window(values), reach, mesh) and (
                keep is None or takes_mask(
                    keep.shape, a_window(keys).shape[1])):
            mask = {} if keep is None else {"keep": keep}
            return chunk_attention(q, keys, values, reach, scale,
                                   layer=layer, window=window, **mask)
        return in_xla()


def chunk_keys_attended(q, keys, values, end: int,
                        mesh=None) -> tuple[bool, int]:
    """(whether the program of a chunk with these queries over this window
    holds the kernel, the window positions it multiplies when the chunk's
    largest ``reach`` is ``end``): the kernel stops at ``end`` rounded up to
    its key block, XLA's code attends the whole window. ``q``, ``keys``,
    ``values`` as ``takes`` reads them (shapes and dtypes do); host
    integers, for the engine's counters."""
    w = keys.shape[1]
    reach = jax.ShapeDtypeStruct(q.shape[:2], jnp.int32)
    kernel = takes(q, keys, values, reach, mesh)
    return kernel, keys_attended(min(end, w), w) if kernel else w


def _row_of_block(ref, r, bk: int, rows: int):
    """Stored row ``r`` (traced) of every token of a block, ``[bk, L]``
    each for the ``4 // itemsize`` rows that share 32-bit words with it,
    ``r`` their first: ``ref [1, 1, bk x rows, L]`` holds a token's rows under
    one another. Mosaic reads with a stride only 32-bit data, so a 16-bit
    block is read through its 32-bit view, a word holding rows 2i (its low
    half) and 2i + 1, and each half is shifted or masked into a float32
    that is the 16-bit value exactly (``decode_attn._grouped_kernel``'s
    ``pool_row``)."""
    dtype = ref.dtype
    if dtype.itemsize == 4:
        return [ref[0, 0, pl.ds(r, bk, stride=rows)]]
    words = ref.bitcast(jnp.uint32)[
        0, 0, pl.ds(r // 2, bk, stride=rows // 2)]
    return [pltpu.bitcast(bits, jnp.float32).astype(dtype)
            for bits in (words << 16, words & jnp.uint32(0xFFFF0000))]


def _kernel(ends_ref, lows_ref, lay_ref, q_ref, reach_ref, k_ref, v_ref,
            *refs, scale, dk, dv, rows, kept=0):
    """One sequence's tile of query rows against one block of the window,
    every key/value head. q_ref [1, Hk, rt, Dk]; reach_ref [1, rt, 1]; k_ref
    and v_ref [1, 1, bk x R, L] (a layer's block, heads under one another)
    or [1, 1, bk, Hk x D] (side by side); o_ref [1, Hk, rt, Dv]; scratch m_ref, l_ref [Hk, rt, 1],
    acc_ref [Hk, rt, Dv], float32. ``lay_ref`` is the index maps'. With
    ``kept`` (window positions a block of a selection's mask), ``refs``
    leads with keep_ref [1, Hk, 1, rt, bk / kept]: which of the key
    block's mask blocks each query row reads (1.0 or 0.0)."""
    del lay_ref
    keep_ref = refs[0] if kept else None
    o_ref, m_ref, l_ref, acc_ref = refs[-4:]
    i, kb = pl.program_id(0), pl.program_id(2)
    hk = q_ref.shape[1]
    bk = k_ref.shape[2] // max(rows, 1)
    dtype = v_ref.dtype

    @pl.when(kb == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _LOW, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def attend(h, k, v, masked: bool):
        s = jax.lax.dot_general(
            q_ref[0, h], k, _NT, preferred_element_type=jnp.float32) * scale
        if masked:
            at = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            s = jnp.where(at < reach_ref[0], s, -jnp.inf)
        if kept:
            # a row's flags a mask block, spread over the block's positions
            # on the MXU: [rt, bk / kept] x [bk / kept, bk]
            per = bk // kept
            spread = (jax.lax.broadcasted_iota(jnp.int32, (per, bk), 1)
                      // kept == jax.lax.broadcasted_iota(
                          jnp.int32, (per, bk), 0)).astype(keep_ref.dtype)
            s = jnp.where(jnp.dot(keep_ref[0, h, 0], spread,
                                  preferred_element_type=jnp.float32) > 0.5,
                          s, -jnp.inf)
        m_old = m_ref[h]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        e = jnp.exp(s - m_new)
        grown = jnp.exp(m_old - m_new)
        m_ref[h] = m_new
        l_ref[h] = grown * l_ref[h] + jnp.sum(e, axis=1, keepdims=True)
        acc_ref[h] = grown * acc_ref[h] + jnp.dot(
            e.astype(dtype), v, preferred_element_type=jnp.float32)

    def block(masked: bool):
        if not rows:
            for h in range(hk):
                attend(h, k_ref[0, 0, :, h * dk:(h + 1) * dk],
                       v_ref[0, 0, :, h * dv:(h + 1) * dv], masked)
            return
        per_row = k_ref.shape[3] // dk
        per_word = 4 // dtype.itemsize

        def word(w, carry):
            r = w * per_word
            for half, (k, v) in enumerate(zip(
                    _row_of_block(k_ref, r, bk, rows),
                    _row_of_block(v_ref, r, bk, rows))):
                for j in range(per_row):
                    attend((r + half) * per_row + j,
                           k[:, j * dk:(j + 1) * dk],
                           v[:, j * dv:(j + 1) * dv], masked)
            return carry

        jax.lax.fori_loop(0, rows // per_word, word, 0)

    first, last = kb * bk, kb * bk + bk

    @pl.when(last <= lows_ref[i])  # every query sees the block whole
    def _():
        block(masked=False)

    @pl.when((first < ends_ref[i]) & (last > lows_ref[i]))
    def _():
        block(masked=True)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _():
        def emit(h, carry):
            o_ref[0, h] = (acc_ref[h] / l_ref[h]).astype(o_ref.dtype)
            return carry

        jax.lax.fori_loop(0, hk, emit, 0)


@functools.partial(jax.jit, static_argnames=(
    "scale", "window", "interpret", "keys_a_step", "rows_a_step"))
def chunk_attention(q: jax.Array, keys: jax.Array, values: jax.Array,
                    reach: jax.Array, scale: float, layer=None,
                    window: int | None = None, interpret: bool = False,
                    keys_a_step: int | None = None,
                    rows_a_step: int | None = None,
                    keep: jax.Array | None = None) -> jax.Array:
    """q ``[N, T, Hq, Dk]``; keys and values the window all T queries of a
    sequence share, as stored: ``[N, W, R, L]`` (a token's R rows of L
    lanes, ``L // Dk`` heads a row) or ``[N, W, Hk x Dk]`` and ``[N, W,
    Hk x Dv]`` (heads side by side); reach ``[N, T]`` int32: query i reads
    window rows ``0 .. reach[i] - 1`` (at least one) -> ``[N, T, Hq, Dv]``
    in q's dtype. Query head h reads key/value head ``h // G``.

    ``layer`` (an int, traced or not): keys and values are a stack of
    layers' windows ``[L, N, W, ...]`` and this is the one to read, picked
    by the blocks' index maps (scalar prefetch), so a layer is not sliced
    out of the stack for the kernel's operand (a copy of a layer's window a
    call: 0.1 ms a plane at the dense family's 4096). ``window``: read the
    first ``window`` of the W positions stored (a slot's row of a dense
    cache under a read bucket), by the grid's extent: no slice either. The
    kernel is ``chunk_attn`` in a trace, under the caller's scope;
    ``keys_a_step`` and ``rows_a_step`` are the piece bench's.

    ``keep`` ``[N, T, Hk, Nb]`` bool (a selection's mask by blocks of ``W /
    Nb`` window positions, a key/value head; ``takes_mask`` says which
    shapes): query i of head group h also reads only the blocks it keeps,
    ``reach`` still the bound inside one. A row must keep a position it
    reaches (a causal query keeps its own block)."""
    if layer is None:
        keys, values, layer = keys[None], values[None], 0
    n, t, hq, _ = q.shape
    stored = keys.shape[2]
    w = window or stored
    hk, g, dk, dv, rows = _heads(q.shape, keys.shape[1:], values.shape[1:])
    bk, rt = key_block(w, keys_a_step), row_tile(t * g, rows_a_step)
    if rows:  # a token's rows under one another: the same bytes
        keys = keys.reshape(keys.shape[:2] + (stored * rows, keys.shape[4]))
        values = values.reshape(
            values.shape[:2] + (stored * rows, values.shape[4]))
    reach = jnp.clip(reach.astype(jnp.int32), 1, w)
    ends, lows = jnp.max(reach, axis=1), jnp.min(reach, axis=1)
    lay = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    # a key/value head's G query heads along its rows, query i's at i * G
    qf = q.reshape(n, t, hk, g, dk).transpose(0, 2, 1, 3, 4).reshape(
        n, hk, t * g, dk)
    reach_rows = jnp.repeat(reach, g, axis=1)[..., None]

    def block(i, r, kb, ends, lows, lay):
        # the layer's block; the last live one again past the chunk's end
        return lay[0], i, jnp.minimum(kb, (ends[i] - 1) // bk), 0

    def tile(i, r, kb, *_):
        return i, 0, r, 0

    def plane(x):
        return pl.BlockSpec((1, 1, bk * max(rows, 1), x.shape[3]), block)

    masks, mask_specs, kept = [], [], 0
    if keep is not None:
        # [N, T, Hk, Nb] -> [N, Hk, key blocks, T x G, mask blocks a key
        # block]: a grid step's flags are one block of whole rows
        kept = w // keep.shape[3]
        per = bk // kept
        flags = jnp.repeat(keep, g, axis=1).reshape(n, t * g, hk, w // bk, per)
        masks = [flags.transpose(0, 2, 3, 1, 4).astype(q.dtype)]
        mask_specs = [pl.BlockSpec(
            (1, hk, 1, rt, per), lambda i, r, kb, ends, *_: (
                i, 0, jnp.minimum(kb, (ends[i] - 1) // bk), r, 0))]
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, dk=dk, dv=dv, rows=rows,
                          kept=kept),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n, t * g // rt, w // bk),
            in_specs=[
                pl.BlockSpec((1, hk, rt, dk), tile),
                pl.BlockSpec((1, rt, 1), lambda i, r, kb, *_: (i, r, 0)),
                plane(keys), plane(values), *mask_specs,
            ],
            out_specs=pl.BlockSpec((1, hk, rt, dv), tile),
            scratch_shapes=[
                pltpu.VMEM((hk, rt, 1), jnp.float32),   # maximum
                pltpu.VMEM((hk, rt, 1), jnp.float32),   # denominator
                pltpu.VMEM((hk, rt, dv), jnp.float32),  # accumulator
            ]),
        out_shape=jax.ShapeDtypeStruct((n, hk, t * g, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret, name="chunk_attn",
    )(ends, lows, lay, qf, reach_rows, keys, values, *masks)
    return out.reshape(n, hk, t, g, dv).transpose(0, 2, 1, 3, 4).reshape(
        n, t, hq, dv)
