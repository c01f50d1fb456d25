"""Latent attention over a learned sparse selection (DeepSeek-V3.2's block):
the indexer's scores over a read window, the selection of the best cached
tokens a query, and attention in the latent space over the selected rows of
a paged pool.

Shapes, throughout: N sequences (decode: the slots; a prefill chunk: 1), T
queries a sequence (decode: 1; a chunk: its tokens), a read window of W
cached positions walked through ``tables [N, W // page]`` of pool block
ids. A pool plane is ``[L, n_blocks, page, R]``: R = latent rank + rotary
width for the latent plane, the indexer's head width for its keys.
Everything is plain XLA; each part runs under the scope that
``vtpu.ops.SCOPES`` names for it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_NEG = float("-inf")


def window_rows(plane: jax.Array, l: int, tables: jax.Array) -> jax.Array:
    """The read window of layer ``l`` of a pool plane, page by page through
    the table: ``[L, n_blocks, page, R]`` and ``[N, Wp]`` -> ``[N, Wp *
    page, R]``. One gather of whole pages from the plane as it is stored:
    no slice of the layer is made first."""
    n, wp = tables.shape
    return plane[l, tables].reshape(n, wp * plane.shape[2], plane.shape[3])


def write_rows(plane: jax.Array, l: int, blocks: jax.Array, offs: jax.Array,
               rows: jax.Array) -> jax.Array:
    """``rows [N, T, R]`` written into layer ``l`` of a pool plane at
    (block, offset) ``[N, T]`` each, in place on a donated plane; a block
    id past the pool drops its row."""
    layers, n_blocks, page, r = plane.shape
    at = jnp.where(blocks < n_blocks, blocks * page + offs, n_blocks * page)
    flat = plane.reshape(layers, n_blocks * page, r)
    return flat.at[l, at].set(rows, mode="drop").reshape(plane.shape)


_SCORE_BYTES = 256 << 20  # what one group of heads' scores may take
_BLOCK_BYTES = 512 << 20  # ... and one block of queries' attention scores


def index_scores(q: jax.Array, w: jax.Array, keys: jax.Array) -> jax.Array:
    """The indexer's score of every window position for every query:
    ``I[n, t, s] = sum_h w[n, t, h] * relu(q[n, t, h] . keys[n, s])``.

    q ``[N, T, Hi, Di]``, w ``[N, T, Hi]`` (float32, the published scales
    folded in), keys ``[N, W, Di]`` -> ``[N, T, W]`` float32. The heads go
    a group at a time and their part is added into the result, so the
    scores of all heads are never held at once ([Hi, T, W] is 8 GB for a
    512-token chunk over a 32 k window): as many heads a group as keep its
    scores under 256 MB (a decode step: all of them, and the keys are read
    once)."""
    n, t, hi, di = q.shape
    g = hi
    while g > 1 and n * t * g * keys.shape[1] * 4 > _SCORE_BYTES:
        g //= 2
    if hi % g:
        raise ValueError(f"the indexer's {hi} heads are no power of two")

    def part(qh, wh):  # [N, T, g, Di], [N, T, g] -> [N, T, W]
        s = jnp.einsum("ntgd,nsd->ntgs", qh, keys,
                       preferred_element_type=jnp.float32)
        return jnp.sum(jax.nn.relu(s) * wh[..., None], axis=2)

    if g == hi:
        return part(q, w)
    qg = jnp.moveaxis(q.reshape(n, t, hi // g, g, di), 2, 0)  # [G, N, T, g, Di]
    wg = jnp.moveaxis(w.reshape(n, t, hi // g, g), 2, 0)      # [G, N, T, g]
    acc, _ = jax.lax.scan(
        lambda acc, xs: (acc + part(*xs), None),
        jnp.zeros((n, t, keys.shape[1]), jnp.float32), (qg, wg))
    return acc


def select_top(scores: jax.Array, positions: jax.Array, k: int
               ) -> tuple[jax.Array, jax.Array]:
    """The ``k`` window positions ``s <= positions[n, t]`` with the largest
    score, all of them while fewer than ``k`` are visible: (indices
    ``[N, T, K]`` int32 into the window, valid ``[N, T, K]`` bool), K =
    min(k, W). An index that is not valid names a position the query may
    not see; the attention masks it."""
    n, t, w = scores.shape
    visible = jnp.arange(w, dtype=jnp.int32) <= positions[..., None]
    # rows flat: a [N, 1, W] operand would be laid out eight rows a query
    vals, idx = jax.lax.top_k(
        jnp.where(visible, scores, _NEG).reshape(n * t, w), min(k, w))
    return (idx.astype(jnp.int32).reshape(n, t, -1),
            (vals > _NEG).reshape(n, t, -1))


def select_mask(scores: jax.Array, positions: jax.Array, k: int) -> jax.Array:
    """The same selection as a mask ``[N, T, W]``: visible, and scoring no
    less than the query's ``k``-th largest visible score, which is found
    exactly, without a sort: float32 keeps its order when its bits are read
    as an integer (negatives flipped), so 32 passes of compare-and-count
    settle that integer a bit at a time. Of the positions that tie with it
    the earliest are kept, as many as make ``k`` (``lax.top_k``'s rule, and
    so the gathering route's and the reference's; the toy sizes' indexers
    of four heads score many positions exactly 0)."""
    w = scores.shape[-1]
    visible = jnp.arange(w, dtype=jnp.int32) <= positions[..., None]
    if w <= k:
        return visible
    bits = jax.lax.bitcast_convert_type(
        jnp.where(visible, scores, _NEG), jnp.uint32)
    key = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))

    def settle(i, kth):
        cand = kth | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(key >= cand[..., None], axis=-1) >= k
        return jnp.where(enough, cand, kth)

    kth = jax.lax.fori_loop(0, 32, settle,
                            jnp.zeros(scores.shape[:-1], jnp.uint32))
    above = key > kth[..., None]
    ties = visible & (key == kth[..., None])
    room = k - jnp.sum(above, axis=-1)
    # the ties before position ``cut`` are kept: the largest cut that keeps
    # no more than there is room for, settled a bit at a time as well (a
    # running count over the window costs a second a layer at 8 k)
    at = jnp.arange(w, dtype=jnp.int32)

    def widen(i, cut):
        cand = cut | (jnp.int32(1) << (w.bit_length() - 1 - i))
        fits = jnp.sum(ties & (at < cand[..., None]), axis=-1) <= room
        return jnp.where(fits, cand, cut)

    cut = jax.lax.fori_loop(0, w.bit_length(), widen,
                            jnp.zeros(scores.shape[:-1], jnp.int32))
    return above | (ties & (at < cut[..., None]))


def selected_rows(tables: jax.Array, idx: jax.Array, page: int) -> jax.Array:
    """Where the selected positions live in the pool: the row of each in
    a plane's ``[L, n_blocks * page, R]`` view (block id from the page
    table ``[N, Wp]``, times the page, plus the offset), ``[N, T, K]``."""
    n, t, k = idx.shape
    blocks = jnp.take_along_axis(tables, (idx // page).reshape(n, t * k), 1)
    return blocks.reshape(n, t, k) * page + idx % page


def _softmax(s: jax.Array) -> jax.Array:
    """softmax over the last axis with its maximum behind an optimization
    barrier: left to fuse the reduction into the subtraction, the chip's
    compiler makes of it a reduce-window twice the window wide, which at
    one window of five (8 k) takes a second a layer (PERF.md section 6,
    PR 28)."""
    top = jax.lax.optimization_barrier(
        jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True)))
    e = jnp.exp(s - top)
    return e / jnp.sum(e, axis=-1, keepdims=True)


def latent_attention(q_abs: jax.Array, q_pe: jax.Array, rows: jax.Array,
                     valid: jax.Array, scale: float) -> jax.Array:
    """Attention in the latent space (the absorbed form): a head's query
    has been taken through that head's key up-projection (``q_abs``
    ``[N, T, H, R]``), so its score against a cached token is its product
    with the token's latent plus ``q_pe [N, T, H, Dr]`` against the token's
    rotated key, both read from one row ``[R + Dr]`` of ``rows
    [N, T, K, R + Dr]``; the output is the probabilities' mix of the
    latents ``[N, T, H, R]``, which the caller takes through the value
    up-projection. Softmax in float32 over the valid rows."""
    r = q_abs.shape[-1]
    q = jnp.concatenate([q_abs, q_pe], axis=-1)
    s = jnp.einsum("nthr,ntkr->nthk", q, rows,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid[:, :, None, :], s, _NEG)
    p = _softmax(s).astype(rows.dtype)
    return jnp.einsum("nthk,ntkr->nthr", p, rows[..., :r],
                      preferred_element_type=jnp.float32).astype(rows.dtype)


def masked_latent_attention(q_abs: jax.Array, q_pe: jax.Array,
                            window: jax.Array, keep: jax.Array,
                            scale: float) -> jax.Array:
    """``latent_attention`` over a whole window ``[N, W, R + Dr]`` that all
    T queries of a sequence share, the selection a mask ``[N, T, W]``."""
    r = q_abs.shape[-1]
    q = jnp.concatenate([q_abs, q_pe], axis=-1)
    s = jnp.einsum("nthr,nsr->nths", q, window,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(keep[:, :, None, :], s, _NEG)
    p = _softmax(s).astype(window.dtype)
    return jnp.einsum("nths,nsr->nthr", p, window[..., :r],
                      preferred_element_type=jnp.float32).astype(window.dtype)


def sparse_latent_attention(ckv: jax.Array, ik: jax.Array, l: int,
                            tables: jax.Array, positions: jax.Array,
                            q_abs: jax.Array, q_pe: jax.Array,
                            q_idx: jax.Array, w_idx: jax.Array, topk: int,
                            scale: float, given=None) -> tuple:
    """Layer ``l``'s selection and attention for ``[N, T]`` queries at
    ``positions`` over the window that ``tables`` maps, both planes whole
    (``[L, n_blocks, page, R]``), read as they are stored.

    **A decode step (T = 1) gathers**: the indexer's scores against the
    window's keys (``ik`` plane), ``lax.top_k``, and attention over the
    selected rows of the ``ckv`` plane alone, read through the page table.
    **A chunk (T > 1) masks**: its queries share one window, so the
    window's latents are read once (whole pages), the selection is a mask
    from the exact threshold (``select_mask``) and attention runs over the
    window under it, a block of queries at a time. Gathering a chunk's rows a query
    (2048 x 1.25 KB each) and sorting a chunk's scores cost more on the
    chip than the masked products at every window up to 32 k: PERF.md,
    section 6, PR 28 has both.

    ``given`` ([N, T, K] window indices; those past a query's position do
    not count) takes the selection's place (tests hold the two sides to
    one selection with it). Returns (mixed latents [N, T, H, R], the
    selection: indices [N, T, K] from the gathering route, the mask
    [N, T, W] from the masking one)."""
    width = q_abs.shape[-1] + q_pe.shape[-1]  # a stored row may be padded
    keys = None
    if given is None:
        with jax.named_scope("indexer"):
            keys = window_rows(ik, l, tables)
    queries = (positions, q_abs, q_pe, q_idx, w_idx)
    if positions.shape[1] == 1:
        return _gathering(ckv, l, tables, keys, queries, given, width, topk,
                          scale)
    with jax.named_scope("latent_attn"):
        window = window_rows(ckv, l, tables)[..., :width]
    return _masking(window, keys, queries, given, topk, scale)


def _gathering(ckv, l, tables, keys, queries, given, width, topk, scale):
    """The decode step's route: top-k, the selected rows, attention."""
    positions, q_abs, q_pe, q_idx, w_idx = queries
    layers, n_blocks, page, _ = ckv.shape
    if given is None:
        with jax.named_scope("indexer"):
            scores = index_scores(q_idx, w_idx, keys)
        with jax.named_scope("select"):
            idx, valid = select_top(scores, positions, topk)
    else:
        idx, valid = given, given <= positions[..., None]
    with jax.named_scope("select"):
        at = selected_rows(tables, idx, page)
    with jax.named_scope("latent_attn"):
        # the selected rows of the latent plane, and no other row
        rows = ckv.reshape(layers, n_blocks * page, -1)[l, at]
        return latent_attention(
            q_abs, q_pe, rows[..., :width], valid, scale), idx


def _masking(window, keys, queries, given, topk, scale):
    """A chunk's route: the threshold's mask and attention over the whole
    window under it, as many queries a block as keep a block's scores
    ``[N, qb, H, W]`` under 512 MB."""
    n, t = queries[0].shape
    w, heads = window.shape[1], queries[1].shape[2]
    qb = t
    while qb > 8 and qb % 2 == 0 and n * qb * heads * w * 4 > _BLOCK_BYTES:
        qb //= 2

    def attend(pos, qa, qp, qi, wi, idx=None):
        if idx is None:
            with jax.named_scope("indexer"):
                scores = index_scores(qi, wi, keys)
            with jax.named_scope("select"):
                keep = select_mask(scores, pos, topk)
        else:
            with jax.named_scope("select"):
                keep = jnp.zeros(pos.shape + (w,), bool).at[
                    jnp.arange(n)[:, None, None],
                    jnp.arange(pos.shape[1])[None, :, None], idx].set(True)
                keep = keep & (jnp.arange(w) <= pos[..., None])
        with jax.named_scope("latent_attn"):
            return masked_latent_attention(qa, qp, window, keep, scale), keep

    args = queries if given is None else queries + (given,)
    if qb == t:
        return attend(*args)

    def blocks_of(x):  # [N, T, ...] -> [T / qb, N, qb, ...]
        return jnp.moveaxis(
            x.reshape(x.shape[0], t // qb, qb, *x.shape[2:]), 1, 0)

    def whole(x):  # and back
        x = jnp.moveaxis(x, 0, 1)
        return x.reshape(x.shape[0], t, *x.shape[3:])

    mixed, keep = jax.lax.map(lambda xs: attend(*xs),
                              tuple(map(blocks_of, args)))
    return whole(mixed), whole(keep)
