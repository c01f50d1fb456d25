"""Latent attention over a learned sparse selection (DeepSeek-V3.2's block):
the indexer's scores over a read window, the selection of the best cached
tokens a query, and attention over the selected: a decode step's in the
latent space (the absorbed form) over rows gathered from a paged pool, a
prefill chunk's over its window under the selection's mask, the window
expanded into a head's keys and values, on a TPU inside a kernel that
keeps a block's scores on the chip and stops at the chunk's last position
(``sparse_latent_attention`` reads which off the shapes and the backend;
``vtpu.ops.latent_chunk``). And the same
attention with no selection (DeepSeek-V2's block: every query attends
every cached latent): a decode step walks its slots' live pages in the
pool (``vtpu.ops.decode_attn.latent_decode_attention``), a chunk attends
its window under the causal mask alone.

Shapes, throughout: N sequences (decode: the slots; a prefill chunk: 1), T
queries a sequence (decode: 1; a chunk: its tokens), a read window of W
cached positions walked through ``tables [N, W // page]`` of pool block
ids. A pool plane is ``[L, n_blocks, page, R]``: R = latent rank + rotary
width for the latent plane, the indexer's head width for its keys.
Everything but that walk and the chunk's kernel is plain XLA; each part
runs under the scope that ``vtpu.ops.SCOPES`` names for it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from vtpu.ops.decode_attn import latent_decode_attention
from vtpu.ops.latent_chunk import chunk_attention, keys_attended

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


def expands_window(t: int, rank: int, dn: int, dv: int) -> bool:
    """Whether ``t`` queries that share one window attend it in the expanded
    form (the window's latents through ``w_uk`` and ``w_uv`` once, then a
    head ``dn + Dr`` and ``dv`` wide) or in the absorbed one (every query
    through ``w_uk``, a head ``rank + Dr`` and ``rank`` wide in the latent
    space, its mix through ``w_uv``).

    Multiply-adds of one window position and head under all ``t`` queries:
    absorbed ``t * ((rank + Dr) + rank)``; expanded ``rank * (dn + dv)`` for
    the position's key and value and ``t * ((dn + Dr) + dv)`` against them.
    The expanded form is the smaller where ``t * (2 * rank - dn - dv) > rank
    * (dn + dv)``: from 171 queries at the published widths (512, 128, 128).
    What a query costs whatever the window's length (its trip through
    ``w_uk`` and ``w_uv`` in the absorbed form) is left out: it would only
    move the line towards fewer queries. Nor is the second time
    ``_expanded`` makes its scores counted (off the chip; the kernel makes
    them once): that product (``t * (dn + Dr)``) stands in for 8 B a score
    of memory traffic, which on a v5e (240 FLOPs a byte) costs five times
    as much. A single query reads its selected rows alone, not a window:
    never expanded."""
    return t > 1 and t * (2 * rank - dn - dv) > rank * (dn + dv)


def attends_in_kernel(t: int, rank: int, dn: int, dv: int) -> bool:
    """Whether ``t`` queries that share one window attend it in
    ``latent_chunk.chunk_attention`` (the expanded form with a block's
    scores kept in VMEM, the key blocks past the queries' last position
    left out) and not in ``_expanded``'s XLA code: the shapes that expand,
    on a TPU. Resolved when a program is traced, from what it can observe,
    as ``models.hybrid.step_in_kernel``; the engine counts its chunks by
    the same call (``stats()["chunk_attn_kernel"]``)."""
    return expands_window(t, rank, dn, dv) and jax.default_backend() == "tpu"


def chunk_keys_attended(t: int, rank: int, dn: int, dv: int, end: int,
                        window: int) -> tuple[bool, int]:
    """(whether the program of ``t`` queries over a read window of
    ``window`` holds the kernel, the window positions it multiplies when
    its last query is at ``end - 1``): the kernel stops at ``end`` rounded
    up to its key block, XLA's code attends the whole window. Host
    integers, for the engine's counters."""
    kernel = attends_in_kernel(t, rank, dn, dv)
    return kernel, keys_attended(end, window) if kernel else window


def sparse_latent_attention(ckv: jax.Array, ik: jax.Array, l: int,
                            tables: jax.Array, positions: jax.Array,
                            q_nope: jax.Array, q_pe: jax.Array,
                            w_uk: jax.Array, w_uv: jax.Array,
                            q_idx: jax.Array, w_idx: jax.Array, topk,
                            scale: float, given=None, lens=None) -> tuple:
    """Layer ``l``'s selection and attention for ``[N, T]`` queries at
    ``positions`` over the window that ``tables`` maps, both planes whole
    (``[L, n_blocks, page, R]``), read as they are stored. A head's query
    comes in its two parts, ``q_nope [N, T, H, dn]`` and the rotated ``q_pe
    [N, T, H, Dr]``, with the layer's up-projections ``w_uk [H, dn, rank]``
    and ``w_uv [H, rank, dv]``: which side of the scores they are applied
    to is this function's choice.

    **A decode step (T = 1) gathers, absorbed**: the indexer's scores
    against the window's keys (``ik`` plane), ``lax.top_k``, the query
    through ``w_uk``, attention in the latent space over the selected rows
    of the ``ckv`` plane alone, read through the page table, and the mix of
    latents through ``w_uv``.
    **A chunk (T > 1) masks**: its queries share one window, so the
    window's latents are read once (whole pages), the selection is a mask
    from the exact threshold (``select_mask``) and attention runs over the
    window under it. Gathering a chunk's rows a query (2048 x 1.25 KB
    each) and sorting a chunk's scores cost more on the chip than the
    masked products at every window up to 32 k: PERF.md, section 6, PR 28
    has both. **In the form its shapes give** (``expands_window``): few
    queries attend absorbed, as the step does; the engine's chunks (512
    queries; the whole-prompt bucket's 256) **expand** the window into a
    head's keys and values and attend a head 192 and 128 wide: 71 % of the
    absorbed form's products (PERF.md, section 6, PR 34). On a TPU
    (``attends_in_kernel``) in one kernel, a block of the window at a
    time, the block's keys, values and scores never leaving the chip, and
    only as far as the chunk's last position (``latent_chunk``; PERF.md,
    section 6, PR 38); elsewhere in XLA's code over the whole window, the
    scores made twice (``_expanded``), which is also the kernel's
    reference.

    **No selection** (``topk`` None: a model without an indexer, whose
    queries attend every cached latent; ``ik``, ``q_idx``, ``w_idx`` None
    with it): a decode step walks each slot's first ``lens [N]`` rows (all
    up to its position unless told: 0 skips a slot) page by page in the
    pool, absorbed, a row read once for both products
    (``latent_decode_attention``: no window is gathered); a chunk attends
    its window in the same two forms under the causal mask alone.

    ``given`` ([N, T, K] window indices; those past a query's position do
    not count) takes the selection's place (tests hold the two sides to
    one selection with it). Returns (a head's values [N, T, H, dv], the
    selection: indices [N, T, K] from the gathering route, the mask
    [N, T, W] from the masking one, None where nothing selects)."""
    t, rank = positions.shape[1], w_uk.shape[-1]
    width = rank + q_pe.shape[-1]  # a stored row may be padded
    expand = expands_window(t, rank, q_nope.shape[-1], w_uv.shape[-1])
    kernel = attends_in_kernel(t, rank, q_nope.shape[-1], w_uv.shape[-1])
    if not expand:
        with jax.named_scope("qkv"):  # where the step's trace has it
            q_abs = jnp.einsum("nthd,hdr->nthr", q_nope, w_uk)
    keys = None
    if given is None and topk is not None:
        with jax.named_scope("indexer"):
            keys = window_rows(ik, l, tables)
    if t == 1 and topk is None:
        with jax.named_scope("latent_attn"):
            if lens is None:
                lens = positions[:, 0] + 1
            mixed, chosen = _walking(ckv, l, tables, lens, q_abs, q_pe,
                                     scale), None
    elif t == 1:
        mixed, chosen = _gathering(
            ckv, l, tables, keys, (positions, q_abs, q_pe, q_idx, w_idx),
            given, width, topk, scale)
    else:
        with jax.named_scope("latent_attn"):
            window = window_rows(ckv, l, tables)[..., :width]
        if topk is not None:
            keep = _selection(keys, positions, q_idx, w_idx, given, topk,
                              window.shape[1])
        elif kernel:
            keep = None
        else:
            with jax.named_scope("latent_attn"):
                keep = (jnp.arange(window.shape[1], dtype=jnp.int32)
                        <= positions[..., None])
        chosen = None if topk is None else keep
        with jax.named_scope("latent_attn"):
            if kernel:
                return chunk_attention(q_nope, q_pe, window, keep, positions,
                                       w_uk, w_uv, scale), chosen
            if expand:
                return _expanded(q_nope, q_pe, window, keep, w_uk, w_uv,
                                 scale), chosen
            mixed = _absorbed(q_abs, q_pe, window, keep, scale)
    with jax.named_scope("o_proj"):
        return jnp.einsum("nthr,hrv->nthv", mixed, w_uv), chosen


def _walking(ckv, l, tables, lens, q_abs, q_pe, scale):
    """The decode step's route where nothing selects: a slot's queries as
    one tile ``[H, stored]`` (absorbed part, rotated part, zeros against a
    row's padding) and the walk of its live pages in the plane."""
    n, _, heads, rank = q_abs.shape
    pad = ckv.shape[-1] - rank - q_pe.shape[-1]
    q = jnp.concatenate(
        [q_abs, q_pe, jnp.zeros((n, 1, heads, pad), q_abs.dtype)], axis=-1)
    return latent_decode_attention(
        q[:, 0], ckv, tables, lens, l, rank, scale)[:, None]


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


def _by_query_blocks(fn, qb: int, *xs):
    """``fn`` over blocks of ``qb`` of the T queries that lead each of
    ``xs [N, T, ...]``, a block after another, its result ``[N, qb, ...]``
    joined to ``[N, T, ...]`` again; called once where one block is all."""
    t = xs[0].shape[1]
    if qb == t:
        return fn(*xs)

    def blocks_of(x):  # [N, T, ...] -> [T / qb, N, qb, ...]
        return jnp.moveaxis(
            x.reshape(x.shape[0], t // qb, qb, *x.shape[2:]), 1, 0)

    def whole(x):  # and back
        x = jnp.moveaxis(x, 0, 1)
        return x.reshape(x.shape[0], t, *x.shape[3:])

    return whole(jax.lax.map(lambda b: fn(*b), tuple(map(blocks_of, xs))))


def _halved(size: int, least: int, fits) -> int:
    """``size`` halved while it is even, over ``least`` and ``fits(size)``
    is false."""
    while size > least and size % 2 == 0 and not fits(size):
        size //= 2
    return size


def _selection(keys, positions, q_idx, w_idx, given, topk, w):
    """A chunk's selection as a mask ``[N, T, W]``: the threshold's over
    the indexer's scores, as many queries a block as keep the scores of
    all its heads ``[N, qb, Hi, W]`` under 256 MB, so that ``index_scores``
    takes a block in one pass; or ``given``'s positions."""
    n, t = positions.shape

    def mask(pos, qi, wi):
        with jax.named_scope("indexer"):
            scores = index_scores(qi, wi, keys)
        with jax.named_scope("select"):
            return select_mask(scores, pos, topk)

    if given is None:
        qb = _halved(t, 8, lambda qb: n * qb * q_idx.shape[2] * w * 4
                     <= _SCORE_BYTES)
        return _by_query_blocks(mask, qb, positions, q_idx, w_idx)
    with jax.named_scope("select"):
        keep = jnp.zeros((n, t, w), bool).at[
            jnp.arange(n)[:, None, None], jnp.arange(t)[None, :, None],
            given].set(True)
        return keep & (jnp.arange(w) <= positions[..., None])


def _absorbed(q_abs, q_pe, window, keep, scale):
    """A chunk of few queries: ``masked_latent_attention`` over as many
    queries a block as keep a block's scores ``[N, qb, H, W]`` under
    512 MB."""
    n, t, heads, _ = q_abs.shape
    w = window.shape[1]
    qb = _halved(t, 8, lambda qb: n * qb * heads * w * 4 <= _BLOCK_BYTES)
    return _by_query_blocks(
        lambda qa, qp, k: masked_latent_attention(qa, qp, window, k, scale),
        qb, q_abs, q_pe, keep)


def _expanded_tiles(n: int, t: int, heads: int, w: int) -> tuple[int, int]:
    """(heads a group, queries a block) of the expanded form: the heads
    halved first, the queries only once a single head's scores ``[N, 1,
    T, W]`` are still over 512 MB as float32."""
    g = _halved(heads, 1, lambda g: n * t * g * w * 4 <= _BLOCK_BYTES)
    return g, _halved(t, 8, lambda qb: n * qb * g * w * 4 <= _BLOCK_BYTES)


def _expanded(q_nope, q_pe, window, keep, w_uk, w_uv, scale):
    """A chunk of many queries, in the expanded form, as XLA code (off the
    chip; on it ``latent_chunk.chunk_attention`` computes the same, and is
    tested against this): a head's keys
    ``window[..., :rank] . w_uk`` and values ``. w_uv`` made once a layer
    in the window's dtype (float32 sums), the rotated key ``window[...,
    rank:]`` shared by the heads as it is stored; scores one product over
    ``dn + Dr`` under ``masked_latent_attention``'s scale and mask, and the
    exponentials against the values: ``[N, T, H, dv]``, with no ``w_uv``
    product left to make.

    **The scores are made twice.** Held in float32 between the products,
    a block's scores cross the chip's memory three times (written, read
    for the row sums, read for the values), which is what bounds a chunk
    in either form (12 B a score against 640 FLOPs: PERF.md, section 6,
    PR 34). At 192 wide the product is cheap enough to repeat: the first
    pass keeps each row's maximum and nothing else; the second, behind a
    barrier that keeps the compiler from making one of the two, has the
    exponential fused onto the product and writes it once in the window's
    dtype beside its float32 row sums. The quotient is taken on the
    values' side (``[N, T, H, dv]``, not ``[N, H, T, W]``): the same
    softmax with the division after the rounding instead of before it.

    Every head has keys of its own here, so a head's product has only its
    own queries for rows: the heads go a group at a time, as many as keep
    a group's scores ``[N, g, T, W]`` under 512 MB as float32 with all T
    queries in one block (8 heads at the 24 k and 32 k windows, 64 at
    4 k; all heads over blocks of 32 queries took 2.8 times as long),
    each group's keys and values made as its turn comes (67 MB each at
    32 k, where all heads' at once would be 2.15 GB); only where one
    head's scores are over that do the queries go in blocks as well."""
    n, t, heads, dn = q_nope.shape
    w, dtype, rank = window.shape[1], window.dtype, w_uk.shape[-1]
    g, qb = _expanded_tiles(n, t, heads, w)
    latents, k_pe = window[..., :rank], window[..., rank:]

    def group(q, uk, uv):  # [N, T, g, dn + Dr], [g, dn, rank], [g, rank, dv]
        k = jnp.einsum("nsr,gdr->nsgd", latents, uk,
                       preferred_element_type=jnp.float32).astype(dtype)
        v = jnp.einsum("nsr,grv->nsgv", latents, uv,
                       preferred_element_type=jnp.float32).astype(dtype)
        k = jnp.concatenate([k, jnp.broadcast_to(
            k_pe[:, :, None], k.shape[:3] + k_pe.shape[-1:])], axis=-1)

        def scores(qs, kp):  # [N, qb, g, dn + Dr], [N, qb, W] -> [N, g, qb, W]
            s = jnp.einsum("ntgd,nsgd->ngts", qs, k,
                           preferred_element_type=jnp.float32) * scale
            return jnp.where(kp[:, None], s, _NEG)

        def attend(qs, kp):
            top = jnp.max(scores(qs, kp), axis=-1, keepdims=True)
            qs, top = jax.lax.optimization_barrier((qs, top))
            e = jnp.exp(scores(qs, kp) - top)
            total = jnp.moveaxis(jnp.sum(e, axis=-1), 1, 2)  # [N, qb, g]
            mixed = jnp.einsum("ngts,nsgv->ntgv", e.astype(dtype), v,
                               preferred_element_type=jnp.float32)
            return (mixed / total[..., None]).astype(dtype)

        return _by_query_blocks(attend, qb, q, keep)

    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    if g == heads:
        return group(q, w_uk, w_uv)
    groups = heads // g
    out = jax.lax.map(lambda xs: group(*xs), (
        jnp.moveaxis(q.reshape(n, t, groups, g, -1), 2, 0),
        w_uk.reshape(groups, g, dn, rank),
        w_uv.reshape(groups, g, rank, -1)))       # [G, N, T, g, dv]
    return jnp.moveaxis(out, 0, 2).reshape(n, t, heads, -1)
