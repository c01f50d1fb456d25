"""Block-sparse attention over a head-cached paged pool: whole blocks of the
cache selected by a score over compressed keys (InfLLM-v2's rule), and
attention over the blocks kept.

A block is a pool page (``block == kv_page``), so what a decode step's
selection yields is a page table a key/value head: the pool's pages are
walked through it by the routes the pool already has (the paged kernel and
the gather route of ``vtpu.ops.decode_attn`` / ``vtpu.ops.attention``),
which see a short session of ``n_sel`` pages. A prefill chunk attends its
gathered window under the selection's mask, a row of blocks a query.

Beside a layer's ``k`` / ``v`` planes lives a third, the **compressed
keys**: the mean of ``kernel = 2 * stride`` consecutive keys from every
``stride``-th position, a key/value head. Window ``j`` (tokens ``stride j
.. stride j + kernel - 1``) is stored in the page of its first token, row
``j % (block // stride)``: the one page table walks all three planes. It is
written when its last token arrives, by the chunk that holds that token or
by the decode step that writes it.

Shapes: N sequences, T queries a sequence, Hk key/value heads of G query
heads each, a window of W positions = Nb blocks = J compressed windows.
``sizes`` is anything with ``kernel_stride``, ``block_size``,
``window_size``, ``init_blocks``, ``topk``, ``dense_len`` (a model's
configuration). Everything here is plain XLA; the scopes are the callers'.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from vtpu.ops.latent import _by_query_blocks, _halved, select_mask

_NEG = float("-inf")
_BLOCK_BYTES = 256 << 20  # what one block of queries' float32 scores may take


def half_sums(keys: jax.Array, stride: int) -> jax.Array:
    """keys ``[..., S, D]`` -> float32 sums of each ``stride`` consecutive
    ones ``[..., S // stride, D]``: a compressed key is two neighbours of
    them over ``2 * stride``."""
    s, d = keys.shape[-2:]
    return keys.astype(jnp.float32).reshape(
        keys.shape[:-2] + (s // stride, stride, d)).sum(-2)


def window_scores(q: jax.Array, comp: jax.Array, positions: jax.Array,
                  stride: int, scale: float) -> jax.Array:
    """(a)-(b): the queries' scores of the compressed windows they see.
    q ``[N, T, Hk, G, D]``, comp ``[Hk, N, J, D]``, positions ``[N, T]``
    (a query's own) -> ``[N, T, Hk, J]`` float32: the softmax over the
    windows that lie whole at or before the query, a head, summed over a
    group's G heads; ``-inf`` at a window the query does not see whole."""
    j = comp.shape[2]
    s = jnp.einsum("nthgd,hnjd->nthgj", q, comp,
                   preferred_element_type=jnp.float32) * scale
    whole = (jnp.arange(j) * stride + 2 * stride - 1) <= positions[..., None]
    whole = whole[:, :, None, None, :]
    p = jax.nn.softmax(jnp.where(whole, s, -1e30), axis=-1)
    return jnp.where(whole[:, :, :, 0], jnp.where(whole, p, 0.0).sum(3), _NEG)


def block_scores(windows: jax.Array, positions: jax.Array, sizes,
                 n_blocks: int) -> jax.Array:
    """(c)-(d): windows ``[N, T, Hk, J]`` -> ``[N, T, Hk, Nb]``: a block's
    score is the maximum over the windows that overlap it (``per = block //
    stride``: windows ``per b - 1 .. per b + per - 1``); the first
    ``init_blocks`` blocks and the ``window_size // block`` that end with
    the query's own are forced (``+inf``); a block past the query's own is
    ``-inf``."""
    block = sizes.block_size
    per = block // sizes.kernel_stride
    j = windows.shape[-1]
    lead = windows.shape[:-1]
    wide = jnp.concatenate(
        [jnp.full(lead + (1,), _NEG), windows,
         jnp.full(lead + (max(n_blocks * per - j, 0),), _NEG)], axis=-1)
    score = wide[..., 0:n_blocks * per:per]
    for i in range(1, per + 1):
        score = jnp.maximum(score, wide[..., i:i + n_blocks * per:per])
    mine = (positions // block)[..., None, None]
    b = jnp.arange(n_blocks)
    forced = (b < sizes.init_blocks) | (
        b > mine - sizes.window_size // block)
    score = jnp.where(forced, jnp.inf, score)
    return jnp.where(b <= mine, score, _NEG)


def selected_pages(score: jax.Array, positions: jax.Array, tables: jax.Array,
                   sizes, n_sel: int) -> tuple[jax.Array, jax.Array]:
    """(e) for a decode step, as a page table a key/value head. score
    ``[N, Hk, Nb]`` (``block_scores`` at T = 1), positions ``[N]``, tables
    ``[N, Nb]`` the slots' own -> (pages ``[N, Hk, n_sel]`` int32, lens
    ``[N, Hk]`` int32): the pool blocks of the kept blocks in the
    sequence's order, the null block after them, and the length a walk of
    that short table reads to (the last kept block is the query's own: its
    tokens up to the query's). A query that sees at most ``dense_len``
    tokens keeps every block it sees (``n_sel`` covers them)."""
    block = sizes.block_size
    nb = score.shape[-1]
    mine = positions // block                                  # [N]
    k = min(sizes.topk, nb)
    vals, idx = jax.lax.top_k(score, k)                        # [N, Hk, k]
    idx = jnp.sort(jnp.where(vals > _NEG, idx, nb), axis=-1)   # unseen: last
    idx = jnp.pad(idx, ((0, 0), (0, 0), (0, n_sel - k)), constant_values=nb)
    dense = (positions < sizes.dense_len)[:, None, None]
    every = jnp.arange(n_sel)
    idx = jnp.where(dense, jnp.where(every <= mine[:, None, None], every, nb),
                    idx)
    count = jnp.sum(idx < nb, axis=-1)                         # [N, Hk]
    pages = jnp.take_along_axis(
        jnp.pad(tables, ((0, 0), (0, 1))),                     # nb: null
        idx.reshape(idx.shape[0], -1), axis=1).reshape(idx.shape)
    lens = (count - 1) * block + (positions % block)[:, None] + 1
    return pages.astype(jnp.int32), lens.astype(jnp.int32)


def kept_mask(score: jax.Array, positions: jax.Array, sizes) -> jax.Array:
    """(e) for a chunk's queries, as a mask. score ``[N, T, Hk, Nb]``,
    positions ``[N, T]`` -> keep ``[N, T, Hk, Nb]`` bool: the ``topk``
    best blocks a query and key/value head (ties to the lower index:
    ``ops.latent.select_mask``'s rule, which is ``lax.top_k``'s), every
    block it sees for a query that sees at most ``dense_len`` tokens."""
    n, t, hk, nb = score.shape
    mine = jnp.repeat(positions // sizes.block_size, hk, axis=1)
    keep = select_mask(score.reshape(n, t * hk, nb), mine, sizes.topk)
    dense = jnp.repeat(positions < sizes.dense_len, hk, axis=1)
    keep = keep | (dense[..., None] & (jnp.arange(nb) <= mine[..., None]))
    return keep.reshape(n, t, hk, nb)


def masked_attention(q: jax.Array, keys: jax.Array, values: jax.Array,
                     keep: jax.Array, positions: jax.Array, block: int,
                     scale: float) -> jax.Array:
    """(f) for a chunk: q ``[N, T, Hk, G, D]`` over the window keys, values
    ``[Hk, N, W, D]`` under keep ``[N, T, Hk, Nb]`` (a query reads the
    tokens of its kept blocks at or before its own position) -> ``[N, T,
    Hk, G, D]``. The exact masked form, a block of queries after another
    so that a block's float32 scores stay under ``_BLOCK_BYTES``."""
    n, t, hk, g, d = q.shape
    w = keys.shape[2]
    at = jnp.arange(w)

    def attend(qb, kb, pb):
        s = jnp.einsum("nthgd,hnwd->nthgw", qb, keys,
                       preferred_element_type=jnp.float32) * scale
        allow = jnp.repeat(kb, block, axis=-1)[..., :w] & (
            at <= pb[..., None])[:, :, None, :]
        p = jax.nn.softmax(jnp.where(allow[:, :, :, None, :], s, -1e30), -1)
        return jnp.einsum("nthgw,hnwd->nthgd", p.astype(values.dtype),
                          values, preferred_element_type=jnp.float32)

    qb = _halved(t, 8, lambda qb: n * qb * hk * g * w * 4 <= _BLOCK_BYTES)
    return _by_query_blocks(attend, qb, q, keep, positions).astype(q.dtype)
