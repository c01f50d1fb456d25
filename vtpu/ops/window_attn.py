"""Attention for a decoder whose layers are of two kinds (MiMo-V2's block):
**window** layers, whose queries read the last ``window`` positions and
whose softmax holds one more term than the keys give, a learned logit a
query head with no value (the sink); and **full** layers, which read all
that is cached. In both, a head is wider for keys than for values, and few
key/value heads serve many query heads.

What a session keeps differs with the kind. A full layer keeps every
token's key and value in a paged pool (``k [L, n_blocks, page, Hk * Dk]``,
``v [L, n_blocks, page, Hk * Dv]``: a token's heads side by side in one row
of whole 128-lane tiles, walked by ``decode_attn.wide_decode_attention``). A
window layer keeps a **ring**: ``window`` rows a sequence, position ``p`` at
row ``p % window``, in the same flat form; a row that holds no position a
query may read is masked by what the reader knows (its own position), so a
ring is never cleared.

Shapes: N sequences, T queries each (a decode step: 1; a chunk: its
tokens), Hq query heads over Hk key/value heads (G = Hq / Hk to each). All
plain XLA; a softmax's maximum sits behind a barrier as
``vtpu.ops.latent._softmax`` says why.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from vtpu.ops.latent import _by_query_blocks, _halved

_NEG = -1e30
_BLOCK_BYTES = 512 << 20  # what one block of queries' scores may take


def spread_queries(q: jax.Array, hk: int) -> jax.Array:
    """q ``[..., Hq, D]`` -> ``[..., Hq, Hk * D]``: query head h's columns
    at key/value head ``h // G``'s place in a cached row, zeros elsewhere,
    so that one product with a row ``[Hk * D]`` of heads side by side is
    that head's product with its own key head."""
    *lead, hq, d = q.shape
    eye = jnp.eye(hk, dtype=q.dtype)
    return jnp.einsum("...kgd,kj->...kgjd", q.reshape(*lead, hk, hq // hk, d),
                      eye).reshape(*lead, hq, hk * d)


def own_values(mixed: jax.Array, hk: int) -> jax.Array:
    """``[..., Hq, Hk * Dv]`` (a query head's weights over every key/value
    head's values) -> ``[..., Hq, Dv]``: its own head's columns."""
    *lead, hq, c = mixed.shape
    eye = jnp.eye(hk, dtype=mixed.dtype)
    return jnp.einsum(
        "...kgjd,kj->...kgd", mixed.reshape(*lead, hk, hq // hk, hk, c // hk),
        eye).reshape(*lead, hq, c // hk)


def _top(scores) -> jax.Array:
    """The largest of several score arrays along their last axes, behind a
    barrier (kept from fusing into the subtraction that follows)."""
    top = jnp.max(scores[0], axis=-1, keepdims=True)
    for s in scores[1:]:
        top = jnp.maximum(top, jnp.max(s, axis=-1, keepdims=True))
    return jax.lax.optimization_barrier(jax.lax.stop_gradient(top))


def full_attention(q: jax.Array, keys: jax.Array, values: jax.Array,
                   qpos: jax.Array, scale: float) -> jax.Array:
    """A full layer's attention over a read window in position order.

    q ``[N, T, Hq, Dk]``; keys ``[N, W, Hk, Dk]``, values ``[N, W, Hk,
    Dv]``: window row s holds position s; qpos ``[N, T]``: a query reads the
    rows up to its own position. Returns ``[N, T, Hq, Dv]``. The queries go
    in blocks that keep a block's scores ``[N, Hq, qb, W]`` under 512 MB as
    float32 (a 512-token chunk over a 32 k window: 64 queries a block)."""
    n, t, hq, dk = q.shape
    w, hk = keys.shape[1], keys.shape[2]
    g = hq // hk
    seen = jnp.arange(w)

    def attend(qs, pos):  # [N, qb, Hq, Dk], [N, qb]
        s = jnp.einsum("ntkgd,nskd->nkgts",
                       qs.reshape(n, -1, hk, g, dk), keys,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where((seen <= pos[..., None])[:, None, None], s, _NEG)
        e = jnp.exp(s - _top([s]))
        total = jnp.sum(e, axis=-1)                          # [N, Hk, G, qb]
        mixed = jnp.einsum("nkgts,nskv->ntkgv", e.astype(values.dtype),
                           values, preferred_element_type=jnp.float32)
        mixed = mixed / jnp.moveaxis(total, 3, 1)[..., None]
        return mixed.reshape(n, -1, hq, values.shape[-1]).astype(q.dtype)

    qb = _halved(t, 8, lambda qb: n * qb * hq * w * 4 <= _BLOCK_BYTES)
    return _by_query_blocks(attend, qb, q, qpos)


def ring_positions(last: jax.Array, window: int) -> jax.Array:
    """The position each ring row holds once positions up to ``last [N]``
    are written: the largest ``p <= last`` with ``p % window == row``;
    negative where no such position exists yet. ``[N, window]``."""
    rows = jnp.arange(window)
    return last[:, None] - (last[:, None] - rows[None, :]) % window


def window_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     ring_k: jax.Array, ring_v: jax.Array,
                     offset: jax.Array, sink: jax.Array, window: int,
                     scale: float) -> jax.Array:
    """A window layer's attention for T tokens a sequence at positions
    ``offset .. offset + T - 1``: each reads the ``window`` positions that
    end with its own, the earlier ones of them from the ring and the
    chunk's own from ``k``, ``v``, under a band mask; the softmax's
    denominator holds ``exp(sink)`` a query head beside the keys' terms.

    q ``[N, T, Hq, Dk]``; k ``[N, T, Hk, Dk]``, v ``[N, T, Hk, Dv]``;
    ring_k ``[N, window, Hk * Dk]``, ring_v ``[N, window, Hk * Dv]`` as the
    sequences' earlier tokens left them (a row that holds no position below
    ``offset`` is masked: at offset 0 all of them); offset ``[N]``; sink
    ``[Hq]`` float32. Returns ``[N, T, Hq, Dv]``."""
    n, t, hq, dk = q.shape
    hk, dv = k.shape[2], v.shape[3]
    g = hq // hk
    qg = q.reshape(n, t, hk, g, dk)
    qpos = offset[:, None] + jnp.arange(t)[None, :]               # [N, T]
    f32 = jnp.float32
    s_own = jnp.einsum("ntkgd,nskd->nkgts", qg, k,
                       preferred_element_type=f32) * scale
    back = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]        # t - s
    s_own = jnp.where((back >= 0) & (back < window), s_own, _NEG)
    s_ring = jnp.einsum("ntkgd,nwkd->nkgtw", qg,
                        ring_k.reshape(n, window, hk, dk),
                        preferred_element_type=f32) * scale
    rpos = ring_positions(offset - 1, window)                     # [N, W]
    ok = (rpos[:, None, :] >= 0) & (rpos[:, None, :] > qpos[..., None] - window)
    s_ring = jnp.where(ok[:, None, None], s_ring, _NEG)
    sink = sink.astype(f32).reshape(1, hk, g, 1, 1)
    top = jnp.maximum(_top([s_own, s_ring]), sink)
    e_own, e_ring = jnp.exp(s_own - top), jnp.exp(s_ring - top)
    total = (jnp.sum(e_own, axis=-1) + jnp.sum(e_ring, axis=-1)
             + jnp.exp(sink - top)[..., 0])                       # [N, Hk, G, T]
    mixed = (jnp.einsum("nkgts,nskv->ntkgv", e_own.astype(v.dtype), v,
                        preferred_element_type=f32)
             + jnp.einsum("nkgtw,nwkv->ntkgv", e_ring.astype(v.dtype),
                          ring_v.reshape(n, window, hk, dv),
                          preferred_element_type=f32))
    mixed = mixed / jnp.moveaxis(total, 3, 1)[..., None]
    return mixed.reshape(n, t, hq, dv).astype(q.dtype)


def ring_step_attention(q: jax.Array, ring_k: jax.Array, ring_v: jax.Array,
                        seen: jax.Array, sink: jax.Array, hk: int,
                        scale: float) -> jax.Array:
    """A decode step's window attention over rings that already hold the
    step's own key and value. q ``[B, Hq, Dk]``; ring_k ``[B, window, Hk *
    Dk]``, ring_v ``[B, window, Hk * Dv]`` as stored; seen ``[B]``: the
    ring rows a slot reads, its first ``seen`` (``min(len + 1, window)``:
    until a session has filled its ring the rows above its length hold an
    earlier session's; 0 for a slot that is not dispatched, whose
    denominator is the sink's term alone). The queries are spread over the
    stored row (``spread_queries``), so the rings are read as they lie.
    Returns ``[B, Hq, Dv]``."""
    f32 = jnp.float32
    window = ring_k.shape[1]
    s = jnp.einsum("bhc,bwc->bhw", spread_queries(q, hk), ring_k,
                   preferred_element_type=f32) * scale
    s = jnp.where(jnp.arange(window)[None, None, :] < seen[:, None, None],
                  s, _NEG)
    sink = sink.astype(f32)[None, :, None]
    top = jnp.maximum(_top([s]), sink)
    e = jnp.exp(s - top)
    total = jnp.sum(e, axis=-1, keepdims=True) + jnp.exp(sink - top)
    mixed = jnp.einsum("bhw,bwc->bhc", e.astype(ring_v.dtype), ring_v,
                       preferred_element_type=f32) / total
    return own_values(mixed, hk).astype(q.dtype)


def ring_after(ring: jax.Array, own: jax.Array, offset: jax.Array,
               new_len: jax.Array) -> jax.Array:
    """The ring rows of N sequences once a chunk's real tokens are in: row
    j holds the largest position below ``new_len`` that is j modulo the
    window; taken from ``own [N, T, C]`` (the chunk's rows, position
    ``offset + i`` at i) where that position is the chunk's, else left as
    ``ring [N, window, C]`` had it. Tokens at or past ``new_len`` (a last
    chunk's padding) enter no ring."""
    window, t = ring.shape[1], own.shape[1]
    pos = ring_positions(new_len - 1, window)                     # [N, W]
    at = jnp.clip(pos - offset[:, None], 0, t - 1)
    picked = jnp.take_along_axis(own, at[:, :, None], axis=1)
    return jnp.where((pos >= offset[:, None])[:, :, None], picked, ring)
