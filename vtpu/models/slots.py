"""The slot pool's step functions for the cached-attention families: what one
decode tick, one speculative verify tick, one prefill chunk and one
whole-prompt admission do to a pool of per-slot KV caches (dense rows or a
paged block pool, bf16 or int8), around the shared trunks in
vtpu/models/transformer (``decode_layer_loop``, ``spec_verify_loop``,
``prefill``).

Pure ``jax.numpy``: the serving adapters (vtpu/serving/adapters.py) bind a
family's parameters, mesh and post-attention block to these, and the engine
jits the adapters' methods. Nothing here knows of slots' owners, admission
or streaming.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from vtpu.models.transformer import (
    ModelConfig,
    Params,
    decode_layer_loop,
    kv_quantized,
    prefill,
    quantize_kv,
    spec_verify_loop,
)


def decode_kv_writer(cfg, cache: dict[str, jax.Array], active: jax.Array):
    """The ``write_kv(l, kv, k, v)`` of one decode tick over the slot pool:
    each slot's new key and value ([B, 1, ...] as the cache's planes store
    a token) land at ITS OWN length, in layer ``l`` of the planes; an
    inactive slot writes nothing. ``batched_decode_step`` says why each
    layout masks the way it does."""
    lens = cache["len"]
    rows = jnp.arange(lens.shape[0])

    if "table" in cache:
        # Paged pool: token t of slot b lands at (table[b, t // page],
        # t % page). Inactive rows (and any position past the context
        # wall) get a deliberately out-of-range block id and mode="drop":
        # a retired slot's STALE table row may name blocks the allocator
        # has since handed to another slot, so the dense path's
        # read-modify-where is not merely wasteful here — it would let a
        # dead slot corrupt a live one's pages.
        page = cache["k"].shape[2]
        nb = cache["k"].shape[1]
        blocks = cache["table"][rows, lens // page]
        off = lens % page
        blk_w = jnp.where(active & (lens < cfg.max_seq), blocks, nb)

        def write_kv(l, kv, k, v):
            out = dict(kv)
            if "k_scale" in kv:
                kq, ksc = quantize_kv(k[:, 0])  # [B, H, Dh] -> int8 + [B, H]
                vq, vsc = quantize_kv(v[:, 0])
                out["k"] = kv["k"].at[l, blk_w, off].set(kq, mode="drop")
                out["v"] = kv["v"].at[l, blk_w, off].set(vq, mode="drop")
                out["k_scale"] = kv["k_scale"].at[l, blk_w, off].set(
                    ksc, mode="drop")
                out["v_scale"] = kv["v_scale"].at[l, blk_w, off].set(
                    vsc, mode="drop")
                return out
            out["k"] = kv["k"].at[l, blk_w, off].set(k[:, 0], mode="drop")
            out["v"] = kv["v"].at[l, blk_w, off].set(v[:, 0], mode="drop")
            return out
    else:
        def write_kv(l, kv, k, v):
            # per-slot scatter at (l, row, lens[row]); inactive rows keep
            # old KV
            out = dict(kv)
            if "k_scale" in kv:
                kq, ksc = quantize_kv(k[:, 0])  # [B, H, Dh] -> int8 + [B, H]
                vq, vsc = quantize_kv(v[:, 0])
                out["k"] = kv["k"].at[l, rows, lens].set(
                    jnp.where(active[:, None, None], kq,
                              kv["k"][l, rows, lens]))
                out["v"] = kv["v"].at[l, rows, lens].set(
                    jnp.where(active[:, None, None], vq,
                              kv["v"][l, rows, lens]))
                out["k_scale"] = kv["k_scale"].at[l, rows, lens].set(
                    jnp.where(active[:, None], ksc,
                              kv["k_scale"][l, rows, lens]))
                out["v_scale"] = kv["v_scale"].at[l, rows, lens].set(
                    jnp.where(active[:, None], vsc,
                              kv["v_scale"][l, rows, lens]))
                return out
            out["k"] = kv["k"].at[l, rows, lens].set(
                jnp.where(active[:, None, None], k[:, 0],
                          kv["k"][l, rows, lens]))
            out["v"] = kv["v"].at[l, rows, lens].set(
                jnp.where(active[:, None, None], v[:, 0],
                          kv["v"][l, rows, lens]))
            return out

    return write_kv


def batched_decode_step(
    params: Params,
    cfg: ModelConfig,
    cache: dict[str, jax.Array],
    tokens: jax.Array,
    active: jax.Array,
    kv_bucket: int = 0,
    ffn_fn=None,
    unroll: bool = False,
    mesh=None,
    paged_attn=None,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """One decode tick for the whole slot pool.

    Unlike models.transformer.decode_step (lockstep: every row at the same
    position), each slot writes its new KV at ITS OWN length via a batched
    scatter, so staggered sequences coexist. tokens: [B] int32; active: [B]
    bool. Inactive slots still compute (uniform work is free on the MXU) but
    neither their cache nor their length advances.

    kv_bucket (static; 0 = max_seq) bounds the attention READS: decode is
    HBM-bandwidth-bound and streaming the whole static cache every step
    wastes bandwidth proportional to max_seq / actual length, so the engine
    passes the smallest bucket covering its longest live sequence. Writes
    still target the full cache — only the read view shrinks.

    ``mesh`` (paged caches under tensor-parallel serving) threads down to
    the trunk so page gathers stay chip-local on the head shard; the paged
    scatter below is head-sharded by propagation (blk_w/off index the
    replicated block/page axes, the written values carry the q/k/v column
    shard). ``paged_attn`` picks the paged READ route (fused table-walking
    kernel vs gather — see spec_verify_loop); the scatter here is
    route-oblivious.
    """
    lens = cache["len"]
    write_kv = decode_kv_writer(cfg, cache, active)
    logits, new_kv = decode_layer_loop(
        params, cfg, cache, tokens, kv_bucket, write_kv, ffn_fn=ffn_fn,
        unroll=unroll, mesh=mesh, paged_attn=paged_attn,
    )
    return logits, {**new_kv, "len": jnp.where(active, lens + 1, lens)}


def batched_spec_step(
    params: Params,
    cfg: ModelConfig,
    cache: dict[str, jax.Array],
    draft: jax.Array,
    active: jax.Array,
    cap: jax.Array,
    kv_bucket: int = 0,
    ffn_fn=None,
    unroll: bool = False,
    mesh=None,
    paged_attn=None,
) -> tuple[jax.Array, jax.Array, dict[str, jax.Array]]:
    """One speculative tick for the slot pool: verify a [B, T] draft chunk
    (column 0 is each slot's pending next token, columns 1..T-1 the
    guessed continuation) and accept greedily.

    Returns (pred [B, T], count [B], cache): pred[b, :count[b]] are the
    tokens slot b emits this tick — the verified draft prefix IS the model's
    own argmax at those positions, so emitting pred needs no re-gather of
    draft. count = accepted + 1 (the first disagreeing argmax is the bonus
    token every tick emits; a tick can never emit less than plain decode),
    capped by ``cap`` (the slot's remaining token budget). The cache length
    advances by count; rejected positions hold stale KV above the new
    length, overwritten by the next chunk write before any query can attend
    to them (see spec_verify_loop).

    Greedy only: acceptance compares argmax — a custom sampler would make
    the emitted stream diverge from its own non-speculative distribution,
    so the engine disables speculation when one is configured.

    ``paged_attn`` makes draft/verify TABLE-AWARE on the pool: under the
    kernel route the verify chunk's ragged window reads walk the page table
    in place (one fused kernel per layer, T = K+1 queries amortizing the
    window bytes) instead of materializing a gathered dense window first.
    A forced override applies to spec ticks exactly as to decode ticks;
    AUTO routes verify chunks (T > 1) to gather — every measured T=4 cell
    in the routing basis lost (DECODE_ATTN_r05.json: 0.28-0.59x; XLA
    amortizes the window across the chunk's queries better) — so the
    adaptive-speculation economics never regress under auto and the kernel
    still proves token-equality on spec ticks whenever forced.
    """
    b, t = draft.shape
    lens = cache["len"]
    rows = jnp.arange(b)[:, None]  # [B, 1], broadcasts against [B, T] indices
    pos = lens[:, None] + jnp.arange(t)[None, :]
    # masked/overflow writes get a deliberately out-of-range index and
    # mode="drop": no gather-and-where, and no duplicate-index scatter race
    # between a genuine write at max_seq-1 and a clipped one
    pos_w = jnp.where(active[:, None] & (pos < cfg.max_seq), pos, cfg.max_seq + 7)

    if "table" in cache:
        # paged scatter: draft position i of slot b lands in block
        # table[b, pos // page] at offset pos % page; the same drop
        # sentinel (an out-of-range block id) covers inactive rows AND
        # positions past the context wall — see batched_decode_step on why
        # drop (not where) is load-bearing for stale tables
        page = cache["k"].shape[2]
        nb = cache["k"].shape[1]
        blocks = jnp.take_along_axis(
            cache["table"], jnp.minimum(pos // page,
                                        cache["table"].shape[1] - 1), axis=1)
        blk_w = jnp.where(
            active[:, None] & (pos < cfg.max_seq), blocks, nb)
        off = pos % page
        scatter_idx = (blk_w, off)
    else:
        scatter_idx = (rows, pos_w)

    def write_kv(l, kv, k, v):
        # k, v: [B, T, H, Dh]; scatter row i at the slot's position
        # len[slot]+i — dense: (l, slot, pos); paged: (l, block, offset)
        i0, i1 = scatter_idx
        out = dict(kv)
        if "k_scale" in kv:
            kq, ksc = quantize_kv(k)
            vq, vsc = quantize_kv(v)
            out["k"] = kv["k"].at[l, i0, i1].set(kq, mode="drop")
            out["v"] = kv["v"].at[l, i0, i1].set(vq, mode="drop")
            out["k_scale"] = kv["k_scale"].at[l, i0, i1].set(ksc, mode="drop")
            out["v_scale"] = kv["v_scale"].at[l, i0, i1].set(vsc, mode="drop")
            return out
        out["k"] = kv["k"].at[l, i0, i1].set(k, mode="drop")
        out["v"] = kv["v"].at[l, i0, i1].set(v, mode="drop")
        return out

    logits, new_kv = spec_verify_loop(
        params, cfg, cache, draft, kv_bucket, write_kv, ffn_fn=ffn_fn,
        unroll=unroll, mesh=mesh, paged_attn=paged_attn,
    )
    pred = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, T]
    match = (draft[:, 1:] == pred[:, :-1]).astype(jnp.int32)
    accepted = jnp.sum(jnp.cumprod(match, axis=1), axis=1)  # leading matches
    count = jnp.where(active, jnp.minimum(accepted + 1, cap), 0)
    return pred, count, {**new_kv, "len": jnp.minimum(lens + count, cfg.max_seq)}


def chunked_prefill_into_slot(
    params: Params,
    cfg: ModelConfig,
    cache: dict[str, jax.Array],
    chunk: jax.Array,
    slot: jax.Array,
    offset: jax.Array,
    new_len: jax.Array,
    kv_bucket: int = 0,
    ffn_fn=None,
    unroll: bool = False,
    block_ids: Optional[jax.Array] = None,
    mesh=None,
    layer_of=None,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """One [1, C] prompt chunk written into *slot* at positions
    offset..offset+C-1: prefill as a sequence of fixed-size chunk forwards
    through the SAME trunk as decode and speculative verify
    (spec_verify_loop) — a chunk is just a T=C verify pass whose "draft" is
    known-correct prompt.

    Why chunks: one compiled executable per chunk size C serves ANY prompt
    length (the bucketed path compiles per bucket and caps prompts at the
    largest), and a C-token chunk bounds how long one admission dispatch
    can stall the decode loop's live streams. The trunk runs on a
    single-row VIEW of the pool cache ([L, 1, S] slices), so chunk FLOPs
    are per-prompt, not per-pool-slot; the written window is scattered back
    afterwards. Pads in the final chunk write junk KV above new_len — same
    staleness contract as rejected speculation: masked by length now,
    overwritten before any query can attend to them.

    ``new_len`` is the slot's length after this chunk (min(offset+C,
    true_len) — the engine passes the running value so the LAST chunk
    leaves the true length with no extra dispatch). ``kv_bucket`` (static;
    0 = max_seq) bounds BOTH the slot-view copy and the attention reads:
    the engine passes the smallest bucket covering offset+C, so early
    chunks of a long-context model never stream the whole empty cache.
    Returns (logits [1, C, vocab], updated pool cache); only the last
    chunk's logits (at the prompt's final position) are consumed.

    ``block_ids`` ([Wp] int32, Wp = bucket // page) switches to the PAGED
    pool: the slot's window pages are gathered from the block pool into the
    same dense [L, 1, bucket] view, the trunk runs unchanged, and the whole
    window scatters back to those blocks afterwards. The engine passes the
    slot's mapped blocks padded with the null block 0 — padding writes land
    on the always-masked null block, so the scatter needs no drop mask. Passing
    block_ids EXPLICITLY (instead of reading cache["table"][slot]) is what
    lets register_prefix prefill a prefix into freshly allocated pool
    blocks with NO slot and NO table row — the zero-copy sharing source.
    ``slot`` may then be out of range (the engine passes the slot count as
    a sentinel): the final length write uses mode="drop", so a prefix
    build never touches any live slot's length.

    ``mesh`` (paged pools under tensor parallelism): the gathered window
    view and the page scatter-back are pinned to the pool's head shard —
    the per-chunk pool traffic stays chip-local exactly like decode's.

    The paged decode KERNEL route deliberately does not apply here: a chunk
    gathers its window into a dense view, writes its own rows into it and
    scatters the written pages back, so the table-walker (one query row a
    slot over pages in place) has nothing to walk. The chunk's attention
    over that view has a kernel of its own since PR 45:
    ``transformer.chunk_window_attention`` asks ``ops.chunk_attn.takes``
    and, on a TPU with bfloat16 planes, 128 query rows or more a key/value
    head and a read window of 2048 positions or more, runs
    ``chunk_attn.chunk_attention`` over the stacked view as it lies (the
    layer picked by the kernel's index maps, a block's scores kept in VMEM,
    the key blocks past the chunk's end neither copied nor multiplied);
    elsewhere, and for int8 planes or a
    head-sharded pool, ``causal_attention``'s ragged form as before. The
    gather and the write-back are as they were (PERF.md section 7).

    A family whose blocks of positions see each other both ways
    (``cfg.attn_block``) takes the same path: the chunk's rows are in the
    view before attention reads it, and ``cached_attention`` lets a query
    read to the end of its own block (chunks a multiple of the block).
    ``layer_of`` is ``spec_verify_loop``'s.
    """
    c = chunk.shape[1]
    bucket = kv_bucket or cfg.max_seq
    quant = kv_quantized(cfg)
    kv_keys = ("k", "v", "k_scale", "v_scale") if quant else ("k", "v")
    view = _chunk_window(cache, kv_keys, bucket, slot, block_ids, mesh)
    view["len"] = jnp.full((1,), offset, jnp.int32)

    def write_kv(l, kv, k, v):
        out = dict(kv)
        if quant:
            kq, ksc = quantize_kv(k)
            vq, vsc = quantize_kv(v)
            out["k"] = jax.lax.dynamic_update_slice(kv["k"], kq[None], (l, 0, offset, 0, 0))
            out["v"] = jax.lax.dynamic_update_slice(kv["v"], vq[None], (l, 0, offset, 0, 0))
            out["k_scale"] = jax.lax.dynamic_update_slice(
                kv["k_scale"], ksc[None], (l, 0, offset, 0))
            out["v_scale"] = jax.lax.dynamic_update_slice(
                kv["v_scale"], vsc[None], (l, 0, offset, 0))
            return out
        out["k"] = jax.lax.dynamic_update_slice(kv["k"], k[None], (l, 0, offset, 0, 0))
        out["v"] = jax.lax.dynamic_update_slice(kv["v"], v[None], (l, 0, offset, 0, 0))
        return out

    logits, new_view = spec_verify_loop(
        params, cfg, view, chunk, bucket, write_kv, ffn_fn=ffn_fn,
        unroll=unroll, mesh=mesh, layer_of=layer_of,
    )
    return logits, _chunk_write_back(
        cache, new_view, kv_keys, bucket, c, slot, offset, new_len, block_ids)


def _token_rows_merged(pool: jax.Array) -> jax.Array:
    """A pool plane whose token is fewer than eight rows ([L, n_blocks,
    page, rows, 128]: grouped heads stored several a row,
    ``transformer.kv_plane_shape``) viewed [L, n_blocks, page * rows, 128],
    the same bytes in the same order. The chip tiles a plane of so few
    rows by four, and a gather or scatter of its pages as they are makes
    the compiler lay the WHOLE pool out by eight first and back after
    (compiled for a v5e, a chunk copied both planes in and out: 4 x 1.07
    GB a launch, 20 ms on the chip, PR 32); merged, a page is 64 rows and
    nothing is laid out anew. Any other plane is returned as it is (rows
    narrower than the 128 lanes do not merge for free)."""
    if pool.ndim == 5 and pool.shape[3] < 8 and pool.shape[4] % 128 == 0:
        return pool.reshape(pool.shape[:2] + (-1, pool.shape[4]))
    return pool


@jax.named_scope("gather_attn")
def _chunk_window(cache, kv_keys, bucket: int, slot, block_ids, mesh):
    """The slot's dense [L, 1, bucket] read window for a prefill chunk:
    gathered from the pool's blocks when ``block_ids`` is given, else a
    slice of the slot's row."""
    if block_ids is not None:
        page = cache["k"].shape[2]
        wp = bucket // page
        view = {}
        for key in kv_keys:
            pool = cache[key]  # [L, n_blocks, page, ...]
            g = _token_rows_merged(pool)[:, block_ids]  # [L, Wp, page, ...]
            view[key] = g.reshape(
                (pool.shape[0], 1, wp * page) + pool.shape[3:])
        if mesh is not None:
            from vtpu.parallel.sharding import constrain_paged_kv

            view = constrain_paged_kv(view, mesh)
        return view
    return {
        key: jax.lax.dynamic_slice(
            cache[key],
            (0, slot) + (0,) * (cache[key].ndim - 2),
            (cache[key].shape[0], 1, bucket) + cache[key].shape[3:],
        )
        for key in kv_keys
    }


@jax.named_scope("kv_write")
def _chunk_write_back(cache, new_view, kv_keys, bucket: int, c: int, slot,
                      offset, new_len, block_ids):
    """The pool cache with a chunk's written span of ``new_view`` put back
    (and the slot's length set)."""
    out = dict(cache)
    if block_ids is not None:
        # Scatter back ONLY the page span [offset, offset + c) can have
        # touched — ceil(c/page)+1 pages (the +1 absorbs an unaligned
        # offset straddling a boundary), a STATIC count, sliced at the
        # dynamic start page. The start is clamped so the value slice and
        # the block-id slice stay aligned; a clamp only shifts the span
        # to cover extra ALREADY-CURRENT pages, and rewriting a page with
        # the view's own content is a value-level no-op (single-writer
        # loop thread). This keeps a chunk's pool write traffic O(chunk),
        # not O(window) — the bound the prefill budget is denominated in.
        page = cache[kv_keys[0]].shape[2]
        wp = bucket // page
        span = min(-(-c // page) + 1, wp)
        p0 = jnp.minimum(offset // page, wp - span)
        ids_w = jax.lax.dynamic_slice(block_ids, (p0,), (span,))
        for key in kv_keys:
            pool = _token_rows_merged(cache[key])
            pages = new_view[key].reshape(
                (pool.shape[0], wp) + pool.shape[2:])
            written = jax.lax.dynamic_slice(
                pages, (0, p0) + (0,) * (pages.ndim - 2),
                (pool.shape[0], span) + pages.shape[2:])
            out[key] = pool.at[:, ids_w].set(written).reshape(
                cache[key].shape)
        # slot may be the engine's out-of-range sentinel (prefix build):
        # drop the length write rather than clamp-corrupt the last slot
        out["len"] = cache["len"].at[slot].set(new_len, mode="drop")
        return out
    for key in kv_keys:
        shape = new_view[key].shape  # [L, 1, S, H(, Dh)]
        sizes = (shape[0], 1, c) + shape[3:]
        written = jax.lax.dynamic_slice(
            new_view[key], (0, 0, offset) + (0,) * (len(shape) - 3), sizes)
        out[key] = jax.lax.dynamic_update_slice(
            cache[key], written, (0, slot, offset) + (0,) * (len(shape) - 3))
    out["len"] = cache["len"].at[slot].set(new_len)
    return out


@jax.named_scope("kv_write")
def _scatter_prefill_pages(
    cache: dict[str, jax.Array],
    seq_cache: dict[str, jax.Array],
    logits: jax.Array,
    slots: jax.Array,
    true_lens: jax.Array,
    s: int,
    mesh=None,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Install N freshly-prefilled rows into a PAGED pool: the dense
    [L, N, s, ...] per-row KV reshapes to page granularity and scatters
    into each row's mapped blocks (cache["table"][slots], set by the
    engine's reservation BEFORE the admission dispatch). Unmapped window
    entries are the null block 0 — pad pages beyond a short reservation
    land there, invisible under the length masks. Returns the last-
    position logits [N, vocab] and the updated pool (len = true_lens).
    ``mesh``: head-sharded pool — the freshly-prefilled rows already carry
    the head shard (q/k/v column split), so the page scatter is chip-local;
    the constraint pins the updated pool to its allocation layout."""
    page = cache["k"].shape[2]
    wp = s // page
    blk = cache["table"][slots, :wp]  # [N, Wp]
    new_cache = dict(cache)
    for key in ("k", "v", "k_scale", "v_scale"):
        if key not in cache:
            continue
        pool = _token_rows_merged(cache[key])
        pages = seq_cache[key][:, :, :s].reshape(
            (pool.shape[0], slots.shape[0], wp) + pool.shape[2:])
        new_cache[key] = pool.at[:, blk].set(pages).reshape(cache[key].shape)
    new_cache["len"] = cache["len"].at[slots].set(true_lens)
    if mesh is not None:
        from vtpu.parallel.sharding import constrain_paged_kv

        new_cache = constrain_paged_kv(new_cache, mesh)
    if logits.ndim == 2:
        last = logits  # prefill_fn already gathered the final positions
    else:
        last = logits[jnp.arange(slots.shape[0]), true_lens - 1]
    return last, new_cache


def prefill_into_slot(
    params: Params,
    cfg: ModelConfig,
    cache: dict[str, jax.Array],
    tokens: jax.Array,
    slot: jax.Array,
    true_len: jax.Array,
    prefill_fn=None,
    mesh=None,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Prefill a [1, bucket] (right-padded) prompt and install it in *slot*.

    Causality makes right padding harmless: real positions never attend to
    the pad tail, and decode masks the cache past true_len. ``prefill_fn``
    swaps the full-sequence forward (dense transformer default; the MoE
    family passes moe_prefill — same cache contract). Returns the first
    generated token's logits ([vocab]) and the updated pool cache.
    """
    if prefill_fn is None:
        logits, seq_cache = prefill(params, cfg, tokens, mesh=mesh)
    else:
        logits, seq_cache = prefill_fn(params, cfg, tokens)
    # [L, 1, max_seq, H, Dh] -> the bucket's worth, written at (layer, slot, 0)
    # (int8 caches carry k_scale/v_scale alongside; copied the same way)
    s = tokens.shape[1]
    new_cache = dict(cache)
    if "table" in cache:
        last, new_cache = _scatter_prefill_pages(
            cache, seq_cache, logits, jnp.asarray(slot)[None],
            jnp.asarray(true_len)[None], s, mesh=mesh)
        return last[0], new_cache
    with jax.named_scope("kv_write"):
        for key in ("k", "v", "k_scale", "v_scale"):
            if key in cache:
                new_cache[key] = cache[key].at[:, slot, :s].set(
                    seq_cache[key][:, 0, :s])
        new_cache["len"] = cache["len"].at[slot].set(true_len)
    last = logits[0, true_len - 1]
    return last, new_cache


def prefill_into_slots(
    params: Params,
    cfg: ModelConfig,
    cache: dict[str, jax.Array],
    tokens: jax.Array,
    slots: jax.Array,
    true_lens: jax.Array,
    prefill_fn=None,
    mesh=None,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Batched admission: prefill N right-padded [N, bucket] prompts in ONE
    dispatch and scatter each row's KV into its own slot — a K-prompt
    same-bucket burst drains in ceil(K/Nmax) dispatches instead of K, and
    the batch shares one trunk forward (lockstep hardware loves uniformity;
    the rows are independent sequences exactly like the decode pool's).

    slots/true_lens: [N] int32; slot indices must be distinct (duplicate
    rows would race the scatter — the engine assigns each waiting request
    its own free slot). ``prefill_fn(params, cfg, tokens)`` may return
    either [N, S, vocab] logits or, when it supports gathering at the final
    position (transformer.prefill's logits_at), [N, vocab] directly —
    detected by rank, so families without the fast path stay correct.
    Returns (last-position logits [N, vocab], updated pool cache).
    """
    if prefill_fn is None:
        logits, seq_cache = prefill(params, cfg, tokens, mesh=mesh)
    else:
        logits, seq_cache = prefill_fn(params, cfg, tokens)
    s = tokens.shape[1]
    if "table" in cache:
        return _scatter_prefill_pages(
            cache, seq_cache, logits, slots, true_lens, s, mesh=mesh)
    new_cache = dict(cache)
    with jax.named_scope("kv_write"):
        for key in ("k", "v", "k_scale", "v_scale"):
            if key in cache:
                # one advanced-index scatter over the slot axis: [L, N, s, ...]
                new_cache[key] = cache[key].at[:, slots, :s].set(
                    seq_cache[key][:, :, :s])
        new_cache["len"] = cache["len"].at[slots].set(true_lens)
    if logits.ndim == 2:
        last = logits  # prefill_fn already gathered the final positions
    else:
        last = logits[jnp.arange(tokens.shape[0]), true_lens - 1]
    return last, new_cache
