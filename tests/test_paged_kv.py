"""Paged KV cache: block pool + page tables + pool-aware admission.

Fast (non-slow) tier for the PR-4 tentpole. The contract under test is
layered exactly like the implementation:

- BlockAllocator: host-side free list + refcounts (block 0 reserved),
  including the share/release lifecycle that makes zero-copy prefixes safe;
- paged engine streams are TOKEN-IDENTICAL to the dense engine (the paged
  read is a gather positionally identical to the dense slice, so the
  attention numerics are shared verbatim) — bf16/f32 and int8-KV pools;
- pool-exhaustion backpressure parks admissions on the waiting list and a
  retire's release un-parks them (never an OOM, never a lost request);
- prefix blocks map read-only into slot tables (install-copy counter stays
  zero), the partial boundary block is copied-on-write so concurrent
  suffixes cannot cross-contaminate, and unregister_prefix with live
  mappings frees blocks only at refcount zero;
- the register_prefix chunk recipe (pad-window read bounds included) is
  teacher-forced-equivalent to a monolithic prefill, for exact and int8
  KV alike (the ISSUE-4 satellite pinning the suspected pad-tail bound).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vtpu.models import ModelConfig, init_params
from vtpu.models.transformer import (
    decode_step, init_kv_cache, prefill,
)
from vtpu.models.slots import chunked_prefill_into_slot
from vtpu.serving import BlockAllocator, ServingConfig, ServingEngine
from vtpu.serving.engine import pad_to_chunks

CFG = ModelConfig(
    vocab=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
    max_seq=32, head_dim=16, dtype=jnp.float32, use_pallas=False,
)
CFG_INT8 = ModelConfig(
    vocab=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
    max_seq=32, head_dim=16, dtype=jnp.float32, use_pallas=False,
    kv_int8=True,
)
PAGE = 8
DENSE = ServingConfig(slots=2, prefill_buckets=(8,), max_new_tokens=6)
PAGED = ServingConfig(slots=2, prefill_buckets=(8,), max_new_tokens=6,
                      kv_page=PAGE)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.key(0), CFG)


@pytest.fixture(scope="module")
def params_int8():
    return init_params(jax.random.key(0), CFG_INT8)


def _prompt(seed, n, lo=0):
    return [int(t) for t in jax.random.randint(
        jax.random.key(seed), (n,), lo, CFG.vocab, jnp.int32)]


def _run(params, serving, prompts, steps=6, cfg=CFG):
    eng = ServingEngine(params, cfg, serving)
    eng.start()
    try:
        reqs = [eng.submit(p, max_new_tokens=steps) for p in prompts]
        streams = [list(r.stream()) for r in reqs]
        stats = eng.stats()
    finally:
        eng.stop()
    return streams, stats


# ------------------------------------------------------------ allocator


def test_allocator_lifecycle_and_null_block():
    """Block 0 is never handed out; alloc starts blocks at refcount 1;
    release returns them at refcount zero; alloc is all-or-nothing."""
    a = BlockAllocator(5)  # null + 4 usable
    assert a.free_blocks == 4
    got = a.alloc(3)
    assert got is not None and 0 not in got and len(set(got)) == 3
    assert all(a.refcount(b) == 1 for b in got)
    assert a.alloc(2) is None  # only 1 free: all-or-nothing
    assert a.free_blocks == 1  # the failed alloc reserved nothing
    a.release(got[:1])
    assert a.free_blocks == 2
    more = a.alloc(2)
    assert more is not None and a.free_blocks == 0
    a.release(got[1:])
    a.release(more)
    assert a.free_blocks == 4


def test_allocator_share_release_refcounts():
    """share() adds mappings; the block frees only when the LAST holder
    releases — the prefix registry + N slots lifecycle in miniature."""
    a = BlockAllocator(4)
    [b] = a.alloc(1)
    a.share([b])  # slot 1 maps it
    a.share([b])  # slot 2 maps it
    assert a.refcount(b) == 3
    a.release([b])  # registry unregisters: still mapped
    a.release([b])  # slot 1 retires
    assert a.free_blocks == 2 and a.refcount(b) == 1
    a.release([b])  # slot 2 retires: NOW it frees
    assert a.free_blocks == 3 and a.refcount(b) == 0
    with pytest.raises(ValueError):
        BlockAllocator(1)  # null block alone is not a pool


# ------------------------------------------- paged engine == dense engine


def test_paged_streams_match_dense_token_for_token(params):
    """Same prompts through the dense ring and the paged pool: identical
    streams (three requests through two slots also covers slot recycling
    over reallocated blocks), and the pool drains back to fully free."""
    prompts = [_prompt(1, 5), _prompt(2, 7), _prompt(3, 3)]
    dense, _ = _run(params, DENSE, prompts)
    paged, stats = _run(params, PAGED, prompts)
    assert dense == paged
    assert stats["paged"] and stats["kv_page"] == PAGE
    assert stats["kv_pool_free"] == stats["kv_pool_blocks"]  # all retired
    assert stats["pool_blocked_admissions"] == 0
    assert stats["kv_bucket_hist"]  # the read-window tax is surfaced
    assert stats["read_pages_ratio"] is not None
    assert stats["kv_hbm_bytes"]["paged"] is not None
    assert stats["kv_hbm_bytes"]["dense"] is not None


def test_paged_int8_streams_match_dense_int8(params_int8):
    """int8-KV planes + scale pools page the same way: paged int8 streams
    equal dense int8 streams."""
    prompts = [_prompt(4, 5), _prompt(5, 6)]
    dense, _ = _run(params_int8, DENSE, prompts, cfg=CFG_INT8)
    paged, stats = _run(params_int8, PAGED, prompts, cfg=CFG_INT8)
    assert dense == paged
    assert stats["kv_pool_free"] == stats["kv_pool_blocks"]


def test_paged_spec_decode_matches_plain(params):
    """Speculation over the paged pool: the verify chunk's [B, T] scatter
    routes through the page tables (the same drop-sentinel write as plain
    decode), and the emitted stream equals the plain paged engine's —
    mirroring the dense spec contract in test_serving_fast."""
    plain = ServingConfig(slots=2, prefill_buckets=(8,), max_new_tokens=8,
                          kv_page=PAGE)
    spec = ServingConfig(slots=2, prefill_buckets=(8,), max_new_tokens=8,
                         kv_page=PAGE, spec_tokens=2, spec_min_mean=0.0)
    prompt = [3, 9, 3, 9, 3, 9]
    want, _ = _run(params, plain, [prompt], steps=8)
    got, stats = _run(params, spec, [prompt], steps=8)
    assert got == want
    assert stats["spec_ticks"] > 0 and stats["spec_emitted"] > 0
    assert stats["kv_pool_free"] == stats["kv_pool_blocks"]


def test_moe_paged_streams_match_moe_dense():
    """The MoE family rides the SAME paged cache machinery (the shared
    decode trunk + engine scatter paths, with routed experts as the FFN):
    paged MoE streams equal dense MoE streams."""
    from vtpu.models.moe import MoEConfig, init_moe_params
    from vtpu.serving.adapters import MoeSlotModel

    cfg = MoEConfig(vocab=96, d_model=64, n_heads=2, n_layers=2, d_ff=64,
                    n_experts=4, top_k=2, max_seq=32, head_dim=32,
                    dtype=jnp.float32)
    mparams = init_moe_params(jax.random.key(5), cfg)
    serving = ServingConfig(slots=2, prefill_buckets=(8,), max_new_tokens=5)
    prompts = [[int(t) % cfg.vocab for t in _prompt(21, 5)],
               [int(t) % cfg.vocab for t in _prompt(22, 7)]]

    def run(model):
        eng = ServingEngine(serving=serving, model=model)
        eng.start()
        try:
            reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
            return [list(r.stream()) for r in reqs], eng.stats()
        finally:
            eng.stop()

    dense, _ = run(MoeSlotModel(mparams, cfg))
    paged, stats = run(MoeSlotModel(mparams, cfg, kv_page=PAGE))
    assert dense == paged
    assert stats["paged"] and stats["kv_pool_free"] == stats["kv_pool_blocks"]


# --------------------------------------------------- pool backpressure


def test_pool_exhaustion_parks_then_admits_after_retire(params):
    """A pool covering ONE request at a time serializes a 3-burst through
    backpressure: every stream completes in full, blocked-admission events
    are counted, and the final pool is fully free (waiting requests admit
    exactly when a retire releases blocks)."""
    serving = ServingConfig(slots=2, prefill_buckets=(8,), max_new_tokens=6,
                            kv_page=PAGE, kv_pool_blocks=2)
    streams, stats = _run(params, serving,
                          [_prompt(i + 10, 5) for i in range(3)])
    assert [len(s) for s in streams] == [6, 6, 6]
    assert stats["pool_blocked_admissions"] > 0
    assert stats["admissions"] == 3
    assert stats["kv_pool_free"] == 2


def test_oversized_request_rejected_at_submit(params):
    """A request whose worst-case pages exceed the whole pool would park
    at the head of the line forever — submit must raise instead."""
    serving = ServingConfig(slots=2, prefill_buckets=(8,), max_new_tokens=6,
                            kv_page=PAGE, kv_pool_blocks=1)
    eng = ServingEngine(params, CFG, serving)
    with pytest.raises(ValueError, match="private KV blocks"):
        eng.submit(_prompt(1, 5), max_new_tokens=20)
    eng.stop()


def test_cancel_mid_batched_prefill_frees_blocks(params):
    """Refcount lifecycle across cancel-mid-batch: cancel one request after
    its batched paged prefill dispatched but before first-token delivery —
    the victim's blocks free at retire, the others stream normally, and the
    pool drains to fully free."""
    serving = ServingConfig(slots=3, prefill_buckets=(8,), max_new_tokens=4,
                            prefill_batch_sizes=(3,), kv_page=PAGE)
    eng = ServingEngine(params, CFG, serving)
    step0 = eng._admit_step
    cell: dict = {}

    def wrapped(params_, state, buf, tokens, *rest):
        out = step0(params_, state, buf, tokens, *rest)
        if "victim" in cell and bool((tokens != 0).any()):
            cell.pop("victim").cancel()
        return out

    eng._admit_step = wrapped
    reqs = [eng.submit(_prompt(40 + i, 5, lo=1), max_new_tokens=4)
            for i in range(3)]
    cell["victim"] = reqs[1]
    eng.start()
    try:
        streams = [list(r.stream()) for r in reqs]
        stats = eng.stats()
    finally:
        eng.stop()
    assert streams[1] == []
    assert len(streams[0]) == 4 and len(streams[2]) == 4
    assert stats["kv_pool_free"] == stats["kv_pool_blocks"]


# ------------------------------------------------- zero-copy prefixes


def test_prefix_blocks_shared_zero_copy_and_cow(params):
    """The acceptance contract: prefix-backed paged admissions perform ZERO
    full-prefix device copies (install counter stays 0), map full blocks
    read-only (prefix_blocks_shared > 0), COW only the partial boundary
    block, and the streams equal a from-scratch full-prompt admission."""
    serving = ServingConfig(slots=2, prefill_buckets=(8,), max_new_tokens=6,
                            prefill_chunk=8, kv_page=PAGE)
    pre = [5, 6, 7, 8, 9, 5, 6, 7, 8, 9]  # 10 tokens: 1 full page + partial
    suf = [1, 2, 3]
    eng = ServingEngine(params, CFG, serving)
    eng.start()
    try:
        pid = eng.register_prefix(pre)
        got = list(eng.submit(suf, max_new_tokens=6, prefix=pid).stream())
        got2 = list(eng.submit(suf, max_new_tokens=6, prefix=pid).stream())
        stats = eng.stats()
    finally:
        eng.stop()
    want, _ = _run(params, serving, [pre + suf])
    assert got == got2 == want[0]
    assert stats["prefix_install_copies"] == 0
    assert stats["prefix_blocks_shared"] == 2   # 1 full page x 2 admissions
    assert stats["prefix_cow_copies"] == 2      # boundary block x 2
    # after both retire only the registry's hold remains (2 pages of pad)
    assert stats["kv_pool_free"] == stats["kv_pool_blocks"] - 2


def test_prefix_cow_isolates_concurrent_suffixes(params):
    """Two requests share an UNALIGNED prefix concurrently: each one's
    suffix writes land in its own COW boundary block, so both streams match
    their solo-run references (a shared boundary write would cross-
    contaminate whichever slot read second)."""
    serving = ServingConfig(slots=2, prefill_buckets=(8,), max_new_tokens=6,
                            prefill_chunk=8, kv_page=PAGE)
    pre = ([3, 9, 4] * 4)[:10]
    suf_a, suf_b = [1, 2, 3, 4], [11, 12, 13, 14]

    def run_together():
        eng = ServingEngine(params, CFG, serving)
        pid_cell = {}
        eng.start()
        try:
            pid = eng.register_prefix(pre)
            pid_cell["pid"] = pid
            ra = eng.submit(suf_a, max_new_tokens=6, prefix=pid)
            rb = eng.submit(suf_b, max_new_tokens=6, prefix=pid)
            return list(ra.stream()), list(rb.stream())
        finally:
            eng.stop()

    def run_solo(suf):
        eng = ServingEngine(params, CFG, serving)
        eng.start()
        try:
            pid = eng.register_prefix(pre)
            return list(eng.submit(suf, max_new_tokens=6,
                                   prefix=pid).stream())
        finally:
            eng.stop()

    got_a, got_b = run_together()
    assert got_a == run_solo(suf_a)
    assert got_b == run_solo(suf_b)


def test_unregister_prefix_frees_only_at_refcount_zero(params):
    """White-box lifecycle (no loop thread, so nothing races): a live
    prefix-backed slot keeps the shared blocks alive across
    unregister_prefix; they free only when the slot retires."""
    serving = ServingConfig(slots=2, prefill_buckets=(8,), max_new_tokens=4,
                            prefill_chunk=8, kv_page=PAGE)
    eng = ServingEngine(params, CFG, serving)
    pre = list(range(1, 17))  # 16 tokens = exactly 2 full pages, no COW
    pid = eng.register_prefix(pre)  # loop not started: builds inline
    usable = eng._n_blocks - 1
    assert eng._alloc.free_blocks == usable - 2
    req = eng.submit([], max_new_tokens=4, prefix=pid)
    eng._tick_head()  # reserve + admit (empty suffix: no chunks needed)
    slot = eng._slot_req.index(req)
    shared = [b for b in eng._slot_blocks[slot]
              if eng._alloc.refcount(b) == 2]
    assert len(shared) == 2  # both full pages mapped read-only
    assert eng.stats()["prefix_install_copies"] == 0
    eng.unregister_prefix(pid)
    # registry hold dropped, slot mapping still pins the shared blocks
    assert all(eng._alloc.refcount(b) == 1 for b in shared)
    eng._retire(slot)
    assert all(eng._alloc.refcount(b) == 0 for b in shared)
    assert eng._alloc.free_blocks == usable
    eng.stop()


# ---------------------------------- satellite: prefix prefill equivalence


def _chunked_prefill_like_register(params, cfg, tokens, c, buckets,
                                   unroll=True):
    """The register_prefix chunk recipe as pure functions: pad to the chunk
    grid, stream [1, C] chunks through the verify trunk with the engine's
    exact pad-window read-bound picks (kv_bucket >= off + c), and take
    last_logits from the true final row of the padded tail."""
    n = len(tokens)
    padded = pad_to_chunks(jnp.asarray(tokens, jnp.int32), n, c)
    pad = padded.shape[1]
    cache = init_kv_cache(cfg, 1)
    logits = None
    for i in range(pad // c):
        off = i * c
        bkt = next((b for b in buckets if b >= off + c), cfg.max_seq)
        logits, cache = chunked_prefill_into_slot(
            params, cfg, cache, padded[:, off:off + c], jnp.int32(0),
            jnp.int32(off), jnp.int32(min(off + c, n)),
            kv_bucket=bkt, unroll=unroll)
    return logits[0, (n - 1) - (pad - c)], cache


@pytest.mark.parametrize("quantized", [False, True],
                         ids=["exact", "int8kv"])
def test_chunked_prefix_prefill_matches_monolithic(params, params_int8,
                                                   quantized):
    """ISSUE-4 satellite: the register_prefix chunk loop (pad-window read
    bounds, padded-tail last_logits row) must reproduce a monolithic
    prefill — installed KV planes (quantized values + scales for int8),
    final-position logits, AND a teacher-forced decode over both caches.
    An off-grid length (n % c != 0) makes the padded tail real."""
    cfg = CFG_INT8 if quantized else CFG
    p = params_int8 if quantized else params
    tokens = _prompt(77, 13, lo=1)  # 13 % 8 != 0: final chunk is padded
    last, cache = _chunked_prefill_like_register(
        p, cfg, tokens, c=8, buckets=(8, 16, 32))
    ref_logits, ref_cache = prefill(p, cfg, jnp.asarray([tokens], jnp.int32))
    n = len(tokens)
    if quantized:
        # int8 round trip: quantized planes and scales install correctly
        # (compare dequantized values — chunked activations may differ by
        # float-reduction order, so exact int equality is too strict)
        for plane in ("k", "v"):
            got = (cache[plane][:, 0, :n].astype(jnp.float32)
                   * cache[f"{plane}_scale"][:, 0, :n, :, None])
            want = (ref_cache[plane][:, 0, :n].astype(jnp.float32)
                    * ref_cache[f"{plane}_scale"][:, 0, :n, :, None])
            assert jnp.allclose(got, want, atol=1e-2, rtol=1e-2), plane
    else:
        for plane in ("k", "v"):
            assert jnp.allclose(cache[plane][:, 0, :n],
                                ref_cache[plane][:, 0, :n],
                                atol=1e-5), plane
    # int8 logits carry an inherent algorithmic gap: chunk i's queries
    # attend over the ALREADY-QUANTIZED KV of chunks < i, while the
    # monolithic prefill attends over exact values and quantizes only at
    # fill time — so equivalence holds at quantization-error scale, not
    # float-noise scale
    tol = 5e-2 if quantized else 1e-3
    assert jnp.allclose(last, ref_logits[0, n - 1], atol=tol)
    # teacher-forced: force the SAME token stream through both caches and
    # compare per-step logits — catches any divergence free-running greedy
    # equality would hide behind an argmax fork
    forced = _prompt(78, 4, lo=1)
    a, b = dict(cache), dict(ref_cache)
    a["len"] = jnp.full((1,), n, jnp.int32)
    b["len"] = jnp.full((1,), n, jnp.int32)
    for t in forced:
        la, a = decode_step(p, cfg, a, jnp.asarray([t], jnp.int32))
        lb, b = decode_step(p, cfg, b, jnp.asarray([t], jnp.int32))
        assert jnp.allclose(la, lb, atol=tol)


def test_int8_prefix_engine_round_trip(params_int8):
    """Engine-level int8 prefix round trip: quantized planes + scales
    install through register_prefix and the prefix-admitted stream equals
    the from-scratch full-prompt stream (dense path — the satellite's
    regression net under the classic ring)."""
    serving = ServingConfig(slots=2, prefill_buckets=(8,), max_new_tokens=6,
                            prefill_chunk=8)
    pre = ([5, 6, 7, 8, 9] * 2)  # off-grid: 10 % 8 != 0
    suf = [1, 2, 3]
    eng = ServingEngine(params_int8, CFG_INT8, serving)
    eng.start()
    try:
        pid = eng.register_prefix(pre)
        got = list(eng.submit(suf, max_new_tokens=6, prefix=pid).stream())
        stats = eng.stats()
    finally:
        eng.stop()
    want, _ = _run(params_int8, serving, [pre + suf], cfg=CFG_INT8)
    assert got == want[0]
    assert stats["prefix_install_copies"] == 1  # dense install, counted
