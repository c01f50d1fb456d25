"""Continuous-batching serving engine: staggered slots must reproduce the
single-sequence reference exactly (greedy decoding, f32 CPU determinism)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vtpu.models import ModelConfig, init_params
from vtpu.models.transformer import greedy_generate
from vtpu.serving import Request, ServingConfig, ServingEngine

# Heavyweight tier: compile-bound, tens of seconds
# each; CI runs them separately so the unit tier stays under two minutes.
pytestmark = pytest.mark.slow

CFG = ModelConfig(
    vocab=128, d_model=64, n_heads=2, n_layers=2, d_ff=128,
    max_seq=64, head_dim=32, dtype=jnp.float32, use_pallas=False,
)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.key(0), CFG)


def _reference(params, prompt, steps):
    out = greedy_generate(params, CFG, jnp.asarray(prompt, jnp.int32)[None], steps)
    return [int(t) for t in out[0]]


def _prompt(seed, n):
    return list(jax.random.randint(jax.random.key(seed), (n,), 0, CFG.vocab, jnp.int32))


def test_single_request_matches_reference(params):
    eng = ServingEngine(params, CFG, ServingConfig(
        slots=2, prefill_buckets=(16, 32), max_new_tokens=8))
    eng.start()
    try:
        prompt = _prompt(1, 10)
        got = list(eng.submit(prompt, max_new_tokens=8).stream())
        assert got == _reference(params, prompt, 8)
    finally:
        eng.stop()


def _solo(params, cfg_serving, prompt, steps):
    """The same prompt through a fresh engine with identical slot geometry —
    the isolation oracle (same compiled shapes, no neighbors)."""
    eng = ServingEngine(params, CFG, cfg_serving)
    eng.start()
    try:
        return list(eng.submit(prompt, max_new_tokens=steps).stream())
    finally:
        eng.stop()


def test_staggered_requests_are_isolated(params):
    """Requests of different lengths admitted at different times must each
    match their SOLO run through the same engine geometry — slot neighbors
    must not perturb a sequence. (Comparing against the unbatched reference
    would test numerics, not isolation: a near-tied argmax can flip with
    batch shape.)"""
    serving = ServingConfig(slots=3, prefill_buckets=(8, 16, 32), max_new_tokens=12)
    prompts = [_prompt(2, 5), _prompt(3, 13), _prompt(4, 27)]
    want = [_solo(params, serving, p, 12) for p in prompts]
    eng = ServingEngine(params, CFG, serving)
    eng.start()
    try:
        reqs = [eng.submit(p, max_new_tokens=12) for p in prompts]
        results = [list(r.stream()) for r in reqs]
        for p, got, solo in zip(prompts, results, want):
            assert got == solo, f"prompt len {len(p)}"
    finally:
        eng.stop()


def test_slot_reuse_more_requests_than_slots(params):
    serving = ServingConfig(slots=2, prefill_buckets=(16,), max_new_tokens=4)
    prompts = [_prompt(i + 10, 6 + i) for i in range(5)]
    want = [_solo(params, serving, p, 4) for p in prompts]
    eng = ServingEngine(params, CFG, serving)
    eng.start()
    try:
        reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
        for r, solo in zip(reqs, want):
            assert list(r.stream()) == solo
    finally:
        eng.stop()


def test_oversized_prompt_rejected(params):
    """Raised to the SUBMITTER on its own thread — the serving loop must
    survive and keep serving other clients."""
    eng = ServingEngine(params, CFG, ServingConfig(
        slots=1, prefill_buckets=(8,), max_new_tokens=2))
    eng.start()
    try:
        with pytest.raises(ValueError, match="exceeds the largest usable bucket"):
            eng.submit(list(range(9)))
        # the loop is still alive and serves a valid request afterwards
        out = list(eng.submit([1, 2, 3], max_new_tokens=2).stream())
        assert len(out) == 2
    finally:
        eng.stop()


def test_cancellation_frees_slot(params):
    """A cancelled request stops decoding and its slot admits the next
    waiter (client-disconnect path)."""
    serving = ServingConfig(slots=1, prefill_buckets=(16,), max_new_tokens=1000)
    eng = ServingEngine(params, CFG, serving)
    eng.start()
    try:
        hog = eng.submit(_prompt(1, 8), max_new_tokens=1000)
        next(iter(hog.stream()))  # it is being served
        hog.cancel()
        follow = eng.submit(_prompt(2, 8), max_new_tokens=3)
        assert len(list(follow.stream())) == 3  # would starve if slot leaked
    finally:
        eng.stop()


def test_budget_clamped_to_cache(params):
    """max_new_tokens beyond the KV cache is clamped, never wrapped."""
    eng = ServingEngine(params, CFG, ServingConfig(
        slots=1, prefill_buckets=(16,), max_new_tokens=8))
    eng.start()
    try:
        got = list(eng.submit(_prompt(5, 10), max_new_tokens=10_000).stream())
        assert len(got) == CFG.max_seq - 10  # 64 - prompt
    finally:
        eng.stop()


def test_tensor_parallel_serving(params):
    """The engine serves with tp-sharded weights and a head-sharded KV cache
    on a multi-device mesh; logits agree with the single-device path."""
    from vtpu.parallel.mesh import make_mesh
    from vtpu.models.slots import batched_decode_step, prefill_into_slot
    from vtpu.models.transformer import init_kv_cache
    from vtpu.parallel.sharding import shard_kv_cache, shard_params

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    mesh = make_mesh(2, tp=2)  # tp-only serving mesh; n_heads=2 shards over tp=2

    # direct numerical check: sharded vs unsharded decode logits
    cache0 = init_kv_cache(CFG, 2)
    padded = jnp.zeros((1, 16), jnp.int32).at[0, :9].set(
        jnp.asarray(_prompt(7, 9), jnp.int32))
    _, cache0 = prefill_into_slot(params, CFG, cache0, padded, jnp.int32(0), jnp.int32(9))
    toks = jnp.asarray([3, 0], jnp.int32)
    act = jnp.asarray([True, False])
    want, _ = batched_decode_step(params, CFG, cache0, toks, act)

    sp = shard_params(params, mesh)
    cache_s = shard_kv_cache(cache0, mesh)
    got, _ = jax.jit(batched_decode_step, static_argnums=1)(sp, CFG, cache_s, toks, act)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)

    # full engine smoke on the mesh
    eng = ServingEngine(params, CFG, ServingConfig(
        slots=2, prefill_buckets=(16,), max_new_tokens=4), mesh=mesh)
    eng.start()
    try:
        out = list(eng.submit(_prompt(8, 7), max_new_tokens=4).stream())
        assert len(out) == 4 and all(0 <= t < CFG.vocab for t in out)
    finally:
        eng.stop()

    # dp>1 meshes are rejected: decode would replicate work across dp groups
    with pytest.raises(ValueError, match="tp-only"):
        ServingEngine(params, CFG, ServingConfig(slots=2, prefill_buckets=(16,)),
                      mesh=make_mesh(8, tp=2))


def test_request_stream_api():
    q = Request(tokens=jnp.zeros((1,), jnp.int32))
    q.out.put(5)
    q.out.put(None)
    assert list(q.stream()) == [5]


def test_ssm_prefill_state_matches_stepped_decode():
    """ssm_prefill's scan-derived state equals stepping the recurrent decode
    over the prompt, within platform matmul precision (the exactness claim
    lives HERE, with tolerances — not as token equality, where a small
    numeric gap could flip an argmax on another seed/backend)."""
    import numpy as np

    from vtpu.models.ssm import (
        SSMConfig, init_ssm_params, init_ssm_state, ssm_decode_step,
        ssm_prefill,
    )

    cfg = SSMConfig(vocab=96, d_model=32, n_layers=2, d_state=8,
                    dtype=jnp.float32)
    params = init_ssm_params(jax.random.key(3), cfg)
    prompt = [int(t) % cfg.vocab for t in _prompt(7, 9)]
    state = init_ssm_state(cfg, 1)
    for t in prompt:
        logits_ref, state = ssm_decode_step(
            params, cfg, state, jnp.asarray([t], jnp.int32))
    padded = jnp.zeros((1, 16), jnp.int32).at[0, :len(prompt)].set(
        jnp.asarray(prompt))
    logits_seq, state_pf = ssm_prefill(params, cfg, padded,
                                       jnp.int32(len(prompt)))
    np.testing.assert_allclose(np.asarray(state_pf["h"]),
                               np.asarray(state["h"]), rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(state_pf["conv"]),
                               np.asarray(state["conv"]), rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(np.asarray(logits_seq[0, len(prompt) - 1]),
                               np.asarray(logits_ref[0]), rtol=1e-3, atol=1e-3)


def test_ssm_slot_model_matches_recurrent_reference():
    """The engine serves the selective-SSM family through its adapter: two
    staggered slots must each reproduce the single-request composition of
    the SAME prefill + recurrent-decode path exactly — this isolates the
    engine machinery (slots, masking, streaming) from numeric path
    differences, which the prefill-state test above bounds separately."""
    from vtpu.models.ssm import (
        SSMConfig, init_ssm_params, ssm_decode_step, ssm_prefill,
    )
    from vtpu.serving.adapters import SsmSlotModel

    cfg = SSMConfig(vocab=96, d_model=32, n_layers=2, d_state=8,
                    dtype=jnp.float32)
    params = init_ssm_params(jax.random.key(3), cfg)

    def reference(prompt, steps, bucket):
        padded = jnp.zeros((1, bucket), jnp.int32).at[0, :len(prompt)].set(
            jnp.asarray(prompt))
        logits, state = ssm_prefill(params, cfg, padded,
                                    jnp.int32(len(prompt)))
        logits = logits[0, len(prompt) - 1]
        out = []
        for _ in range(steps):
            tok = int(jnp.argmax(logits))
            out.append(tok)
            logits, state = ssm_decode_step(
                params, cfg, state, jnp.asarray([tok], jnp.int32))
            logits = logits[0]
        return out

    eng = ServingEngine(
        serving=ServingConfig(slots=2, prefill_buckets=(8, 16),
                              max_new_tokens=6),
        model=SsmSlotModel(params, cfg),
    )
    eng.start()
    try:
        p1 = [int(t) % cfg.vocab for t in _prompt(11, 5)]
        p2 = [int(t) % cfg.vocab for t in _prompt(12, 9)]
        r1 = eng.submit(p1, max_new_tokens=6)
        r2 = eng.submit(p2, max_new_tokens=6)
        got1, got2 = list(r1.stream()), list(r2.stream())
        assert got1 == reference(p1, 6, 8)
        assert got2 == reference(p2, 6, 16)
    finally:
        eng.stop()


def test_moe_slot_model_serves_and_matches_prefill_path():
    """The engine serves the MoE family through its adapter: slot decode with
    the routed-expert FFN must match the single-request composition of
    moe_prefill + the shared decode loop (engine machinery isolated from
    numeric path differences, as with the SSM test)."""
    from vtpu.models.moe import MoEConfig, init_moe_params, moe_prefill
    from vtpu.models.transformer import decode_layer_loop
    from vtpu.models.moe import moe_decode_ffn
    from vtpu.serving.adapters import MoeSlotModel

    cfg = MoEConfig(vocab=96, d_model=64, n_heads=2, n_layers=2, d_ff=64,
                    n_experts=4, top_k=2, max_seq=32, head_dim=32,
                    dtype=jnp.float32)
    params = init_moe_params(jax.random.key(5), cfg)

    def reference(prompt, steps, bucket):
        padded = jnp.zeros((1, bucket), jnp.int32).at[0, :len(prompt)].set(
            jnp.asarray(prompt))
        logits, cache = moe_prefill(params, cfg, padded)
        cache["len"] = jnp.asarray([len(prompt)], jnp.int32)
        logits = logits[0, len(prompt) - 1]
        out = []
        for _ in range(steps):
            tok = int(jnp.argmax(logits))
            out.append(tok)
            pos0 = cache["len"][0]

            def write_kv(l, kv, k, v):
                return {
                    "k": jax.lax.dynamic_update_slice(
                        kv["k"], k[None], (l, 0, pos0, 0, 0)),
                    "v": jax.lax.dynamic_update_slice(
                        kv["v"], v[None], (l, 0, pos0, 0, 0)),
                }

            lg, new_kv = decode_layer_loop(
                params, cfg, cache, jnp.asarray([tok], jnp.int32), 0,
                write_kv, ffn_fn=moe_decode_ffn(cfg))
            cache = {**new_kv, "len": cache["len"] + 1}
            logits = lg[0]
        return out

    eng = ServingEngine(
        serving=ServingConfig(slots=2, prefill_buckets=(8, 16),
                              max_new_tokens=5),
        model=MoeSlotModel(params, cfg),
    )
    eng.start()
    try:
        p1 = [int(t) % cfg.vocab for t in _prompt(21, 5)]
        p2 = [int(t) % cfg.vocab for t in _prompt(22, 9)]
        r1 = eng.submit(p1, max_new_tokens=5)
        r2 = eng.submit(p2, max_new_tokens=5)
        got1, got2 = list(r1.stream()), list(r2.stream())
        assert got1 == reference(p1, 5, 8)
        assert got2 == reference(p2, 5, 16)
    finally:
        eng.stop()


def test_moe_decode_isolated_from_retired_slots():
    """Routing in a decode tick sees every slot's token — including stale
    ones in retired slots. With the decode capacity override, a capacity
    drop can never be triggered by garbage, so a request's tokens match its
    solo run regardless of what previously occupied the other slots."""
    from vtpu.models.moe import MoEConfig, init_moe_params
    from vtpu.serving.adapters import MoeSlotModel

    # tight routing: 2 experts, top-1-ish pressure via top_k=2 over 4 slots
    cfg = MoEConfig(vocab=96, d_model=64, n_heads=2, n_layers=2, d_ff=64,
                    n_experts=2, top_k=2, capacity_factor=1.0, max_seq=32,
                    head_dim=32, dtype=jnp.float32)
    params = init_moe_params(jax.random.key(6), cfg)
    serving = ServingConfig(slots=4, prefill_buckets=(8,), max_new_tokens=6)
    probe = [int(t) % cfg.vocab for t in _prompt(31, 6)]

    def run(dirty: bool):
        eng = ServingEngine(serving=serving, model=MoeSlotModel(params, cfg))
        eng.start()
        try:
            if dirty:  # occupy + retire every slot, leaving stale tokens
                warm = [eng.submit([(i * 7 + 1) % cfg.vocab] * 5,
                                   max_new_tokens=3) for i in range(4)]
                for w in warm:
                    list(w.stream())
            return list(eng.submit(probe, max_new_tokens=6).stream())
        finally:
            eng.stop()

    assert run(dirty=True) == run(dirty=False)


def test_mesh_engine_with_int8_kv_cache():
    """TransformerSlotModel with a tp mesh AND kv_int8: the sharded-alloc
    path must cover the scale planes (kv_cache_shardings quantized=True) and
    the engine must serve through the post-scale attention under the mesh."""
    import dataclasses

    from vtpu.parallel.mesh import make_axis_mesh
    from vtpu.serving.adapters import TransformerSlotModel

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    cfg = dataclasses.replace(CFG, kv_int8=True)
    params = init_params(jax.random.key(0), cfg)
    mesh = make_axis_mesh("tp", 2)  # n_heads=2 shards over tp=2
    eng = ServingEngine(
        model=TransformerSlotModel(params, cfg, mesh=mesh),
        serving=ServingConfig(slots=2, prefill_buckets=(16,), max_new_tokens=4),
    )
    assert eng.state["k"].dtype == jnp.int8
    assert "k_scale" in eng.state
    eng.start()
    try:
        toks = list(eng.submit([3, 1, 4, 1, 5]).stream())
        assert len(toks) == 4
    finally:
        eng.stop()


# ------------------------------------------------------------- speculative


def _spec_cfg(**kw):
    base = dict(slots=2, prefill_buckets=(16, 32), max_new_tokens=16,
                spec_tokens=4)
    base.update(kw)
    return ServingConfig(**base)


def test_spec_decode_stream_identical_to_plain(params):
    """The speculative engine must emit EXACTLY the plain engine's greedy
    stream — drafts only change how many ticks it takes, never a token.

    The invariant is engine-vs-engine deliberately: on this random tiny
    model, different executables (engine vs lockstep greedy_generate, padded
    vs unpadded prefill) flip argmax at repetition attractors and near-tie
    first tokens — both valid greedy streams, a numerics fact that predates
    speculation. The engine-vs-reference anchor lives in
    test_single_request_matches_reference at its stable seed/horizon; what
    speculation must guarantee is that it never changes ITS engine's
    stream."""
    for seed, n in ((1, 10), (2, 7), (3, 12)):
        prompt = _prompt(seed, n)
        plain = _solo(params, _spec_cfg(spec_tokens=0), prompt, 16)
        spec = _solo(params, _spec_cfg(), prompt, 16)
        assert spec == plain


def test_spec_decode_repetitive_prompt_fewer_ticks(params):
    """A repetitive stream is where prompt-lookup pays: the engine emits the
    same tokens in FEWER verify/decode dispatches than plain decode would
    take (the accepted-drafts win), and still matches greedy exactly."""
    # a prompt whose greedy continuation settles into repetition (random
    # tiny models do this readily; the reference oracle keeps us honest)
    prompt = [5, 6, 7, 8, 5, 6, 7, 8, 5, 6]
    steps = 24
    eng = ServingEngine(params, CFG, _spec_cfg(max_new_tokens=steps))
    calls = {"spec": 0, "decode": 0}
    # plain fallback ticks route through the fused sampled step on the
    # default (device-sampling) path; _decode exists only for custom samplers
    spec_fn, decode_fn = eng._spec, eng._decode_sampled

    def counting_spec(*a, **kw):
        calls["spec"] += 1
        return spec_fn(*a, **kw)

    def counting_decode(*a, **kw):
        calls["decode"] += 1
        return decode_fn(*a, **kw)

    eng._spec, eng._decode_sampled = counting_spec, counting_decode
    eng.start()
    try:
        got = list(eng.submit(prompt, max_new_tokens=steps).stream())
    finally:
        eng.stop()
    assert got == _reference(params, prompt, steps)
    # warm-up compiles per bucket don't count: subtract them
    warm = len(eng._kv_buckets)
    ticks = calls["spec"] + calls["decode"] - 2 * warm
    # plain decode would take steps-1 ticks (first token comes from prefill)
    assert ticks < steps - 1, (calls, warm)


def test_spec_decode_staggered_slots_isolated(params):
    """Speculation over a staggered pool (different lengths, ragged
    acceptance) must not leak between slots. Oracle: each prompt SOLO
    through a fresh engine with identical slot geometry — engine-vs-engine,
    full streams, so a dropped or shifted token can never slip through an
    accidental realignment (the lockstep reference disagrees with the
    engine on the padded-prefill first token at some seeds)."""
    serving = _spec_cfg(max_new_tokens=12)
    eng = ServingEngine(params, CFG, serving)
    eng.start()
    try:
        p1, p2 = _prompt(4, 9), [5, 6, 7, 8, 5, 6, 7, 8]
        r1 = eng.submit(p1, max_new_tokens=12)
        it1 = iter(r1.stream())
        first1 = next(it1)  # slot 0 mid-flight before slot 1 joins
        r2 = eng.submit(p2, max_new_tokens=12)
        got2 = list(r2.stream())
        got1 = [first1] + [t for t in it1 if t is not None]
    finally:
        eng.stop()
    assert got1 == _solo(params, serving, p1, 12)
    assert got2 == _solo(params, serving, p2, 12)


def test_spec_decode_with_int8_kv(params):
    """Speculation composes with the int8 KV cache: the quantized verify
    path must emit the same stream as the quantized plain path."""
    import dataclasses

    qcfg = dataclasses.replace(CFG, kv_int8=True)
    qparams = init_params(jax.random.key(0), qcfg)
    prompt = [5, 6, 7, 8, 5, 6, 7, 8, 5, 6]

    def run(spec):
        eng = ServingEngine(qparams, qcfg, _spec_cfg(
            spec_tokens=spec, max_new_tokens=16))
        eng.start()
        try:
            return list(eng.submit(prompt, max_new_tokens=16).stream())
        finally:
            eng.stop()

    assert run(4) == run(0)


def test_spec_disabled_for_custom_sampler(params):
    """A non-greedy sampler makes argmax verification unsound; the engine
    must fall back to plain decode rather than emit a diverged stream."""
    eng = ServingEngine(params, CFG, _spec_cfg(),
                        sample=lambda logits: int(jnp.argmax(logits)))
    assert eng._spec_tokens == 0 and eng._spec is None
    eng2 = ServingEngine(params, CFG, _spec_cfg())
    assert eng2._spec_tokens == 4 and eng2._spec is not None


def test_lookup_draft_prefers_longest_recent_match():
    from vtpu.serving.engine import lookup_draft

    #          0  1  2  3  4  5  6  7
    history = [1, 2, 3, 9, 1, 2, 3, 4, 1, 2, 3]
    # trigram [1,2,3] matched at its most recent earlier occurrence (idx 4)
    assert lookup_draft(history, 3, 3) == [4, 1, 2]
    # continuation shorter than k: zero-padded
    assert lookup_draft([7, 8, 7, 8, 7], 4, 2)[:1] == [8]
    # no match at any n-gram size
    assert lookup_draft([1, 2, 3], 4, 3) is None
    assert lookup_draft([], 4, 3) is None


def test_spec_decode_moe_family(params):
    """Speculation rides the shared trunk for the MoE family too: the spec
    engine's stream equals the plain MoE engine's stream."""
    from vtpu.models.moe import MoEConfig, init_moe_params
    from vtpu.serving.adapters import MoeSlotModel

    mcfg = MoEConfig(
        vocab=128, d_model=64, n_heads=2, n_layers=2, d_ff=128,
        max_seq=64, head_dim=32, dtype=jnp.float32,
        n_experts=4, top_k=2,
    )
    mparams = init_moe_params(jax.random.key(0), mcfg)
    prompt = [5, 6, 7, 8, 5, 6, 7, 8, 5, 6]

    def run(spec):
        eng = ServingEngine(
            model=MoeSlotModel(mparams, mcfg),
            serving=_spec_cfg(spec_tokens=spec, max_new_tokens=12),
        )
        eng.start()
        try:
            return list(eng.submit(prompt, max_new_tokens=12).stream())
        finally:
            eng.stop()

    assert run(4) == run(0)


# --------------------------------------------------------- chunked prefill


def test_chunked_prefill_matches_oneshot_cache_and_logits(params):
    """ceil(n/C) chunk forwards must leave the same KV and final logits as
    the one-shot bucketed prefill (tolerances: different executables)."""
    from vtpu.models.transformer import init_kv_cache
    from vtpu.models.slots import chunked_prefill_into_slot, prefill_into_slot

    n, c = 21, 8
    prompt = jnp.asarray(_prompt(9, n), jnp.int32)
    cache_a = init_kv_cache(CFG, 3)
    padded = jnp.zeros((1, 32), jnp.int32).at[0, :n].set(prompt)
    logits_a, cache_a = prefill_into_slot(
        params, CFG, cache_a, padded, jnp.int32(1), jnp.int32(n))

    cache_b = init_kv_cache(CFG, 3)
    pad = -(-n // c) * c
    pb = jnp.zeros((1, pad), jnp.int32).at[0, :n].set(prompt)
    fn = jax.jit(chunked_prefill_into_slot, static_argnums=(1,))
    for i in range(pad // c):
        off = i * c
        logits_b, cache_b = fn(params, CFG, cache_b, pb[:, off:off + c],
                               jnp.int32(1), jnp.int32(off),
                               jnp.int32(min(off + c, n)))
    assert int(cache_b["len"][1]) == n
    for key in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(cache_a[key][:, 1, :n]), np.asarray(cache_b[key][:, 1, :n]),
            rtol=1e-4, atol=1e-5)
    last = logits_b[0, (n - 1) - (pad - c)]
    np.testing.assert_allclose(
        np.asarray(logits_a), np.asarray(last), rtol=1e-4, atol=1e-4)


def test_chunked_prefill_admits_beyond_largest_bucket(params):
    """A prompt longer than every bucket admits through chunks, generates
    its budget, and leaves neighbors untouched (solo oracle with identical
    geometry — same executables both runs)."""
    serving = ServingConfig(slots=2, prefill_buckets=(16,),
                            max_new_tokens=6, prefill_chunk=16)
    long_p = _prompt(11, 40)  # > bucket 16, needs 3 chunks
    short_p = _prompt(12, 9)
    want_long = _solo(params, serving, long_p, 6)
    want_short = _solo(params, serving, short_p, 6)
    assert len(want_long) == 6
    eng = ServingEngine(params, CFG, serving)
    eng.start()
    try:
        r1 = eng.submit(long_p, max_new_tokens=6)
        r2 = eng.submit(short_p, max_new_tokens=6)
        assert list(r1.stream()) == want_long
        assert list(r2.stream()) == want_short
    finally:
        eng.stop()
    # beyond max_context still refuses, with the chunked cap in the message
    eng2 = ServingEngine(params, CFG, serving)
    try:
        with pytest.raises(ValueError, match="max_context"):
            eng2.submit(list(range(CFG.max_seq + 1)))
    finally:
        eng2.stop()


def test_chunked_prefill_config_validation(params):
    """A chunk size that does not divide max_context would let the last
    chunk's scatter clamp into earlier positions — rejected at build."""
    with pytest.raises(ValueError, match="must divide"):
        ServingEngine(params, CFG, ServingConfig(
            slots=1, prefill_buckets=(16,), prefill_chunk=24))
    # SSM has no chunkable KV trunk: chunking silently stays off
    from vtpu.models.ssm import SSMConfig, init_ssm_params
    from vtpu.serving.adapters import SsmSlotModel

    scfg = SSMConfig(vocab=64, d_model=32, d_state=8, n_layers=2)
    eng = ServingEngine(
        model=SsmSlotModel(init_ssm_params(jax.random.key(0), scfg), scfg),
        serving=ServingConfig(slots=1, prefill_buckets=(16,), prefill_chunk=8),
    )
    assert eng._prefill_chunk is None


def test_chunked_prefill_composes_with_speculation(params):
    """Chunk-admitted requests speculate like any other: stream equals the
    plain chunked engine's stream."""
    long_p = ([5, 6, 7, 8] * 12)[:44]

    def run(spec):
        serving = ServingConfig(slots=2, prefill_buckets=(16,),
                                max_new_tokens=10, prefill_chunk=16,
                                spec_tokens=spec)
        return _solo(params, serving, long_p, 10)

    assert run(4) == run(0)


def test_chunked_admission_interleaves_with_decode(params):
    """The head-of-line bound is real: while a long prompt admits chunk by
    chunk, the live slot gets a decode tick between chunks (call order
    chunk,decode,chunk,decode,... — never all chunks back-to-back)."""
    serving = ServingConfig(slots=2, prefill_buckets=(16,),
                            max_new_tokens=20, prefill_chunk=16)
    eng = ServingEngine(params, CFG, serving)
    order = []
    # default config fuses sampling into the decode step (_decode_sampled);
    # _decode exists only on the host-sampler fallback
    chunk_fn, dec_fn = eng._prefill_chunk, eng._decode_sampled

    def chunk_w(*a, **kw):
        order.append("chunk")
        return chunk_fn(*a, **kw)

    def dec_w(*a, **kw):
        order.append("decode")
        return dec_fn(*a, **kw)

    eng._prefill_chunk, eng._decode_sampled = chunk_w, dec_w
    # both submitted BEFORE the loop starts: the first sweep admits the
    # short prompt into slot 0 (bucketed) and parks the long one (chunked),
    # so decode ticks and admission chunks deterministically coexist
    live = eng.submit(_prompt(1, 8), max_new_tokens=20)
    long_req = eng.submit(_prompt(11, 48), max_new_tokens=4)  # 3 chunks
    eng.start()
    try:
        assert len(list(long_req.stream())) == 4
        assert len(list(live.stream())) == 20
    finally:
        eng.stop()
    # strip warm-up entries (they precede any admission)
    chunks = [i for i, o in enumerate(order) if o == "chunk"]
    serving_chunks = chunks[-3:]  # the admission's three chunks
    between = order[serving_chunks[0]:serving_chunks[-1]]
    assert "decode" in between, order[-12:]


# ---------------------------------------------------------- prefix caching


def test_prefix_cache_stream_matches_full_prompt(params):
    """register_prefix + suffix submit must generate the same stream as the
    full prompt through the same chunked engine (attractor prompt: stable
    across chunk-boundary executables)."""
    serving = ServingConfig(slots=2, prefill_buckets=(16,),
                            max_new_tokens=8, prefill_chunk=16)
    pre = ([5, 6, 7, 8] * 6)[:20]  # off-grid prefix (20 % 16 != 0)
    suf = [5, 6, 7, 8, 5, 6]
    want = _solo(params, serving, pre + suf, 8)

    eng = ServingEngine(params, CFG, serving)
    eng.start()
    try:
        pid = eng.register_prefix(pre)
        got = list(eng.submit(suf, max_new_tokens=8, prefix=pid).stream())
        # two requests sharing the prefix: the install path is reusable
        got2 = list(eng.submit(suf, max_new_tokens=8, prefix=pid).stream())
    finally:
        eng.stop()
    assert got == want == got2


def test_prefix_cache_empty_suffix_and_validation(params):
    serving = ServingConfig(slots=1, prefill_buckets=(16,),
                            max_new_tokens=4, prefill_chunk=16)
    eng = ServingEngine(params, CFG, serving)
    eng.start()
    try:
        pid = eng.register_prefix([5, 6, 7, 8] * 4)
        # empty suffix: first token comes from the prefix's stored logits
        got = list(eng.submit([], max_new_tokens=4, prefix=pid).stream())
        assert len(got) == 4
        with pytest.raises(ValueError, match="unknown prefix"):
            eng.submit([1], prefix=999)
        with pytest.raises(ValueError, match="exceeds"):
            eng.submit(list(range(CFG.max_seq)), prefix=pid)
        with pytest.raises(ValueError, match="no room"):
            eng.register_prefix(list(range(CFG.max_seq)))
    finally:
        eng.stop()
    # chunking off: registration refuses up front
    eng2 = ServingEngine(params, CFG, ServingConfig(
        slots=1, prefill_buckets=(16,)))
    try:
        with pytest.raises(ValueError, match="requires prefill_chunk"):
            eng2.register_prefix([1, 2, 3])
    finally:
        eng2.stop()


def test_prefix_cache_composes_with_speculation(params):
    """Prefix-admitted requests speculate with the prefix in their lookup
    history: stream equality vs the plain prefix engine."""
    pre = ([5, 6, 7, 8] * 5)[:18]
    suf = [5, 6, 7, 8]

    def run(spec):
        eng = ServingEngine(params, CFG, ServingConfig(
            slots=2, prefill_buckets=(16,), max_new_tokens=10,
            prefill_chunk=16, spec_tokens=spec))
        eng.start()
        try:
            pid = eng.register_prefix(pre)
            return list(eng.submit(suf, max_new_tokens=10, prefix=pid).stream())
        finally:
            eng.stop()

    assert run(4) == run(0)


def test_unregister_prefix_releases_and_raced_submit_fails_softly(params):
    """unregister_prefix drops the pinned KV entry (long-lived engines with
    rotating system prompts must not leak device memory); a submit that
    raced past validation before the unregister retires with end-of-stream
    instead of killing the serving loop; the per-pad install executables
    survive so re-registration at the same pad does not recompile."""
    serving = ServingConfig(slots=2, prefill_buckets=(16,),
                            max_new_tokens=6, prefill_chunk=16)
    pre = [5, 6, 7, 8] * 4
    eng = ServingEngine(params, CFG, serving)
    try:
        pid = eng.register_prefix(pre)
        jits_before = dict(eng._install_jits)
        # race shape: submitted (validated) while registered, admitted after
        # unregister — the engine loop has not started yet, so the request
        # is still queued when the prefix disappears
        raced = eng.submit([5, 6], max_new_tokens=6, prefix=pid)
        eng.unregister_prefix(pid)
        assert eng._prefixes == {}
        with pytest.raises(ValueError, match="unknown prefix"):
            eng.unregister_prefix(pid)
        with pytest.raises(ValueError, match="unknown prefix"):
            eng.submit([1], prefix=pid)
        eng.start()
        assert list(raced.stream()) == []  # unserved, not a hang or a crash
        # the loop survived: re-register at the same pad (no recompile) and
        # serve a normal prefix request end-to-end
        pid2 = eng.register_prefix(pre)
        assert all(eng._install_jits[pad] is exe
                   for pad, exe in jits_before.items())
        got = list(eng.submit([5, 6], max_new_tokens=6, prefix=pid2).stream())
        assert len(got) == 6
    finally:
        eng.stop()


def test_spec_adaptive_gate_and_stats(params):
    """Below-breakeven acceptance pauses drafting (cooloff), the cooloff
    expiry re-probes with an optimistic EMA, and stats() reports the
    counters. An unattainable threshold must never change the stream."""
    eng = ServingEngine(params, CFG, _spec_cfg())
    assert eng._spec_allowed()
    eng._spec_cooloff = 3
    assert not eng._spec_allowed()
    assert not eng._spec_allowed()
    assert not eng._spec_allowed()  # hits 0: next call re-probes
    assert eng._spec_allowed()
    # re-probe starts slightly above breakeven, not at the optimistic
    # maximum: a losing probe must shut back off within a few ticks
    assert eng._spec_ema == eng.serving.spec_min_mean + 0.25

    prompt = [5, 6, 7, 8, 5, 6, 7, 8, 5, 6]

    def run(**kw):
        serving = _spec_cfg(max_new_tokens=16, **kw)
        eng = ServingEngine(params, CFG, serving)
        eng.start()
        try:
            out = list(eng.submit(prompt, max_new_tokens=16).stream())
        finally:
            eng.stop()
        return out, eng.stats()

    plain, _ = run(spec_tokens=0)
    # threshold no speculation can meet: the gate must only cost ticks,
    # never tokens
    got, stats = run(spec_min_mean=99.0, spec_cooloff_ticks=4)
    assert got == plain
    assert stats["spec_ticks"] >= 1  # probed at least once
    assert stats["decode_ticks"] >= 1  # then cooled off to plain ticks
    assert stats["generated_tokens"] == 16
    assert stats["admissions"] == 1
    # healthy acceptance keeps speculating (the repetitive stream)
    got2, stats2 = run()
    assert got2 == plain
    assert stats2["spec_ema"] > 1.25
    assert stats2["mean_emitted_per_spec_tick"] > 1.25


def test_choose_kv_int8_measured_edges():
    """The router encodes INT8_AB_r05's measured cells: int8 wins at
    batch >= 16 or windows <= 1024; the 8 x 2048 corner is the one
    measured regression (-4.4%) and routes bf16."""
    from vtpu.serving.engine import choose_kv_int8

    assert choose_kv_int8(8, 1024) is True
    assert choose_kv_int8(32, 1024) is True
    assert choose_kv_int8(32, 2048) is True
    assert choose_kv_int8(8, 2048) is False


def test_kv_int8_auto_resolves_at_engine_construction(params):
    """ModelConfig.kv_int8="auto" must resolve to a concrete bool via the
    measured router BEFORE any cache is built ("auto" is truthy — leaking
    it into init_kv_cache would quantize everywhere)."""
    import dataclasses

    cfg_auto = dataclasses.replace(CFG, kv_int8="auto")
    # CFG.max_seq=64 <= 1024 -> router says int8 regardless of slots
    eng = ServingEngine(params, cfg_auto, ServingConfig(
        slots=2, prefill_buckets=(16,), max_new_tokens=2))
    eng.start()
    try:
        assert eng.cfg.kv_int8 is True
        assert "k_scale" in eng.state
        out = list(eng.submit(_prompt(1, 8), max_new_tokens=2).stream())
        assert len(out) == 2
    finally:
        eng.stop()
