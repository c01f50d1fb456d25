"""Fast (non-slow) serving smoke tier.

tests/test_serving.py is entirely behind the ``slow`` marker (compile-bound,
tens of seconds each), so before this file tier-1 never started the engine at
all — a broken serving loop shipped green. This tier keeps the model small
enough (1 layer, d_model 32, one prefill bucket) that engine construction +
warm compiles stay a few seconds, and covers the lifecycle the slow tier
proves exhaustively: submit -> stream -> retire with slot reuse, cancellation,
device-vs-host greedy sampler parity, pipelined-vs-sync parity, the one-
device_get-per-tick transfer contract, and spec-decode acceptance under
device sampling.
"""

import jax
import jax.numpy as jnp
import pytest

from vtpu.models import ModelConfig, init_params
from vtpu.serving import ServingConfig, ServingEngine

CFG = ModelConfig(
    vocab=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
    max_seq=32, head_dim=16, dtype=jnp.float32, use_pallas=False,
)
SERVING = ServingConfig(slots=2, prefill_buckets=(8,), max_new_tokens=6)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.key(0), CFG)


def _prompt(seed, n):
    return [int(t) for t in jax.random.randint(
        jax.random.key(seed), (n,), 0, CFG.vocab, jnp.int32)]


def _run(params, serving, prompts, steps=6, **engine_kw):
    eng = ServingEngine(params, CFG, serving, **engine_kw)
    eng.start()
    try:
        reqs = [eng.submit(p, max_new_tokens=steps) for p in prompts]
        streams = [list(r.stream()) for r in reqs]
        stats = eng.stats()
    finally:
        eng.stop()
    return streams, stats


def test_submit_stream_retire_with_slot_reuse(params):
    """Three requests through two slots: every stream completes with exactly
    its token budget, all ids in-vocab, and the third request proves retire
    -> re-admit recycling under the pipelined loop (a stale lookahead token
    leaking into the recycled slot would corrupt its stream length or
    content)."""
    prompts = [_prompt(1, 5), _prompt(2, 7), _prompt(3, 3)]
    streams, stats = _run(params, SERVING, prompts)
    for got in streams:
        assert len(got) == 6
        assert all(0 <= t < CFG.vocab for t in got)
    assert stats["admissions"] == 3
    assert stats["device_sampling"] and stats["pipelined"]
    assert stats["pipelined_ticks"] > 0


def test_one_device_get_per_tick_contract(params):
    """The transfer contract, asserted via stats(): a default-config
    (device-sampled) decode tick performs EXACTLY one jax.device_get of B*4
    token bytes, and admission adds ZERO blocking syncs — first tokens ride
    the tick fetch (n*4 bytes per batched prefill dispatch) or, on an idle
    engine, one standalone batched admission fetch. The host-sampler
    fallback also fetches once per tick but pays B*vocab*4 logit bytes
    (its per-admission sync stays a counted legacy cost). Streams are
    drained before stop(), so every dispatched tick has been delivered and
    the ratios are exact."""
    streams, stats = _run(params, SERVING, [_prompt(4, 5), _prompt(5, 6)])
    assert stats["decode_ticks"] > 0
    assert stats["tick_fetches"] == stats["decode_ticks"]
    assert stats["device_gets"] == (
        stats["tick_fetches"] + stats["admission_fetches"])
    assert stats["device_gets_per_tick"] == 1.0
    assert stats["admission_syncs"] == 0
    hist = stats["prefill_batch_hist"]
    admission_bytes = sum(n * count * 4 for n, count in enumerate(hist))
    assert stats["bytes_fetched"] == (
        stats["decode_ticks"] * SERVING.slots * 4 + admission_bytes)
    # the host's share of a tick is the tick_phase_ms totals: one dispatch
    # and one fetch note a decode tick, an admission note a loop pass
    phases = stats["tick_phase_ms"]
    assert phases["dispatch"]["count"] == stats["decode_ticks"]
    assert phases["fetch"]["count"] == stats["device_gets"]
    assert phases["admission"]["count"] >= stats["decode_ticks"]
    assert phases["deliver"]["total_ms"] > 0.0

    _, hstats = _run(params, SERVING, [_prompt(4, 5)],
                     sample=lambda l: int(jnp.argmax(l)))
    assert hstats["device_gets_per_tick"] == 1.0
    assert hstats["admission_syncs"] == hstats["admissions"]
    assert (hstats["bytes_fetched"]
            == hstats["decode_ticks"] * SERVING.slots * CFG.vocab * 4)


def test_device_greedy_matches_host_greedy_token_for_token(params):
    """The fused on-device argmax (pipelined, tokens never leave the device
    between ticks) must emit the exact stream of the host argmax fallback
    (synchronous, full logits fetched per tick) — and of the forced-sync
    device path, isolating pipelining from sampling."""
    prompts = [_prompt(6, 5), _prompt(7, 7)]
    dev, dstats = _run(params, SERVING, prompts)
    host, hstats = _run(params, SERVING, prompts,
                        sample=lambda l: int(jnp.argmax(l)))
    sync, sstats = _run(
        params,
        ServingConfig(slots=2, prefill_buckets=(8,), max_new_tokens=6,
                      pipeline_decode=False),
        prompts)
    assert dstats["pipelined"] and not hstats["pipelined"]
    assert not sstats["pipelined"] and sstats["device_sampling"]
    assert dev == host == sync


def test_cancellation_mid_stream_and_engine_survives(params):
    """Cancel a live request: its stream terminates (finite), its slot frees,
    and the engine keeps serving later submissions."""
    eng = ServingEngine(params, CFG, SERVING)
    eng.start()
    try:
        victim = eng.submit(_prompt(8, 5), max_new_tokens=64)
        first = next(iter(victim.stream()))
        assert 0 <= first < CFG.vocab
        victim.cancel()
        leftover = list(victim.stream())
        assert len(leftover) < 64
        after = list(eng.submit(_prompt(9, 5), max_new_tokens=4).stream())
        assert len(after) == 4
    finally:
        eng.stop()


def test_temperature_stream_seeded_and_replayable(params):
    """temperature > 0 on-device sampling: same sampling_seed -> identical
    streams across engine instances (per-slot PRNG streams are engine
    state, not wall-clock), different seed -> (this model, these prompts)
    a different draw somewhere. Both requests are submitted BEFORE start()
    so admission lands in one deterministic sweep: a slot's key advances on
    every dispatched tick (all rows, active or not), so racing submits
    against a running loop would make the replay depend on tick/admission
    interleaving rather than the seed."""
    serving = ServingConfig(slots=2, prefill_buckets=(8,), max_new_tokens=6,
                            temperature=0.9, top_k=16, sampling_seed=123)
    prompts = [_prompt(10, 5), _prompt(11, 6)]

    def run_seeded(cfg):
        eng = ServingEngine(params, CFG, cfg)
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        eng.start()
        try:
            streams = [list(r.stream()) for r in reqs]
            stats = eng.stats()
        finally:
            eng.stop()
        return streams, stats

    a, astats = run_seeded(serving)
    b, _ = run_seeded(serving)
    assert a == b
    assert astats["pipelined"]  # temperature sampling still pipelines
    import dataclasses
    c, _ = run_seeded(dataclasses.replace(serving, sampling_seed=7))
    assert c != a


def test_spec_decode_acceptance_unchanged_under_device_sampling(params):
    """Speculation composes with device-side greedy sampling: a repetitive
    prompt speculates (spec_emitted > 0), the stream is token-identical to
    the plain device-sampled engine, and the engine correctly forces the
    synchronous loop (a spec tick drafts from host-side history)."""
    plain = ServingConfig(slots=2, prefill_buckets=(8,), max_new_tokens=8)
    spec = ServingConfig(slots=2, prefill_buckets=(8,), max_new_tokens=8,
                         spec_tokens=2, spec_min_mean=0.0)
    prompt = [3, 9, 3, 9, 3, 9]
    want, _ = _run(params, plain, [prompt], steps=8)
    got, stats = _run(params, spec, [prompt], steps=8)
    assert got == want
    assert stats["device_sampling"] and not stats["pipelined"]
    assert stats["spec_ticks"] > 0 and stats["spec_emitted"] > 0
    assert stats["device_gets_per_tick"] == 1.0


def test_logprobs_stream_pairs_with_tokens_and_disables_spec(params):
    """logprobs=True: every DECODED token gets exactly one logprob (<= 0;
    the prefill first token has none), and speculation is forced off — a
    verify tick returns ids only, so spec-emitted tokens would silently
    skew the stream/logprobs pairing."""
    import dataclasses
    serving = ServingConfig(slots=2, prefill_buckets=(8,), max_new_tokens=5,
                            logprobs=True)
    eng = ServingEngine(params, CFG, serving)
    eng.start()
    try:
        req = eng.submit(_prompt(12, 5), max_new_tokens=5)
        toks = list(req.stream())
    finally:
        eng.stop()
    assert len(toks) == 5
    assert len(req.logprobs) == 4
    assert all(lp <= 0.0 for lp in req.logprobs)
    spec_lp = dataclasses.replace(serving, spec_tokens=2, spec_min_mean=0.0)
    eng = ServingEngine(params, CFG, spec_lp)
    assert eng._spec_tokens == 0  # logprobs forces plain ticks


# ----------------------------------------------- batched async admission


def test_batched_admission_coalesces_and_matches_legacy(params):
    """Two same-bucket prompts waiting together admit as ONE [2, bucket]
    prefill dispatch (prefill_batch_hist), with zero blocking admission
    syncs, and the streams are token-identical to the legacy serial path
    (async_admission=False: per-prompt dispatch + blocking first-token
    sync)."""
    import dataclasses
    prompts = [_prompt(20, 5), _prompt(21, 7)]

    def run_presubmitted(serving):
        eng = ServingEngine(params, CFG, serving)
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        eng.start()
        try:
            streams = [list(r.stream()) for r in reqs]
            stats = eng.stats()
        finally:
            eng.stop()
        return streams, stats

    got, stats = run_presubmitted(SERVING)
    assert stats["batched_admission"]
    assert stats["prefill_batch_hist"][2] == 1  # one coalesced dispatch
    assert stats["admission_syncs"] == 0
    assert stats["admissions"] == 2
    legacy, lstats = run_presubmitted(
        dataclasses.replace(SERVING, async_admission=False))
    assert not lstats["batched_admission"]
    assert lstats["prefill_batch_hist"][1] == 2  # two serial dispatches
    assert lstats["admission_syncs"] == 2
    assert got == legacy


def test_coalescing_skips_other_bucket_waiters(params):
    """Same-bucket companions coalesce from BEHIND a different-bucket
    waiter without disturbing it. Regression: list.remove(req) used the
    dataclass-generated Request.__eq__, which compares jnp token arrays
    and RAISES when the scan passes the other-bucket request — the serving
    loop thread died and every stream ended early (Request is eq=False,
    identity semantics, precisely because every engine check is
    `is`-based)."""
    serving = ServingConfig(slots=3, prefill_buckets=(8, 16),
                            max_new_tokens=4)
    eng = ServingEngine(params, CFG, serving)
    reqs = [eng.submit(_prompt(50, 5), max_new_tokens=4),   # bucket 8
            eng.submit(_prompt(51, 12), max_new_tokens=4),  # bucket 16
            eng.submit(_prompt(52, 6), max_new_tokens=4)]   # bucket 8
    eng.start()
    try:
        streams = [list(r.stream()) for r in reqs]
        stats = eng.stats()
    finally:
        eng.stop()
    assert all(len(s) == 4 for s in streams)
    assert stats["admissions"] == 3
    assert stats["prefill_batch_hist"][2] >= 1  # the two bucket-8 coalesced


def test_prefill_budget_defers_admission_while_decoding(params):
    """With prefill_budget == one bucket, a 2-prompt burst arriving while a
    slot decodes admits ONE prompt per tick (two N=1 dispatches, never an
    N=2 batch); with no slot decoding the budget is BYPASSED and the same
    burst coalesces into one N=2 dispatch. White-box via _tick_head so the
    decoding state is exact, not a race against the loop thread."""
    from vtpu.serving.engine import Request
    serving = ServingConfig(slots=4, prefill_buckets=(8,), max_new_tokens=6,
                            prefill_budget=8)
    eng = ServingEngine(params, CFG, serving)
    occupant = Request(tokens=jnp.zeros((1,), jnp.int32))
    eng._slot_req[0] = occupant  # a decoding slot: the budget applies
    eng._slot_budget[0] = 5
    r1 = eng.submit(_prompt(22, 5), max_new_tokens=4)
    r2 = eng.submit(_prompt(23, 6), max_new_tokens=4)
    eng._tick_head()
    hist = eng.stats()["prefill_batch_hist"]
    assert hist[1] == 1 and hist[2] == 0  # one bucket fit the 8-token budget
    assert eng._slot_req[1] is r1 and r2 in eng._waiting
    eng._tick_head()  # budget refreshes per tick: the deferral was one tick
    hist = eng.stats()["prefill_batch_hist"]
    assert hist[1] == 2 and hist[2] == 0
    assert eng._slot_req[2] is r2
    eng._slot_req[0] = None
    eng.stop()

    # same burst, idle engine: bypassed budget coalesces both into one N=2
    eng = ServingEngine(params, CFG, serving)
    eng.submit(_prompt(22, 5), max_new_tokens=4)
    eng.submit(_prompt(23, 6), max_new_tokens=4)
    eng._tick_head()
    assert eng.stats()["prefill_batch_hist"][2] == 1
    eng.stop()


@pytest.mark.parametrize("budget, in_flight", [(0, 4), (8, 1), (16, 2)])
def test_prefill_budget_caps_chunked_admissions_in_flight(
        params, budget, in_flight):
    """Four long prompts at an idle engine with four free slots: without a
    budget all four start their chunks at once; with one, only as many as a
    tick's budget advances (budget // prefill_chunk), oldest first, and the
    rest keep their place in the queue until a lane ends. Every stream
    still comes out whole."""
    serving = ServingConfig(slots=4, prefill_buckets=(8,), max_new_tokens=4,
                            prefill_chunk=8, prefill_budget=budget)
    eng = ServingEngine(params, CFG, serving)
    reqs = [eng.submit(_prompt(40 + i, 20), max_new_tokens=3)
            for i in range(4)]
    eng._tick_head()
    assert eng.stats()["admitting_slots"] == in_flight
    assert [r for r in reqs if r in eng._waiting] == reqs[in_flight:]
    eng.start()
    try:
        streams = [list(r.stream()) for r in reqs]
    finally:
        eng.stop()
    assert all(len(s) == 3 for s in streams)


def test_idle_wait_admits_into_first_free_slot(params):
    """Regression for the hardcoded `_admit(0, req)`: _idle_wait must never
    pick a slot itself — the request joins the waiting list and the next
    _tick_head admits it into the first FREE slot, even when slot 0 is
    occupied (a state the old guard made unreachable, which is exactly why
    a refactor could silently break it)."""
    from vtpu.serving.engine import Request
    eng = ServingEngine(params, CFG, SERVING)
    occupant = Request(tokens=jnp.zeros((1,), jnp.int32))
    eng._slot_req[0] = occupant
    eng._slot_budget[0] = 5
    req = eng.submit(_prompt(30, 4), max_new_tokens=3)
    eng._idle_wait(admitted=False)
    assert eng._slot_req[0] is occupant  # untouched
    assert req in eng._waiting
    eng._tick_head()
    assert eng._slot_req[1] is req
    eng._slot_req[0] = None  # detach the fake occupant before drain
    eng.stop()


def test_chunked_admission_interleaves_with_live_decode(params):
    """Starvation bound: while a long chunked admission is in flight, live
    streams keep emitting — the loop advances at most ONE chunk per
    admitting slot between decode ticks, so no two chunk dispatches land
    without a decode tick in between (the per-admission ITL bound, in
    ticks). Asserted by recording the actual dispatch order. Both requests
    are submitted before start() so the sequencing is deterministic; the
    warm-up's own dispatches are stripped by their exact counts."""
    serving = ServingConfig(slots=2, prefill_buckets=(8,), max_new_tokens=6,
                            prefill_chunk=8)
    eng = ServingEngine(params, CFG, serving)
    events: list = []
    chunk0, decode0 = eng._prefill_chunk, eng._decode_sampled

    def rec_chunk(*a, **k):
        events.append("chunk")
        return chunk0(*a, **k)

    def rec_decode(*a, **k):
        events.append("decode")
        return decode0(*a, **k)

    eng._prefill_chunk, eng._decode_sampled = rec_chunk, rec_decode
    live = eng.submit(_prompt(31, 5), max_new_tokens=20)
    long_req = eng.submit(_prompt(32, 20), max_new_tokens=4)
    eng.start()
    try:
        live_toks = list(live.stream())
        long_toks = list(long_req.stream())
    finally:
        eng.stop()
    assert len(live_toks) == 20
    assert len(long_toks) == 4
    # _warm_executables runs first: one decode per kv read bucket, one
    # chunk per bucket >= the chunk size — drop exactly those
    warm_decodes = len(eng._kv_buckets)
    warm_chunks = sum(1 for bkt in eng._kv_buckets if bkt >= 8)
    served = events[:]
    for _ in range(warm_decodes):
        served.remove("decode")
    for _ in range(warm_chunks):
        served.remove("chunk")
    assert served.count("chunk") == 3  # ceil(20/8) admission chunks
    for i, ev in enumerate(served[:-1]):
        if ev == "chunk":
            assert served[i + 1] != "chunk", (
                f"two chunk dispatches back to back: {served}")


def test_cancel_mid_batched_prefill_others_land(params):
    """Cancel one request AFTER its batched [3, bucket] prefill dispatched
    but BEFORE its first token was delivered: the victim's stream ends
    empty, and the other two requests of the same batch stream normally."""
    serving = ServingConfig(slots=3, prefill_buckets=(8,), max_new_tokens=4,
                            prefill_batch_sizes=(3,))
    eng = ServingEngine(params, CFG, serving)
    step0 = eng._admit_step
    cell: dict = {}

    def wrapped(params_, state, buf, tokens, *rest):
        out = step0(params_, state, buf, tokens, *rest)
        # warm dispatches use all-zero tokens; a real admission batch
        # carries the (nonzero-id) prompts — cancel the victim exactly
        # between its prefill dispatch and its first-token delivery
        if "victim" in cell and bool((tokens != 0).any()):
            cell.pop("victim").cancel()
        return out

    eng._admit_step = wrapped
    prompts = [[int(t) for t in jax.random.randint(
        jax.random.key(40 + i), (5,), 1, CFG.vocab, jnp.int32)]
        for i in range(3)]
    reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
    cell["victim"] = reqs[1]
    eng.start()
    try:
        streams = [list(r.stream()) for r in reqs]
        stats = eng.stats()
    finally:
        eng.stop()
    assert streams[1] == []  # cancelled mid-prefill: end-of-stream only
    assert len(streams[0]) == 4 and len(streams[2]) == 4
    assert stats["prefill_batch_hist"][3] == 1
    assert stats["admission_syncs"] == 0


# ------------------------------------------- warm-up covers what serving runs


def _compiles_while(fn):
    """Names of the jitted functions XLA compiled while *fn* ran."""
    import io
    import logging
    import re

    stream = io.StringIO()
    handler = logging.StreamHandler(stream)
    logger = logging.getLogger("jax._src.interpreters.pxla")
    logger.addHandler(handler)
    jax.config.update("jax_log_compiles", True)
    try:
        fn()
    finally:
        jax.config.update("jax_log_compiles", False)
        logger.removeHandler(handler)
    return re.findall(r"Compiling jit\((.*?)\) with global shapes",
                      stream.getvalue())


@pytest.mark.parametrize("mode", ["dense", "paged_other_device", "tp2",
                                  "loop4", "fused_spec"])
def test_warm_up_compiles_every_step_variant_serving_uses(params, mode):
    """_warm_executables exists so that no executable compiles mid-stream.
    jit keys an executable on each operand's sharding AND on whether the
    operand is committed, and the loops feed tokens both from the host
    (first tick) and from the previous step (every later tick): before
    ISSUE 21 the second kind missed the warm executable and the decode step
    compiled again at the first real tick of every read window — invisible
    at toy size, seconds on a chip, and enough for a fleet heartbeat to
    fence a healthy replica. After warm-up, a stream that crosses a
    read-window boundary must compile NONE of the functions the engine
    holds jitted, wherever the engine lives."""
    import numpy as np
    from jax.sharding import Mesh

    kw = dict(slots=2, prefill_buckets=(8,), max_new_tokens=20)
    placed, mesh = params, None
    if mode == "paged_other_device":
        if len(jax.devices()) < 3:
            pytest.skip("needs 3 devices")
        placed = jax.device_put(params, jax.devices()[2])
        kw.update(kv_page=4, kv_swap=4, prefill_chunk=8)
    elif mode == "tp2":
        if len(jax.devices()) < 2:
            pytest.skip("needs 2 devices")
        mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
        kw.update(kv_page=4, kv_swap=4, prefill_chunk=8)
    elif mode == "loop4":
        kw.update(kv_page=4, decode_loop_k=4)
    elif mode == "fused_spec":
        kw.update(kv_page=4, decode_loop_k=4, spec_tokens=3)
    eng = ServingEngine(placed, CFG, ServingConfig(**kw), mesh=mesh)
    # the swap staging pair wraps functions named like two eager lax ops
    held = {fn.__name__ for fn in vars(eng).values()
            if callable(fn) and hasattr(fn, "lower")} - {"gather", "scatter"}
    assert "step" in held
    with eng._on_device():
        eng._warm_executables()
    streams = []

    def serve():
        eng.start()
        try:
            # 5-token prompts decoding 20: the read window goes 8 -> 32
            reqs = [eng.submit(_prompt(s, 5), max_new_tokens=20)
                    for s in (1, 2, 3)]
            if "prefill_chunk" in kw:  # longer than the bucket: chunked
                reqs.append(eng.submit(_prompt(4, 14), max_new_tokens=4))
            streams.extend(list(r.stream()) for r in reqs)
        finally:
            eng.stop()

    compiled = _compiles_while(serve)
    assert [len(s) for s in streams[:3]] == [20, 20, 20]
    assert [name for name in compiled if name in held] == []
