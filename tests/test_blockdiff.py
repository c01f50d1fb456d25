"""Generation by diffusion over blocks (vtpu/models/blockdiff.py,
``BlockDiffSlotModel``, the engine's loop of passes) at toy widths on the
CPU, on the benchmark's own seeded weights: hidden 64, eight query heads on
two key/value heads of 16, QK-norm a head, an untied head, three layers, 4 of
16 experts held (experts 4-7), blocks of 4, pages of 8, chunks of 16.

Two references. The benchmark's plain one (vbench/reference/blockdiff.py:
float32, no cache, its own replay of a stream from the commit trail), and a
**straight-line generator** here: a Python loop over blocks and passes over
one sequence, no cache and no batch, every pass a whole forward of the
sequence so far with the block's present state at its end, the commits
chosen in numpy. The program in float32 has to serve the same tokens **and
the same trails** as the generator, for the static rule at 1, 2 and 4
commits a pass and for the threshold rule with its floor.

Tolerances, and why. With float32 on both sides a pass's logits differ by
the order of their sums (a cache and a block's own keys joined, against one
masked softmax): about 1e-6, and 2e-5 is held (``F32_TOL``). The same
program in bfloat16 reads hundredths off, and fails it (asserted).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vbench import check, weights
from vbench.reference import blockdiff as ref
from vbench.reference import common
from vbench.sut import blockdiff as sut
from vtpu.models import blockdiff as M
from vtpu.models import moe
from vtpu.models.transformer import hold_projections
from vtpu.serving import (
    FaultPlan, FaultSpec, ServingConfig, ServingEngine, Status,
)
from vtpu.serving.adapters import BlockDiffSlotModel

F32_TOL = 2e-5
SEED = 2**31 + 43
BL, PAGE, CHUNK, CONTEXT, MASK = 4, 8, 16, 64, 95

TOY = dict(
    family="blockdiff", hidden_size=64, num_attention_heads=8,
    num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
    num_experts=4, num_experts_published=16, held_experts_first=4,
    num_experts_per_tok=4, num_hidden_layers=3, vocab_size=96,
    max_position_embeddings=CONTEXT, rope_theta=1000000, rms_norm_eps=1e-6,
    norm_topk_prob=True, decoder_sparse_step=1, mlp_only_layers=[],
    tie_word_embeddings=False, attention_bias=False, rope_scaling=None,
    use_sliding_window=False, dtype="float32", output_head="lm_head",
    block_length=BL, mask_token_id=MASK,
    serving=dict(slots=3, kv_page=PAGE, kv_pool_blocks=30,
                 prefill_chunk=CHUNK, max_new_tokens=16,
                 read_windows=[32], denoising_steps=2,
                 confidence_threshold=None),
    check=dict(requests=8, min_tokens=1, limits={"logit_gap_mean": 1e-4}))
# (prompt length, tokens asked for): a prompt of every L % 4, one shorter
# than a block, streams cut inside their last block
REQUESTS = [(8, 2), (21, 10), (3, 7), (34, 16), (18, 9), (7, 5)]
# (denoising_steps, confidence_threshold): the static rule at 1, 2 and 4
# commits a pass; the threshold rule over its floor of one
RULES = {"static4": (1, None), "static2": (2, None), "static1": (4, None),
         "threshold": (4, 0.04)}


def _with_rule(name, cfg=TOY):
    steps, threshold = RULES[name]
    return {**cfg, "serving": {**cfg["serving"], "denoising_steps": steps,
                               "confidence_threshold": threshold}}


def _weights(cfg=TOY):
    return weights.make_all(SEED, ref.weight_specs(cfg),
                            cfg["num_hidden_layers"])


def _prompts():
    rng = np.random.default_rng(43)
    return [(rng.integers(1, MASK, n).astype(np.int32), m)
            for n, m in REQUESTS]


# ------------------------------------------- the straight-line generator


@pytest.fixture(scope="module")
def forward():
    """logits [CONTEXT, V] of a whole forward of ``tokens`` (padded to
    CONTEXT; position -1 marks the padding), float32, through the plain
    reference's layers under the block mask of the clean sequence."""
    specs = ref.weight_specs(TOY)
    key = weights.seed_key(SEED)
    g = weights.make_globals(key, specs)
    layers = [weights.make_layer(key, specs, l)
              for l in range(TOY["num_hidden_layers"])]

    @jax.jit
    def run(tokens, pos):
        beside = {"pos": pos, "block": pos // BL,
                  "seg": jnp.where(pos >= 0, 0, -1)}
        x = g["embed"][tokens].astype(jnp.float32)
        for w in layers:
            x = ref.layer(TOY, w, x, "f32", beside)
        return common.head(TOY, g, x, "f32")

    def logits_of(tokens):
        n = len(tokens)
        toks = np.zeros(CONTEXT, np.int32)
        toks[:n] = tokens
        pos = np.where(np.arange(CONTEXT) < n, np.arange(CONTEXT), -1)
        return np.asarray(run(jnp.asarray(toks), jnp.asarray(pos)))[:n]

    return logits_of


def straight_line(forward, prompt, max_new, steps, threshold):
    """(tokens, trail) of one stream: blocks in order, a block's passes
    until no row it may commit is masked, then the writing pass (it counts
    as one of the request's passes and changes nothing here: there is no
    cache)."""
    seq = [int(t) for t in prompt]
    n, end = len(seq), len(prompt) + max_new
    served, trail, passes = {}, {}, 0
    first = n // BL * BL
    while first < end:
        here = np.arange(first, first + BL)
        ids = np.array([seq[p] if p < n else 0 for p in here])
        masked = here >= n
        while (masked & (here < end)).any():
            state = np.where(masked, MASK, ids)
            logits = forward(seq[:first] + state.tolist())[first:]
            z = logits - logits.max(-1, keepdims=True)
            conf = 1.0 / np.exp(z).sum(-1)
            eligible = masked & (here < end)
            order = sorted(np.flatnonzero(eligible),
                           key=lambda i: (-conf[i], i))
            take = set(order[:BL // steps])
            if threshold is not None:
                take |= {i for i in order if conf[i] > threshold}
            for i in sorted(take):
                ids[i] = int(logits[i].argmax())
                masked[i] = False
                served[here[i]], trail[here[i]] = int(ids[i]), passes
            passes += 1
        passes += 1  # the writing pass
        seq = seq[:first] + [int(t) for t, p in zip(ids, here) if p < end]
        first += BL
    order = sorted(served)
    return [served[p] for p in order], [trail[p] for p in order]


# ----------------------------------------------------- streams and trails


def _serve(cfg, prompts):
    eng = sut.build(cfg, _weights(cfg))
    eng.start()
    try:
        reqs = [eng.submit(p, max_new_tokens=m) for p, m in prompts]
        out = [(list(r.stream()), r) for r in reqs]
    finally:
        eng.stop()
    stats = eng.stats()  # after the stop: the pass in flight is fetched too
    assert stats["loop_error"] is None
    return out, stats


@pytest.fixture(scope="module")
def served():
    return {rule: _serve(_with_rule(rule), _prompts()) for rule in RULES}


@pytest.mark.parametrize("rule", list(RULES))
def test_streams_and_trails_equal_the_straight_line_generator(
        rule, served, forward):
    steps, threshold = RULES[rule]
    out, _ = served[rule]
    for (prompt, max_new), (tokens, req) in zip(_prompts(), out):
        want, trail = straight_line(forward, prompt, max_new, steps,
                                    threshold)
        assert req.status == "OK" and len(tokens) == max_new
        assert tokens == want, (rule, len(prompt), max_new)
        assert req.trail == trail, (rule, len(prompt), max_new)


@pytest.mark.parametrize("rule,per_pass", [
    ("static4", 4), ("static2", 2), ("static1", 1)])
def test_the_static_rule_commits_its_count_a_pass(rule, per_pass, served):
    """A whole block of a prompt that ends on a block's edge takes exactly
    block_length / commits denoising passes: the trail's values of one
    block are that many distinct passes, each with its count of rows."""
    out, stats = served[rule]
    trail = np.asarray(out[3][1].trail)           # prompt 34, 16 tokens
    block = trail[2:6]                            # positions 36..39
    counts = np.unique(block, return_counts=True)[1]
    assert counts.tolist() == [per_pass] * (BL // per_pass)
    assert stats["block_tokens_committed"] == sum(m for _, m in REQUESTS)


def test_the_threshold_rule_commits_over_its_floor(served):
    """Some pass commits more than the floor of one, and none commits
    fewer: what the static rule at the same steps cannot do."""
    (_, stats), (_, floor) = served["threshold"], served["static1"]
    assert stats["block_slot_passes"] < floor["block_slot_passes"]
    trails = [np.asarray(r.trail) for _, r in served["threshold"][0]]
    assert any(np.unique(t, return_counts=True)[1].max() > 1 for t in trails)


@pytest.mark.parametrize("rule", list(RULES))
def test_the_replay_of_a_served_stream_reads_no_gap(rule, served):
    """vbench/check.py's numbers over what the engine served, through the
    family's own ``passes``: every token answered once, gaps of rounding."""
    from vbench.stamps import Record

    cfg = _with_rule(rule)
    records = []
    for i, ((prompt, max_new), (tokens, req)) in enumerate(
            zip(_prompts(), served[rule][0])):
        records.append(Record(i, len(prompt), max_new, 0.0, True,
                              tokens=tokens, status="OK", prompt=prompt,
                              trail=list(req.trail)))
    numbers = check.compare(cfg, SEED, records)
    assert numbers["tokens_unanswered"]["value"] == 0
    assert numbers["trail_wrong_length"]["value"] == 0
    assert numbers["streams_wrong_length"]["value"] == 0
    assert numbers["logit_gap_max"]["value"] < F32_TOL
    assert check.verdict(numbers)


def test_a_trail_moved_by_a_pass_reads_not_correct(served):
    from vbench.stamps import Record

    records = []
    for i, ((prompt, max_new), (tokens, req)) in enumerate(
            zip(_prompts(), served["static2"][0])):
        trail = list(req.trail)
        if i == 3:  # the later of a block's two passes said to be the first
            trail[2:6] = [min(trail[2:6])] * 4
        records.append(Record(i, len(prompt), max_new, 0.0, True,
                              tokens=tokens, status="OK", prompt=prompt,
                              trail=trail))
    numbers = check.compare(_with_rule("static2"), SEED, records)
    assert not check.verdict(numbers)
    assert numbers["logit_gap_max"]["value"] > 100 * F32_TOL


# ------------------------------------------------------- a pass, by itself


def _program(cfg=TOY, dtype=jnp.float32, **rule):
    mcfg = dataclasses.replace(sut.model_config(cfg, dtype), **rule)
    params = sut.params_of(cfg, _weights(cfg))
    params = {**params, "layers": hold_projections(params["layers"], mcfg)}
    return mcfg, params


def _state_with(mcfg, lens, blocks):
    """A state of len(lens) slots whose slot i holds ``lens[i]`` cached
    tokens of random keys and values in pages of its own and the block
    ``blocks[i]`` = (ids, masked, end)."""
    slots = len(lens)
    pages = CONTEXT // PAGE
    state = M.init_block_state(mcfg, slots, PAGE, 1 + slots * pages)
    rng = np.random.default_rng(7)
    table = 1 + np.arange(slots * pages, dtype=np.int32).reshape(slots, pages)
    state = {**state, "table": jnp.asarray(table),
             "len": jnp.asarray(lens, jnp.int32),
             "k": jnp.asarray(rng.normal(size=state["k"].shape), mcfg.dtype),
             "v": jnp.asarray(rng.normal(size=state["v"].shape), mcfg.dtype)}
    for i, (ids, masked, end) in enumerate(blocks):
        state = M.open_block(state, i, jnp.asarray(ids, jnp.int32),
                             jnp.asarray(masked), end)
    return state


def test_a_denoising_pass_leaves_the_pool_bit_for_bit():
    mcfg, params = _program()
    state = _state_with(mcfg, [8, 12], [
        ([5, 0, 0, 0], [False, True, True, True], 40),
        ([0, 0, 0, 0], [True] * 4, 40)])
    result, new = M.block_pass(params, mcfg, state, jnp.ones(2, bool),
                               CONTEXT)
    assert result[:, M.PHASE].tolist() == [M.DENOISE, M.DENOISE]
    for plane in ("k", "v"):
        assert np.array_equal(np.asarray(new[plane]), np.asarray(state[plane]))
    assert new["len"].tolist() == [8, 12]
    assert result[:, M.COMMITTED].tolist() == [2, 2]


def test_slots_in_both_phases_share_a_launch():
    """Slot 0's block is clean (the writing pass), slot 1's masked (a
    denoising pass), slot 2 is idle: only slot 0's pages change, its length
    moves on a block and its next block opens masked; slot 1 commits."""
    mcfg, params = _program()
    state = _state_with(mcfg, [8, 12, 4], [
        ([5, 6, 7, 9], [False] * 4, 40),
        ([0, 0, 0, 0], [True] * 4, 40),
        ([0, 0, 0, 0], [True] * 4, 40)])
    active = jnp.asarray([True, True, False])
    result, new = M.block_pass(params, mcfg, state, active, CONTEXT)
    assert result[:, M.PHASE].tolist() == [M.WRITE, M.DENOISE, M.NONE]
    assert new["len"].tolist() == [12, 12, 4]
    assert new["blk_masked"][0].all() and new["blk_pass"].tolist() == [1, 1, 0]
    changed = np.flatnonzero(np.any(
        np.asarray(new["k"]) != np.asarray(state["k"]), axis=(0, 2, 3, 4)))
    # positions 8..11 of slot 0 lie in its second page: block 2
    assert changed.tolist() == [2]
    page = np.asarray(new["k"])[:, 2]
    assert np.array_equal(page[:, 4:], np.asarray(state["k"])[:, 2, 4:])
    assert (page[:, :4] != np.asarray(state["k"])[:, 2, :4]).any()


def test_a_row_that_holds_the_mask_id_as_a_token_is_not_masked():
    """The flag says what is masked, never a comparison with the mask id:
    a committed row whose token is the mask id stays committed."""
    mcfg, params = _program()
    state = _state_with(mcfg, [8], [
        ([MASK, 0, 0, 0], [False, True, True, True], 40)])
    result, new = M.block_pass(params, mcfg, state, jnp.ones(1, bool), CONTEXT)
    assert int(result[0, M.ELIGIBLE]) == 3
    assert int(new["blk_ids"][0, 0]) == MASK and not bool(
        new["blk_masked"][0, 0])


def test_rows_past_the_requests_end_are_never_committed():
    mcfg, params = _program(denoising_steps=1)
    state = _state_with(mcfg, [8], [([0] * 4, [True] * 4, 10)])
    result, new = M.block_pass(params, mcfg, state, jnp.ones(1, bool), CONTEXT)
    assert result[0, [M.PHASE, M.CLEAN, M.ELIGIBLE, M.COMMITTED]].tolist() \
        == [M.DENOISE, 1, 2, 2]
    assert new["blk_masked"][0].tolist() == [False, False, True, True]
    # its writing pass, and then nothing more
    result, new = M.block_pass(params, mcfg, new, jnp.ones(1, bool), CONTEXT)
    assert int(result[0, M.PHASE]) == M.WRITE
    result, _ = M.block_pass(params, mcfg, new, jnp.ones(1, bool), CONTEXT)
    assert int(result[0, M.PHASE]) == M.NONE


def test_the_commit_rule_breaks_ties_to_the_lower_position():
    mcfg, _ = _program(denoising_steps=2, confidence_threshold=0.9)
    logits = np.zeros((1, 4, 96), np.float32)
    logits[0, :, 3] = [2.0, 5.0, 5.0, 2.0]      # rows 1 and 2 tie
    logits[0, 3, 7] = 30.0                       # row 3 over the threshold
    eligible = jnp.asarray([[True, True, True, True]])
    tokens, commit = M.commit_rows(mcfg, jnp.asarray(logits), eligible)
    assert commit[0].tolist() == [False, True, False, True]
    assert tokens[0].tolist() == [3, 3, 3, 7]
    # the floor: nothing over the threshold, the two most confident, the
    # tie to the lower position
    logits[0, 3, 7] = 0.0
    _, commit = M.commit_rows(mcfg, jnp.asarray(logits), eligible)
    assert commit[0].tolist() == [False, True, True, False]
    _, commit = M.commit_rows(
        mcfg, jnp.asarray(logits), jnp.asarray([[True, False, False, True]]))
    assert commit[0].tolist() == [True, False, False, True]


def test_the_walk_joined_with_the_own_block_equals_the_gathered_window():
    """The kernel's route (interpreted here: numerics alone) against the
    gather route, one pass of slots at lengths on and off a page's edge,
    one of them empty."""
    mcfg, params = _program()
    # the slot with nothing cached holds a prompt's tail: four masked rows
    # over no context are the same row four times, and tie
    blocks = [([0] * 4, [True] * 4, 60)] * 2 + [
        ([5, 6, 0, 0], [False, False, True, True], 60)]
    state = _state_with(mcfg, [16, 28, 0], blocks)
    active = jnp.ones(3, bool)
    want, _ = M.block_pass(params, mcfg, state, active, CONTEXT, "gather")
    got, _ = M.block_pass(params, mcfg, state, active, CONTEXT, "kernel")
    assert np.array_equal(np.asarray(got), np.asarray(want))
    seen = {}

    def keep(cfg, logits, eligible):
        seen.setdefault("logits", []).append(logits)
        return M_commit(cfg, logits, eligible)

    M_commit = M.commit_rows
    try:
        M.commit_rows = keep
        for route in ("gather", "kernel"):
            M.block_pass(params, mcfg, state, active, CONTEXT, route)
    finally:
        M.commit_rows = M_commit
    a, b = (np.asarray(x) for x in seen["logits"])
    assert np.abs(a - b).max() < F32_TOL


def test_bfloat16_fails_the_float32_tolerance():
    """The tolerance tells a lower precision apart: the same pass computed
    in bfloat16 reads far over it."""
    seen = []
    keep = M.commit_rows

    def spy(cfg, logits, eligible):
        seen.append(np.asarray(logits))
        return keep(cfg, logits, eligible)

    try:
        M.commit_rows = spy
        for dtype in (jnp.float32, jnp.bfloat16):
            mcfg, params = _program(dtype=dtype)
            params = jax.tree_util.tree_map_with_path(
                lambda path, a: a if path[-1].key == "router"
                else a.astype(dtype), params)
            state = _state_with(mcfg, [16], [([0] * 4, [True] * 4, 60)])
            state = {**state, "k": state["k"].astype(dtype),
                     "v": state["v"].astype(dtype)}
            M.block_pass(params, mcfg, state, jnp.ones(1, bool), CONTEXT)
    finally:
        M.commit_rows = keep
    assert np.abs(seen[0] - seen[1]).max() > 100 * F32_TOL


# ------------------------------------------------------------ the engine


def test_the_counters_count_what_the_passes_did(served):
    out, stats = served["static2"]
    asked = sum(m for _, m in REQUESTS)
    assert stats["block_tokens_committed"] == asked == stats[
        "generated_tokens"]
    assert stats["block_rows_dispatched"] == BL * stats["block_slot_passes"]
    assert 0 < stats["block_write_passes"] < stats["block_slot_passes"]
    assert asked <= stats["block_rows_masked"] < stats["block_rows_dispatched"]
    assert stats["block_length"] == BL and stats["pipelined"]
    assert stats["pipelined_ticks"] > 0
    assert stats["device_gets_per_tick"] == 1.0
    assert stats["expert_rows"] > 0 and stats["attn_visible_tokens"] > 0
    assert stats["admission_syncs"] == 0
    # a prompt of 3 tokens has no whole block: it opens its block at once
    assert stats["prefill_tokens"] == sum(n - n % BL for n, _ in REQUESTS)


def test_a_whole_block_costs_three_passes_at_two_commits():
    """Prompts and outputs on a block's edge: 4 tokens every 3 passes."""
    prompts = [(np.arange(1, 9, dtype=np.int32), 8),
               (np.arange(1, 17, dtype=np.int32), 12)]
    _, stats = _serve(_with_rule("static2"), prompts)
    assert stats["block_tokens_committed"] == 20
    assert stats["block_slot_passes"] == 15
    assert stats["block_write_passes"] == 5
    assert stats["block_rows_masked"] == 4 * 5 + 2 * 5


def test_an_end_of_sequence_token_ends_the_stream_inside_a_block(served):
    tokens = served["static2"][0][3][0]
    eos = tokens[5]
    cfg = _with_rule("static2")
    cfg = {**cfg, "serving": {**cfg["serving"], "eos_token": int(eos)}}
    out, _ = _serve(cfg, [_prompts()[3]])
    got = out[0][0]
    assert got == tokens[:tokens.index(eos) + 1]
    assert len(out[0][1].trail) == len(got)


def test_an_injected_fault_retires_one_stream_and_no_other(served):
    """The one ``_emit`` delivers a clean block's tokens too, so its fault
    seam reaches this family: the stream whose delivery raised is retired
    FAULTED, and the others are what a run without the fault serves."""
    cfg = _with_rule("static2")
    plan = FaultPlan([FaultSpec("dispatch_exc", at=3)])
    cfg = {**cfg, "serving": {**cfg["serving"], "faults": plan}}
    out, stats = _serve(cfg, _prompts()[:3])
    sound = served["static2"][0][:3]
    faulted = [i for i, (_, r) in enumerate(out)
               if r.status == Status.FAULTED]
    assert len(faulted) == 1 and stats["faulted_requests"] == 1
    for i, ((tokens, r), (want, w)) in enumerate(zip(out, sound)):
        if i in faulted:
            assert tokens == want[:len(tokens)] and len(tokens) < len(want)
        else:
            assert r.status == Status.OK
            assert (tokens, r.trail) == (want, w.trail)


@pytest.mark.parametrize("what,change,message", [
    ("speculation", dict(spec_tokens=2), "spec_step"),
    ("the device loop", dict(decode_loop_k=4), "device loop"),
    ("park and swap", dict(kv_swap=8), "park or swap"),
    ("a sampler's temperature", dict(temperature=0.7), "temperature"),
    ("no chunked admission", dict(prefill_chunk=None), "prefill_chunk"),
    ("a chunk that cuts a block", dict(prefill_chunk=18), "block_length"),
    ("the synchronous loop", dict(pipeline_decode=False), "pipelined"),
])
def test_what_cannot_serve_this_family_is_refused_by_name(what, change,
                                                          message):
    cfg = {**TOY, "serving": {**TOY["serving"], **change,
                              "max_new_tokens": 8}}
    if change.get("prefill_chunk") == 18:
        cfg["max_position_embeddings"] = 72
        cfg["serving"]["kv_page"] = 4
        cfg["serving"]["read_windows"] = [36]
    with pytest.raises(ValueError, match=message):
        sut.build(cfg, _weights(cfg))


def test_refusals_that_are_no_serving_field():
    mcfg, params = _program()
    raw = sut.params_of(TOY, _weights())
    with pytest.raises(ValueError, match="no mesh"):
        BlockDiffSlotModel(raw, mcfg, kv_page=PAGE, mesh=object())
    with pytest.raises(ValueError, match="paged cache only"):
        BlockDiffSlotModel(raw, mcfg)
    with pytest.raises(ValueError, match="int8"):
        BlockDiffSlotModel(raw, dataclasses.replace(mcfg, kv_int8=True),
                           kv_page=PAGE)
    model = BlockDiffSlotModel(raw, mcfg, kv_page=PAGE, kv_pool_blocks=20)
    serving = ServingConfig(slots=2, kv_page=PAGE, kv_pool_blocks=20,
                            prefill_chunk=CHUNK, max_new_tokens=8)
    with pytest.raises(ValueError, match="sample= callable"):
        ServingEngine(serving=serving, model=model, sample=lambda row: 0)
    from vtpu.serving.disagg import DisaggConfig
    with pytest.raises(ValueError, match="slot-less prefill"):
        ServingEngine(serving=dataclasses.replace(
            serving, disagg=DisaggConfig()), model=model)
    eng = ServingEngine(serving=serving, model=model)
    with pytest.raises(ValueError, match="register_prefix"):
        eng.register_prefix(np.arange(1, 9, dtype=np.int32))
    with pytest.raises(ValueError, match="drain"):
        eng.drain(eng)


def test_a_configuration_that_cuts_a_block_is_refused():
    with pytest.raises(ValueError, match="denoising_steps"):
        M.BlockDiffConfig(denoising_steps=3)
    with pytest.raises(ValueError, match="max_seq"):
        M.BlockDiffConfig(max_seq=1022)
    with pytest.raises(ValueError, match="mask_token_id"):
        M.BlockDiffConfig(mask_token_id=4096)
    with pytest.raises(ValueError, match="kv page"):
        M.init_block_state(M.BlockDiffConfig(), 2, 6, 8)


# ------------------------------------------ the fields the trunk gained


def test_the_shares_of_the_experts_add_up_to_the_whole_layer():
    """Every share's part of an expert layer's result (the program's
    ``held_moe_ffn``, told which experts it holds), summed over the shares
    that together hold all 16, is the uncut layer's."""
    cfg = moe.MoEConfig(vocab=64, d_model=32, n_heads=2, n_layers=1, d_ff=16,
                        n_experts=16, top_k=4, max_seq=32, head_dim=16,
                        dtype=jnp.float32)
    params = moe.init_moe_params(jax.random.key(3), cfg)
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.key(4), (2, 5, 32), jnp.float32)
    whole = moe.held_moe_ffn(cfg)(lp, x)
    parts = 0.0
    for first in range(0, 16, 2):
        share = dataclasses.replace(cfg, held=(first, 2))
        held = {**lp, **{k: lp[k][first:first + 2]
                         for k in ("w_gate", "w_up", "w_down")}}
        parts = parts + moe.held_moe_ffn(share)(held, x)
    assert np.abs(np.asarray(parts - whole)).max() < 1e-5
    assert np.abs(np.asarray(whole)).max() > 1e-2
    gates = moe.topk_softmax_gates(lp["router"], x.reshape(-1, 32), 4)
    assert np.allclose(np.asarray(gates.sum(-1)), 1.0, atol=1e-6)
    assert (np.asarray(gates) > 0).sum(-1).tolist() == [4] * 10


def test_qk_norm_and_an_untied_head_are_fields_that_default_to_olmoes():
    base = moe.MoEConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                         d_ff=16, n_experts=4, top_k=2, max_seq=32,
                         head_dim=8)
    assert (base.qk_norm, base.tied_head, base.n_kv_heads, base.held) == (
        False, True, None, None)
    plain = moe.init_moe_params(jax.random.key(0), base)
    assert "head" not in plain and "q_norm" not in plain["layers"]
    full = dataclasses.replace(base, qk_norm=True, tied_head=False,
                               n_kv_heads=2, held=(2, 2))
    params = moe.init_moe_params(jax.random.key(0), full)
    assert params["head"].shape == (64, 32)
    assert params["layers"]["q_norm"].shape == (2, 8)
    assert params["layers"]["wk"].shape == (2, 32, 16)
    assert params["layers"]["w_gate"].shape == (2, 2, 32, 16)
    assert params["layers"]["router"].shape == (2, 32, 4)
    # the leaves both have are the same draws
    assert np.array_equal(np.asarray(plain["layers"]["wq"]),
                          np.asarray(params["layers"]["wq"]))
