"""A program whose pass commits several tokens of a stream, not in their
order, is held to its reference through the family's own replay
(``passes`` in ``reference/<family>.py``) and the commit trail
(``Record.trail``); a family without ``passes`` is served as before.

- The six families the benchmark has: the programs ``check.compare``
  jits and every number it returns are the parent's (commit 8a838d4,
  before check.py knew of passes; PR 42), plus the two new exact numbers
  at 0.
- ``toy_blockdiff`` (toy_families/reference/, a reference alone):
  generation by diffusion over blocks of 4. A straight-line generator
  written here makes the tokens and the trail; a sound stream reads gaps
  of nought, and a trail moved by a pass, a token changed, the float8
  control, a trail cut short and a replay that answers a token twice or
  not at all each read not correct, by the number that is theirs.
"""

import hashlib
import importlib
import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import test_vbench_hybrid  # noqa: E402
import test_vbench_latent  # noqa: E402
import test_vbench_mla  # noqa: E402
import test_vbench_swa  # noqa: E402
import vbench_toyroot  # noqa: E402
from test_vbench_kinds import _f64, _rms, _rope, _swiglu  # noqa: E402

from vbench import check, weights  # noqa: E402
from vbench.stamps import Record  # noqa: E402

SEED = 2**31 + 42

# -- the six families the benchmark has: nothing moved -----------------------

TOYS = {"dense": vbench_toyroot.CONFIGS["toy-dense"],
        "moe": vbench_toyroot.CONFIGS["toy-moe"],
        "latent": test_vbench_latent.TOY, "hybrid": test_vbench_hybrid.TOY,
        "mla": test_vbench_mla.TOY, "swa": test_vbench_swa.TOY}
LENGTHS = [(21, 9), (5, 30), (40, 12), (9, 17), (130, 20)]

# From the parent's code (commit 8a838d4), on the CPU: sha256 over the
# sorted, distinct lowered texts of every program check.compare jitted
# (control on: the float32 and the float8 programs) over _made_up(cfg),
# and the numbers it returned: program_logit_gap_max, program_logit_gap_mean
# (the made-up tokens' gaps), logit_gap_max, logit_gap_mean (the control's).
PARENT = {
    "dense": ("2ce641ad6093482e28ff57e8310e9bb4a885a6c2ea14d2de587407cc6ea2e345",
              5.425199508666992, 2.9595587253570557,
              0.6778111457824707, 0.031526874750852585),
    "moe": ("03b1802fc27c3733fc71caf95aa36eadf96101518a37b8bf8569fd7ecf8cdc98",
            6.452749729156494, 3.312626838684082,
            2.4179162979125977, 0.08486983925104141),
    "latent": ("faf8c7595ce29416716967a429595489725395d0b361d1ae74ed058f0215ea0a",
               5.192334175109863, 2.7290878295898438,
               3.7817463874816895, 0.506019651889801),
    "hybrid": ("0c146140e64152f65c0ddfeb210e8b2d53cc8dd7bbccbcdada27b271598d7f63",
               0.7085639238357544, 0.3837929964065552,
               0.13696417212486267, 0.010751316323876381),
    "mla": ("7ac7fccf4bc17e8298c9a60fa1bace6c47b41cefa52355bb9efe6bd3944d72d9",
            6.103320598602295, 2.9566290378570557,
            4.596563816070557, 0.21694955229759216),
    "swa": ("d642b069348139b0c3ae5f9844bad7a098682e2877ad59d3138e169242373bda",
            5.601691246032715, 2.783555507659912,
            1.041504979133606, 0.0941014513373375),
}


def _made_up(cfg):
    """Finished requests of made-up tokens: no program served them, so the
    gaps are wide, and they are the same on every tree."""
    rng = np.random.default_rng(7)
    return [Record(i, p, n, 0.0, True, status="OK",
                   prompt=rng.integers(0, cfg["vocab_size"], p).astype(
                       np.int32),
                   tokens=[int(t) for t in
                           rng.integers(0, cfg["vocab_size"], n)])
            for i, (p, n) in enumerate(LENGTHS)]


def _lowered(monkeypatch, fn):
    """fn(), and a digest of every program vbench/check.py jitted in it."""
    texts, real = set(), jax.jit

    def jit(f, *a, **kw):
        jitted = real(f, *a, **kw)
        if sys._getframe(1).f_globals.get("__name__") != "vbench.check":
            return jitted

        def call(*args):
            texts.add(jitted.lower(*args).as_text())
            return jitted(*args)
        return call

    with monkeypatch.context() as m:
        m.setattr(jax, "jit", jit)
        out = fn()
    h = hashlib.sha256()
    for t in sorted(texts):
        h.update(t.encode())
    return out, h.hexdigest()


@pytest.mark.parametrize("family", list(PARENT))
def test_a_family_without_passes_is_served_as_before(family, monkeypatch):
    cfg = TOYS[family]
    assert cfg["family"] == family
    assert not hasattr(check._family(cfg), "passes")
    digest, *parent = PARENT[family]
    got, lowered = _lowered(monkeypatch, lambda: check.compare(
        cfg, SEED, _made_up(cfg), control=True))
    assert lowered == digest
    assert [got[k]["value"] for k in (
        "program_logit_gap_max", "program_logit_gap_mean", "logit_gap_max",
        "logit_gap_mean")] == parent
    assert {k: v["value"] for k, v in got.items() if v["limit"] == 0} == {
        "streams_wrong_length": 0, "tokens_outside_vocab": 0,
        "tokens_short_of_sample": 0, "tokens_unanswered": 0,
        "trail_wrong_length": 0}
    assert got["tokens_compared"]["value"] == sum(n for _, n in LENGTHS)
    assert list(got)[-1] == "tokens_compared"


def test_the_one_pass_of_a_family_without_passes():
    cfg = TOYS["dense"]
    prompt, served = np.arange(3, 10), [11, 12, 13, 14]
    (p,) = check.replay(cfg, prompt, served)
    assert p["tokens"].tolist() == [3, 4, 5, 6, 7, 8, 9, 11, 12, 13]
    assert p["rows"].tolist() == [6, 7, 8, 9]
    assert p["chosen"].tolist() == served and p["beside"] is None
    assert check.unanswered(served, [p]) == 0


def test_a_cap_on_the_sample_leaves_the_longer_requests_out():
    def rec(i, p, n, status="OK"):
        return Record(i, p, n, 0.0, True, status=status, tokens=[1] * n)

    records = [rec(0, 10, 5), rec(1, 90, 20), rec(2, 40, 9),
               rec(3, 70, 31, None), rec(4, 20, 16, None)]
    assert [r.index for r in check.pick_sample(records, 5, 3)] == [1, 0, 2]
    # sizes 15, 110, 49, 101 (cut), 36 (cut): the longest left leads
    for cap, want in ((None, [1, 3, 0, 2]), (101, [3, 4, 0, 2]),
                      (49, [2, 0, 4]), (36, [4, 0]), (14, [])):
        got = check.pick_sample(records, 5, 4, cap)
        assert [r.index for r in got] == want, cap
        assert all(r.prompt_len + len(r.tokens) <= (cap or 1e9) for r in got)


# -- a family that replays its own passes ------------------------------------

# Limits from readings on the CPU at this size (8 seeds of weights and
# prompts, 6 requests of 12-40 tokens, 152 tokens compared a run in 83-101
# passes, 51-69 of which committed two; PR 42): sound streams read 0.0 on
# both numbers on every seed (the float64 generator's choice is the float32
# reference's first at every row); the float8 control a widest gap of
# 0.275-0.470 and a mean of 0.0178-0.0302; the trail moved by a pass a
# mean of 0.057-0.171 (widest 1.17-2.85); one token changed a request a
# mean of 0.128-0.240 (widest 4.05-5.20). The limits sit between nought and
# the control's smallest, nearer nought.
BLOCKDIFF = dict(
    family="toy_blockdiff", hidden_size=64, num_attention_heads=2,
    head_dim=32, num_hidden_layers=2, intermediate_size=128, vocab_size=384,
    block_length=4, mask_token_id=383, replay_blocks=3, rope_theta=10000.0,
    rms_norm_eps=1e-6, dtype="bfloat16", output_head="embed",
    check=dict(requests=6, min_tokens=40,
               limits=dict(logit_gap_max=0.1, logit_gap_mean=0.004)))
REQUESTS = [(5, 23), (16, 40), (9, 12), (30, 21), (2, 24), (13, 32)]
SECOND = 0.8   # a pass commits its second most confident position too
#                where that is this sure, as a share of the most confident


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    """The copied benchmark with toy_families/ added as files, which puts
    toy_blockdiff's reference where a PR would."""
    root = str(tmp_path_factory.mktemp("vbench_root"))
    return root, vbench_toyroot.build(root)


def _forward(cfg, w, tokens):
    """The whole toy model over one sequence that starts at position 0, in
    float64, every row's logits: a row attends every row of its own block
    and of the blocks before it."""
    s, (h, dh) = len(tokens), (cfg["num_attention_heads"], cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    block = np.arange(s) // cfg["block_length"]
    sees = block[None, :] <= block[:, None]
    x = _f64(w["embed"])[tokens]
    for l in range(cfg["num_hidden_layers"]):
        lw = {k: _f64(v[l]) for k, v in w["layers"].items()}
        n = _rms(x, lw["attn_norm"], eps)
        q = _rope((n @ lw["wq"]).reshape(s, h, dh), theta)
        k = _rope((n @ lw["wk"]).reshape(s, h, dh), theta)
        v = (n @ lw["wv"]).reshape(s, h, dh)
        out = np.zeros((s, h, dh))
        for head in range(h):
            scores = q[:, head] @ k[:, head].T / np.sqrt(dh)
            scores = np.where(sees, scores, -np.inf)
            p = np.exp(scores - scores.max(-1, keepdims=True))
            out[:, head] = p / p.sum(-1, keepdims=True) @ v[:, head]
        x = x + out.reshape(s, h * dh) @ lw["wo"]
        n = _rms(x, lw["mlp_norm"], eps)
        x = x + _swiglu(n, lw["w_gate"], lw["w_up"], lw["w_down"])
    return _rms(x, _f64(w["final_norm"]), eps) @ _f64(w["embed"]).T


def _generate(cfg, w, prompt, max_new):
    """Block by block, pass by pass, one sequence: (tokens, trail). A
    position past the request's end stays masked and is never committed."""
    bl, mask = cfg["block_length"], cfg["mask_token_id"]
    p, end = len(prompt), len(prompt) + max_new
    clean = [int(t) for t in prompt]
    trail, n_pass = {}, 0
    for lo in range(p // bl * bl, end, bl):
        state = clean[lo:] + [mask] * (lo + bl - len(clean))
        masked = [q for q in range(lo, lo + bl) if p <= q < end]
        while masked:
            logits = _forward(cfg, w, clean[:lo] + state)
            prob = np.exp(logits - logits.max(-1, keepdims=True))
            sure = (prob / prob.sum(-1, keepdims=True)).max(-1)
            first, *rest = sorted(masked, key=lambda q: -sure[q])
            take = [first] + [q for q in rest[:1]
                              if sure[q] >= SECOND * sure[first]]
            for q in take:
                state[q - lo] = int(logits[q].argmax())
                trail[q] = n_pass
                masked.remove(q)
            n_pass += 1
        clean = clean[:lo] + state
    return clean[p:end], [trail[q] for q in range(p, end)]


@pytest.fixture(scope="module")
def streams(toy):
    cfg = BLOCKDIFF
    ref = importlib.import_module("vbench.reference.toy_blockdiff")
    w = weights.make_all(SEED, ref.weight_specs(cfg),
                         cfg["num_hidden_layers"])
    rng = np.random.default_rng(5)
    out = []
    for i, (p, n) in enumerate(REQUESTS):
        prompt = rng.integers(0, cfg["mask_token_id"], p).astype(np.int32)
        tokens, trail = _generate(cfg, w, prompt, n)
        out.append(Record(i, p, n, 0.0, True, status="OK", prompt=prompt,
                          tokens=tokens, trail=trail))
    return out


def _copies(streams, **changed):
    return [Record(**{**vars(r), **{k: f(r) for k, f in changed.items()}})
            for r in streams]


def test_the_generator_commits_out_of_order_one_or_two_a_pass(streams):
    bl = BLOCKDIFF["block_length"]
    sizes = set()
    for r in streams:
        assert len(r.tokens) == len(r.trail) == r.max_new
        by_pass = {}
        for q, t in enumerate(r.trail, r.prompt_len):
            by_pass.setdefault(t, []).append(q // bl)
        assert sorted(by_pass) == list(range(len(by_pass)))
        for blocks in by_pass.values():      # a pass stays in its block
            assert len(set(blocks)) == 1
            sizes.add(len(blocks))
    assert sizes == {1, 2}
    assert any(r.trail != sorted(r.trail) for r in streams)


def test_the_replay_lays_the_clean_sequence_and_the_blocks_side_by_side(
        streams):
    cfg, r = BLOCKDIFF, streams[1]          # 16 + 40: ten whole blocks
    passes = check.replay(cfg, r.prompt, r.tokens, r.trail)
    assert len(passes) == 4                 # replay_blocks 3: 3, 3, 3, 1
    assert check.unanswered(r.tokens, passes) == 0
    assert sum(len(p["rows"]) for p in passes) == r.max_new
    first = passes[0]
    clean = first["beside"]["seg"] == 0
    assert clean.sum() == 16 + 8            # all before the group's last
    assert first["tokens"][clean].tolist() == \
        list(r.prompt) + r.tokens[:8]
    assert (first["tokens"][first["rows"]] == cfg["mask_token_id"]).all()
    assert set(first["beside"]) == {"pos", "block", "seg"}


def test_a_sound_stream_reads_no_gap(streams):
    got = check.compare(BLOCKDIFF, SEED, streams)
    assert check.verdict(got) is True, got
    assert got["logit_gap_max"]["value"] < 1e-4    # float32's rounding
    assert got["tokens_compared"]["value"] == sum(n for _, n in REQUESTS)
    for k in ("tokens_unanswered", "trail_wrong_length",
              "tokens_short_of_sample", "streams_wrong_length"):
        assert got[k] == {"value": 0, "limit": 0}


def _a_pass_earlier(r):
    """Every token not of its block's first pass, said to be of the pass
    before: held against an input with more masks than it was chosen
    under."""
    bl, first = BLOCKDIFF["block_length"], {}
    for q, t in enumerate(r.trail, r.prompt_len):
        first[q // bl] = min(t, first.get(q // bl, t))
    return [t - (t > first[q // bl])
            for q, t in enumerate(r.trail, r.prompt_len)]


def _one_changed(r):
    return r.tokens[:7] + [(r.tokens[7] + 1) % 383] + r.tokens[8:]


def _twice(real):
    def passes(cfg, prompt, served, trail):
        out = real(cfg, prompt, served, trail)
        out[0] = dict(out[0], rows=np.append(out[0]["rows"], out[0]["rows"][0]),
                      chosen=np.append(out[0]["chosen"], out[0]["chosen"][0]))
        return out
    return passes


def _not_at_all(real):
    def passes(cfg, prompt, served, trail):
        out = real(cfg, prompt, served, trail)
        out[-1] = dict(out[-1], rows=out[-1]["rows"][:-1],
                       chosen=out[-1]["chosen"][:-1])
        return out
    return passes


FAULTS = {
    "the trail moved by a pass": (dict(trail=_a_pass_earlier), None,
                                  "logit_gap_mean", 0.03),
    "a token changed": (dict(tokens=_one_changed), None,
                        "logit_gap_mean", 0.03),
    "a trail cut short": (dict(trail=lambda r: r.trail[:len(r.trail)
                                                       - (r.index in (1, 4))]),
                          None, "trail_wrong_length", 2),
    "no trail": (dict(trail=lambda r: None if r.index == 2 else r.trail),
                 None, "trail_wrong_length", 1),
    "a token answered twice": ({}, _twice, "tokens_unanswered", 6),
    "a token answered by no pass": ({}, _not_at_all, "tokens_unanswered", 6),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_fault_reads_not_correct_by_its_own_number(fault, streams,
                                                     monkeypatch):
    changed, replay, number, at_least = FAULTS[fault]
    if replay is not None:
        ref = check._family(BLOCKDIFF)
        monkeypatch.setattr(ref, "passes", replay(ref.passes))
    got = check.compare(BLOCKDIFF, SEED, _copies(streams, **changed))
    assert check.verdict(got) is False
    if got[number]["limit"] == 0:              # a count: exactly so many
        assert got[number]["value"] == at_least, got
    assert got[number]["value"] >= at_least > got[number]["limit"], got
    others = [k for k, v in got.items() if k != number and v["limit"] == 0]
    assert all(got[k]["value"] == 0 for k in others), got


def test_the_control_goes_through_the_same_passes(streams):
    detail = {}
    got = check.compare(BLOCKDIFF, SEED, streams, control=True,
                        detail=detail)
    assert check.verdict(got) is False
    assert got["logit_gap_mean"]["value"] > \
        3 * got["logit_gap_mean"]["limit"]
    assert got["program_logit_gap_max"]["value"] < 1e-4   # the program's
    assert got["tokens_unanswered"]["value"] == 0
    n = sum(n for _, n in REQUESTS)
    assert len(detail["margin"]) == len(detail["gap"]) == \
        len(detail["control_gap"]) == n


@pytest.mark.parametrize("fault, message", [
    (dict(rows=[0, 99]), "a row outside its 12"),
    (dict(tokens=[384] * 12), "outside the vocabulary"),
    (dict(chosen=[1]), "rows"),
    (dict(beside={"pos": [0] * 11}), "beside"),
])
def test_a_pass_out_of_range_is_refused(fault, message, monkeypatch, toy):
    ref = check._family(BLOCKDIFF)
    sound = dict(tokens=[1] * 12, rows=[10, 11], chosen=[5, 6],
                 beside={"pos": list(range(12))})
    monkeypatch.setattr(ref, "passes", lambda *a: [{**sound, **fault}])
    with pytest.raises(ValueError, match=message):
        check.replay(BLOCKDIFF, [1] * 10, [5, 6], [0, 0])
