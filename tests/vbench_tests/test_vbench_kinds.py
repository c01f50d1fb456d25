"""Layer kinds: a model whose layers are not all alike goes through the
harness as files and entries ADDED to a copy of the benchmark
(vbench_toyroot.py, toy_families/), and the two families the benchmark has
are made leaf for leaf as before.

- ``toy_ab``: two kinds the program can serve (both the dense block, in
  the pattern a b b a), one cell through ``run.run_cell``: weights,
  builder, engine, comparison. A sound run is correct, the float8 control
  is not, and a builder that swaps two layers' kinds is not.
- ``toy_lead``: a reference alone, shaped like the configuration the kinds
  make room for (leading dense layer, shared expert, a held share of the
  experts, untied head, sliced vocabulary): ``check.reference_logits``
  equals a straight-line computation written here.
"""

import hashlib
import importlib
import os
import sys
import types

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import vbench_toyroot  # noqa: E402

from vbench import check, run, weights  # noqa: E402

SECONDS = 2.0
SEED = 2**31 + 17
CELL = "toy_ab_open"

TOY_LEAD = dict(
    family="toy_lead", hidden_size=64, num_attention_heads=2, head_dim=32,
    num_hidden_layers=3, first_k_dense_replace=1, intermediate_size=128,
    moe_intermediate_size=32, n_routed_experts=16, experts_held=6,
    experts_held_from=4, num_experts_per_tok=4, n_shared_experts=1,
    routed_scaling_factor=2.5, vocab_size=384, rope_theta=10000.0,
    rms_norm_eps=1e-6, dtype="bfloat16", output_head="lm_head")

# sha256 over every leaf of weights.make_all(SEED, ...) for the toy
# configuration of each family the benchmark has, taken from the parent's
# code (commit b0c9fe6, before weights.py knew of kinds; PR 27)
PARENT_DIGESTS = {
    "toy-dense":
        "dae061f1b306657a9245e28ec79044b244c2e4a545621afafdc8679c041065ab",
    "toy-moe":
        "036f33dce61b63662199f58fb34cc7cf5d424da6b6f339381e31c3e0f4729aac",
}


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("vbench_root"))
    return root, vbench_toyroot.build(root)


def _family(cfg):
    ref = importlib.import_module(f"vbench.reference.{cfg['family']}")
    return ref, ref.weight_specs(cfg), weights.layer_kinds(ref, cfg)


def _same(a, b):
    return a.dtype == b.dtype and a.shape == b.shape and bool((a == b).all())


# -- the two families the benchmark has: nothing moved -----------------------

@pytest.mark.parametrize("name", list(PARENT_DIGESTS))
def test_every_leaf_is_what_the_parent_made(name):
    cfg = vbench_toyroot.CONFIGS[name]
    _, specs, kinds = _family(cfg)
    assert kinds == [None] * cfg["num_hidden_layers"]
    w = weights.make_all(SEED, specs, cfg["num_hidden_layers"], kinds)
    leaves = sorted(((jax.tree_util.keystr(p), np.asarray(x)) for p, x
                     in jax.tree_util.tree_flatten_with_path(w)[0]),
                    key=lambda kv: kv[0])
    h = hashlib.sha256()
    for path, a in leaves:
        h.update(f"{path} {a.dtype} {a.shape}".encode())
        h.update(a.tobytes())
    assert h.hexdigest() == PARENT_DIGESTS[name]


@pytest.mark.parametrize("name", list(PARENT_DIGESTS))
def test_one_kind_is_the_stack_of_its_layers(name):
    cfg = vbench_toyroot.CONFIGS[name]
    _, specs, _ = _family(cfg)
    n = cfg["num_hidden_layers"]
    w = weights.make_all(SEED, specs, n)       # as a caller of today
    key = weights.seed_key(SEED)
    for l in range(n):
        one = weights.make_layer(key, specs, l)
        assert set(one) == set(w["layers"])
        for leaf, a in one.items():
            assert _same(a, w["layers"][leaf][l]), (leaf, l)


# -- two kinds: the stacks, and the whole path -------------------------------

def test_a_kind_owns_its_leaves_and_a_layer_is_itself_in_any_stack(toy):
    """{kind: leaves stacked over that kind's layers, in the model's
    order}; a leaf without a kind is in every stack; layer l is the same
    leaf for leaf whether made alone or in its kind's stack, and is what a
    family of one kind with the same spec list would have at l."""
    cfg = vbench_toyroot.CONFIGS["toy-ab"]
    _, specs, kinds = _family(cfg)
    assert kinds == ["a", "b", "b", "a"]
    w = weights.make_all(SEED, specs, len(kinds), kinds)
    assert set(w) == {"embed", "final_norm", "layers"}
    assert set(w["layers"]) == {"a", "b"}
    shared = {"wq", "wk", "wv", "wo", "attn_norm", "mlp_norm"}
    for kind in "ab":
        assert set(w["layers"][kind]) == shared | {
            f"{kind}_gate", f"{kind}_up", f"{kind}_down"}
    key = weights.seed_key(SEED)
    unowned = [{k: v for k, v in s.items() if k != "kind"} for s in specs]
    flat = weights.make_all(SEED, unowned, len(kinds))["layers"]
    rows = {"a": 0, "b": 0}
    for l, kind in enumerate(kinds):
        one = weights.make_layer(key, specs, l, kind)
        stack = w["layers"][kind]
        assert set(one) == set(stack)
        for leaf, a in one.items():
            assert _same(a, stack[leaf][rows[kind]]), (leaf, l)
            assert _same(a, flat[leaf][l]), (leaf, l)
        rows[kind] += 1
    assert not _same(w["layers"]["a"]["wq"][0], w["layers"]["b"]["wq"][0])
    shapes = jax.eval_shape(lambda: weights.build(key, specs, 4, kinds))
    assert jax.tree.map(lambda s: s.shape, shapes) == \
        jax.tree.map(lambda x: x.shape, w)


def test_kinds_must_name_every_layer(toy):
    cfg = vbench_toyroot.CONFIGS["toy-ab"]
    _, specs, kinds = _family(cfg)
    with pytest.raises(ValueError, match="3 kinds for 4 layers"):
        weights.build(weights.seed_key(1), specs, 4, kinds[:3])
    short = types.SimpleNamespace(__name__="short",
                                  layer_kinds=lambda cfg: ["a"])
    with pytest.raises(ValueError, match="num_hidden_layers is 4"):
        weights.layer_kinds(short, cfg)


@pytest.fixture(scope="module")
def sound(toy):
    return run.run_cell(toy[0], CELL, SEED, SECONDS, False)


def test_a_sound_run_of_two_kinds_is_correct(sound):
    c = sound["compared"]
    assert sound["correct"] is True, c
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert c["tokens_short_of_sample"]["value"] == 0
    assert c["tokens_compared"]["value"] >= 40
    for k in ("logit_gap_max", "logit_gap_mean"):
        assert c[k]["value"] <= c[k]["limit"]


def test_the_control_of_two_kinds_is_not_correct(toy):
    res = run.run_cell(toy[0], CELL, SEED, SECONDS, False, control=True)
    c = res["compared"]
    assert res["correct"] is False
    assert c["logit_gap_mean"]["value"] > c["logit_gap_mean"]["limit"]
    for k in ("logit_gap_max", "logit_gap_mean"):   # the program was sound
        assert c[f"program_{k}"]["value"] <= c[k]["limit"]


def test_two_layers_kinds_swapped_in_the_builder_is_not_correct(
        toy, monkeypatch):
    """The builder takes the model for b a b a: layers 0 and 1 change
    places in the program's stack, each still a whole sound layer."""
    import vbench.sut.toy_ab as sut

    monkeypatch.setattr(sut, "layer_kinds", lambda cfg: list("baba"))
    res = run.run_cell(toy[0], CELL, SEED, SECONDS, False)
    c = res["compared"]
    assert res["correct"] is False
    assert res["failed"] == 0 and c["streams_wrong_length"]["value"] == 0
    for k in ("logit_gap_max", "logit_gap_mean"):
        assert c[k]["value"] > 3 * c[k]["limit"], c


# -- the shape the room is for, reference side -------------------------------

def _f64(x):
    return np.asarray(x.astype("float32"), np.float64)


def _rms(x, gain, eps):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * gain


def _rope(x, theta):
    s, _, dh = x.shape
    half = dh // 2
    ang = np.arange(s)[:, None] / theta ** (np.arange(half) / half)[None, :]
    cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return np.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _swiglu(n, gate, up, down):
    return (_silu(n @ gate) * (n @ up)) @ down


def _attention(cfg, w, x):
    s, (h, dh) = x.shape[0], (cfg["num_attention_heads"], cfg["head_dim"])
    n = _rms(x, w["attn_norm"], cfg["rms_norm_eps"])
    q = _rope((n @ w["wq"]).reshape(s, h, dh), cfg["rope_theta"])
    k = _rope((n @ w["wk"]).reshape(s, h, dh), cfg["rope_theta"])
    v = (n @ w["wv"]).reshape(s, h, dh)
    out = np.zeros((s, h, dh))
    for head in range(h):
        scores = q[:, head] @ k[:, head].T / np.sqrt(dh)
        scores = np.where(np.tril(np.ones((s, s), bool)), scores, -np.inf)
        p = np.exp(scores - scores.max(-1, keepdims=True))
        out[:, head] = p / p.sum(-1, keepdims=True) @ v[:, head]
    return x + out.reshape(s, h * dh) @ w["wo"]


def _straight_line(cfg, w, tokens):
    """The whole toy_lead model over one sequence, in float64, every layer
    written out: dense, sparse, sparse."""
    dense = {k: _f64(v[0]) for k, v in w["layers"]["dense"].items()}
    sparse = [{k: _f64(v[r]) for k, v in w["layers"]["sparse"].items()}
              for r in (0, 1)]
    x = _f64(w["embed"])[tokens]

    x = _attention(cfg, dense, x)
    n = _rms(x, dense["mlp_norm"], cfg["rms_norm_eps"])
    x = x + _swiglu(n, dense["w_gate"], dense["w_up"], dense["w_down"])

    for lw in sparse:
        x = _attention(cfg, lw, x)
        n = _rms(x, lw["mlp_norm"], cfg["rms_norm_eps"])
        scores = 1.0 / (1.0 + np.exp(-(n @ lw["router"])))
        out = _swiglu(n, lw["s_gate"], lw["s_up"], lw["s_down"])
        for t in range(len(tokens)):
            chosen = np.argsort(-(scores[t] + lw["router_bias"]))[
                :cfg["num_experts_per_tok"]]
            share = scores[t, chosen] / scores[t, chosen].sum() \
                * cfg["routed_scaling_factor"]
            for e, g in zip(chosen, share):
                held = e - cfg["experts_held_from"]
                if 0 <= held < cfg["experts_held"]:   # else: another chip's
                    out[t] += g * _swiglu(n[t], lw["e_gate"][held],
                                          lw["e_up"][held],
                                          lw["e_down"][held])
        x = x + out
    n = _rms(x, _f64(w["final_norm"]), cfg["rms_norm_eps"])
    return n @ _f64(w["lm_head"]).T


def test_reference_walks_a_leading_dense_layer_then_sparse_ones(toy):
    cfg = TOY_LEAD
    ref, specs, kinds = _family(cfg)
    assert kinds == ["dense", "sparse", "sparse"]
    w = weights.make_all(SEED, specs, len(kinds), kinds)
    assert set(w) == {"embed", "final_norm", "lm_head", "layers"}
    assert w["embed"].shape == w["lm_head"].shape == (384, 64)
    assert not _same(w["embed"], w["lm_head"])
    assert w["layers"]["dense"]["w_gate"].shape == (1, 64, 128)
    assert w["layers"]["sparse"]["e_gate"].shape == (2, 6, 64, 32)
    assert "router" not in w["layers"]["dense"]
    assert "w_gate" not in w["layers"]["sparse"]
    # a bias's small range through fan_in; ones where fan_in is None
    bias = np.asarray(w["layers"]["sparse"]["router_bias"])
    assert bias.dtype == np.float32 and bias.shape == (2, 16)
    assert 0.005 < np.abs(bias).max() <= np.sqrt(3.0 / ref.BIAS_FAN_IN)
    for gain in (w["final_norm"], w["layers"]["sparse"]["mlp_norm"],
                 w["layers"]["dense"]["attn_norm"]):
        assert bool((gain == 1).all()) and gain.dtype == w["embed"].dtype

    rng = np.random.default_rng(3)
    pairs = [(rng.integers(0, 384, 21), list(rng.integers(0, 384, 9))),
             (rng.integers(0, 384, 5), list(rng.integers(0, 384, 30)))]
    got = check.reference_logits(
        cfg, SEED, [check.replay(cfg, p, s) for p, s in pairs])
    for (prompt, served), logits in zip(pairs, got):
        tokens = np.concatenate([prompt, served[:-1]])
        want = _straight_line(cfg, w, tokens)[len(prompt) - 1:]
        assert logits.shape == want.shape == (len(served), 384)
        np.testing.assert_allclose(logits, want, rtol=0, atol=1e-5)
