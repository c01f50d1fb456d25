"""The functions that count a decode step's operations and bytes, against
counts made by hand from the two configurations' shapes; and the weights."""

import importlib
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from vbench_toyroot import CONFIGS, REPO  # noqa: E402

from vbench import manifest, weights  # noqa: E402


def _cfg(name):
    with open(os.path.join(REPO, "vbench", "configs", f"{name}.json")) as f:
        return json.load(f)


def test_dense_step_cost_by_hand():
    """deepseek-llm-7b-15l, 14 streams holding 10,000 cached tokens."""
    cost = importlib.import_module("vbench.reference.dense").decode_step_cost
    flops, byts = cost(_cfg("deepseek-llm-7b-15l"), 14, 10000)
    proj = 14 * 2 * 4 * 4096 * 4096          # q, k, v, o
    attn = 2 * 2 * 10000 * 4096              # scores and values, live only
    mlp = 14 * 2 * 3 * 4096 * 11008
    head = 14 * 2 * 4096 * 102400
    assert proj == 1_879_048_192 and mlp == 3_787_456_512
    assert flops == 15 * (proj + attn + mlp) + head == 99_199_221_760
    w_attn, w_mlp = 4 * 4096 * 4096 * 2, 3 * 4096 * 11008 * 2
    kv = 2 * (10000 + 14) * 4096 * 2         # read the cache, write 14 rows
    w_head = (102400 * 4096 + 14 * 4096) * 2
    assert byts == 15 * (w_attn + kv + w_mlp) + w_head == 9_371_271_168


def test_moe_step_cost_by_hand():
    """olmoe-1b-7b-8l, 4 streams holding 1,000 cached tokens: 32 of the 64
    experts can be touched, 8 computed a token."""
    cost = importlib.import_module("vbench.reference.moe").decode_step_cost
    flops, byts = cost(_cfg("olmoe-1b-7b-8l"), 4, 1000)
    proj, attn = 4 * 2 * 4 * 2048 * 2048, 2 * 2 * 1000 * 2048
    router, experts = 4 * 2 * 2048 * 64, 4 * 8 * 2 * 3 * 2048 * 1024
    head = 4 * 2 * 2048 * 50304
    assert flops == 8 * (proj + attn + router + experts) + head \
        == 5_193_072_640
    w_attn, kv = 4 * 2048 * 2048 * 2, 2 * 1004 * 2048 * 2
    w_router, w_exp = 2048 * 64 * 4, 32 * 3 * 2048 * 1024 * 2
    w_head = (50304 * 2048 + 4 * 2048) * 2
    assert byts == 8 * (w_attn + kv + w_router + w_exp) + w_head \
        == 3_765_714_944


def test_moe_reads_every_expert_once_a_batch_is_wide_enough():
    cost = importlib.import_module("vbench.reference.moe").decode_step_cost
    cfg = _cfg("olmoe-1b-7b-8l")
    _, b8 = cost(cfg, 8, 0)
    _, b64 = cost(cfg, 64, 0)
    all_experts = 8 * 64 * 3 * 2048 * 1024 * 2
    assert b64 - b8 < 0.01 * all_experts      # nothing but rows added
    assert b8 > all_experts


@pytest.mark.parametrize("name,params,kv_per_token", [
    ("deepseek-llm-7b-15l", 3_455_184_896, 245_760),
    ("olmoe-1b-7b-8l", 3_459_549_184, 65_536)])
def test_weight_specs_hold_the_parameters_the_config_states(
        name, params, kv_per_token):
    cfg = _cfg(name)
    ref = importlib.import_module(f"vbench.reference.{cfg['family']}")
    total = 0
    for s in ref.weight_specs(cfg):
        n = int(np.prod(s["shape"]))
        total += n * (cfg["num_hidden_layers"] if s["layered"] else 1)
    assert total == params
    kv = (2 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
          * cfg["head_dim"] * 2)
    assert kv == kv_per_token


def test_peaks_are_keyed_by_device_kind_and_unknown_is_an_error():
    row = manifest.peaks(REPO, "TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9 and row["source"]
    with pytest.raises(KeyError):
        manifest.peaks(REPO, "TPU v9 imaginary")


@pytest.mark.parametrize("family", ["toy-dense", "toy-moe"])
def test_weights_are_a_function_of_the_seed_alone(family):
    import jax

    cfg = CONFIGS[family]
    ref = importlib.import_module(f"vbench.reference.{cfg['family']}")
    specs = ref.weight_specs(cfg)
    big = 2**31 + 11
    a = weights.make_all(big, specs, cfg["num_hidden_layers"])
    b = weights.make_all(big, specs, cfg["num_hidden_layers"])
    c = weights.make_all(big + 1, specs, cfg["num_hidden_layers"])
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert np.array_equal(np.asarray(x, np.float32),
                              np.asarray(y, np.float32))
    assert not np.array_equal(np.asarray(a["embed"], np.float32),
                              np.asarray(c["embed"], np.float32))
    # the reference's layer-by-layer leaves are the stacked ones
    key = weights.seed_key(big)
    for l in range(cfg["num_hidden_layers"]):
        one = weights.make_layer(key, specs, l)
        for name, leaf in one.items():
            assert np.array_equal(
                np.asarray(leaf, np.float32),
                np.asarray(a["layers"][name][l], np.float32)), (name, l)
    g = weights.make_globals(key, specs)
    assert np.array_equal(np.asarray(g["embed"], np.float32),
                          np.asarray(a["embed"], np.float32))
    w = np.asarray(a["layers"]["wq"], np.float32)
    assert abs(w.std() - (1 / cfg["hidden_size"]) ** 0.5) < 0.02
    assert np.all(np.asarray(a["layers"]["attn_norm"], np.float32) == 1)


def test_negative_seed_is_refused():
    with pytest.raises(ValueError):
        weights.seed_key(-1)
