"""The family ``mla`` (DeepSeek-V2's block) in the harness: as a cell ADDED
to a copy of the benchmark (vbench_toyroot.py's root plus one
configuration, one mix and one cell written here), through
``run.run_cell`` on the CPU: a sound run is correct and the float8 control
is not. Its two cost functions against counts done by hand, the real
cell's entries and files, and each of its two readers on a small recorded
trace and on a program without what they read.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import vbench_toyroot  # noqa: E402

from vbench import latent_scopes, manifest, run, scopes, traffic  # noqa: E402
from vbench.reference import mla as ref  # noqa: E402
from vbench.rundata import Run  # noqa: E402

SECONDS = 2.0
SEED = 2**31 + 35
CELL = "toy_mla_longgen"
REAL, REAL_CFG = "dsv2_longgen", "deepseek-v2-5l-ep8"
MS = 10 ** 9  # a millisecond in the trace's picoseconds
NEW = ["latent_walk_roofline", "latent_walk_live_pct"]
SHARED = ["latent_attn_ms_per_step", "latent_attn_ms_per_chunk"]

# The toy computes in float32, as the latent family's toy does: sound runs
# read 0.0 on both numbers (every served token is the reference's first;
# four seeds on the CPU, 134-154 tokens compared a run, PR 35), the float8
# control a mean of 0.26-0.43 and a widest gap of 2.3-3.8.
TOY = dict(
    family="mla", hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_attention_heads=4, q_lora_rank=48,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=4, n_routed_experts_published=16, held_experts_first=4,
    num_experts_per_tok=3, n_group=4, topk_group=2, n_shared_experts=2,
    routed_scaling_factor=16, scoring_func="softmax",
    topk_method="group_limited_greedy", norm_topk_prob=False,
    first_k_dense_replace=1, num_hidden_layers=3, vocab_size=384,
    max_position_embeddings=256, rope_theta=10000,
    rope_scaling=dict(beta_fast=32, beta_slow=1, factor=40, mscale=0.707,
                      mscale_all_dim=0.707,
                      original_max_position_embeddings=16, type="yarn"),
    rms_norm_eps=1e-6, dtype="float32", output_head="lm_head",
    serving=dict(slots=4, kv_page=8, kv_pool_blocks=90,
                 prefill_buckets=[16], prefill_batch_sizes=[1],
                 prefill_chunk=32, prefill_budget=64, max_new_tokens=48,
                 read_windows=[32, 64, 128, 256]),
    check=dict(requests=6, min_tokens=40,
               limits=dict(logit_gap_max=0.25, logit_gap_mean=0.008)))
MIX = dict(kind="saturated", ahead=2, settle_s=0.5, ramp_stagger=4,
           drain_s=0, grid=4, schedule_seed=13,
           prompt=dict(median=40, sigma=0.5, min=16, max=120),
           output=dict(median=24, sigma=0.3, min=12, max=48))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The toy root, and the family's cell added as a PR would."""
    root = str(tmp_path_factory.mktemp("vbench_mla_root"))
    man = vbench_toyroot.build(root)
    with open(os.path.join(root, "vbench/configs/toy-mla.json"), "w") as f:
        json.dump(TOY, f)
    with open(os.path.join(root, "vbench/traffic/toy-longgen.json"), "w") as f:
        json.dump(MIX, f)
    man["configs"].append(dict(
        name="toy-mla", source="tests", reduced=[], why="toy size",
        file="vbench/configs/toy-mla.json"))
    man["workloads"].append(dict(name=CELL, config="toy-mla",
                                 traffic="toy-longgen", chips=1, why="toy"))
    for group in ("end_to_end", "per_layer"):
        for m in man[group]:
            if REAL in m.get("workloads", ()):
                m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return root


def test_the_real_cell_is_in_the_manifest_with_its_files():
    man = manifest.load(vbench_toyroot.REPO)
    cell = manifest.cell(man, REAL)
    cfg = manifest.config(man, vbench_toyroot.REPO, cell["config"])
    entry = next(c for c in man["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 1 and cfg["family"] == "mla"
    assert cell["config"] == REAL_CFG and cell["traffic"] == "longgen"
    assert set(entry["reduced"]) == set(cfg["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "max_position_embeddings"}
    assert ref.layer_kinds(cfg) == ["dense"] + ["sparse"] * 4
    per_layer = {m["name"] for m in manifest.metrics_of(
        man, "per_layer", REAL)}
    assert set(NEW + SHARED) <= per_layer
    # no indexer, no selection, no head-cached pool: their metrics are
    # another family's
    assert not per_layer & {
        "indexer_ms_per_step", "indexer_ms_per_chunk", "selected_share_pct",
        "sparse_attn_roofline", "kernel_route_pct", "paged_attn_ms_per_step",
        "pool_relayout_ms_per_step"}
    assert {m["name"] for m in manifest.metrics_of(
        man, "end_to_end", REAL)} == {
            "itl_mean_ms", "out_tokens_per_s", "setup_s"}
    for name in NEW:
        manifest.reader(vbench_toyroot.REPO, name)
        row = next(m for m in man["per_layer"] if m["name"] == name)
        assert row["workloads"] == [REAL]
        assert (row["layer"], row["moves"]) == ("latent attention",
                                                "itl_mean_ms")
    # the mix is the issue's table
    mix = traffic.load_mix(cell["traffic"], vbench_toyroot.REPO)
    assert {k: mix[k] for k in ("kind", "ahead", "settle_s", "drain_s",
                                "ramp_stagger", "grid")} == dict(
        kind="saturated", ahead=2, settle_s=3, drain_s=0, ramp_stagger=96,
        grid=16)
    assert mix["prompt"] == dict(median=4096, sigma=0.6, min=1024, max=16384)
    assert mix["output"] == dict(median=4096, sigma=0.4, min=2048, max=8192)
    sizes = cfg["serving"]
    assert (sizes["slots"], sizes["kv_page"], sizes["prefill_chunk"],
            sizes["prefill_budget"], sizes["max_new_tokens"]) == (
                96, 64, 512, 1024, 8192)


def test_the_file_holds_the_catalogs_numbers_but_the_reduced():
    """Every number of the catalog's entry under the same key, those in
    ``reduced`` apart; no width among the reduced."""
    published = dict(
        first_k_dense_replace=1, hidden_size=5120, intermediate_size=12288,
        kv_lora_rank=512, max_position_embeddings=163840,
        moe_intermediate_size=1536, moe_layer_freq=1, n_group=8,
        n_routed_experts=160, n_shared_experts=2, num_attention_heads=128,
        num_experts_per_tok=6, num_hidden_layers=60, num_key_value_heads=128,
        q_lora_rank=1536, qk_nope_head_dim=128, qk_rope_head_dim=64,
        rms_norm_eps=1e-06, rope_theta=10000, routed_scaling_factor=16,
        topk_group=3, v_head_dim=128, vocab_size=102400)
    cfg = manifest.config(manifest.load(vbench_toyroot.REPO),
                          vbench_toyroot.REPO, REAL_CFG)
    differs = {k for k, v in published.items() if cfg[k] != v}
    assert differs == set(cfg["reduced"])
    assert cfg["rope_scaling"] == dict(
        beta_fast=32, beta_slow=1, factor=40, mscale=0.707,
        mscale_all_dim=0.707, original_max_position_embeddings=4096,
        type="yarn")
    assert (cfg["scoring_func"], cfg["topk_method"], cfg["norm_topk_prob"],
            cfg["model_type"]) == ("softmax", "group_limited_greedy", False,
                                   "deepseek_v2")
    assert cfg["n_routed_experts_published"] == 160
    assert (cfg["held_experts_first"], cfg["n_routed_experts"]) == (0, 20)
    assert "8 chips" in cfg["stands_for"]
    assert ref.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * 1.5896,
                                                   rel=1e-4)


@pytest.fixture(scope="module")
def sound(root):
    return run.run_cell(root, CELL, SEED, SECONDS, False)


def test_a_sound_run_of_the_family_is_correct(sound):
    c = sound["compared"]
    assert sound["correct"] is True, c
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert c["tokens_short_of_sample"]["value"] == 0
    assert set(sound["metrics"]) == {"itl_mean_ms", "out_tokens_per_s",
                                     "setup_s"}
    for k in ("logit_gap_max", "logit_gap_mean"):
        assert c[k]["value"] <= c[k]["limit"]


def test_the_float8_control_of_the_family_is_not_correct(root):
    res = run.run_cell(root, CELL, SEED, SECONDS, False, control=True)
    c = res["compared"]
    assert res["correct"] is False
    assert c["logit_gap_mean"]["value"] > c["logit_gap_mean"]["limit"]
    for k in ("logit_gap_max", "logit_gap_mean"):   # the program was sound
        assert c[f"program_{k}"]["value"] <= c[k]["limit"]


# -- operations and bytes, counted by hand -----------------------------------

def _real():
    man = manifest.load(vbench_toyroot.REPO)
    return manifest.config(man, vbench_toyroot.REPO, REAL_CFG)


def test_latent_attn_step_cost_against_a_count_by_hand():
    """96 streams holding 710400 tokens, five layers: 128 heads against
    every live row, 576 wide in and 512 out; the row's 576 bfloat16 read
    once. 278.5 kFLOP and 1152 B a cached token a layer: 242 FLOP a byte,
    on the v5e's ridge of 240."""
    flops, byts = ref.latent_attn_step_cost(_real(), 96, 710400)
    assert flops == 5 * 2 * 128 * (576 + 512) * 710400 == 989_331_456_000
    assert byts == 5 * 576 * 2 * 710400 == 4_091_904_000
    assert 2 * 128 * (576 + 512) == 278_528 and flops / byts == pytest.approx(
        241.8, abs=0.1)
    # the batch does not enter: the cached tokens alone
    assert ref.latent_attn_step_cost(_real(), 1, 710400) == (flops, byts)


def test_decode_step_cost_against_a_count_by_hand():
    """Latent attention 149,225,472 parameters a layer (the issue's 149.2
    M); the dense layer's SwiGLU 3 x 5120 x 12288; a sparse layer's router
    in float32, the two shared experts, and of the 20 held experts 6 x 20 /
    160 = 0.75 a token computed, min(20, 96 x 0.75) = 20 read; embedding
    rows and the untied head."""
    cfg = _real()
    flops, byts = ref.decode_step_cost(cfg, 96, 710400)
    af, ab = ref.latent_attn_step_cost(cfg, 96, 710400)
    latent = (5120 * 1536 + 1536 * 128 * 192 + 5120 * 576
              + 512 * 128 * 256 + 128 * 128 * 5120)
    assert latent == 149_225_472
    expert, dense = 3 * 5120 * 1536, 3 * 5120 * 12288
    want_f = (af + 5 * 96 * 2 * latent + 96 * 2 * dense
              + 4 * 96 * (2 * 5120 * 160 + 2 * expert * 2.75)
              + 96 * 2 * 5120 * 12800)
    want_b = (ab + 5 * (latent * 2 + 96 * 576 * 2) + dense * 2
              + 4 * (5120 * 160 * 4 + expert * 2 * (2 + 20))
              + (12800 * 5120 + 96 * 5120) * 2)
    assert flops == pytest.approx(want_f, rel=1e-12)
    assert byts == pytest.approx(want_b, rel=1e-12)
    # the weights' 6.2 GB and the cache's 4.1: 12.6 ms at 819 GB/s
    assert 10.2e9 < byts < 10.5e9


def test_the_weights_are_the_issues_bytes():
    """3.15 B parameters, 6.29 GB in bfloat16 (the router in float32)."""
    specs = ref.weight_specs(_real())
    kinds = ref.layer_kinds(_real())
    total = 0
    for s in specs:
        n = 1
        for d in s["shape"]:
            n *= d
        layers = (1 if not s["layered"] else
                  sum(1 for k in kinds if s.get("kind", k) == k))
        total += n * layers * (4 if s["dtype"] == "float32" else 2)
    assert 6.27e9 < total < 6.31e9, total
    assert not any(s["name"].startswith("idx_") or s["name"] == "route_bias"
                   for s in specs)


# -- the readers, on a small recorded trace -----------------------------------

def _steps():
    """Three 30 ms decode launches and one 40 ms chunk launch. A decode
    launch: 6 ms of qkv, then under ``attn`` 14 ms of the walk
    (``latent_attn``, the kernel ``latent_walk``), 2 of o_proj, 8 of
    experts. The chunk: 22 ms of latent_attn, 10 of experts."""
    ops, modules = [], []
    for i in range(3):
        t = 10 + 50 * i
        modules.append(["jit_step(9)", t * MS, 30 * MS])
        for name, at, dur, path in (
                ("%fusion.1", 0, 6, "jit(step)/qkv/dot_general:"),
                ("%latent_walk.2", 6, 14,
                 "jit(step)/attn/latent_attn/latent_walk:"),
                ("%fusion.3", 20, 2, "jit(step)/o_proj/dot_general:"),
                ("%fusion.4", 22, 8, "jit(step)/experts/dot_general:")):
            ops.append([name, (t + at) * MS, dur * MS, path])
    modules.append(["jit_prefill_chunk_into_slot(3)", 200 * MS, 40 * MS])
    for name, at, dur, scope in (("%fusion.8", 200, 22, "attn/latent_attn/dot:"),
                                 ("%fusion.9", 222, 10, "experts/dot:")):
        ops.append([name, at * MS, dur * MS,
                    "jit(prefill_chunk_into_slot)/" + scope])
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "spans": []}


def _run(**kw):
    base = dict(records=[], seconds=10.0, setup_s=1.0, give_up_s=10.0,
                stats0={}, stats1={}, cfg=_real(), mix={}, peaks={},
                step_cost=ref.decode_step_cost)
    return Run(**{**base, **kw})


@pytest.mark.parametrize("name", NEW + SHARED)
def test_each_reader_on_the_recorded_trace(name, monkeypatch):
    from vbench.stamps import Record

    raw = _steps()
    monkeypatch.setattr(scopes, "load", lambda root=None: scopes.reduce(raw))
    monkeypatch.setattr(latent_scopes, "load",
                        lambda root=None: latent_scopes.by_program(raw))
    # the walk is ``attn`` to the copy of the vocabulary the harness holds
    by = scopes.reduce(raw)["programs"]["jit_step"]["scopes"]
    assert by["attn"] == pytest.approx(0.042) and "unscoped" not in by
    records = [Record(index=i, prompt_len=7000, max_new=4096, due_s=0.0,
                      in_window=True, stamps=[0.5 + 0.1 * j for j in range(64)])
               for i in range(96)]
    r = _run(records=records, trace_span=(2.0, 4.0),
             stats0=dict(latent_rows_live=1000, latent_rows_walked=1024),
             stats1=dict(latent_rows_live=701000, latent_rows_walked=704024),
             peaks=dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9))
    got = manifest.reader(vbench_toyroot.REPO, name)(r)
    if name == "latent_attn_ms_per_step":
        assert got == pytest.approx(14.0)
    elif name == "latent_attn_ms_per_chunk":
        assert got == pytest.approx(22.0)
    elif name == "latent_walk_live_pct":
        assert got == pytest.approx(100 * 700000 / 703000)
    else:
        live = 96 * (7000 + 25.5)    # tokens held at the eight sample times
        flops, byts = ref.latent_attn_step_cost(r.cfg, 96, live)
        least = max(flops / 197e12, byts / 819e9)
        assert got == pytest.approx(100 * least / 0.014, rel=0.01)
        assert 0 < got < 100


@pytest.mark.parametrize("name", NEW)
def test_each_new_reader_reads_none_from_a_program_without_it(
        name, monkeypatch):
    """The parent of PR 35: no such scope in the trace, no such counter;
    and another family's cost module has no ``latent_attn_step_cost``."""
    raw = _steps()
    for op in raw["devices"]["/device:TPU:0"]["ops"]:
        op[3] = "jit(step)/attn/dot:"
    monkeypatch.setattr(scopes, "load", lambda root=None: scopes.reduce(raw))
    monkeypatch.setattr(latent_scopes, "load", lambda root=None: None)
    r = _run(trace_span=(2.0, 4.0), stats0=dict(decode_ticks=0),
             stats1=dict(decode_ticks=5),
             peaks=dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9))
    assert manifest.reader(vbench_toyroot.REPO, name)(r) is None
    dense = dict(r.cfg, family="dense")
    monkeypatch.setattr(latent_scopes, "load",
                        lambda root=None: latent_scopes.by_program(_steps()))
    assert manifest.reader(vbench_toyroot.REPO, NEW[0])(
        _run(cfg=dense, trace_span=(2.0, 4.0))) is None
