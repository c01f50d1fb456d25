"""The latent family in the harness: as a cell ADDED to a copy of the
benchmark (vbench_toyroot.py's root plus one configuration, one mix and
one cell written here), through ``run.run_cell`` on the CPU: a sound run
is correct and the float8 control is not. Its two cost functions against
counts done by hand, and each of its readers on a small recorded trace.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import vbench_toyroot  # noqa: E402

from vbench import latent_scopes, manifest, run, scopes, traffic  # noqa: E402
from vbench.reference import latent as ref  # noqa: E402
from vbench.rundata import Run  # noqa: E402

SECONDS = 2.0
SEED = 2**31 + 28
CELL = "toy_latent_long"
MS = 10 ** 9  # a millisecond in the trace's picoseconds
NEW = ["indexer_ms_per_step", "latent_attn_ms_per_step", "selected_share_pct",
       "sparse_attn_roofline", "indexer_ms_per_chunk",
       "latent_attn_ms_per_chunk"]

# The toy computes in float32. At this size bfloat16 cannot be held to the
# reference: with 16 positions kept, each one that the indexer's rounding
# swaps at the threshold is a sixteenth of a query's attention
# (tests/test_latent_sparse.py reads 2.3 in the logits selecting for
# itself, 0.13 given the reference's selection); at the published 2048 a
# swap weighs 1/2048. Limits from readings on the CPU (four seeds, 85-109
# tokens compared a run; PR 28): sound runs read 0.0 on both numbers (every
# served token is the reference's first), the float8 control a mean of
# 0.83-1.06 and a widest gap of 3.0-5.0.
TOY = dict(
    family="latent", hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_attention_heads=4, q_lora_rank=48,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    index_n_heads=4, index_head_dim=16, index_topk=16, n_routed_experts=4,
    n_routed_experts_published=16, held_experts_first=4,
    num_experts_per_tok=4, n_group=4, topk_group=2, n_shared_experts=1,
    routed_scaling_factor=2.5, first_k_dense_replace=1, num_hidden_layers=3,
    vocab_size=384, max_position_embeddings=256, rope_theta=10000,
    rope_scaling=dict(beta_fast=32, beta_slow=1, factor=40, mscale=1,
                      mscale_all_dim=1, original_max_position_embeddings=16,
                      type="yarn"),
    rms_norm_eps=1e-6, dtype="float32", output_head="lm_head",
    serving=dict(slots=4, kv_page=8, kv_pool_blocks=90,
                 prefill_buckets=[16], prefill_batch_sizes=[1],
                 prefill_chunk=32, prefill_budget=64, max_new_tokens=32,
                 read_windows=[32, 64, 128, 256]),
    check=dict(requests=6, min_tokens=40,
               limits=dict(logit_gap_max=0.25, logit_gap_mean=0.008)))
MIX = dict(kind="saturated", ahead=2, settle_s=0.5, ramp_stagger=3,
           drain_s=0, grid=4, schedule_seed=13,
           prompt=dict(median=80, sigma=0.4, min=40, max=160),
           output=dict(median=16, sigma=0.3, min=8, max=32))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The toy root, and the latent family's cell added as a PR would."""
    root = str(tmp_path_factory.mktemp("vbench_latent_root"))
    man = vbench_toyroot.build(root)
    with open(os.path.join(root, "vbench/configs/toy-latent.json"), "w") as f:
        json.dump(TOY, f)
    with open(os.path.join(root, "vbench/traffic/toy-long.json"), "w") as f:
        json.dump(MIX, f)
    man["configs"].append(dict(
        name="toy-latent", source="tests", reduced=[], why="toy size",
        file="vbench/configs/toy-latent.json"))
    man["workloads"].append(dict(name=CELL, config="toy-latent",
                                 traffic="toy-long", chips=1, why="toy"))
    for group in ("end_to_end", "per_layer"):
        for m in man[group]:
            if "dsv32_longctx" in m.get("workloads", ()):
                m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return root


def test_the_real_cell_is_in_the_manifest_with_its_files():
    man = manifest.load(vbench_toyroot.REPO)
    cell = manifest.cell(man, "dsv32_longctx")
    cfg = manifest.config(man, vbench_toyroot.REPO, cell["config"])
    entry = next(c for c in man["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 1 and cfg["family"] == "latent"
    assert set(entry["reduced"]) == set(cfg["reduced"])
    assert ref.layer_kinds(cfg) == ["dense"] + ["sparse"] * 4
    per_layer = {m["name"] for m in manifest.metrics_of(
        man, "per_layer", "dsv32_longctx")}
    assert set(NEW) <= per_layer
    assert not per_layer & {"kernel_route_pct", "paged_attn_ms_per_step",
                            "pool_relayout_ms_per_step"}
    assert {m["name"] for m in manifest.metrics_of(
        man, "end_to_end", "dsv32_longctx")} == {
            "itl_mean_ms", "out_tokens_per_s", "setup_s"}
    for name in NEW:
        manifest.reader(vbench_toyroot.REPO, name)
    # the mix is the issue's, settle_s 3 with it: the slots fill one lane
    # of chunks after another (prefill_budget // prefill_chunk at once), so
    # "filled" means nearly all are decoding, not sixteen first chunks
    mix = traffic.load_mix(cell["traffic"], vbench_toyroot.REPO)
    assert (mix["settle_s"], mix["ahead"], mix["ramp_stagger"]) == (3, 2, 6)
    sizes = cfg["serving"]
    assert sizes["prefill_budget"] == 4 * sizes["prefill_chunk"]


@pytest.fixture(scope="module")
def sound(root):
    return run.run_cell(root, CELL, SEED, SECONDS, False)


def test_a_sound_run_of_the_latent_family_is_correct(sound):
    c = sound["compared"]
    assert sound["correct"] is True, c
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert c["tokens_short_of_sample"]["value"] == 0
    assert set(sound["metrics"]) == {"itl_mean_ms", "out_tokens_per_s",
                                     "setup_s"}
    for k in ("logit_gap_max", "logit_gap_mean"):
        assert c[k]["value"] <= c[k]["limit"]


def test_the_float8_control_of_the_latent_family_is_not_correct(root):
    res = run.run_cell(root, CELL, SEED, SECONDS, False, control=True)
    c = res["compared"]
    assert res["correct"] is False
    assert c["logit_gap_mean"]["value"] > c["logit_gap_mean"]["limit"]
    for k in ("logit_gap_max", "logit_gap_mean"):   # the program was sound
        assert c[f"program_{k}"]["value"] <= c[k]["limit"]


# -- operations and bytes, counted by hand -----------------------------------

def _real():
    man = manifest.load(vbench_toyroot.REPO)
    return manifest.config(man, vbench_toyroot.REPO, "deepseek-v3.2-5l-ep16")


def test_sparse_attn_step_cost_against_a_count_by_hand():
    """16 streams holding 272000 tokens, five layers. Indexer weights:
    1536 x 8192 + 7168 x 128 + 7168 x 64 = 13,959,168. Scores: 64 heads x
    128 wide against each live key. Attention: 128 heads against 16 x 2048
    chosen rows, 576 wide in and 512 out."""
    flops, byts = ref.sparse_attn_step_cost(_real(), 16, 272000)
    idx = 1536 * 8192 + 7168 * 128 + 7168 * 64
    assert idx == 13_959_168
    chosen = 16 * 2048
    layer_flops = (16 * 2 * idx + 2 * 64 * 128 * 272000
                   + 2 * 128 * (576 + 512) * chosen)
    layer_bytes = idx * 2 + (272000 + 16) * 128 * 2 + chosen * 576 * 2
    assert flops == 5 * layer_flops == 70_149_734_400
    assert byts == 5 * layer_bytes == 676_515_840
    # a short batch reads what it holds, not 2048 a stream
    f2, b2 = ref.sparse_attn_step_cost(_real(), 16, 16000)
    assert b2 == 5 * (idx * 2 + 16016 * 256 + 16000 * 1152)


def test_decode_step_cost_against_a_count_by_hand():
    """Latent attention 187,105,280 parameters a layer (the issue's 187.1
    M); the dense layer's SwiGLU 3 x 7168 x 18432; a sparse layer's router
    in float32, the shared expert, and of the 16 held experts 8 x 16 / 256
    = 0.5 a token computed, min(16, 16 x 0.5) = 8 read; embedding rows and
    the untied head."""
    cfg = _real()
    flops, byts = ref.decode_step_cost(cfg, 16, 272000)
    sf, sb = ref.sparse_attn_step_cost(cfg, 16, 272000)
    latent = (7168 * 1536 + 1536 * 128 * 192 + 7168 * 576
              + 512 * 128 * 256 + 128 * 128 * 7168)
    assert latent == 187_105_280
    expert, dense = 3 * 7168 * 2048, 3 * 7168 * 18432
    want_f = (sf + 5 * 16 * 2 * latent + 16 * 2 * dense
              + 4 * 16 * (2 * 7168 * 256 + 2 * expert * 1.5)
              + 16 * 2 * 7168 * 16160)
    want_b = (sb + 5 * (latent * 2 + 16 * 576 * 2) + dense * 2
              + 4 * (7168 * 256 * 4 + expert * 2 * (1 + 8))
              + (16160 * 7168 + 16 * 7168) * 2)
    assert flops == pytest.approx(want_f, rel=1e-12)
    assert byts == pytest.approx(want_b, rel=1e-12)
    assert 6.7e9 < byts < 6.9e9       # 8.3 ms at 819 GB/s: the step's floor


# -- the readers, on a small recorded trace -----------------------------------

def _steps():
    """Three 40 ms decode launches and one 30 ms chunk launch. A decode
    launch: 8 ms of qkv, then under ``attn``: 6 ms of indexer, 9 of select,
    5 of latent_attn; 12 of experts. The chunk: 4 ms of indexer, 8 of
    select, 18 of latent_attn."""
    ops, modules = [], []
    for i in range(3):
        t = 10 + 50 * i
        modules.append(["jit_step(9)", t * MS, 40 * MS])
        for name, at, dur, path in (
                ("%fusion.1", 0, 8, "jit(step)/qkv/dot_general:"),
                ("%fusion.2", 8, 6, "jit(step)/attn/indexer/while/body/dot:"),
                ("%sort.3", 14, 9, "jit(step)/attn/select/top_k:"),
                ("%fusion.4", 23, 5, "jit(step)/attn/latent_attn/gather:"),
                ("%fusion.5", 28, 12, "jit(step)/experts/dot_general:")):
            ops.append([name, (t + at) * MS, dur * MS, path])
    modules.append(["jit_prefill_chunk_into_slot(3)", 200 * MS, 30 * MS])
    for name, at, dur, scope in (("%fusion.8", 200, 4, "indexer/dot:"),
                                 ("%fusion.9", 204, 8, "select/reduce:"),
                                 ("%fusion.10", 212, 18, "latent_attn/dot:")):
        ops.append([name, at * MS, dur * MS,
                    "jit(prefill_chunk_into_slot)/attn/" + scope])
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "spans": []}


def test_seconds_by_the_new_scopes_are_the_decode_launches_own():
    raw = _steps()
    assert latent_scopes.by_scope(raw) == pytest.approx(
        {"indexer": 0.018, "select": 0.027, "latent_attn": 0.015})
    assert latent_scopes.by_scope(raw, latent_scopes.CHUNK) == pytest.approx(
        {"indexer": 0.004, "select": 0.008, "latent_attn": 0.018})
    # vbench/scopes.py's vocabulary reads the same operations as ``attn``
    by = scopes.reduce(raw)["programs"]["jit_step"]["scopes"]
    assert by["attn"] == pytest.approx(0.060) and "unscoped" not in by
    assert latent_scopes.scope_of("jit(step)/attn/indexer/dot:") == "indexer"
    assert latent_scopes.scope_of("jit(step)/qkv/dot:") is None
    # a program from before the names: nothing to read, and no error
    for op in raw["devices"]["/device:TPU:0"]["ops"]:
        op[3] = op[3].replace("/attn/indexer", "").replace(
            "/attn/select", "").replace("/attn/latent_attn", "")
    assert latent_scopes.by_scope(raw) == {}


def _run(**kw):
    base = dict(records=[], seconds=10.0, setup_s=1.0, give_up_s=10.0,
                stats0={}, stats1={}, cfg=_real(), mix={}, peaks={},
                step_cost=ref.decode_step_cost)
    return Run(**{**base, **kw})


@pytest.mark.parametrize("name", NEW)
def test_each_new_reader_on_the_recorded_trace(name, monkeypatch):
    from vbench.stamps import Record

    raw = _steps()
    monkeypatch.setattr(scopes, "load", lambda root=None: scopes.reduce(raw))
    monkeypatch.setattr(latent_scopes, "load",
                        lambda root=None: latent_scopes.by_program(raw))
    records = [Record(index=i, prompt_len=17000, max_new=64, due_s=0.0,
                      in_window=True, stamps=[0.5 + 0.1 * j for j in range(64)])
               for i in range(16)]
    r = _run(records=records, trace_span=(2.0, 4.0),
             stats0=dict(attn_visible_tokens=1000, attn_selected_tokens=1000),
             stats1=dict(attn_visible_tokens=273000, attn_selected_tokens=33768),
             peaks=dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9))
    got = manifest.reader(vbench_toyroot.REPO, name)(r)
    if name == "indexer_ms_per_step":
        assert got == pytest.approx(15.0)
    elif name == "latent_attn_ms_per_step":
        assert got == pytest.approx(5.0)
    elif name == "indexer_ms_per_chunk":
        assert got == pytest.approx(12.0)
    elif name == "latent_attn_ms_per_chunk":
        assert got == pytest.approx(18.0)
    elif name == "selected_share_pct":
        assert got == pytest.approx(100 * 32768 / 272000)
    else:
        live = 16 * (17000 + 25.5)   # tokens held at the eight sample times
        flops, byts = ref.sparse_attn_step_cost(r.cfg, 16, live)
        least = max(flops / 197e12, byts / 819e9)
        assert got == pytest.approx(100 * least / 0.020, rel=0.01)
        assert 0 < got < 100


@pytest.mark.parametrize("name", NEW)
def test_each_new_reader_reads_none_from_a_program_without_it(
        name, monkeypatch):
    """The parent of PR 28: no such scope in the trace, no such counter."""
    raw = _steps()
    for op in raw["devices"]["/device:TPU:0"]["ops"]:
        op[3] = "jit(step)/attn/dot:"
    monkeypatch.setattr(scopes, "load", lambda root=None: scopes.reduce(raw))
    assert not any(latent_scopes.by_program(raw).values())
    monkeypatch.setattr(latent_scopes, "load", lambda root=None: None)
    r = _run(trace_span=(2.0, 4.0), stats0=dict(decode_ticks=0),
             stats1=dict(decode_ticks=5),
             peaks=dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9))
    assert manifest.reader(vbench_toyroot.REPO, name)(r) is None
