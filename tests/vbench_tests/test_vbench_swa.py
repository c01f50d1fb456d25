"""The family ``swa`` (MiMo-V2.5's block) in the harness: as a cell ADDED to a
copy of the benchmark (vbench_toyroot.py's root plus one configuration, one
mix and one cell written here), through ``run.run_cell`` on the CPU: a sound
run is correct, the float8 control is not, and a program that serves a
softmax without the sink is not. Its three cost functions against counts
done by hand, the real cell's entries and files, and each of its four
readers on a small recorded trace and on a program without what they read.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import vbench_toyroot  # noqa: E402

from vbench import manifest, run, scopes, traffic, window_scopes  # noqa: E402
from vbench.reference import swa as ref  # noqa: E402
from vbench.rundata import Run  # noqa: E402

SECONDS = 2.0
SEED = 2**31 + 39
CELL = "toy_swa_mixedqueue"
REAL, REAL_CFG = "mimo_mixedqueue", "mimo-v2.5-7l-ep16"
MS = 10 ** 9  # a millisecond in the trace's picoseconds
NEW = ["window_attn_ms_per_step", "window_attn_ms_per_chunk",
       "full_attn_roofline", "window_cache_held_pct"]

# The toy computes in float32, as the latent families' toys do: sound runs
# read 0.0 on both numbers (every served token is the reference's first),
# the float8 control a mean of 0.2-0.5, and a program without the sink a
# mean of 0.05-0.3 (CPU, two seeds, PR 39).
TOY = dict(
    family="swa", hidden_size=64, num_attention_heads=8, head_dim=24,
    v_head_dim=16, swa_head_dim=24, swa_v_head_dim=16,
    swa_num_attention_heads=8, num_key_value_heads=2,
    swa_num_key_value_heads=4, partial_rotary_factor=0.334,
    rope_theta=10000000, swa_rope_theta=10000, sliding_window=8,
    attention_value_scale=0.707, add_swa_attention_sink_bias=True,
    add_full_attention_sink_bias=False,
    hybrid_layer_pattern=[0, 1, 1, 1, 1, 0, 1, 1],
    moe_layer_freq=[0, 1, 1, 1, 1, 1, 1, 1], num_hidden_layers=7,
    intermediate_size=128, moe_intermediate_size=32, n_routed_experts=4,
    n_routed_experts_published=16, held_experts_first=4,
    num_experts_per_tok=4, n_group=1, topk_group=1, norm_topk_prob=True,
    routed_scaling_factor=None, n_shared_experts=None,
    scoring_func="sigmoid", topk_method="noaux_tc", layernorm_epsilon=1e-5,
    rms_norm_eps=1e-5, vocab_size=384, max_position_embeddings=256,
    dtype="float32", output_head="lm_head",
    serving=dict(slots=4, kv_page=8, kv_pool_blocks=90,
                 prefill_buckets=[16], prefill_batch_sizes=[1],
                 prefill_chunk=32, prefill_budget=64, max_new_tokens=48,
                 read_windows=[32, 64, 128, 256]),
    check=dict(requests=6, min_tokens=40,
               limits=dict(logit_gap_max=0.25, logit_gap_mean=0.008)))
MIX = dict(kind="saturated", ahead=2, settle_s=0.5, ramp_stagger=4,
           drain_s=0, grid=4, schedule_seed=13,
           prompt=dict(median=40, sigma=0.8, min=8, max=120),
           output=dict(median=24, sigma=0.3, min=12, max=48))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The toy root, and the family's cell added as a PR would."""
    root = str(tmp_path_factory.mktemp("vbench_swa_root"))
    man = vbench_toyroot.build(root)
    with open(os.path.join(root, "vbench/configs/toy-swa.json"), "w") as f:
        json.dump(TOY, f)
    with open(os.path.join(root, "vbench/traffic/toy-mixedqueue.json"),
              "w") as f:
        json.dump(MIX, f)
    man["configs"].append(dict(
        name="toy-swa", source="tests", reduced=[], why="toy size",
        file="vbench/configs/toy-swa.json"))
    man["workloads"].append(dict(name=CELL, config="toy-swa",
                                 traffic="toy-mixedqueue", chips=1, why="toy"))
    for group in ("end_to_end", "per_layer"):
        for m in man[group]:
            if REAL in m.get("workloads", ()):
                m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return root


def _real():
    man = manifest.load(vbench_toyroot.REPO)
    return manifest.config(man, vbench_toyroot.REPO, REAL_CFG)


def test_the_real_cell_is_in_the_manifest_with_its_files():
    man = manifest.load(vbench_toyroot.REPO)
    cell = manifest.cell(man, REAL)
    cfg = manifest.config(man, vbench_toyroot.REPO, cell["config"])
    entry = next(c for c in man["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 1 and cfg["family"] == "swa"
    assert cell["config"] == REAL_CFG and cell["traffic"] == "mixedqueue"
    assert set(entry["reduced"]) == set(cfg["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "max_position_embeddings"}
    assert ref.layer_kinds(cfg) == [
        "full_dense", "window_moe", "window_moe", "window_moe", "window_moe",
        "full_moe", "window_moe"]
    per_layer = {m["name"] for m in manifest.metrics_of(
        man, "per_layer", REAL)}
    assert set(NEW) <= per_layer
    assert {"kernel_route_pct", "paged_attn_ms_per_step",
            "pool_relayout_ms_per_step", "experts_ms_per_step",
            "kv_pool_peak_pct", "decode_step_roofline"} <= per_layer
    # no latents, no selection, no recurrent rows: another family's
    assert not per_layer & {
        "indexer_ms_per_step", "latent_attn_ms_per_step",
        "latent_walk_roofline", "ssm_scan_ms_per_step", "ssm_state_roofline",
        "selected_share_pct", "chunk_keys_live_pct"}
    assert {m["name"] for m in manifest.metrics_of(
        man, "end_to_end", REAL)} == {
            "itl_mean_ms", "out_tokens_per_s", "setup_s"}
    layers = {"full_attn_roofline": "paged pool and attention route"}
    for name in NEW:
        manifest.reader(vbench_toyroot.REPO, name)
        row = next(m for m in man["per_layer"] if m["name"] == name)
        assert row["workloads"] == [REAL]
        assert (row["layer"], row["moves"]) == (
            layers.get(name, "window attention"), "itl_mean_ms")
    # the mix is the issue's table
    mix = traffic.load_mix(cell["traffic"], vbench_toyroot.REPO)
    assert {k: mix[k] for k in ("kind", "ahead", "settle_s", "drain_s",
                                "ramp_stagger", "grid", "schedule_seed")
            } == dict(kind="saturated", ahead=2, settle_s=3, drain_s=0,
                      ramp_stagger=96, grid=16, schedule_seed=39301)
    assert mix["prompt"] == dict(median=4096, sigma=1.0, min=512, max=32768)
    assert mix["output"] == dict(median=2048, sigma=0.4, min=1024, max=4096)
    pairs = traffic.length_pairs(mix)
    prompts, outputs = sorted(p for p, _ in pairs), sorted(o for _, o in pairs)
    assert (prompts[0], prompts[-1], round(sum(prompts) / 16)) == (
        782, 21455, 5949)
    assert (outputs[0], outputs[-1], round(sum(outputs) / 16)) == (
        1136, 3693, 2157)
    assert max(p + o for p, o in pairs) <= cfg["max_position_embeddings"]
    sizes = cfg["serving"]
    assert (sizes["slots"], sizes["kv_page"], sizes["kv_pool_blocks"],
            sizes["prefill_chunk"], sizes["prefill_budget"],
            sizes["max_new_tokens"], sizes["prefill_buckets"]) == (
                96, 64, 14000, 512, 1024, 4096, [256])
    assert sizes["read_windows"] == [4096, 8192, 16384, 24576, 32768]


def test_the_file_holds_the_catalogs_numbers_but_the_reduced():
    """Every number of the catalog's entry under the same key, those in
    ``reduced`` apart; no width among the reduced; the two patterns whole."""
    published = dict(
        attention_chunk_size=128, attention_value_scale=0.707,
        swa_num_key_value_heads=8, swa_num_attention_heads=64,
        swa_head_dim=192, swa_v_head_dim=128, head_dim=192, hidden_size=4096,
        intermediate_size=16384, layernorm_epsilon=1e-05,
        max_position_embeddings=1048576, moe_intermediate_size=2048,
        n_group=1, n_routed_experts=256, num_attention_heads=64,
        num_experts_per_tok=8, num_hidden_layers=48, num_key_value_heads=4,
        partial_rotary_factor=0.334, rope_theta=10000000, sliding_window=128,
        sliding_window_size=128, swa_rope_theta=10000, topk_group=1,
        v_head_dim=128, vocab_size=152576)
    cfg = _real()
    differs = {k for k, v in published.items() if cfg[k] != v}
    assert differs == set(cfg["reduced"])
    pattern = ([0, 1, 1, 1, 1] + [0, 1, 1, 1, 1, 1] * 7 + [0])
    assert cfg["hybrid_layer_pattern"] == pattern and len(pattern) == 48
    assert (pattern.count(0), pattern.count(1)) == (9, 39)
    assert cfg["moe_layer_freq"] == [0] + [1] * 47
    assert cfg["rope_scaling"] == dict(rope_type="default", type="default")
    for key, want in dict(
            attention_bias=False, attention_projection_layout="fused_qkv",
            add_full_attention_sink_bias=False,
            add_swa_attention_sink_bias=True, hidden_act="silu",
            hybrid_block_size=None, model_type="mimo_v2",
            n_shared_experts=None, norm_topk_prob=True,
            routed_scaling_factor=None, scoring_func="sigmoid",
            tie_word_embeddings=False, topk_method="noaux_tc").items():
        assert cfg[key] == want, key
    assert cfg["n_routed_experts_published"] == 256
    assert (cfg["held_experts_first"], cfg["n_routed_experts"]) == (0, 16)
    assert cfg["vocab_size"] * 8 == 152576
    assert "16 chips" in cfg["stands_for"]
    assert {"attention_chunk_size", "hybrid_block_size",
            "attention_projection_layout", "rotary_pairing",
            "sink_range"} <= set(cfg["assumed"])
    assert cfg["rms_norm_eps"] == cfg["layernorm_epsilon"]
    assert ref._dims(cfg)["dr"] == 64


@pytest.fixture(scope="module")
def sound_run(root):
    """(the result line, the run's details) of one sound run."""
    out = {}
    return run.run_cell(root, CELL, SEED, SECONDS, False, out=out), out


@pytest.fixture(scope="module")
def sound(sound_run):
    return sound_run[0]


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


def test_a_program_that_serves_no_sink_is_not_correct(root, monkeypatch):
    """The softmax's extra term left out of the window layers' decode
    steps and chunks, served through the timed path: the comparison that
    decides ``correct`` sees it."""
    import jax.numpy as jnp

    from vtpu.models import swa as M

    real = M._window_layer

    def no_sink(cfg, lp, *rest):
        class Lp:
            def __getitem__(self, name):
                leaf = lp[name]
                return jnp.full_like(leaf, -1e9) if name == "sink" else leaf
        return real(cfg, Lp(), *rest)

    monkeypatch.setattr(M, "_window_layer", no_sink)
    res = run.run_cell(root, CELL, SEED + 1, SECONDS, False)
    assert res["correct"] is False
    c = res["compared"]
    assert c["logit_gap_mean"]["value"] > c["logit_gap_mean"]["limit"]


def test_a_runs_stats_hold_what_the_counters_readers_ask_for(
        root, sound_run):
    """The rings' bytes as the configuration's sizes give them (4 slots x 5
    window layers x 8 rows x 4 heads x 40 columns, float32), what a paged
    token would cost those layers, and the two counters, in the stats a run
    keeps of the window's end."""
    stats = sound_run[1]["stats1"]
    assert stats["window_ring"] == 8
    assert stats["ring_bytes_per_position"] == 5 * 4 * 40 * 4
    assert stats["recurrent_state_bytes"] == 4 * 8 * 5 * 4 * 40 * 4
    assert 0 < stats["window_rows_read"] < stats["attn_visible_tokens"]
    held = manifest.reader(root, "window_cache_held_pct")(Run(
        records=[], seconds=SECONDS, setup_s=0.0, give_up_s=SECONDS,
        stats0=stats, stats1=stats, cfg=TOY, mix=MIX, peaks={},
        step_cost=ref.decode_step_cost))
    assert held == pytest.approx(
        100 * stats["recurrent_state_bytes"]
        / (stats["kv_pool_used"] * 8 * stats["ring_bytes_per_position"]))


# -- operations and bytes, counted by hand -----------------------------------

def test_full_attn_step_cost_against_a_count_by_hand():
    """96 streams holding 675000 tokens, two full layers: 64 heads against
    every live key (192 wide) and value (128); a token's four key/value
    heads' 320 columns in bfloat16 read once: 2560 B a token a layer. 40960
    FLOP against 2560 B: 16 FLOP a byte, far under the v5e's ridge of 240:
    the walk is the memory's."""
    flops, byts = ref.full_attn_step_cost(_real(), 96, 675000)
    assert flops == 2 * 2 * 64 * 320 * 675000 == 55_296_000_000
    assert byts == 2 * 2560 * 675000 == 3_456_000_000
    assert flops / byts == 16.0
    assert ref.full_attn_step_cost(_real(), 1, 675000) == (flops, byts)


def test_window_attn_step_cost_against_a_count_by_hand():
    """Five window layers, 96 streams with full rings: 128 rows of eight
    key/value heads' 320 columns a stream a layer, 5120 B a row, and the
    new row written; the cached tokens beyond the ring do not enter."""
    flops, byts = ref.window_attn_step_cost(_real(), 96, 675000)
    assert flops == 5 * 2 * 64 * 320 * 96 * 128 == 2_516_582_400
    assert byts == 5 * 5120 * (96 * 128 + 96) == 317_030_400
    assert ref.window_attn_step_cost(_real(), 96, 5) == (flops, byts)


def test_decode_step_cost_against_a_count_by_hand():
    """W_q and W_o 4096 x 64 x (192 + 128) a layer, W_k and W_v 4096 x Hk x
    320; the dense layer's SwiGLU 3 x 4096 x 16384; an expert layer's router
    in float32 and, of the 16 held experts, 8 x 16 / 256 = 0.5 a token
    computed and min(16, 96 x 0.5) = 16 read; embedding rows and the untied
    head."""
    cfg = _real()
    flops, byts = ref.decode_step_cost(cfg, 96, 675000)
    ff, fb = ref.full_attn_step_cost(cfg, 96, 675000)
    wf, wb = ref.window_attn_step_cost(cfg, 96, 675000)
    shared = 4096 * 64 * 320
    proj = 7 * shared + 4096 * 320 * (2 * 4 + 5 * 8)
    assert (shared + 4096 * 320 * 4) * 2 == 178_257_920    # full: 89.13 M x 2
    assert (shared + 4096 * 320 * 8) * 2 == 188_743_680    # window: 94.37 M
    expert, dense = 3 * 4096 * 2048, 3 * 4096 * 16384
    assert (expert, dense) == (25_165_824, 201_326_592)
    want_f = (ff + wf + 96 * 2 * proj + 96 * 2 * dense
              + 6 * 96 * (2 * 4096 * 256 + 2 * expert * 0.5)
              + 96 * 2 * 4096 * 19072)
    want_b = (fb + wb + proj * 2 + 2 * 96 * 2560 + dense * 2
              + 6 * (4096 * 256 * 4 + expert * 2 * 16)
              + (19072 * 4096 + 96 * 4096) * 2)
    assert flops == pytest.approx(want_f, rel=1e-12)
    assert byts == pytest.approx(want_b, rel=1e-12)
    # the weights' 6.7 GB read and the caches' 3.8: 12.8 ms at 819 GB/s
    assert 10.3e9 < byts < 10.7e9


def test_the_weights_are_the_issues_bytes():
    """3.43 B parameters, 6.86 GB in bfloat16 (routers, biases and sinks
    in float32)."""
    specs = ref.weight_specs(_real())
    kinds = ref.layer_kinds(_real())
    total = params = 0
    for s in specs:
        n = 1
        for d in s["shape"]:
            n *= d
        layers = (1 if not s["layered"] else
                  sum(1 for k in kinds if s.get("kind", k) == k))
        params += n * layers
        total += n * layers * (4 if s["dtype"] == "float32" else 2)
    assert 3.42e9 < params < 3.44e9, params
    assert 6.85e9 < total < 6.88e9, total
    names = {s["name"] for s in specs}
    assert {"sink", "route_bias", "router", "lm_head"} <= names
    assert not any(n.startswith(("s_", "idx_")) for n in names)
    sinks = [s for s in specs if s["name"] == "sink"]
    assert [s["kind"] for s in sinks] == ["window_moe"]
    assert sinks[0]["shape"] == [64] and sinks[0]["fan_in"] == 1.0


# -- the readers, on a small recorded trace -----------------------------------

def _steps():
    """Three 20 ms decode launches and one 30 ms chunk launch. A decode
    launch: 4 ms of qkv, 8 ms of the full layers' walk (``paged_attn``, the
    kernel ``wide_walk``), under ``attn`` 1.5 ms of the rings' read and 0.5
    of their write, 6 of experts. The chunk: 3 ms of window_attn, 1 of
    ring_write, 12 of the gathered window's attention, 14 of experts."""
    ops, modules = [], []
    for i in range(3):
        t = 10 + 40 * i
        modules.append(["jit_step(9)", t * MS, 20 * MS])
        for name, at, dur, path in (
                ("%fusion.1", 0, 4, "jit(step)/qkv/dot_general:"),
                ("%wide_walk.2", 4, 8, "jit(step)/paged_attn/wide_walk:"),
                ("%fusion.3", 12, 1.5,
                 "jit(step)/attn/window_attn/dot_general:"),
                ("%fusion.4", 13.5, 0.5, "jit(step)/attn/ring_write/scatter:"),
                ("%fusion.5", 14, 6, "jit(step)/experts/dot_general:")):
            ops.append([name, int((t + at) * MS), int(dur * MS), path])
    modules.append(["jit_prefill_chunk_into_slot(3)", 200 * MS, 30 * MS])
    for name, at, dur, scope in (
            ("%fusion.7", 200, 3, "attn/window_attn/dot:"),
            ("%fusion.8", 203, 1, "attn/ring_write/select:"),
            ("%fusion.9", 204, 12, "gather_attn/dot:"),
            ("%fusion.10", 216, 14, "experts/dot:")):
        ops.append([name, at * MS, dur * MS,
                    "jit(prefill_chunk_into_slot)/" + scope])
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "spans": []}


def _run(**kw):
    base = dict(records=[], seconds=10.0, setup_s=1.0, give_up_s=10.0,
                stats0={}, stats1={}, cfg=_real(), mix={}, peaks={},
                step_cost=ref.decode_step_cost)
    return Run(**{**base, **kw})


@pytest.mark.parametrize("name", NEW)
def test_each_reader_on_the_recorded_trace(name, monkeypatch):
    raw = _steps()
    monkeypatch.setattr(scopes, "load", lambda root=None: scopes.reduce(raw))
    monkeypatch.setattr(window_scopes, "load",
                        lambda root=None: window_scopes.by_program(raw))
    # the rings' read and write are ``attn`` to the copy of the vocabulary
    # the harness holds: nothing of them is unscoped
    by = scopes.reduce(raw)["programs"]["jit_step"]["scopes"]
    assert by["attn"] == pytest.approx(0.006) and "unscoped" not in by
    rings = 96 * 5 * 128 * 5120
    stats = dict(window_ring=128, ring_bytes_per_position=25600,
                 recurrent_state_bytes=rings, kv_page=64)
    r = _run(trace_span=(2.0, 4.0),
             trace_stats=(dict(stats, decode_ticks=100,
                               attn_visible_tokens=67_500_000),
                          dict(stats, decode_ticks=200,
                               attn_visible_tokens=135_000_000)),
             stats0=dict(stats, kv_pool_used=10500),
             stats1=dict(stats, kv_pool_used=10700),
             peaks=dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9))
    got = manifest.reader(vbench_toyroot.REPO, name)(r)
    if name == "window_attn_ms_per_step":
        assert got == pytest.approx(2.0)
    elif name == "window_attn_ms_per_chunk":
        assert got == pytest.approx(4.0)
    elif name == "window_cache_held_pct":
        assert got == pytest.approx(100 * rings / (10600 * 64 * 25600))
        assert 1.7 < got < 1.9
    else:
        flops, byts = ref.full_attn_step_cost(r.cfg, 96, 675000)
        least = max(flops / 197e12, byts / 819e9)
        assert least == pytest.approx(3_456_000_000 / 819e9)
        assert got == pytest.approx(100 * least / 0.008)
        assert 0 < got < 100


@pytest.mark.parametrize("name", NEW)
def test_each_new_reader_reads_none_from_a_program_without_it(
        name, monkeypatch):
    """The parent of PR 39: no such scope in the trace, no such counter;
    and another family's cost module has no ``full_attn_step_cost``."""
    raw = _steps()
    for op in raw["devices"]["/device:TPU:0"]["ops"]:
        op[3] = "jit(step)/attn/dot:"
    monkeypatch.setattr(scopes, "load", lambda root=None: scopes.reduce(raw))
    monkeypatch.setattr(window_scopes, "load", lambda root=None: None)
    old = dict(decode_ticks=5, kv_pool_used=40, kv_page=64,
               recurrent_state_bytes=0, attn_visible_tokens=0)
    r = _run(trace_span=(2.0, 4.0), trace_stats=(old, old), stats0=old,
             stats1=old,
             peaks=dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9))
    assert manifest.reader(vbench_toyroot.REPO, name)(r) is None
    dense = dict(r.cfg, family="dense")
    assert manifest.reader(vbench_toyroot.REPO, "full_attn_roofline")(
        _run(cfg=dense, trace_span=(2.0, 4.0), trace_stats=(old, old))) is None


def test_window_scopes_reads_the_innermost_name():
    assert window_scopes.scope_of("jit(step)/attn/window_attn/dot:") == \
        "window_attn"
    assert window_scopes.scope_of("jit(step)/attn/ring_write/scatter:") == \
        "ring_write"
    assert window_scopes.scope_of("jit(step)/paged_attn/wide_walk:") is None
    got = window_scopes.by_program(_steps())
    assert got[scopes.DECODE] == pytest.approx(
        dict(window_attn=0.0045, ring_write=0.0015))
    assert got[window_scopes.CHUNK] == pytest.approx(
        dict(window_attn=0.003, ring_write=0.001))
