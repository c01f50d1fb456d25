"""The family ``blockdiff`` (SDAR's language model: generation by diffusion
over blocks) in the harness: as a cell ADDED to a copy of the benchmark
(vbench_toyroot.py's root plus one configuration, one mix and one cell
written here), through ``run.run_cell`` on the CPU: a sound run is correct
with every token answered once and every trail as long as its stream, the
float8 control is not, and a program whose block is causal inside is not.
The real cell's entries and files, the catalog's numbers, the bytes from the
specs, the two cost functions against counts done by hand, and each of its
five readers on a small recorded trace and on a program without what they
read.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import vbench_toyroot  # noqa: E402

from vbench import block_scopes, manifest, run, scopes, traffic  # noqa: E402
from vbench.reference import blockdiff as ref  # noqa: E402
from vbench.rundata import Run  # noqa: E402

SECONDS = 2.0
SEED = 2**31 + 43
CELL = "toy_sdar_blockgen"
REAL, REAL_CFG = "sdar_blockgen", "sdar-30b-a3b-24l-ep8"
MS = 10 ** 9  # a millisecond in the trace's picoseconds
NEW = ["block_tokens_per_pass", "block_rows_answering_pct",
       "block_write_pass_pct", "block_attn_ms_per_pass",
       "block_attn_roofline"]
V5E = dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9)

# The toy computes in float32: sound runs read gaps of rounding (1e-6), the
# float8 control a mean of 0.1-0.4, a block causal inside a mean of 0.05-0.3
# (CPU, two seeds, PR 43).
TOY = dict(
    family="blockdiff", hidden_size=64, num_attention_heads=8,
    num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
    num_experts=4, num_experts_published=16, held_experts_first=4,
    num_experts_per_tok=4, num_hidden_layers=3, vocab_size=384,
    max_position_embeddings=192, rope_theta=1000000, rms_norm_eps=1e-6,
    norm_topk_prob=True, decoder_sparse_step=1, mlp_only_layers=[],
    tie_word_embeddings=False, attention_bias=False, rope_scaling=None,
    use_sliding_window=False, dtype="float32", output_head="lm_head",
    block_length=4, mask_token_id=383,
    serving=dict(slots=4, kv_page=8, kv_pool_blocks=80, prefill_chunk=32,
                 prefill_budget=64, max_new_tokens=48,
                 read_windows=[64, 128], denoising_steps=2,
                 confidence_threshold=None),
    check=dict(requests=6, min_tokens=40,
               limits=dict(logit_gap_max=0.05, logit_gap_mean=0.005)))
MIX = dict(kind="saturated", ahead=2, settle_s=0.5, ramp_stagger=4,
           drain_s=0, grid=4, schedule_seed=17,
           prompt=dict(median=40, sigma=0.8, min=8, max=120),
           output=dict(median=24, sigma=0.3, min=12, max=48))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The toy root, and the family's cell added as a PR would."""
    root = str(tmp_path_factory.mktemp("vbench_blockdiff_root"))
    man = vbench_toyroot.build(root)
    with open(os.path.join(root, "vbench/configs/toy-sdar.json"), "w") as f:
        json.dump(TOY, f)
    with open(os.path.join(root, "vbench/traffic/toy-blockgen.json"),
              "w") as f:
        json.dump(MIX, f)
    man["configs"].append(dict(
        name="toy-sdar", source="tests", reduced=[], why="toy size",
        file="vbench/configs/toy-sdar.json"))
    man["workloads"].append(dict(name=CELL, config="toy-sdar",
                                 traffic="toy-blockgen", chips=1, why="toy"))
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
    assert cell["chips"] == 1 and cfg["family"] == "blockdiff"
    assert cell["config"] == REAL_CFG and cell["traffic"] == "blockgen"
    assert "attention more" in cell["why"]
    assert set(entry["reduced"]) == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size",
        "max_position_embeddings"}
    per_layer = {m["name"] for m in manifest.metrics_of(
        man, "per_layer", REAL)}
    assert set(NEW) <= per_layer
    assert "79 of 96 slots" in cell["why"]  # what a run holds (PERF.md 4)
    assert {"kernel_route_pct", "pool_relayout_ms_per_step",
            "paged_attn_ms_per_step",  # the walk is the kernel it reads
            "experts_ms_per_step", "kv_pool_peak_pct",
            "decode_step_roofline", "device_idle_pct",
            "prefill_ms_per_ktoken"} <= per_layer
    # it has no other family's state (``full_attn_roofline`` reads a model
    # with window layers), and test_vbench_experts.py holds the two
    # grouped-experts metrics' lists to PR 41's three cells (a file this PR
    # may not edit: PERF.md section 7)
    assert not per_layer & {
        "experts_ms_per_chunk", "experts_grouped_pct", "full_attn_roofline",
        "latent_attn_ms_per_step", "ssm_scan_ms_per_step",
        "window_attn_ms_per_step", "selected_share_pct"}
    assert {m["name"] for m in manifest.metrics_of(
        man, "end_to_end", REAL)} == {
            "itl_mean_ms", "out_tokens_per_s", "setup_s"}
    moves = {"block_attn_ms_per_pass": "itl_mean_ms",
             "block_attn_roofline": "itl_mean_ms"}
    for name in NEW:
        manifest.reader(vbench_toyroot.REPO, name)
        row = next(m for m in man["per_layer"] if m["name"] == name)
        assert row["workloads"] == [REAL]
        assert (row["layer"], row["moves"]) == (
            "block generation", moves.get(name, "out_tokens_per_s"))
    # the mix is the issue's
    mix = traffic.load_mix(cell["traffic"], vbench_toyroot.REPO)
    assert {k: mix[k] for k in ("kind", "ahead", "settle_s", "drain_s",
                                "ramp_stagger", "grid")
            } == dict(kind="saturated", ahead=2, settle_s=3, drain_s=0,
                      ramp_stagger=96, grid=16)
    assert mix["prompt"] == dict(median=768, sigma=0.6, min=128, max=4096)
    assert mix["output"] == dict(median=1024, sigma=0.3, min=512, max=2048)
    others = {traffic.load_mix(w["traffic"], vbench_toyroot.REPO)[
        "schedule_seed"] for w in man["workloads"] if w["name"] != REAL}
    assert mix["schedule_seed"] not in others
    pairs = traffic.length_pairs(mix)
    prompts, outputs = sorted(p for p, _ in pairs), sorted(o for _, o in pairs)
    assert (prompts[0], prompts[-1]) == (254, 2300)
    assert (outputs[0], outputs[-1]) == (608, 1725)
    # about 144 k tokens live at 96 streams half way through their outputs
    live = 96 * (sum(prompts) + sum(outputs) / 2) / 16
    assert 130e3 < live < 150e3
    sizes = cfg["serving"]
    assert (sizes["slots"], sizes["kv_page"], sizes["kv_pool_blocks"],
            sizes["prefill_chunk"], sizes["max_new_tokens"],
            sizes["denoising_steps"], sizes["confidence_threshold"]) == (
                96, 16, 10240, 512, 2048, 2, None)
    assert sizes["kv_pool_blocks"] * sizes["kv_page"] >= 1.1 * live
    assert "prefill_buckets" not in sizes


def test_the_file_holds_the_catalogs_numbers_but_the_reduced():
    """Every number of the catalog's entry under the same key, those in
    ``reduced`` apart; no width among the reduced; nested groups whole."""
    published = dict(
        decoder_sparse_step=1, head_dim=128, hidden_size=2048,
        intermediate_size=6144, max_position_embeddings=32768,
        max_window_layers=48, moe_intermediate_size=768,
        num_attention_heads=32, num_experts=128, num_experts_per_tok=8,
        num_hidden_layers=48, num_key_value_heads=4, rms_norm_eps=1e-06,
        rope_theta=1000000, vocab_size=151936)
    cfg = _real()
    differs = {k for k, v in published.items() if cfg[k] != v}
    assert differs == set(cfg["reduced"])
    for key, want in dict(
            attention_bias=False, hidden_act="silu", mlp_only_layers=[],
            model_type="sdar_moe", norm_topk_prob=True, rope_scaling=None,
            sliding_window=None, tie_word_embeddings=False,
            use_sliding_window=False).items():
        assert cfg[key] == want, key
    assert cfg["num_experts_published"] == 128
    assert (cfg["held_experts_first"], cfg["num_experts"]) == (0, 16)
    assert cfg["vocab_size"] * 8 == 151936
    assert cfg["mask_token_id"] == cfg["vocab_size"] - 1
    assert cfg["max_position_embeddings"] == 4096 + 2048
    assert "8 chips share each layer" in cfg["stands_for"]
    assert {"dtype", "block_length", "mask_token_id", "rotary_pairing",
            "last_block"} <= set(cfg["assumed"])
    assert not any("QK-norm" in d and "no QK-norm" in d
                   for d in cfg["departures"])
    assert cfg["output_head"] == "lm_head" and cfg["block_length"] == 4


def test_the_weights_are_the_issues_bytes():
    """2.35 B parameters, 4.70 GB in bfloat16 (the routers in float32):
    a layer 19.1 M outside its experts and 16 experts of 4.72 M, an eighth
    of the vocabulary in two planes."""
    cfg = _real()
    specs = {s["name"]: s for s in ref.weight_specs(cfg)}
    total = params = 0
    for s in specs.values():
        n = 1
        for d in s["shape"]:
            n *= d
        n *= cfg["num_hidden_layers"] if s["layered"] else 1
        params += n
        total += n * (4 if s["dtype"] == "float32" else 2)
    assert 2.34e9 < params < 2.36e9, params
    assert 4.69e9 < total < 4.72e9, total
    assert specs["e_gate"]["shape"] == [16, 2048, 768]
    assert 3 * 2048 * 768 == 4_718_592
    assert specs["router"]["shape"] == [2048, 128]
    assert specs["wk"]["shape"] == [2048, 512]
    assert specs["q_norm"]["shape"] == specs["k_norm"]["shape"] == [128]
    assert specs["lm_head"]["shape"] == specs["embed"]["shape"] == [18992, 2048]
    outside = sum(
        s["shape"][0] * (s["shape"][1] if len(s["shape"]) > 1 else 1)
        for n, s in specs.items()
        if s["layered"] and not n.startswith("e_"))
    assert 19.0e6 < outside < 19.2e6
    # the cache: 4 heads x 128 x 2 planes x 2 B a token a layer
    assert 24 * 4 * 128 * 2 * 2 == 49152
    pool = cfg["serving"]["kv_pool_blocks"] * cfg["serving"]["kv_page"]
    assert 8.0e9 < pool * 49152 < 8.1e9
    assert 0.70 < (total + pool * 49152) / 17.18e9 < 0.78


# -- operations and bytes, counted by hand -----------------------------------

def test_block_attn_pass_cost_against_a_count_by_hand():
    """96 streams holding 144000 cached tokens, 24 layers: each stream's 4
    rows' 32 heads against every cached key and the block's own 4, both
    products; a cached token's 4 key heads and 4 value heads of 128 in
    bfloat16 read once a layer (2048 B) whatever the rows that read them,
    the block's own and the queries and results besides. 31 FLOP a byte
    (four rows read each cached token): far under the v5e's ridge of 240:
    the walk is the memory's."""
    flops, byts = ref.block_attn_pass_cost(_real(), 96, 144000)
    pairs = 4 * 144000 + 96 * 16
    assert flops == 24 * 2 * 2 * 32 * 128 * pairs
    assert byts == 24 * (2048 * (144000 + 384) + 2 * 32 * 128 * 2 * 384)
    assert 7.2e9 < byts < 7.3e9 and 30 < flops / byts < 33
    # nothing cached: the block's own rows alone
    f0, b0 = ref.block_attn_pass_cost(_real(), 96, 0)
    assert f0 == 24 * 2 * 2 * 32 * 128 * 96 * 16 and b0 < 0.2e9


def test_decode_step_cost_against_a_count_by_hand():
    """A pass of 4 rows a stream: W_q and W_o 2048 x 4096, W_k and W_v
    2048 x 512 a layer; the router in float32 and, of the 16 held experts,
    8 x 16 / 128 = 1 a row computed and min(16, 384) = 16 read; embedding
    rows and the untied head over 384 rows."""
    cfg = _real()
    flops, byts = ref.decode_step_cost(cfg, 96, 144000)
    af, ab = ref.block_attn_pass_cost(cfg, 96, 144000)
    proj = 2048 * 128 * (2 * 32 + 2 * 4)
    assert proj == 18_874_368
    expert = 3 * 2048 * 768
    want_f = (af + 384 * 2 * 2048 * 18992
              + 24 * 384 * (2 * proj + 2 * 2048 * 128 + 2 * expert * 1.0))
    want_b = (ab + (18992 * 2048 + 384 * 2048) * 2
              + 24 * (proj * 2 + 2048 * 128 * 4 + expert * 2 * 16))
    assert flops == pytest.approx(want_f, rel=1e-12)
    assert byts == pytest.approx(want_b, rel=1e-12)
    # the cache's 7.2 GB, the experts' 3.6, attention's weights 0.9, the
    # head 0.08: 14.5 ms at 819 GB/s
    assert 11.7e9 < byts < 12.0e9
    assert 24 * expert * 2 * 16 == pytest.approx(3.62e9, rel=0.01)
    assert flops / 197e12 < byts / 819e9


# -- the cell on the CPU ------------------------------------------------------

@pytest.fixture(scope="module")
def sound_run(root):
    """(the result line, the run's details) of one sound run."""
    out = {}
    return run.run_cell(root, CELL, SEED, SECONDS, False, out=out), out


def test_a_sound_run_of_the_family_is_correct(sound_run):
    sound, out = sound_run
    c = sound["compared"]
    assert sound["correct"] is True, c
    assert sound["attempted"] > 0 and sound["failed"] == 0
    for k in ("tokens_short_of_sample", "tokens_unanswered",
              "trail_wrong_length", "streams_wrong_length",
              "tokens_outside_vocab"):
        assert c[k]["value"] == 0, k
    assert set(sound["metrics"]) == {"itl_mean_ms", "out_tokens_per_s",
                                     "setup_s"}
    assert c["logit_gap_max"]["value"] < 1e-4
    assert out["compiles_in_window"] == []
    # every request carries its trail, as long as its stream
    assert all(len(r[9] or ()) == r[3] for r in out["requests"])
    assert any(r[9] for r in out["requests"])


def test_the_float8_control_of_the_family_is_not_correct(root):
    res = run.run_cell(root, CELL, SEED, SECONDS, False, control=True)
    c = res["compared"]
    assert res["correct"] is False
    assert c["logit_gap_mean"]["value"] > c["logit_gap_mean"]["limit"]
    for k in ("logit_gap_max", "logit_gap_mean"):   # the program was sound
        assert c[f"program_{k}"]["value"] <= c[k]["limit"]


def test_a_program_whose_block_is_causal_inside_is_not_correct(
        root, monkeypatch):
    """A pass whose rows see only the rows before them in their block, the
    mask of a model that yields a token a step, served through the timed
    path: the comparison that decides ``correct`` sees it."""
    import jax.numpy as jnp

    from vtpu.models import blockdiff as M

    real = M.causal_attention

    def causal_inside(q, k, v, kv_len, scale):
        bl = q.shape[1]
        return real(q, k, v, kv_len=kv_len - bl + 1 + jnp.arange(bl)[None],
                    scale=scale)

    monkeypatch.setattr(M, "causal_attention", causal_inside)
    res = run.run_cell(root, CELL, SEED + 1, SECONDS, False)
    assert res["correct"] is False
    c = res["compared"]
    assert c["logit_gap_mean"]["value"] > c["logit_gap_mean"]["limit"]
    assert c["tokens_unanswered"]["value"] == 0


def test_a_runs_stats_hold_what_the_counters_readers_ask_for(root, sound_run):
    stats = sound_run[1]["stats1"]
    assert stats["block_length"] == 4
    assert stats["block_rows_dispatched"] == 4 * stats["block_slot_passes"]
    assert 0 < stats["block_write_passes"] < stats["block_slot_passes"]
    r = Run(records=[], seconds=SECONDS, setup_s=0.0, give_up_s=SECONDS,
            stats0={k: 0 for k in stats}, stats1=stats, cfg=TOY, mix=MIX,
            peaks={}, step_cost=ref.decode_step_cost)
    per_pass = manifest.reader(root, "block_tokens_per_pass")(r)
    assert 1.0 < per_pass < 1.4
    assert 30 < manifest.reader(root, "block_write_pass_pct")(r) < 40
    assert 40 < manifest.reader(root, "block_rows_answering_pct")(r) < 55


# -- the readers, on a small recorded trace -----------------------------------

def _passes():
    """Three 20 ms launches of the pass program: 3 ms of qkv, under
    ``attn`` and ``block_attn`` 1 ms of the queries' preparation
    (``pool_relayout``), 9 ms of the walk (the kernel ``paged_attn``, the
    one every grouped walk runs) and 1 ms of the own block's scores and
    the join, 6 of experts; and one chunk launch, which holds no such
    scope."""
    ops, modules = [], []
    for i in range(3):
        t = 10 + 40 * i
        modules.append(["jit_step(9)", t * MS, 20 * MS])
        for name, at, dur, path in (
                ("%fusion.1", 0, 3, "jit(step)/qkv/dot_general:"),
                ("%fusion.2", 3, 1,
                 "jit(step)/attn/block_attn/pool_relayout/transpose:"),
                ("%paged_attn.3", 4, 9,
                 "jit(step)/attn/block_attn/paged_attn/pallas_call:"),
                ("%fusion.4", 13, 1, "jit(step)/attn/block_attn/dot_general:"),
                ("%fusion.5", 14, 6, "jit(step)/experts/dot_general:")):
            ops.append([name, int((t + at) * MS), int(dur * MS), path])
    modules.append(["jit_prefill_chunk_into_slot(3)", 200 * MS, 30 * MS])
    ops.append(["%fusion.9", 200 * MS, 30 * MS,
                "jit(prefill_chunk_into_slot)/gather_attn/dot:"])
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "spans": []}


def _run(**kw):
    base = dict(records=[], seconds=10.0, setup_s=1.0, give_up_s=10.0,
                stats0={}, stats1={}, cfg=_real(), mix={}, peaks={},
                step_cost=ref.decode_step_cost)
    return Run(**{**base, **kw})


@pytest.mark.parametrize("name", NEW)
def test_each_reader_on_the_recorded_trace(name, monkeypatch):
    raw = _passes()
    monkeypatch.setattr(scopes, "load", lambda root=None: scopes.reduce(raw))
    monkeypatch.setattr(block_scopes, "load",
                        lambda root=None: block_scopes.by_program(raw))
    # to the copy of the vocabulary the harness holds, the walk is the
    # kernel ``paged_attn`` it already reads, its preparation
    # ``pool_relayout`` and the rest of the scope ``attn``: nothing unscoped
    by = scopes.reduce(raw)["programs"]["jit_step"]["scopes"]
    assert by["paged_attn"] == pytest.approx(0.027) and "unscoped" not in by
    assert by["attn"] == pytest.approx(0.003)
    assert by["pool_relayout"] == pytest.approx(0.003)
    assert manifest.reader(vbench_toyroot.REPO, "paged_attn_ms_per_step")(
        _run(trace=dict(busy_s=1.0))) == pytest.approx(9.0)

    def counters(ticks):
        return dict(decode_ticks=ticks, block_slot_passes=90 * ticks,
                    block_write_passes=30 * ticks,
                    block_rows_dispatched=360 * ticks,
                    block_rows_masked=180 * ticks,
                    block_tokens_committed=120 * ticks,
                    attn_visible_tokens=135_000 * ticks)

    r = _run(trace=dict(busy_s=1.0), trace_span=(2.0, 4.0),
             trace_stats=(counters(100), counters(200)),
             stats0=counters(50), stats1=counters(450), peaks=V5E)
    got = manifest.reader(vbench_toyroot.REPO, name)(r)
    want = {"block_tokens_per_pass": 120 / 90,
            "block_rows_answering_pct": 50.0,
            "block_write_pass_pct": 100 / 3,
            "block_attn_ms_per_pass": 11.0}
    if name in want:
        assert got == pytest.approx(want[name])
    else:
        flops, byts = ref.block_attn_pass_cost(r.cfg, 90, 135_000)
        least = max(flops / 197e12, byts / 819e9)
        assert least == pytest.approx(byts / 819e9)
        assert got == pytest.approx(100 * least / 0.011)
        assert 0 < got < 100


@pytest.mark.parametrize("name", NEW)
def test_each_new_reader_reads_none_from_a_program_without_it(
        name, monkeypatch):
    """The parent of PR 43: no such scope in the trace, no such counter;
    and another family's cost module has no ``block_attn_pass_cost``."""
    raw = _passes()
    for op in raw["devices"]["/device:TPU:0"]["ops"]:
        op[3] = "jit(step)/attn/dot:"
    monkeypatch.setattr(scopes, "load", lambda root=None: scopes.reduce(raw))
    monkeypatch.setattr(block_scopes, "load", lambda root=None: None)
    old = dict(decode_ticks=5, attn_visible_tokens=0)
    r = _run(trace=dict(busy_s=1.0), trace_span=(2.0, 4.0),
             trace_stats=(old, old), stats0=old, stats1=old, peaks=V5E)
    assert manifest.reader(vbench_toyroot.REPO, name)(r) is None
    dense = dict(r.cfg, family="dense")
    assert manifest.reader(vbench_toyroot.REPO, "block_attn_roofline")(
        _run(cfg=dense, trace_span=(2.0, 4.0), trace_stats=(old, old))) is None
    assert block_scopes.by_program(raw) == {}


def test_block_scopes_reads_the_innermost_name():
    assert block_scopes.scope_of(
        "jit(step)/attn/block_attn/pool_relayout/transpose:") == "block_attn"
    assert block_scopes.scope_of("jit(step)/attn/dot:") is None
    assert block_scopes.scope_of("jit(step)/paged_attn/paged_attn:") is None
    assert block_scopes.by_program(_passes()) == pytest.approx(
        dict(block_attn=0.033))
