"""The family of block-sparse attention beside linear attention in the
harness: as a cell ADDED to a copy of the benchmark (vbench_toyroot.py's
root plus one configuration, one mix and one cell written here), through
``run.run_cell`` on the CPU: a sound run is correct, the float8 control is
not, and neither is a program whose selection is the most recent blocks nor
one that loses the linear layers' rows at every chunk boundary. Its four
cost functions against counts done by hand, the bytes of the cut from the
specs, and each of its two readers on a small recorded trace.
"""

import importlib.util
import json
import os
import pathlib
import sys

import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import vbench_toyroot  # noqa: E402

from vbench import latent_scopes, manifest, run, scopes, traffic  # noqa: E402
from vbench.reference import sparselinear as ref  # noqa: E402
from vbench.rundata import Run  # noqa: E402

SECONDS = 2.0
SEED = 2**31 + 47
CELL = "toy_sala_sessions"
REAL = "sala_longsessions"
CONFIG = "minicpm-sala-8l-pp4"
MS = 10 ** 9  # a millisecond in the trace's picoseconds
NEW = ["blocksparse_attn_roofline", "select_engaged_pct"]

# The toy computes in float32: a served token is the reference's first but
# where two logits tie within the order of the sums, so both gaps of a
# sound run read about 0 (LIMITS' comment has the readings). Prompts are 3
# to 10 chunks of 16, every one past the toy's dense_len of 40 before it
# decodes, and a late query keeps 5 of up to 24 blocks, three of them forced.
MIXERS = ["minicpm4", "lightning-attn", "lightning-attn", "minicpm4",
          "lightning-attn"]
# Readings on the CPU at SEED (PR 47; max / mean, in the harness's units,
# the model's logits times 4): sound 0.0 / 0.0; the float8 control 2.41 /
# 0.174; the most recent blocks for a selection 4.05 / 1.25; rows lost at
# every chunk boundary 3.99 / 0.764: the limits sit under half the least
LIMITS = dict(logit_gap_max=0.5, logit_gap_mean=0.08)
TOY = dict(
    family="sparselinear", hidden_size=128, intermediate_size=256,
    num_attention_heads=4, num_key_value_heads=2, head_dim=32,
    lightning_nh=4, lightning_nkv=4, lightning_head_dim=32,
    lightning_chunk=8, mixer_types=MIXERS, num_hidden_layers=5,
    residual_depth=32, layer_indices=[0, 5, 13, 20, 28], scale_emb=12, scale_depth=1.4, dim_model_base=32,
    rope_theta=10000, rms_norm_eps=1e-6, vocab_size=384,
    max_position_embeddings=256,
    sparse_config=dict(kernel_size=4, kernel_stride=2, block_size=8,
                       window_size=16, init_blocks=1, topk=5, dense_len=40),
    attn_use_rope=False, lightning_use_rope=True, qk_norm=True,
    use_output_gate=True, use_output_norm=True, attn_use_output_gate=True,
    tie_word_embeddings=False, dtype="float32", output_head="head",
    serving=dict(slots=4, kv_page=8, kv_pool_blocks=120,
                 prefill_buckets=[16], prefill_batch_sizes=[1],
                 prefill_chunk=16, prefill_budget=32, max_new_tokens=32,
                 read_windows=[64, 128, 256]),
    check=dict(requests=6, min_tokens=40, limits=LIMITS))
MIX = dict(kind="saturated", ahead=2, settle_s=0.5, ramp_stagger=3,
           drain_s=0, grid=4, schedule_seed=13,
           prompt=dict(median=80, sigma=0.4, min=48, max=160),
           output=dict(median=16, sigma=0.3, min=8, max=32))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The toy root, and the family's cell added as a PR would."""
    root = str(tmp_path_factory.mktemp("vbench_sala_root"))
    man = vbench_toyroot.build(root)
    with open(os.path.join(root, "vbench/configs/toy-sala.json"), "w") as f:
        json.dump(TOY, f)
    with open(os.path.join(root, "vbench/traffic/toy-long.json"), "w") as f:
        json.dump(MIX, f)
    man["configs"].append(dict(
        name="toy-sala", source="tests", reduced=[], why="toy size",
        file="vbench/configs/toy-sala.json"))
    man["workloads"].append(dict(name=CELL, config="toy-sala",
                                 traffic="toy-long", chips=1, why="toy"))
    for group in ("end_to_end", "per_layer"):
        for m in man[group]:
            if REAL in m.get("workloads", ()):
                m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return root


def _real():
    man = manifest.load(vbench_toyroot.REPO)
    return manifest.config(man, vbench_toyroot.REPO, CONFIG)


def test_the_real_cell_is_in_the_manifest_with_its_files():
    man = manifest.load(vbench_toyroot.REPO)
    cell = manifest.cell(man, REAL)
    cfg = manifest.config(man, vbench_toyroot.REPO, cell["config"])
    entry = next(c for c in man["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 1 and cfg["family"] == "sparselinear"
    assert entry["reduced"] == ["num_hidden_layers", "mixer_types",
                                "max_position_embeddings"] == list(
        cfg["reduced"])
    kinds = ref.layer_kinds(cfg)
    assert kinds == ["sparse_in", "linear.4", "linear.8", "linear.12",
                     "sparse", "linear.20", "linear.24", "linear.28"]
    per_layer = {m["name"] for m in manifest.metrics_of(
        man, "per_layer", REAL)}
    assert set(NEW) <= per_layer
    assert {"kernel_route_pct", "decode_step_roofline", "step_unscoped_pct",
            "itl_p95_ms.watch", "selected_share_pct", "indexer_ms_per_step",
            "ssm_state_roofline", "ssm_chunk_roofline", "ssm_rows_live_pct",
            "paged_attn_ms_per_step", "kv_pool_peak_pct"} <= per_layer
    assert {m["name"] for m in manifest.metrics_of(
        man, "end_to_end", REAL)} == {
            "itl_mean_ms", "out_tokens_per_s", "setup_s"}
    for name in NEW:
        manifest.reader(vbench_toyroot.REPO, name)
    mix = traffic.load_mix(cell["traffic"], vbench_toyroot.REPO)
    assert (mix["kind"], mix["ahead"], mix["settle_s"], mix["ramp_stagger"],
            mix["drain_s"], mix["grid"], mix["schedule_seed"]) == (
                "saturated", 2, 3, 96, 0, 16, 47301)
    assert mix["prompt"] == dict(median=12288, sigma=0.4, min=8192, max=32768)
    assert mix["output"] == dict(median=4096, sigma=0.4, min=2048, max=8192)
    sizes = cfg["serving"]
    assert sizes["slots"] == 96 and sizes["prefill_chunk"] == 512
    assert sizes["kv_page"] == cfg["sparse_config"]["block_size"] == 64
    assert sizes["prefill_budget"] == 2 * sizes["prefill_chunk"]
    # the comparison: two finished sessions of at most 18,432 tokens, so the
    # longest compared starts its decoding 42 tokens after a chunk boundary
    # (the grid's 17,450-token prompt), where rows lost at a boundary show
    assert cfg["check"] == dict(requests=2, min_tokens=512,
                                max_request_tokens=18432,
                                limits=dict(logit_gap_mean=0.18))
    grid = traffic.quantile_grid(mix["prompt"], mix["grid"])
    under = max(p for p in grid if p < cfg["check"]["max_request_tokens"])
    assert under % sizes["prefill_chunk"] == 42
    # every request of the mix is past dense_len from its first decode step
    assert min(traffic.quantile_grid(mix["prompt"], mix["grid"])) \
        > cfg["sparse_config"]["dense_len"]


def test_the_configuration_is_the_published_one_but_for_the_cut():
    """Every number of the catalog's config under its own key; the cut is
    every fourth of the published mixers, kept whole beside it."""
    cfg = _real()
    published = dict(
        attention_bias=False, attn_use_rope=False, head_dim=128,
        hidden_act="silu", hidden_size=4096, intermediate_size=16384,
        lightning_head_dim=128, lightning_nh=32, lightning_nkv=32,
        lightning_scale="1/sqrt(d)", lightning_use_rope=True,
        model_type="minicpm_sala", num_attention_heads=32,
        num_key_value_heads=2, qk_norm=True, rand_init=False,
        rms_norm_eps=1e-6, vocab_size=73448, rope_theta=10000, scale_emb=12,
        scale_depth=1.4, mup_denominator=32, dim_model_base=256,
        tie_word_embeddings=False, use_output_gate=True,
        use_output_norm=True, attn_use_output_gate=True)
    for key, value in published.items():
        assert cfg[key] == value, key
    whole = cfg["mixer_types_published"]
    assert len(whole) == 32 and whole.count("minicpm4") == 8
    assert [i for i, m in enumerate(whole) if m == "minicpm4"] == [
        0, 9, 16, 17, 22, 29, 30, 31]
    assert cfg["mixer_types"] == whole[0::4] and cfg["num_hidden_layers"] == 8
    assert cfg["residual_depth"] == 32
    assert cfg["layer_indices"] == list(range(0, 32, 4))
    assert cfg["max_position_embeddings"] == 49152
    assert cfg["sparse_config"] == dict(
        kernel_size=32, kernel_stride=16, block_size=64, window_size=2048,
        init_blocks=1, topk=64, dense_len=8192)


def test_the_bytes_of_the_cut_from_the_specs():
    """2 x 253.8 M + 6 x 285.2 M + 601.7 M = 2.82 B parameters, 5.64 GB;
    2 KB + 64 B of cache a token; 12.6 MB of rows a slot."""
    import math

    cfg = _real()
    kinds = ref.layer_kinds(cfg)
    count = dict.fromkeys([None] + kinds, 0)
    for s in ref.weight_specs(cfg):
        n = math.prod(s["shape"])
        if not s["layered"]:
            count[None] += n
            continue
        for kind in set(kinds):
            if s.get("kind", kind) == kind:
                count[kind] += n
    assert count[None] == 2 * 73448 * 4096 + 4096
    assert round(count["sparse"] / 1e6, 1) == 253.8
    assert count["sparse_in"] == count["sparse"]
    assert {round(count[k] / 1e6, 1) for k in ref.linear_kinds(cfg)} == {285.2}
    total = count[None] + sum(count[k] for k in kinds)
    assert round(total / 1e9, 2) == 2.82 and round(2 * total / 1e9, 2) == 5.64
    from vbench.sut import sparselinear as sut
    mc = sut.model_config(cfg)
    assert mc.kv_bytes_per_token == 2048 + 64
    assert mc.recurrent_bytes_per_slot == 6 * 32 * 128 * 128 * 4 == 12_582_912
    assert mc.n_sel == 128 and mc.attention.n_layers == 4
    pool = 30001 * 64 * mc.kv_bytes_per_token
    assert round((pool + 96 * mc.recurrent_bytes_per_slot + 2 * total) / 1e9,
                 1) == 10.9


@pytest.fixture(scope="module")
def sound(root):
    return run.run_cell(root, CELL, SEED, SECONDS, False)


def test_a_sound_run_of_the_family_is_correct(sound):
    c = sound["compared"]
    print({k: v["value"] for k, v in c.items()})
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
    print({k: v["value"] for k, v in c.items()})
    assert res["correct"] is False
    for k in ("logit_gap_max", "logit_gap_mean"):
        assert c[k]["value"] > c[k]["limit"]
        assert c[f"program_{k}"]["value"] <= c[k]["limit"]  # it was sound


def _hack(name):
    path = pathlib.Path(vbench_toyroot.REPO) / "hack" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_a_selection_of_the_most_recent_blocks_is_not_correct(
        root, monkeypatch):
    """The first planted fault, as hack/sala_recent_selection.py plants it."""
    from vtpu.ops import blocksparse

    monkeypatch.setattr(blocksparse, "block_scores",
                        _hack("sala_recent_selection").recent)
    res = run.run_cell(root, CELL, SEED, SECONDS, False)
    c = res["compared"]
    print({k: v["value"] for k, v in c.items()})
    assert res["correct"] is False, c
    assert res["failed"] == 0
    for k in ("logit_gap_max", "logit_gap_mean"):
        assert c[k]["value"] > c[k]["limit"]


def test_a_program_that_loses_the_rows_is_not_correct(root, monkeypatch):
    """The second planted fault, as hack/sala_lost_rows.py plants it."""
    from vtpu.models import sparselinear

    monkeypatch.setattr(sparselinear, "sparselinear_prefill_chunk",
                        _hack("sala_lost_rows").lossy(
                            sparselinear.sparselinear_prefill_chunk))
    res = run.run_cell(root, CELL, SEED, SECONDS, False)
    c = res["compared"]
    print({k: v["value"] for k, v in c.items()})
    assert res["correct"] is False, c
    assert res["failed"] == 0
    for k in ("logit_gap_max", "logit_gap_mean"):
        assert c[k]["value"] > c[k]["limit"]


# -- operations and bytes, counted by hand -----------------------------------

STATE = 32 * 128 * 128         # a linear layer's rows a stream
W_SPARSE = 4096 * (3 * 4096 + 2 * 256)
W_LINEAR = 5 * 4096 * 4096
W_MLP = 3 * 4096 * 16384


def test_ssm_step_cost_against_a_count_by_hand():
    """96 streams, 6 layers: rows of 524,288 float32 read and written, the
    step's q, k, v and gate in and its output out; the norm's gain once."""
    assert STATE == 524_288
    flops, byts = ref.ssm_step_cost(_real(), 96)
    assert byts == 6 * (96 * (2 * STATE * 4 + 5 * 4096 * 2) + 128 * 2)
    assert flops == 6 * 96 * (5 * STATE + 8 * 4096)
    assert 2.43e9 < byts < 2.45e9      # 2.98 ms at 819 GB/s
    assert flops / 197e12 < 0.01 * byts / 819e9


def test_ssm_chunk_cost_against_a_count_by_hand():
    """A 512-token chunk, 6 layers, chunk 256, a head: q k^T and the mix
    2 T Q d each, state in and out 2 T d d each, the decays T Q."""
    flops, byts = ref.ssm_chunk_cost(_real(), 512)
    assert flops == 6 * 512 * 32 * (4 * 256 * 128 + 4 * 128 * 128 + 256
                                    + 8 * 128)
    assert byts == 6 * (512 * 5 * 4096 * 2 + 2 * STATE * 4)
    # the bytes bound it: 0.18 ms at 819 GB/s against 0.10 at 197 TFLOP/s
    assert 0.09e-3 < flops / 197e12 < 0.11e-3
    assert 0.17e-3 < byts / 819e9 < 0.19e-3


def test_blocksparse_attn_step_cost_against_a_count_by_hand():
    """96 streams that see 2 M tokens (20.8 k each): 125 k compressed keys
    of 2 x 128 scored by 32 heads, 96 x 4096 selected tokens' keys and
    values read once, both products for 32 heads; 2 layers."""
    cfg = _real()
    flops, byts = ref.blocksparse_attn_step_cost(cfg, 96, 2_000_000)
    comp, sel = 2_000_000 / 16, 96 * 4096
    assert byts == 2 * (comp * 256 + 2 * sel * 256) * 2
    assert flops == 2 * (2 * comp * 4096 + 4 * sel * 4096)
    assert 0.93e9 < byts < 0.94e9      # 1.14 ms at 819 GB/s
    # streams that see at most dense_len attend everything they see
    flops, byts = ref.blocksparse_attn_step_cost(cfg, 96, 96 * 8000)
    assert byts == 2 * (96 * 8000 / 16 * 256 + 2 * 96 * 8000 * 256) * 2


def test_decode_step_cost_against_a_count_by_hand():
    """52.4 M a sparse mixer's five projections, 83.9 M a linear one's,
    201.3 M a SwiGLU; the untied head read once; 1 KB of keys and values a
    new token a sparse layer."""
    cfg = _real()
    assert W_SPARSE == 52_428_800 and W_LINEAR == 83_886_080
    assert W_MLP == 201_326_592
    flops, byts = ref.decode_step_cost(cfg, 96, 2_000_000)
    sf, sb = ref.ssm_step_cost(cfg, 96)
    af, ab = ref.blocksparse_attn_step_cost(cfg, 96, 2_000_000)
    params = 2 * W_SPARSE + 6 * W_LINEAR + 8 * W_MLP
    assert byts == (params * 2 + sb + ab + (73448 * 4096 + 96 * 4096) * 2
                    + 2 * 96 * 2 * 256 * 2)
    assert flops == 96 * 2 * params + sf + af + 96 * 2 * 4096 * 73448
    # the weights are 5.0 GB of the step's 8.4; rows and selection 3.4
    assert 8.4e9 < byts < 8.5e9 and 0.39 < (sb + ab) / byts < 0.41


# -- the readers, on a small recorded trace -----------------------------------

def _steps():
    """Three 30 ms decode launches: 6 ms of qkv, under ``attn`` 2 ms of
    indexer, 1 of select, 4 of paged_attn (the kernel, with 0.5 of
    pool_relayout beside it), 9 of ssm_scan; 8 ms of lm_head."""
    ops, modules = [], []
    for i in range(3):
        t = 10 + 40 * i
        modules.append(["jit_step(9)", t * MS, 30 * MS])
        for name, at, dur, path in (
                ("%fusion.1", 0, 6, "jit(step)/qkv/dot_general:"),
                ("%fusion.2", 6, 2, "jit(step)/attn/indexer/dot_general:"),
                ("%fusion.3", 8, 1, "jit(step)/attn/select/sort:"),
                ("%fusion.4", 9, 0.5, "jit(step)/attn/pool_relayout/min:"),
                ("%paged_attn.5", 9.5, 4, "jit(step)/attn/paged_attn:"),
                ("%fusion.6", 13.5, 9, "jit(step)/while/body/attn/ssm_scan/mul:"),
                ("%fusion.7", 22.5, 7.5, "jit(step)/lm_head/dot_general:")):
            ops.append([name, int((t + at) * MS), int(dur * MS), path])
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "spans": []}


def _run(**kw):
    base = dict(records=[], seconds=10.0, setup_s=1.0, give_up_s=10.0,
                stats0={}, stats1={}, cfg=_real(), mix={}, peaks={},
                step_cost=ref.decode_step_cost)
    return Run(**{**base, **kw})


def _counters(ticks, rows, dense, visible):
    return dict(decode_ticks=ticks, select_rows=rows,
                select_rows_dense=dense, attn_visible_tokens=visible)


@pytest.mark.parametrize("name", NEW)
def test_each_new_reader_on_the_recorded_trace(name, monkeypatch):
    raw = _steps()
    monkeypatch.setattr(scopes, "load", lambda root=None: scopes.reduce(raw))
    monkeypatch.setattr(latent_scopes, "load",
                        lambda root=None: latent_scopes.by_program(raw))
    peaks = dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9)
    before = _counters(100, 9000, 600, 100 * 96 * 20000)
    after = _counters(103, 9000 + 3 * 90, 600 + 3 * 6,
                      103 * 96 * 20000)
    r = _run(trace_span=(2.0, 4.0), trace_stats=(before, after), peaks=peaks,
             stats0=_counters(0, 0, 0, 0),
             stats1=_counters(2000, 180000, 12000, 0))
    got = manifest.reader(vbench_toyroot.REPO, name)(r)
    if name == "select_engaged_pct":
        assert got == pytest.approx(100 * 180000 / 192000)
    else:  # 96 streams at 20 k: the cost's bytes in 2 + 1 + 4 ms
        _, byts = ref.blocksparse_attn_step_cost(r.cfg, 96, 96 * 20000)
        assert got == pytest.approx(100 * byts / 819e9 / 0.007)
        assert 5 < got < 20


@pytest.mark.parametrize("name", NEW)
def test_each_new_reader_finds_nothing_in_another_program(name, monkeypatch):
    """The parent of PR 47, or another family's cell: no counter, no cost
    function, none of the scopes: None, and no error."""
    raw = _steps()
    for op in raw["devices"]["/device:TPU:0"]["ops"]:
        for scope in ("indexer", "select"):
            op[3] = op[3].replace("/attn/" + scope, "/attn")
    monkeypatch.setattr(scopes, "load", lambda root=None: scopes.reduce(raw))
    monkeypatch.setattr(latent_scopes, "load", lambda root=None: None)
    peaks = dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9)
    ticks = (dict(decode_ticks=100, attn_visible_tokens=5),
             dict(decode_ticks=103, attn_visible_tokens=9))
    # the parent's program under this family's configuration: no counters
    assert manifest.reader(vbench_toyroot.REPO, name)(
        _run(trace_span=(2.0, 4.0), trace_stats=ticks, peaks=peaks,
             stats0=dict(decode_ticks=0), stats1=dict(decode_ticks=9))) is None
    # another family's cell on this program: counters, no cost function
    other = manifest.config(manifest.load(vbench_toyroot.REPO),
                            vbench_toyroot.REPO, "granite-4.0-h-micro")
    full = (_counters(100, 0, 0, 0), _counters(103, 0, 0, 0))
    got = manifest.reader(vbench_toyroot.REPO, name)(
        _run(cfg=other, trace_span=(2.0, 4.0), trace_stats=full, peaks=peaks,
             stats0=_counters(0, 0, 0, 0), stats1=_counters(9, 0, 0, 0)))
    assert got is None
