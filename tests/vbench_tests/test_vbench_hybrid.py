"""The hybrid family in the harness: as a cell ADDED to a copy of the
benchmark (vbench_toyroot.py's root plus one configuration, one mix and one
cell written here), through ``run.run_cell`` on the CPU: a sound run is
correct, the float8 control is not, and neither is a program that loses the
carried state at every chunk boundary. Its three cost functions against
counts done by hand, and each of its readers on a small recorded trace.
"""

import json
import os
import sys

import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import vbench_toyroot  # noqa: E402

from vbench import manifest, run, scopes, ssm_scopes, traffic  # noqa: E402
from vbench.reference import hybrid as ref  # noqa: E402
from vbench.rundata import Run  # noqa: E402

SECONDS = 2.0
SEED = 2**31 + 32
CELL = "toy_hybrid_sessions"
REAL = "granite4h_sessions"
MS = 10 ** 9  # a millisecond in the trace's picoseconds
NEW = ["ssm_scan_ms_per_step", "ssm_scan_ms_per_chunk", "ssm_state_roofline",
       "ssm_chunk_roofline", "ssm_rows_live_pct"]

# The toy computes in float32: every served token is then the reference's
# first and both gaps read 0.0 (four seeds, 81-99 tokens compared a run; PR
# 32, on the CPU), so a limit can sit anywhere under the two faults. The
# float8 control reads a widest gap of 0.091-0.108 and a mean of
# 0.0068-0.0112; the program that starts every chunk from zeros 0.131-0.287
# and 0.0148-0.0227: the limits sit at half the least of either, on both
# numbers. (Logits here spread by 0.125: the embedding's range,
# vbench/reference/hybrid.py.) The prompts are 2.5 to 10 chunks of 16 long
# and the steps are the published initialiser's (``map_leaves``), so a lost
# carry is still in the state, and in the keys the attention layers cached,
# when the outputs are served.
PERIOD = ["mamba", "mamba", "attention", "mamba", "mamba"]
TOY = dict(
    family="hybrid", hidden_size=128, shared_intermediate_size=256,
    num_attention_heads=4, num_key_value_heads=2, head_dim=64,
    layer_types=PERIOD * 2, num_hidden_layers=10, mamba_n_heads=8,
    mamba_d_head=32, mamba_d_state=16, mamba_n_groups=1, mamba_d_conv=4,
    mamba_chunk_size=8, mamba_expand=2, embedding_multiplier=12,
    attention_multiplier=0.015625, residual_multiplier=0.22, logits_scaling=8,
    rms_norm_eps=1e-5, vocab_size=384, max_position_embeddings=256,
    position_embedding_type="nope", tie_word_embeddings=True,
    dtype="float32", output_head="embed",
    serving=dict(slots=4, kv_page=8, kv_pool_blocks=120,
                 prefill_buckets=[16], prefill_batch_sizes=[1],
                 prefill_chunk=16, prefill_budget=32, max_new_tokens=32,
                 read_windows=[64, 128, 256]),
    check=dict(requests=6, min_tokens=40,
               limits=dict(logit_gap_max=0.045, logit_gap_mean=0.003)))
MIX = dict(kind="saturated", ahead=2, settle_s=0.5, ramp_stagger=3,
           drain_s=0, grid=4, schedule_seed=13,
           prompt=dict(median=80, sigma=0.4, min=40, max=160),
           output=dict(median=16, sigma=0.3, min=8, max=32))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The toy root, and the hybrid family's cell added as a PR would."""
    root = str(tmp_path_factory.mktemp("vbench_hybrid_root"))
    man = vbench_toyroot.build(root)
    with open(os.path.join(root, "vbench/configs/toy-hybrid.json"), "w") as f:
        json.dump(TOY, f)
    with open(os.path.join(root, "vbench/traffic/toy-sessions.json"), "w") as f:
        json.dump(MIX, f)
    man["configs"].append(dict(
        name="toy-hybrid", source="tests", reduced=[], why="toy size",
        file="vbench/configs/toy-hybrid.json"))
    man["workloads"].append(dict(name=CELL, config="toy-hybrid",
                                 traffic="toy-sessions", chips=1, why="toy"))
    for group in ("end_to_end", "per_layer"):
        for m in man[group]:
            if REAL in m.get("workloads", ()):
                m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return root


def _real():
    man = manifest.load(vbench_toyroot.REPO)
    return manifest.config(man, vbench_toyroot.REPO, "granite-4.0-h-micro")


def test_the_real_cell_is_in_the_manifest_with_its_files():
    man = manifest.load(vbench_toyroot.REPO)
    cell = manifest.cell(man, REAL)
    cfg = manifest.config(man, vbench_toyroot.REPO, cell["config"])
    entry = next(c for c in man["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 1 and cfg["family"] == "hybrid"
    assert entry["reduced"] == ["max_position_embeddings"] == list(
        cfg["reduced"])
    kinds = ref.layer_kinds(cfg)
    assert len(kinds) == 40 and kinds.count("attention") == 4
    assert kinds[0] == "mamba_in" and kinds.count("mamba") == 35
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [
        5, 15, 25, 35]
    per_layer = {m["name"] for m in manifest.metrics_of(
        man, "per_layer", REAL)}
    assert set(NEW) <= per_layer
    assert {"kernel_route_pct", "decode_step_roofline", "step_unscoped_pct",
            "itl_p95_ms.watch"} <= per_layer
    assert {m["name"] for m in manifest.metrics_of(
        man, "end_to_end", REAL)} == {
            "itl_mean_ms", "out_tokens_per_s", "setup_s"}
    for name in NEW:
        manifest.reader(vbench_toyroot.REPO, name)
    mix = traffic.load_mix(cell["traffic"], vbench_toyroot.REPO)
    assert (mix["kind"], mix["ahead"], mix["settle_s"], mix["ramp_stagger"],
            mix["drain_s"], mix["grid"], mix["schedule_seed"]) == (
                "saturated", 2, 3, 16, 0, 16, 24301)
    assert mix["prompt"] == dict(median=2048, sigma=0.6, min=512, max=8192)
    assert mix["output"] == dict(median=512, sigma=0.35, min=256, max=1024)
    sizes = cfg["serving"]
    assert sizes["slots"] == 64 and sizes["prefill_chunk"] == 512
    assert sizes["prefill_budget"] == 2 * sizes["prefill_chunk"]


def test_the_configuration_is_the_published_one_but_for_the_context():
    """Every number of the catalog's config under its own key; the
    multipliers, the head counts and the Mamba sizes as published."""
    cfg = _real()
    published = dict(
        attention_multiplier=0.015625, embedding_multiplier=12,
        residual_multiplier=0.22, logits_scaling=8, hidden_size=2048,
        intermediate_size=8192, shared_intermediate_size=8192,
        num_hidden_layers=40, num_attention_heads=32, num_key_value_heads=8,
        mamba_n_heads=64, mamba_d_head=64, mamba_d_state=128,
        mamba_n_groups=1, mamba_d_conv=4, mamba_chunk_size=256,
        mamba_expand=2, vocab_size=100352, rms_norm_eps=1e-5,
        num_local_experts=0, num_experts_per_tok=0, rope_theta=10000,
        tie_word_embeddings=True, position_embedding_type="nope")
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["max_position_embeddings"] == 16384
    assert list(cfg["reduced"]) == ["max_position_embeddings"]


@pytest.fixture(scope="module")
def sound(root):
    return run.run_cell(root, CELL, SEED, SECONDS, False)


def test_a_sound_run_of_the_hybrid_family_is_correct(sound):
    c = sound["compared"]
    assert sound["correct"] is True, c
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert c["tokens_short_of_sample"]["value"] == 0
    assert set(sound["metrics"]) == {"itl_mean_ms", "out_tokens_per_s",
                                     "setup_s"}
    for k in ("logit_gap_max", "logit_gap_mean"):
        assert c[k]["value"] <= c[k]["limit"]


def test_the_float8_control_of_the_hybrid_family_is_not_correct(root):
    res = run.run_cell(root, CELL, SEED, SECONDS, False, control=True)
    c = res["compared"]
    assert res["correct"] is False
    for k in ("logit_gap_max", "logit_gap_mean"):
        assert c[k]["value"] > c[k]["limit"]
        assert c[f"program_{k}"]["value"] <= c[k]["limit"]  # it was sound


def test_a_program_that_loses_the_carried_state_is_not_correct(
        root, monkeypatch):
    """The planted fault: every chunk starts from zeros, as a prefill that
    did not carry the state would."""
    from vtpu.models import hybrid

    def lossy(state, slot, offset):
        conv, h = state["conv"][:, slot], state["h"][:, slot]
        return jnp.zeros_like(conv)[:, None], jnp.zeros_like(h)[:, None]

    monkeypatch.setattr(hybrid, "carried_rows", lossy)
    res = run.run_cell(root, CELL, SEED, SECONDS, False)
    c = res["compared"]
    assert res["correct"] is False, c
    assert res["failed"] == 0
    for k in ("logit_gap_max", "logit_gap_mean"):
        assert c[k]["value"] > c[k]["limit"]


# -- operations and bytes, counted by hand -----------------------------------

STATE = 64 * 64 * 128          # a layer's recurrent state a stream
W_MAMBA = 2048 * 8512 + 4096 * 2048
W_ATTN = 2048 * (2048 + 2 * 512) + 2048 * 2048
W_MLP = 3 * 2048 * 8192


def test_ssm_step_cost_against_a_count_by_hand():
    """64 streams, 36 layers: a state of 524,288 float32 read and written,
    a window of 3 x 4352 bfloat16 read and written, the step's projected
    channels in and gated output out; the taps and per-head leaves once."""
    assert STATE == 524_288
    flops, byts = ref.ssm_step_cost(_real(), 64)
    stream = 2 * STATE * 4 + 2 * 3 * 4352 * 2 + (2 * 4096 + 4352) * 2
    leaves = 5 * 4352 * 2 + 3 * 64 * 4 + 4096 * 2
    assert byts == 36 * (64 * stream + leaves)
    assert flops == 36 * 64 * (5 * STATE + 2 * 4 * 4352 + 8 * 4096)
    assert 9.7e9 < byts < 9.9e9      # 11.9 ms at 819 GB/s
    # bandwidth bounds it by far: 6.2 GFLOP is 0.03 ms at the peak
    assert flops / 197e12 < 0.01 * byts / 819e9


def test_ssm_chunk_cost_against_a_count_by_hand():
    """A 512-token chunk, 36 layers, SSD chunk 256: C B^T 2 T Q N, the mix
    2 T Q H P, state in and out 4 T H P N, the decays T Q H."""
    flops, byts = ref.ssm_chunk_cost(_real(), 512)
    layer = 512 * (2 * 256 * 128 + 2 * 256 * 4096 + 4 * 4096 * 128
                   + 256 * 64 + 2 * 4 * 4352 + 8 * 4096)
    assert flops == 36 * layer
    assert byts == 36 * (512 * (4352 + 2 * 4096 + 64) * 2 + 2 * STATE * 4
                         + 2 * 3 * 4352 * 2)
    # the bytes bound it: 0.75 ms at 819 GB/s against 0.41 at 197 TFLOP/s
    assert 0.40e-3 < flops / 197e12 < 0.42e-3
    assert 0.74e-3 < byts / 819e9 < 0.76e-3


def test_decode_step_cost_against_a_count_by_hand():
    """25.82 M a Mamba mixer's two projections, 10.49 M an attention
    layer's four, 50.33 M a SwiGLU; the tied head read once; 8 KB of keys
    and values a live token."""
    cfg = _real()
    assert W_MAMBA == 25_821_184 and W_ATTN == 10_485_760
    assert W_MLP == 50_331_648
    flops, byts = ref.decode_step_cost(cfg, 64, 170000)
    sf, sb = ref.ssm_step_cost(cfg, 64)
    want_b = (40 * W_MLP * 2 + sb + 36 * W_MAMBA * 2
              + 4 * (W_ATTN * 2 + 2 * (170000 + 64) * 512 * 2)
              + (100352 * 2048 + 64 * 2048) * 2)
    want_f = (40 * 64 * 2 * W_MLP + sf + 36 * 64 * 2 * W_MAMBA
              + 4 * (64 * 2 * W_ATTN + 4 * 170000 * 2048)
              + 64 * 2 * 2048 * 100352)
    assert byts == want_b and flops == want_f
    # the state's bytes are more than half of the step's, over the weights'
    assert sb > 0.5 * byts and 17.5e9 < byts < 17.7e9


# -- the readers, on a small recorded trace -----------------------------------

def _steps():
    """Three 30 ms decode launches and two 40 ms chunk launches. A decode
    launch: 6 ms of qkv, then a ``while`` of 16 ms that holds, under
    ``attn``, 2 ms of ssm_conv, 9 of ssm_scan and 1 of ssm_gate and 3 ms of
    mlp (1 ms its own); 8 ms of lm_head. A chunk: 4 ms of ssm_conv, 10 of
    ssm_scan, 2 of ssm_gate, 24 of mlp."""
    ops, modules = [], []
    for i in range(3):
        t = 10 + 40 * i
        modules.append(["jit_step(9)", t * MS, 30 * MS])
        for name, at, dur, path in (
                ("%fusion.1", 0, 6, "jit(step)/qkv/dot_general:"),
                ("%while.2", 6, 16, "jit(step)/while:"),
                ("%fusion.3", 6, 2, "jit(step)/while/body/attn/ssm_conv/add:"),
                ("%fusion.4", 8, 9, "jit(step)/while/body/attn/ssm_scan/mul:"),
                ("%fusion.5", 17, 1, "jit(step)/while/body/attn/ssm_gate/mul:"),
                ("%fusion.6", 18, 3, "jit(step)/while/body/mlp/dot_general:"),
                ("%fusion.7", 22, 8, "jit(step)/lm_head/dot_general:")):
            ops.append([name, (t + at) * MS, dur * MS, path])
    for i in range(2):
        t = 200 + 50 * i
        modules.append(["jit_prefill_chunk_into_slot(3)", t * MS, 40 * MS])
        for name, at, dur, scope in (("%fusion.8", 0, 4, "attn/ssm_conv/add:"),
                                     ("%fusion.9", 4, 10, "attn/ssm_scan/dot:"),
                                     ("%fusion.10", 14, 2, "attn/ssm_gate/mul:"),
                                     ("%fusion.11", 16, 24, "mlp/dot_general:")):
            ops.append([name, (t + at) * MS, dur * MS,
                        "jit(prefill_chunk_into_slot)/while/body/" + scope])
    spans = [["vtpu.admit.chunk", (195 + 50 * i) * MS, 2 * MS,
              {"tokens": 512 if i == 0 else 256}] for i in range(2)]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "spans": spans}


def test_seconds_by_the_new_scopes_are_the_launches_own():
    raw = _steps()
    got = ssm_scopes.by_program(raw)
    assert got[scopes.DECODE] == pytest.approx(
        {"ssm_conv": 0.006, "ssm_scan": 0.027, "ssm_gate": 0.003})
    assert got[ssm_scopes.CHUNK] == pytest.approx(
        {"ssm_conv": 0.008, "ssm_scan": 0.020, "ssm_gate": 0.004})
    # vbench/scopes.py's vocabulary reads the same operations as ``attn``
    by = scopes.reduce(raw)["programs"]["jit_step"]["scopes"]
    assert by["attn"] == pytest.approx(0.036)
    assert by["unscoped"] == pytest.approx(0.003)  # the while's own 1 ms each
    assert ssm_scopes.scope_of("jit(step)/while/body/attn/ssm_scan/mul:") \
        == "ssm_scan"
    assert ssm_scopes.scope_of("jit(step)/attn/dot:") is None
    # a program from before the names: nothing to read, and no error
    for op in raw["devices"]["/device:TPU:0"]["ops"]:
        for name in ssm_scopes.NAMES:
            op[3] = op[3].replace("/attn/" + name, "/attn")
    assert ssm_scopes.by_program(raw) == {scopes.DECODE: {},
                                          ssm_scopes.CHUNK: {}}


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
    monkeypatch.setattr(ssm_scopes, "load",
                        lambda root=None: ssm_scopes.by_program(raw))
    records = [Record(index=i, prompt_len=2000, max_new=64, due_s=0.0,
                      in_window=True, stamps=[0.5 + 0.1 * j for j in range(64)])
               for i in range(48)]
    peaks = dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9)
    r = _run(records=records, trace_span=(2.0, 4.0), peaks=peaks,
             stats0=dict(ssm_rows_stepped=640, ssm_rows_live=600),
             stats1=dict(ssm_rows_stepped=64640, ssm_rows_live=48600))
    got = manifest.reader(vbench_toyroot.REPO, name)(r)
    if name == "ssm_scan_ms_per_step":
        assert got == pytest.approx(12.0)
    elif name == "ssm_scan_ms_per_chunk":
        assert got == pytest.approx(16.0)
    elif name == "ssm_state_roofline":  # 48 live streams' rows in 12 ms
        _, byts = ref.ssm_step_cost(r.cfg, 48)
        assert got == pytest.approx(100 * byts / 819e9 / 0.012)
        assert 60 < got < 100
    elif name == "ssm_chunk_roofline":  # a mean of 384 true tokens a chunk
        _, byts = ref.ssm_chunk_cost(r.cfg, 384)
        assert got == pytest.approx(100 * byts / 819e9 / 0.016)
    else:
        assert got == pytest.approx(75.0)


@pytest.mark.parametrize("name", NEW)
def test_each_new_reader_finds_nothing_in_another_program(name, monkeypatch):
    """The parent of PR 32, or another family's cell: no scope of the
    three, no counter, no cost function: None, and no error."""
    raw = _steps()
    for op in raw["devices"]["/device:TPU:0"]["ops"]:
        for scope in ssm_scopes.NAMES:
            op[3] = op[3].replace("/attn/" + scope, "/attn")
    monkeypatch.setattr(scopes, "load", lambda root=None: scopes.reduce(raw))
    monkeypatch.setattr(
        ssm_scopes, "load", lambda root=None: None)
    r = _run(trace_span=(2.0, 4.0), stats0={}, stats1={},
             peaks=dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9))
    assert manifest.reader(vbench_toyroot.REPO, name)(r) is None
