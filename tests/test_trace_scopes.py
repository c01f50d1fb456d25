"""What a profiler trace can name: the scope vocabulary on every compiled
step, the programs' names, the loop's phases as spans, the warm-up's clock
and the prefill-token counter (ISSUE 25)."""

import dataclasses
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vtpu.models import ModelConfig, init_params
from vtpu.models.blockdiff import BlockDiffConfig
from vtpu.models.hybrid import HybridConfig, init_hybrid_params
from vtpu.models.latent import LatentConfig, init_latent_params
from vtpu.models.moe import MoEConfig, init_moe_params
from vtpu.models.sparselinear import (
    SparseLinearConfig, init_sparselinear_params)
from vtpu.models.swa import SwaConfig, init_swa_params
from vtpu.obs.tickprof import HOST_PHASES, TickProfiler, host_ms_per_tick
from vtpu.ops import SCOPES
from vtpu.serving import ServingConfig, ServingEngine
from vtpu.serving.adapters import (
    BlockDiffSlotModel, HybridSlotModel, LatentSlotModel, MoeSlotModel,
    SparseLinearSlotModel, WindowSlotModel)

PAGE, CHUNK, BUCKET = 8, 8, 16
DENSE = ModelConfig(
    vocab=64, d_model=32, n_heads=2, n_layers=2, d_ff=64, max_seq=32,
    head_dim=16, dtype=jnp.float32, use_pallas=False)
MOE = MoEConfig(
    vocab=64, d_model=32, n_heads=2, n_layers=2, d_ff=32, n_experts=4,
    top_k=2, max_seq=32, head_dim=16, dtype=jnp.float32)
LATENT = LatentConfig(
    vocab=64, d_model=32, n_heads=2, d_ff=64, d_ff_expert=16, q_rank=16,
    kv_rank=16, nope_dim=8, rope_dim=4, v_dim=8, index_heads=2, index_dim=8,
    index_topk=4, n_experts=8, held=(2, 4), top_k=2, n_group=2, topk_group=1,
    max_seq=32, dtype=jnp.float32)
# the same family without an indexer (DeepSeek-V2's block): no ``indexer``
# and no ``select`` in any of its programs, the walk under ``latent_attn``
LATENT_DENSE = dataclasses.replace(
    LATENT, index_heads=0, index_dim=0, index_topk=0, d_ff_shared=32,
    topk_method="group_limited_greedy")
HYBRID = HybridConfig(
    vocab=64, d_model=32, layer_types=("mamba", "attention", "mamba"),
    n_heads=4, n_kv_heads=2, head_dim=64, d_ff=64, ssm_heads=4,
    ssm_head_dim=16, ssm_state=8, ssd_chunk=4, max_seq=32, dtype=jnp.float32)
SWA = SwaConfig(
    vocab=64, d_model=32, n_heads=4, head_dim=24, v_head_dim=16, rope_dim=8,
    layer_types=("full", "window", "full"), ffn_types=("dense", "moe", "moe"),
    n_kv_heads=1, n_kv_heads_window=2, window=4, d_ff=64, d_ff_expert=16,
    n_experts=8, held=(2, 4), top_k=2, max_seq=32, dtype=jnp.float32)
BLOCKDIFF = BlockDiffConfig(
    vocab=64, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2, d_ff=16,
    n_experts=8, held=(2, 4), top_k=2, max_seq=32, head_dim=16,
    dtype=jnp.float32, mask_token_id=63)
# block-sparse attention beside linear attention: a page a selection block,
# a window of two pages past ``dense_len``, so every program selects
SPARSELIN = SparseLinearConfig(
    vocab=64, d_model=32, layer_types=("sparse", "linear", "sparse"),
    n_heads=4, n_kv_heads=2, head_dim=16, d_ff=64, lin_heads=2,
    lin_head_dim=16, ssd_chunk=4, kernel_stride=2, block_size=PAGE,
    window_size=PAGE, topk=1, dense_len=PAGE, max_seq=32, dtype=jnp.float32)
BLOCK = {"dense": {"mlp"}, "moe": {"route", "experts"}}
# that family: the selection's two parts and the linear layers' two nested
# under ``attn`` as the latent and Mamba parts are, the selected pages
# walked by the paged pool's routes, a chunk's window attended under the
# selection's mask inside ``gather_attn`` / ``chunk_attn``
SELECTS = {"attn", "indexer", "select", "ssm_scan", "ssm_gate", "mlp"}
# generation by blocks: a pass's attention nested under ``attn`` as the
# other families' own parts are; on the kernel's route the walk of the pool
# (the kernel ``paged_attn``) and its preparation of its queries lie inside
# it; its chunk is the expert family's
BLOCK_PASS = {"attn", "block_attn", "route", "experts", "sample"}
# the window family: a window layer's ring read and write nested under
# ``attn`` as the latent and Mamba parts are, a dense layer then expert
# ones, and the paged pool's routes for its full layers
WINDOW = {"attn", "window_attn", "ring_write", "mlp", "route", "experts"}
# the hybrid family: its Mamba layers' three parts nested under ``attn``
# as the latent family's are, the SwiGLU of every layer, and the paged
# pool's routes for its attention layers
SSM = {"attn", "ssm_conv", "ssm_scan", "ssm_gate", "mlp"}
# the latent family: a dense layer then sparse ones, and its attention's
# three parts nested under ``attn`` (the name vbench/scopes.py knows)
SPARSE_ATTN = {"attn", "indexer", "select", "latent_attn", "mlp", "route",
               "experts"}
DENSE_ATTN = SPARSE_ATTN - {"indexer", "select"}
ROUTE = {"kernel": {"pool_relayout", "paged_attn"}, "gather": {"gather_attn"},
         None: {"attn"}}
TRUNK = {"embed", "qkv", "kv_write", "o_proj", "lm_head"}


def _engine(family: str, route, **serving):
    paged = {} if route is None else {
        "kv_page": PAGE, "prefill_chunk": CHUNK,
        "paged_attn": None if route == "paged" else route}
    cfg = ServingConfig(slots=2, prefill_buckets=(BUCKET,), max_new_tokens=4,
                        **paged, **serving)
    if family == "dense":
        return ServingEngine(init_params(jax.random.key(0), DENSE), DENSE, cfg)
    if family in ("latent", "latent_dense"):
        mc = LATENT if family == "latent" else LATENT_DENSE
        model = LatentSlotModel(
            init_latent_params(jax.random.key(0), mc), mc,
            kv_page=cfg.kv_page)
        return ServingEngine(serving=cfg, model=model)
    if family == "swa":
        model = WindowSlotModel(
            init_swa_params(jax.random.key(0), SWA), SWA,
            kv_page=cfg.kv_page, paged_attn=cfg.paged_attn)
        return ServingEngine(serving=cfg, model=model)
    if family == "blockdiff":
        model = BlockDiffSlotModel(
            init_moe_params(jax.random.key(0), BLOCKDIFF), BLOCKDIFF,
            kv_page=cfg.kv_page, paged_attn=cfg.paged_attn)
        return ServingEngine(serving=cfg, model=model)
    if family == "sparselinear":
        model = SparseLinearSlotModel(
            init_sparselinear_params(jax.random.key(0), SPARSELIN), SPARSELIN,
            kv_page=cfg.kv_page, paged_attn=cfg.paged_attn)
        return ServingEngine(serving=cfg, model=model)
    if family == "hybrid":
        model = HybridSlotModel(
            init_hybrid_params(jax.random.key(0), HYBRID), HYBRID,
            kv_page=cfg.kv_page, paged_attn=cfg.paged_attn)
        return ServingEngine(serving=cfg, model=model)
    model = MoeSlotModel(
        init_moe_params(jax.random.key(0), MOE), MOE,
        kv_page=cfg.kv_page, paged_attn=cfg.paged_attn)
    return ServingEngine(serving=cfg, model=model)


def _lowered(eng, step: str):
    """The step lowered as the engine's warm-up calls it."""
    b = eng.serving.slots
    if step == "pass":
        return eng._decode_sampled.lower(
            eng.params, eng.state, jnp.zeros((b,), bool), BUCKET)
    if step == "decode":
        return eng._decode_sampled.lower(
            eng.params, eng.state, jnp.zeros((b,), jnp.int32),
            jnp.zeros((b,), bool), eng._rng, BUCKET, unroll=eng._unroll)
    if step == "admit":
        return eng._admit_step.lower(
            eng.params, eng.state, eng._admit_buf,
            jnp.zeros((1, BUCKET), jnp.int32), jnp.arange(1, dtype=jnp.int32),
            jnp.ones((1,), jnp.int32), jax.random.split(jax.random.key(0), 1))
    return eng._prefill_chunk.lower(
        eng.params, eng.state, jnp.zeros((1, CHUNK), jnp.int32),
        jnp.int32(0), jnp.int32(0), jnp.int32(1), kv_bucket=BUCKET,
        unroll=eng._unroll,
        block_ids=np.zeros((BUCKET // PAGE,), np.int32))


def _scopes_in(lowered) -> set:
    text = lowered.as_text(debug_info=True)
    return {s for s in SCOPES if re.search(rf'[/"]{s}[/"]', text)}


CASES = [
    ("dense", "kernel", "decode", TRUNK | BLOCK["dense"] | ROUTE["kernel"]
     | {"sample"}),
    ("dense", "gather", "decode", TRUNK | BLOCK["dense"] | ROUTE["gather"]
     | {"sample"}),
    ("dense", None, "decode", TRUNK | BLOCK["dense"] | ROUTE[None]
     | {"sample"}),
    ("moe", "kernel", "decode", TRUNK | BLOCK["moe"] | ROUTE["kernel"]
     | {"sample"}),
    ("dense", "kernel", "admit", TRUNK | BLOCK["dense"] | {"attn", "sample"}),
    ("moe", "kernel", "admit", TRUNK | BLOCK["moe"] | {"attn", "sample"}),
    ("dense", "kernel", "chunk", TRUNK | BLOCK["dense"]
     | {"gather_attn", "attn", "chunk_attn"}),
    ("moe", "kernel", "chunk", TRUNK | BLOCK["moe"]
     | {"gather_attn", "attn", "chunk_attn"}),
    ("latent", "paged", "decode", TRUNK | SPARSE_ATTN | {"sample"}),
    ("latent", "paged", "admit", TRUNK | SPARSE_ATTN | {"sample"}),
    ("latent", "paged", "chunk", TRUNK | SPARSE_ATTN),
    ("latent_dense", "paged", "decode", TRUNK | DENSE_ATTN | {"sample"}),
    ("latent_dense", "paged", "admit", TRUNK | DENSE_ATTN | {"sample"}),
    ("latent_dense", "paged", "chunk", TRUNK | DENSE_ATTN),
    ("hybrid", "kernel", "decode", TRUNK | SSM | ROUTE["kernel"]
     | {"sample"}),
    ("hybrid", "gather", "decode", TRUNK | SSM | ROUTE["gather"]
     | {"sample"}),
    ("hybrid", "kernel", "admit", TRUNK | SSM | {"chunk_attn", "sample"}),
    ("hybrid", "kernel", "chunk", TRUNK | SSM
     | {"gather_attn", "chunk_attn"}),
    ("sparselinear", "kernel", "decode", TRUNK | SELECTS | ROUTE["kernel"]
     | {"sample"}),
    ("sparselinear", "gather", "decode", TRUNK | SELECTS | ROUTE["gather"]
     | {"sample"}),
    ("sparselinear", "kernel", "admit", TRUNK | SELECTS
     | {"gather_attn", "chunk_attn", "sample"}),
    ("sparselinear", "kernel", "chunk", TRUNK | SELECTS
     | {"gather_attn", "chunk_attn"}),
    ("swa", "kernel", "decode", TRUNK | WINDOW | ROUTE["kernel"]
     | {"sample"}),
    ("swa", "gather", "decode", TRUNK | WINDOW | ROUTE["gather"]
     | {"sample"}),
    ("swa", "kernel", "admit", TRUNK | WINDOW
     | {"gather_attn", "chunk_attn", "sample"}),
    ("swa", "kernel", "chunk", TRUNK | WINDOW
     | {"gather_attn", "chunk_attn"}),
    ("blockdiff", "kernel", "pass", TRUNK | BLOCK_PASS
     | {"pool_relayout", "paged_attn"}),
    ("blockdiff", "gather", "pass", TRUNK | BLOCK_PASS),
    ("blockdiff", "kernel", "chunk", TRUNK | BLOCK["moe"]
     | {"gather_attn", "attn", "chunk_attn"}),
]


@pytest.mark.parametrize(
    "family,route,step,expected", CASES,
    ids=[f"{f}-{r}-{s}" for f, r, s, _ in CASES])
def test_lowered_step_carries_its_scopes(family, route, step, expected):
    """Each part of each compiled step lies under the scope the per-layer
    metrics read it by: exactly the vocabulary's names for that step."""
    assert _scopes_in(_lowered(_engine(family, route), step)) == expected


def test_vocabulary_has_no_name_that_no_step_uses():
    assert set().union(*(c[3] for c in CASES)) == set(SCOPES)


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_programs_are_told_apart_by_name(family):
    """The trace names a program after its closure: decode is ``jit_step``
    (the benchmark's decode_step_roofline looks for it), admission and
    chunks are not."""
    eng = _engine(family, "kernel")

    def name(step):
        return re.search(r"module @(\w+)", _lowered(eng, step).as_text())[1]

    assert name("decode") == "jit_step"
    assert name("admit") == "jit_admit_step"
    assert name("chunk") == "jit_prefill_chunk_into_slot"


def test_kernels_are_named():
    from vtpu.ops.decode_attn import decode_attention, paged_decode_attention

    q = jnp.zeros((2, 1, 2, 16), jnp.float32)
    pool = jnp.zeros((2, 5, PAGE, 2, 16), jnp.float32)
    table = jnp.zeros((2, 2), jnp.int32)
    lens = jnp.ones((2, 1), jnp.int32)
    paged = jax.make_jaxpr(
        lambda: paged_decode_attention(q, pool, pool, table, lens, layer=0))()
    dense = jax.make_jaxpr(lambda: decode_attention(
        q, pool[0, :2].reshape(2, PAGE, 2, 16),
        pool[0, :2].reshape(2, PAGE, 2, 16), lens))()
    assert "paged_attn" in str(paged) and "decode_attn" in str(dense)
    from vtpu.ops.decode_attn import latent_decode_attention

    walk = jax.make_jaxpr(lambda: latent_decode_attention(
        jnp.zeros((2, 2, 128), jnp.float32),
        jnp.zeros((2, 5, PAGE, 128), jnp.float32), table, lens[:, 0], 0,
        64, 1.0))()
    assert "latent_walk" in str(walk)


def test_phase_notes_what_note_noted():
    """phase() is note() around a block: count, total and ticks; a phase
    opened inside another comes off the outer one's note."""
    tick = iter(range(100))
    prof = TickProfiler(tick=lambda: next(tick))
    with prof.phase("dispatch", ticks=4):
        time.sleep(0.002)
    with prof.phase("dispatch", ticks=4, n=2):
        pass
    with prof.phase("admission"):
        time.sleep(0.001)
        with prof.phase("swap_drain"):
            time.sleep(0.004)
    snap = prof.snapshot()
    d, a, s = snap["dispatch"], snap["admission"], snap["swap_drain"]
    assert (d["count"], d["ticks"]) == (2, 8)
    assert 2.0 <= d["total_ms"] < 50.0 and d["max_ms"] >= 2.0
    assert (a["count"], s["count"]) == (1, 1)
    assert s["total_ms"] >= 4.0 and 1.0 <= a["total_ms"] < s["total_ms"]
    ref = TickProfiler()
    ref.note("dispatch", 0.002, ticks=4)
    ref.note("dispatch", 0.0, ticks=4)
    assert {k: ref.snapshot()["dispatch"][k] for k in ("count", "ticks")} == {
        k: d[k] for k in ("count", "ticks")}
    assert host_ms_per_tick(snap) == pytest.approx(
        sum(snap[p]["total_ms"] for p in HOST_PHASES) / 8)
    assert host_ms_per_tick(TickProfiler().snapshot()) is None


def test_phase_spans_reach_the_profiler(tmp_path):
    """In a profiler session every phase is a ``vtpu.tick.<phase>`` span
    on the loop's thread, with the ``tick`` id and the caller's."""
    from jax.profiler import ProfileData

    prof = TickProfiler(tick=lambda: 7)
    jax.profiler.start_trace(str(tmp_path))
    with prof.phase("admission"):
        with prof.phase("swap_drain"):
            pass
    with prof.phase("fetch", ticks=2, rows=3):
        pass
    jax.profiler.stop_trace()
    [path] = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    spans = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("vtpu.tick."):
                    spans[e.name] = dict(e.stats)
    assert set(spans) == {"vtpu.tick.admission", "vtpu.tick.swap_drain",
                          "vtpu.tick.fetch"}
    assert all(s["tick"] == 7 for s in spans.values())
    assert spans["vtpu.tick.fetch"]["rows"] == 3


def test_warmup_clock_and_prefill_tokens():
    """stats()["warmup_s"] has its six keys, holds what JAX reported
    while the engine warmed, and stands still afterwards; prefill_tokens
    counts the prompts' true tokens, batch or chunks."""
    eng = _engine("dense", "kernel")
    assert eng.stats()["warmup_s"]["total"] == 0.0
    eng.start()
    try:
        short, long_ = [3, 4, 5], list(range(1, 20))  # one bucket, 3 chunks
        for prompt in (short, long_):
            assert len(list(eng.submit(prompt, max_new_tokens=2).stream())) == 2
        w = eng.stats()["warmup_s"]
        assert set(w) == {"total", "trace_lower", "compile", "cache_load",
                          "run", "programs"}
        assert w["programs"] >= 3 and w["trace_lower"] > 0 and w["total"] > 0
        parts = w["trace_lower"] + w["compile"] + w["cache_load"] + w["run"]
        assert parts == pytest.approx(w["total"], abs=2e-3)
        jax.jit(lambda x: x * 3)(jnp.ones((7,)))  # another compile, after
        assert eng.stats()["warmup_s"] == w
        s = eng.stats()
        assert s["prefill_tokens"] == len(short) + len(long_)
        assert s["prefill_chunks"] == 3 and s["admissions"] == 2
        assert s["tick_phase_ms"]["idle_wait"]["count"] >= 0
    finally:
        eng.stop()
