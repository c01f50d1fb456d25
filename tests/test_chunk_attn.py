"""The chunk kernel of the families that cache heads
(vtpu/ops/chunk_attn.py) under the interpreter at cut-down lengths and the
four cells' head shapes, against the XLA code it replaces on a TPU
(``causal_attention``'s ragged form, and ``full_attention`` for rows of heads
side by side); the rule that routes a program to it; and the engine's
counters of it (``chunk_attn_launches``, ``chunk_attn_kernel``,
``chunk_keys_live``, ``chunk_keys_attended``) with the benchmark's metric
over them.

Tolerance: in float32 the two sides differ by the order of their sums (a
running maximum a block against one over the whole row): outputs of size 1
agree to 2e-6, and 1e-5 is held. In bfloat16 they differ by the rounding of
the outputs and of the exponentials (each side rounds ``exp(s - m)`` under
its own maximum): one step of an output, which is 2 ** -6 between 2 and 4
(a query that sees few keys returns nearly a value row); two steps are held.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vtpu.models import ModelConfig, init_params
from vtpu.models import transformer
from vtpu.ops import chunk_attn as K
from vtpu.ops.attention import causal_attention
from vtpu.ops.window_attn import full_attention
from vtpu.serving import ServingConfig, ServingEngine
from vtpu.serving.adapters import TransformerSlotModel

KEYS = 64  # the tests' key block; the cells' is 1024

# name: (queries, query heads, key/value heads, Dk, Dv, stored form): the
# four cells' heads, the queries cut so that a key/value head has 128 rows
SHAPES = {
    "dense_g1": (128, 2, 2, 128, 128, "heads"),
    "hybrid_g4_two_a_row": (32, 16, 4, 64, 64, "packed"),
    "sdar_g8": (16, 16, 2, 128, 128, "heads"),
    "mimo_g16_192_128": (8, 32, 2, 192, 128, "flat"),
}


def _reach(mask, offsets, t):
    at = jnp.asarray(offsets, jnp.int32)[:, None] + jnp.arange(t)
    if mask == "blocks_of_4":
        return (at // 4 + 1) * 4
    return at + 1  # causal (len + i + 1) and position + 1 alike


def _inputs(seed, shape, w, n, dtype):
    t, hq, hk, dk, dv, stored = SHAPES[shape]
    rng = np.random.default_rng(seed)

    def normal(*dims):
        return jnp.asarray(rng.standard_normal(dims, np.float32), dtype)

    return normal(n, t, hq, dk), normal(n, w, hk, dk), normal(n, w, hk, dv)


def _stored(shape, k, v):
    """The window as the family's chunk program holds it."""
    stored = SHAPES[shape][5]
    if stored == "packed":  # two 64-wide heads a row of 128 lanes
        return (k.reshape(k.shape[:2] + (-1, 128)),
                v.reshape(v.shape[:2] + (-1, 128)))
    if stored == "flat":    # a token's heads side by side
        return k.reshape(k.shape[:2] + (-1,)), v.reshape(v.shape[:2] + (-1,))
    return k, v


def _xla(shape, q, k, v, reach, scale):
    if SHAPES[shape][5] == "flat":
        return full_attention(q, k, v, reach - 1, scale)
    return causal_attention(q, *_stored(shape, k, v), kv_len=reach,
                            scale=scale)


PLACES = {
    # name: (window, each sequence's chunk offset)
    "window_start": (256, (0, 0)),
    "middle_and_block_edge": (256, (100, 64)),
    "window_end_and_short_of_it": (256, (128, 56)),
    "shorter_than_a_key_block": (32, (0, 0)),
}


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2 ** -5)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("mask", ["causal", "blocks_of_4"])
@pytest.mark.parametrize("place", sorted(PLACES))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_chunk_kernel_equals_xlas_ragged_form(shape, place, mask, dtype,
                                                  tol):
    """``chunk_attention`` (interpreted, blocks of 64 keys and 128 rows)
    against XLA's form over the window in the family's stored form: two
    sequences whose chunks end in different key blocks, the chunk at the
    window's start, in its middle, at its end, a window of less than one
    key block, under the causal ``reach`` (which is ``position + 1``) and
    the one that reads to the end of a block of 4."""
    w, offsets = PLACES[place]
    t = SHAPES[shape][0]
    if w < t:
        offsets, w = (0, 0), t  # the longest chunk needs its own length
    q, k, v = _inputs(3, shape, w, 2, dtype)
    reach = jnp.minimum(_reach(mask, offsets, t), w)
    scale = 0.11
    want = _xla(shape, q, k, v, reach, scale)
    got = K.chunk_attention(q, *_stored(shape, k, v), reach, scale,
                            interpret=True, keys_a_step=KEYS,
                            rows_a_step=128)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.abs(np.asarray(got, np.float32)
                  - np.asarray(want, np.float32)).max() < tol


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_a_last_chunk_with_pads_reads_like_xlas_form(shape):
    """A prompt's last chunk: the rows past the true length are pads whose
    ``reach`` is clipped at the context's end, as ``cached_attention``
    clips it; the kernel's rows equal XLA's, pads included."""
    t = SHAPES[shape][0]
    w = 256
    q, k, v = _inputs(5, shape, w, 1, jnp.float32)
    reach = jnp.minimum(_reach("causal", (w - t // 2,), t), w)
    want = _xla(shape, q, k, v, reach, 0.2)
    got = K.chunk_attention(q, *_stored(shape, k, v), reach, 0.2,
                            interpret=True, keys_a_step=KEYS)
    assert np.abs(np.asarray(got - want)).max() < 1e-5


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_a_key_block_past_the_chunks_end_changes_nothing(shape):
    """A key block that starts at or past a sequence's ``ends`` is never
    multiplied, nor copied: NaN in every such block leaves every output as
    it was. (The rest of the last live block is masked, not skipped: a NaN
    value there would still be NaN under a weight of nought.)"""
    t = SHAPES[shape][0]
    w, offsets = 256, (10, 64)
    q, k, v = _inputs(7, shape, w, 2, jnp.float32)
    reach = _reach("causal", offsets, t)
    ends = -(-np.asarray(reach).max(axis=1) // KEYS) * KEYS
    assert (ends < w).all()
    dead = jnp.arange(w)[None, :, None, None] >= ends[:, None, None, None]
    clean = K.chunk_attention(q, *_stored(shape, k, v), reach, 0.2,
                              interpret=True, keys_a_step=KEYS)
    poisoned = K.chunk_attention(
        q, *_stored(shape, jnp.where(dead, jnp.nan, k),
                    jnp.where(dead, jnp.nan, v)), reach, 0.2,
        interpret=True, keys_a_step=KEYS)
    assert np.isfinite(np.asarray(poisoned)).all()
    assert (np.asarray(poisoned) == np.asarray(clean)).all()


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_a_layer_of_a_stack_is_read_where_it_lies(shape):
    """``layer`` and ``window``: the kernel over layer 1 of a stack of
    three layers' planes ``[L, N, 256, ...]``, reading its first 128
    positions, equals the kernel over that layer's window sliced out (what
    ``cached_attention`` hands it: no slice is made for the operand), with
    the layer a traced integer as under ``fori_loop`` and a static one."""
    t = SHAPES[shape][0]
    layers = [_stored(shape, *_inputs(20 + i, shape, 256, 2, jnp.float32)[1:])
              for i in range(3)]
    q = _inputs(19, shape, 256, 2, jnp.float32)[0]
    keys = jnp.stack([k for k, _ in layers])
    values = jnp.stack([v for _, v in layers])
    reach = jnp.minimum(_reach("causal", (128 - t, 3), t), 128)
    want = K.chunk_attention(q, keys[1][:, :128], values[1][:, :128], reach,
                             0.2, interpret=True, keys_a_step=KEYS)
    for layer in (1, jnp.int32(1)):
        got = K.chunk_attention(q, keys, values, reach, 0.2, layer=layer,
                                window=128, interpret=True, keys_a_step=KEYS)
        assert (np.asarray(got) == np.asarray(want)).all()


def _sds(*shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


# what a program hands ``takes``: (q, keys, values, whether ``reach`` is the
# ragged [N, T], mesh), and the rule's answer on a TPU
PROGRAMS = {
    # the four cells' chunk programs, at a window of theirs
    "dsllm7b_chunk": ((1, 512, 32, 128), (1, 4096, 32, 128),
                      (1, 4096, 32, 128), True, None, True),
    "granite_chunk": ((1, 512, 32, 64), (1, 8192, 4, 128),
                      (1, 8192, 4, 128), True, None, True),
    "sdar_chunk": ((1, 512, 32, 128), (1, 4096, 4, 128),
                   (1, 4096, 4, 128), True, None, True),
    "mimo_chunk": ((1, 512, 64, 192), (1, 24576, 768), (1, 24576, 512),
                   True, None, True),
    # the hybrid's whole-prompt admission: the bucket's rows, ragged, by
    # shape: a bucket of 2048 or more is the kernel's, the cell's 512 is not
    "granite_admission_2048": ((4, 2048, 32, 64), (4, 2048, 4, 128),
                               (4, 2048, 4, 128), True, None, True),
    "granite_admission": ((1, 512, 32, 64), (1, 512, 4, 128),
                          (1, 512, 4, 128), True, None, False),
    # the dense cell's first two chunks, over its 1024 window
    "dsllm7b_chunk_1024": ((1, 512, 32, 128), (1, 1024, 32, 128),
                           (1, 1024, 32, 128), True, None, False),
    # a bucket admission of the dense and expert trunks: kv_len None
    "bucket_admission": ((4, 512, 32, 128), (4, 512, 32, 128),
                         (4, 512, 32, 128), False, None, False),
    # a decode step on the gather route, olmoe_chat's short windows
    "gather_decode_step": ((64, 1, 16, 128), (64, 1024, 16, 128),
                           (64, 1024, 16, 128), True, None, False),
    # a verify chunk of K + 1 = 5 rows
    "verify_chunk": ((16, 5, 32, 128), (16, 4096, 32, 128),
                     (16, 4096, 32, 128), True, None, False),
    # a head-sharded pool
    "tp_chunk": ((1, 512, 32, 128), (1, 4096, 32, 128),
                 (1, 4096, 32, 128), True, "mesh", False),
    # heads of 16 (the toys'), and rows that fill no tile of 128
    "toy_heads": ((1, 512, 2, 16), (1, 64, 2, 16), (1, 64, 2, 16), True,
                  None, False),
    "ragged_rows": ((1, 200, 32, 128), (1, 4096, 32, 128),
                    (1, 4096, 32, 128), True, None, False),
}


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_the_rule_reads_the_programs_shapes(monkeypatch, program):
    """``takes`` for every program that runs ``cached_attention``'s window
    attention or ``full_attention``: off a TPU none; on one the chunk
    programs of the four cells at windows of 2048 and more (and a
    whole-prompt admission of the hybrid that long), and neither a shorter
    window, a bucket admission, a decode step, a verify chunk, a
    head-sharded pool nor widths the kernel has no tile for."""
    q, k, v, ragged, mesh, want = PROGRAMS[program]
    args = (_sds(*q), _sds(*k), _sds(*v),
            _sds(*q[:2], dtype=jnp.int32) if ragged else None, mesh)
    assert K.takes(*args) is False  # the CPU these tests run on
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert K.takes(*args) is want
    kernel, attended = K.chunk_keys_attended(*args[:3], 1500, mesh) \
        if ragged else (False, k[1])
    assert kernel is want
    assert attended == (min(k[1], 2048) if want else k[1])


def test_int8_and_float32_windows_keep_xlas_code(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = _sds(1, 512, 32, 128)
    reach = _sds(1, 512, dtype=jnp.int32)
    for dtype in (jnp.int8, jnp.float32):
        kv = _sds(1, 4096, 32, 128, dtype=dtype)
        assert not K.takes(q, kv, kv, reach)
    kv = _sds(1, 4096, 32, 128)
    assert K.takes(q, kv, kv, reach)


def test_keys_attended_rounds_the_end_up_to_a_block():
    assert K.key_block(4096) == 1024 and K.key_block(1536) == 512
    assert K.key_block(96) == 96 and K.row_tile(2048) == 512
    assert K.row_tile(384) == 384 and K.row_tile(640) == 128
    assert K.keys_attended(1, 4096) == 1024
    assert K.keys_attended(1024, 4096) == 1024
    assert K.keys_attended(1025, 4096) == 2048
    assert K.keys_attended(4096, 4096) == 4096


# ---------------------------------------------------- through the program

CFG = ModelConfig(vocab=96, d_model=32, n_heads=2, n_layers=2, d_ff=64,
                  max_seq=512, head_dim=128, dtype=jnp.float32,
                  use_pallas=False)
PAGE, CHUNK = 16, 128


@pytest.fixture
def kernel_route(monkeypatch):
    """The kernel forced for the programs traced from here on, interpreted,
    in blocks of 64 keys (the kernel told so, the counters by the module's
    constant): the rule's shapes alone decide (the list of its traced
    calls' windows)."""
    calls = []

    def shapes_alone(q, keys, values, reach, mesh=None):
        return (reach is not None and mesh is None and len(reach.shape) == 2
                and K.fits(q.shape, keys.shape, values.shape,
                           jnp.dtype(keys.dtype).itemsize))

    def counted(q, keys, values, reach, scale, layer=None, window=None):
        calls.append(window or keys.shape[1])
        return chunk_attention(q, keys, values, reach, scale, layer=layer,
                               window=window, interpret=True,
                               keys_a_step=KEYS)

    chunk_attention = K.chunk_attention
    monkeypatch.setattr(K, "_KEYS", KEYS)
    monkeypatch.setattr(K, "takes", shapes_alone)
    monkeypatch.setattr(K, "chunk_attention", counted)
    return calls


def _engine():
    model = TransformerSlotModel(
        init_params(jax.random.key(2), CFG), CFG, kv_page=PAGE,
        kv_pool_blocks=70)
    model.read_windows = (256,)  # beside the context's 512
    return ServingEngine(
        serving=ServingConfig(
            slots=2, prefill_buckets=(16,), max_new_tokens=8, kv_page=PAGE,
            kv_pool_blocks=70, prefill_chunk=CHUNK),
        model=model)


def _served(eng, prompts):
    eng.start()
    try:
        out = [list(eng.submit(p, max_new_tokens=3).stream())
               for p in prompts]
        return out, eng.stats()
    finally:
        eng.stop()


def test_the_engine_counts_what_its_chunks_attend(kernel_route, monkeypatch):
    """Chunks of 128 of prompts of 150 and 300, the last chunk of each
    padded, over read windows of 256 and 512. With the kernel forced every
    chunk is its and attends up to its own end (blocks of 64), and the
    streams are XLA's token for token; on the CPU as it is
    ``chunk_attn_kernel`` is 0 and a chunk attends its whole window. The
    benchmark's ``chunk_attn_kernel_pct`` reads the two counters."""
    from vbench.metrics import chunk_attn_kernel_pct

    prompts = [np.random.default_rng(6).integers(1, 90, n).astype(np.int32)
               for n in (150, 300)]
    ends = [128, 256, 128, 256, 384]
    forced, stats = _served(_engine(), prompts)
    assert stats["loop_error"] is None
    assert set(kernel_route) == {256, 512}
    assert stats["chunk_attn_kernel"] == stats["chunk_attn_launches"] == 5
    assert stats["chunk_attn_expanded"] == 0
    assert stats["chunk_keys_live"] == sum(ends)
    assert stats["chunk_keys_attended"] == sum(ends)  # each a whole block
    run = types.SimpleNamespace(
        stats1=stats, counter=lambda name: stats[name])
    assert chunk_attn_kernel_pct.read(run) == 100.0
    traced = len(kernel_route)
    monkeypatch.undo()
    plain, stats = _served(_engine(), prompts)
    assert len(kernel_route) == traced and plain == forced
    assert stats["chunk_attn_launches"] == 5
    assert stats["chunk_attn_kernel"] == stats["chunk_attn_expanded"] == 0
    assert stats["chunk_keys_live"] == sum(ends)
    assert stats["chunk_keys_attended"] == 256 * 4 + 512
    run.stats1 = stats
    assert chunk_attn_kernel_pct.read(run) == 0.0
    run.stats1 = {"prefill_chunks": 4}  # a program without the counters
    assert chunk_attn_kernel_pct.read(run) is None
    run.stats1, run.counter = stats, lambda name: 0  # no chunk in the window
    assert chunk_attn_kernel_pct.read(run) is None


def test_a_chunk_program_routed_to_the_kernel_equals_xlas_route(kernel_route):
    """``chunk_window_attention``, the one place ``cached_attention`` asks
    the rule: the kernel's route and XLA's on the same chunk, and a decode
    step's single row, which the rule leaves to XLA's code."""
    q, k, v = _inputs(9, "dense_g1", 256, 1, jnp.float32)
    reach = _reach("causal", (60,), 128)
    got = transformer.chunk_window_attention(q, k, v, reach, 0.2)
    assert kernel_route == [256]
    want = causal_attention(q, k, v, kv_len=reach, scale=0.2)
    assert np.abs(np.asarray(got - want)).max() < 1e-5
    one = transformer.chunk_window_attention(
        q[:, :1], k, v, reach[:, :1], None)
    assert kernel_route == [256]
    assert np.abs(np.asarray(one - causal_attention(
        q[:, :1], k, v, kv_len=reach[:, :1]))).max() == 0


def test_the_piece_bench_runs_at_a_cut_down_shape(tmp_path):
    """``benchmarks/chunk_attn_bench.py --tiny`` (the table PERF.md's PR 45
    entry wired the four shapes in with) runs on the CPU, the kernel
    interpreted over each stored form, and holds it to XLA's form in
    bfloat16; its times there are no speeds."""
    import json
    import pathlib
    import subprocess
    import sys

    root = pathlib.Path(__file__).resolve().parent.parent
    out = tmp_path / "bench.json"
    done = subprocess.run(
        [sys.executable, str(root / "benchmarks/chunk_attn_bench.py"),
         "--tiny", "--out", str(out)], capture_output=True, text=True,
        timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    got = json.loads(out.read_text())
    assert got["device"]["platform"] == "cpu" and got["queries"] == 32
    assert [(r["shape"], r["chunk_end"]) for r in got["rows"]] == [
        (name, end) for name in ("dense", "hybrid", "sdar", "mimo")
        for end in (256, 128)]
    assert all(r["kernel_128x128"]["max_abs_diff"] < 0.02
               for r in got["rows"])
