"""The chunk kernel of the latent family (vtpu/ops/latent_chunk.py) under
the interpreter at toy widths, against the XLA code it replaces on a TPU
(``vtpu.ops.latent._expanded``); the route rule; and the engine's counters
of it (``chunk_attn_kernel``, ``chunk_keys_live``, ``chunk_keys_attended``)
with the benchmark's metric over them.

Tolerance: float32 on both sides, which differ by the order of their sums
(a running maximum a block against one over the whole row): outputs of
size 1 agree to 2e-6, and 2e-5 is held.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_latent_sparse import PAGE, TOY, _both_sides
from vbench.metrics import chunk_keys_live_pct
from vtpu.ops import latent as L
from vtpu.ops import latent_chunk as K
from vtpu.serving import ServingConfig, ServingEngine
from vtpu.serving.adapters import LatentSlotModel

BLOCK = 64  # the toy's key block; the cell's is 1024


def _inputs(seed, t, w, offsets, selects):
    """Two sequences' chunks of ``t`` queries at ``offsets`` in windows of
    ``w``; a selection of 24 a query over scores rounded to eighths, so
    that many positions tie at the threshold."""
    h, rank, dr, dn, dv = 4, 32, 8, 16, 16
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape, np.float32))

    positions = jnp.asarray(offsets, jnp.int32)[:, None] + jnp.arange(t)
    keep = None
    if selects:
        scores = jnp.asarray(
            np.round(rng.uniform(size=(2, t, w)) * 8), jnp.float32)
        keep = L.select_mask(scores, positions, 24)
    return dict(q_nope=normal(2, t, h, dn), q_pe=normal(2, t, h, dr),
                window=normal(2, w, rank + dr), keep=keep,
                positions=positions, w_uk=normal(h, dn, rank) / rank ** 0.5,
                w_uv=normal(h, rank, dv) / rank ** 0.5, scale=0.3)


@pytest.mark.parametrize("selects", [True, False],
                         ids=["selection", "causal"])
@pytest.mark.parametrize("t", [40, 64])  # the toy's 256 and 512: both expand
@pytest.mark.parametrize("w,ends", [
    (256, (0, 64)),      # the chunk at offset 0, and one block further
    (256, (100, 150)),   # its end in the middle of a key block
    (256, (256, 192)),   # at the window's end, and a block short of it
    (64, (64, 64)),      # a window of one block
], ids=["offset0", "mid_block", "window_end", "one_block"])
def test_the_chunk_kernel_equals_the_expanded_form(
        monkeypatch, selects, t, w, ends):
    """``chunk_attention`` (interpreted, blocks of 64 keys, two heads a
    grid step) against ``_expanded`` on the same operands, each sequence
    with an end of its own. The key blocks wholly past a sequence's last
    position hold nan on the kernel's side alone: it never reads them."""
    monkeypatch.setattr(K, "_KEYS", BLOCK)
    monkeypatch.setattr(K, "_HEADS", 2)
    offsets = [max(0, e - t) for e in ends]
    kw = _inputs(1000 * w + 10 * t + ends[0], t, w, offsets, selects)
    assert L.expands_window(t, 32, 16, 16)
    keep = kw["keep"]
    if keep is None:
        keep = jnp.arange(w) <= kw["positions"][..., None]
    else:  # ties at the threshold: more score the k-th value than fit
        assert int(keep.sum(-1).max()) == 24
    want = L._expanded(kw["q_nope"], kw["q_pe"], kw["window"], keep,
                       kw["w_uk"], kw["w_uv"], kw["scale"])
    last = np.asarray(kw["positions"][:, -1]) + 1
    live = -(-last // BLOCK) * BLOCK
    assert K.key_block(w) == BLOCK
    assert [K.keys_attended(int(e), w) for e in last] == live.tolist()
    poisoned = jnp.where(jnp.arange(w)[None, :, None] >= live[:, None, None],
                         jnp.nan, kw["window"])
    got = K.chunk_attention(**{**kw, "window": poisoned}, interpret=True)
    assert got.shape == want.shape == (2, t, 4, 16)
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(got - want)).max() < 2e-5


@pytest.mark.parametrize("w,keys,block", [
    (4096, 1024, 1024), (24576, 1024, 1024), (24576, 2048, 2048),
    (256, 1024, 256), (3072, 2048, 1024), (192, 128, 192)])
def test_a_key_block_divides_its_window(monkeypatch, w, keys, block):
    monkeypatch.setattr(K, "_KEYS", keys)
    assert K.key_block(w) == block
    assert K.keys_attended(1, w) == block
    assert K.keys_attended(w - 1, w) == K.keys_attended(w, w) == w


def test_the_kernel_route_is_the_expanding_shapes_on_a_tpu(monkeypatch):
    """Off a TPU nothing takes the kernel; on one (the backend's name
    patched) the shapes that expand do, and a chunk's program multiplies
    its end rounded up to a key block of its read window."""
    assert jax.default_backend() == "cpu"
    assert not L.attends_in_kernel(512, 512, 128, 128)
    assert L.chunk_keys_attended(512, 512, 128, 128, 9000, 16384) == (
        False, 16384)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert L.attends_in_kernel(512, 512, 128, 128)
    assert L.attends_in_kernel(256, 512, 128, 128)
    assert not L.attends_in_kernel(170, 512, 128, 128)
    assert not L.attends_in_kernel(1, 512, 128, 128)
    assert L.chunk_keys_attended(512, 512, 128, 128, 9000, 16384) == (
        True, 9216)
    assert L.chunk_keys_attended(170, 512, 128, 128, 9000, 16384) == (
        False, 16384)


@pytest.fixture
def kernel_route(monkeypatch):
    """The kernel forced for the programs traced from here on, interpreted,
    in blocks of 32 keys and two heads a grid step (the list of its traced
    calls' windows)."""
    calls = []

    def counted(*a, **kw):
        calls.append(a[2].shape[1])
        return K.chunk_attention(*a, **kw, interpret=True)

    monkeypatch.setattr(K, "_KEYS", 32)
    monkeypatch.setattr(K, "_HEADS", 2)
    monkeypatch.setattr(L, "attends_in_kernel", L.expands_window)
    monkeypatch.setattr(L, "chunk_attention", counted)
    return calls


@pytest.mark.parametrize("topk", [12, None], ids=["selection", "causal"])
def test_a_chunk_routed_to_the_kernel_equals_xlas_route(
        monkeypatch, kernel_route, topk):
    """``sparse_latent_attention`` for a chunk of 40 queries that ends at
    position 43 of a window of 64, through the pool's pages: the kernel's
    route against XLA's, with the indexer's selection (the same mask comes
    back) and with none (no mask is made)."""
    from test_latent_sparse import _layer_inputs
    kw = _layer_inputs(np.random.default_rng(11), 2, 40, 40, False)
    kw["positions"] = kw["positions"] - 20
    if topk is None:
        kw.update(topk=None, ik=None, q_idx=None, w_idx=None)
    got, chosen = L.sparse_latent_attention(**kw)
    assert kernel_route == [64]
    monkeypatch.setattr(L, "attends_in_kernel", lambda *a: False)
    want, mask = L.sparse_latent_attention(**kw)
    assert kernel_route == [64]
    assert np.abs(np.asarray(got - want)).max() < 2e-5
    if topk is None:
        assert chosen is None and mask is None
    else:
        assert (np.asarray(chosen) == np.asarray(mask)).all()


def _served(eng, prompts):
    eng.start()
    try:
        out = [list(eng.submit(p, max_new_tokens=3).stream())
               for p in prompts]
        return out, eng.stats()
    finally:
        eng.stop()


def test_the_engine_counts_what_its_chunks_attend(kernel_route, monkeypatch):
    """Chunks of 64 (the toy's expanding length that divides its context)
    of prompts of 70 and 100, the second chunk of each padded, all over one
    read window of 128. With the kernel forced every chunk is its and
    attends up to its own end (blocks of 32), and the streams are XLA's
    token for token; on the CPU as it is ``chunk_attn_kernel`` is 0 and a
    chunk attends the whole window. ``chunk_keys_live_pct`` reads the two
    counters."""
    mc, params = _both_sides(TOY)
    prompts = [np.random.default_rng(6).integers(1, 90, n).astype(np.int32)
               for n in (70, 100)]

    def engine():
        return ServingEngine(
            serving=ServingConfig(
                slots=3, prefill_buckets=(16,), max_new_tokens=8,
                kv_page=PAGE, kv_pool_blocks=40, prefill_chunk=64),
            model=LatentSlotModel(params, mc, kv_page=PAGE,
                                  kv_pool_blocks=40, read_windows=(128,)))

    forced, stats = _served(engine(), prompts)
    assert set(kernel_route) == {128} and stats["loop_error"] is None
    assert stats["chunk_attn_kernel"] == stats["chunk_attn_launches"] == 4
    assert stats["chunk_keys_live"] == 64 + 128 + 64 + 128
    assert stats["chunk_keys_attended"] == 64 + 128 + 64 + 128
    traced = len(kernel_route)
    monkeypatch.undo()
    plain, stats = _served(engine(), prompts)
    assert len(kernel_route) == traced and plain == forced
    assert stats["chunk_attn_launches"] == stats["chunk_attn_expanded"] == 4
    assert stats["chunk_attn_kernel"] == 0
    assert stats["chunk_keys_live"] == 384
    assert stats["chunk_keys_attended"] == 4 * 128
    run = types.SimpleNamespace(
        stats1=stats, counter=lambda name: stats[name] // 2)
    assert chunk_keys_live_pct.read(run) == 75.0
    run.stats1 = {"prefill_chunks": 4}  # a program without the counters
    assert chunk_keys_live_pct.read(run) is None
    run.stats1, run.counter = stats, lambda name: 0  # no chunk in the window
    assert chunk_keys_live_pct.read(run) is None


# sha256 (16 hex digits) of the lowered text of the toy programs at the
# commit before the kernel (d46bc38), on the CPU
OFF_CHIP = {
    ("selects", "chunk64x128"): "6a00750c0ed3368b",
    ("selects", "chunk16x64"): "e1e5b2cb83ed46a7",
    ("selects", "rows2x48"): "7cf1615187f30a73",
    ("selects", "step64"): "31162848c8bafc8e",
    ("dense", "chunk64x128"): "2ed703baadaca8a0",
    ("dense", "chunk16x64"): "023c7abd21eea787",
    ("dense", "rows2x48"): "f0b7988b1ba09be9",
    ("dense", "step64"): "a03643b5f7d08586",
}


@pytest.mark.parametrize("family,program", sorted(OFF_CHIP))
def test_off_the_chip_the_familys_programs_keep_their_text(family, program):
    """``vtpu/models/latent.py``'s entry points lowered on the CPU at toy
    sizes, with the indexer and without (an expanding chunk of 64 over a
    window of 128, an absorbed one of 16, a whole-prompt admission, a
    decode step): the text is the parent's to the byte, so every CPU run
    and test of the family computes what it did (ISSUE 38)."""
    import hashlib

    from vtpu.models import latent as M

    mc, params = _both_sides(
        TOY if family == "selects" else {**TOY, "index_n_heads": 0})
    state = M.init_latent_cache(mc, 3, PAGE, 40)
    i32 = jnp.int32
    if program.startswith("chunk"):
        c, window = map(int, program[5:].split("x"))
        lowered = jax.jit(
            M.latent_prefill_chunk, static_argnums=(1, 7)).lower(
            params, mc, state, jnp.zeros((1, c), i32), i32(0), i32(0),
            i32(c), window, jnp.zeros((window // PAGE,), i32))
    elif program == "rows2x48":
        lowered = jax.jit(M.latent_prefill_rows, static_argnums=(1,)).lower(
            params, mc, state, jnp.zeros((2, 48), i32), jnp.zeros((2,), i32),
            jnp.ones((2,), i32))
    else:
        lowered = jax.jit(
            M.latent_decode_step, static_argnums=(1, 5)).lower(
            params, mc, state, jnp.zeros((3,), i32), jnp.ones((3,), bool), 64)
    digest = hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]
    assert digest == OFF_CHIP[family, program]
