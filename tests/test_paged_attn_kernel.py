"""Fused paged-attention decode kernel (ISSUE 10 tentpole).

Fast (non-slow) tier; Pallas runs in interpret mode under the conftest's
JAX_PLATFORMS=cpu. The contract under test, layered like the change:

- function level: ``paged_decode_attention{,_int8kv}`` (the table-walking
  kernel over the WHOLE pool, layer via scalar prefetch) equals
  ``paged_causal_attention{,_int8kv}`` (gather-then-dense) on the same
  operands — exact and int8, ragged [B, T] and flat [B] kv_len, null-block
  padding rows, COW-boundary tables, and a traced (fori-style) layer index;
- routing: ``paged_attn_route`` honors forced overrides everywhere and on
  auto keeps the kernel OFF non-TPU backends and below the measured window
  floor (per-shape routing never selects the kernel where it measured
  slower);
- compiled evidence: the kernel-route decode step's HLO carries ZERO
  pool-window-sized gathers (the gather route carries one per value plane
  per layer), and under a tp=2 mesh the kernel route's per-kind collective
  counts equal the gather route's exactly (the PR-5 audit style) — the
  shard_map wrapper walks the head shard chip-locally;
- engine level: kernel-route streams are token-equal to gather-route and
  dense streams for the exact, int8, and MoE families, single-chip and
  tp=2, with the route counters and the one-fetch-per-tick contract
  holding; ``batched_spec_step`` runs draft/verify table-aware on the pool
  (spec ticks fire on the kernel route and the stream never changes);
- config: forcing a route without a paged pool raises, and an
  engine/adapter route mismatch is rejected at construction.

Engine shapes are deliberately minimal (1 layer, one KV bucket, 4-token
streams): every kernel-route executable compiles an interpreted pallas
trunk on this rig, so the suite buys its coverage per compile, not per
token — the long-window behavior lives in the function-level cases and
the bench's --attn-kernel arm.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vtpu.models import ModelConfig, init_params
from vtpu.ops.attention import (
    paged_causal_attention,
    paged_causal_attention_int8kv,
)
from vtpu.ops.decode_attn import (
    PAGED_ATTN_MIN_WINDOW,
    PAGED_ATTN_MIN_WINDOW_INT8,
    count_pool_gathers,
    paged_attn_route,
    paged_decode_attention,
    paged_decode_attention_int8kv,
)
from vtpu.parallel.mesh import make_axis_mesh
from vtpu.serving import ServingConfig, ServingEngine
from vtpu.serving.adapters import TransformerSlotModel

# single layer + max_seq == the one prefill bucket -> exactly ONE decode
# executable (and one spec executable where used) per engine, so each
# kernel-route engine pays one interpreted-pallas compile
CFG = ModelConfig(
    vocab=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
    max_seq=16, head_dim=16, dtype=jnp.float32, use_pallas=False,
)
CFG_INT8 = ModelConfig(
    vocab=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
    max_seq=16, head_dim=16, dtype=jnp.float32, use_pallas=False,
    kv_int8=True,
)
PAGE = 8
needs_devices = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs 2 virtual devices")


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.key(0), CFG)


@pytest.fixture(scope="module")
def params_int8():
    return init_params(jax.random.key(0), CFG_INT8)


def _prompt(seed, n, vocab=CFG.vocab):
    return [int(t) for t in jax.random.randint(
        jax.random.key(seed), (n,), 1, vocab, jnp.int32)]


def _pool(rng, n_layers=2, nb=9, page=8, h=2, dh=16):
    k = jnp.asarray(rng.randn(n_layers, nb, page, h, dh), jnp.float32)
    v = jnp.asarray(rng.randn(n_layers, nb, page, h, dh), jnp.float32)
    return k, v


def _int8_pool(rng, n_layers=2, nb=9, page=8, h=2, dh=16):
    kq = jnp.asarray(rng.randint(-127, 128, (n_layers, nb, page, h, dh)),
                     jnp.int8)
    vq = jnp.asarray(rng.randint(-127, 128, (n_layers, nb, page, h, dh)),
                     jnp.int8)
    ks = jnp.asarray(
        rng.rand(n_layers, nb, page, h).astype(np.float32) * 0.02 + 1e-3)
    vs = jnp.asarray(
        rng.rand(n_layers, nb, page, h).astype(np.float32) * 0.02 + 1e-3)
    return kq, ks, vq, vs


# Every padded row maps the reserved null block 0 past its live pages —
# the engine's table contract the kernel must honor (masked, deduped).
TABLE = jnp.asarray([[1, 2, 0, 0], [3, 4, 5, 0], [6, 7, 8, 1]], jnp.int32)
LENS = jnp.asarray([[9, 10], [20, 21], [31, 32]], jnp.int32)


# ------------------------------------------------- function-level equality


def test_paged_kernel_matches_gather_exact():
    """The tentpole equality: walking the table in place == gather-then-
    dense, per layer, ragged [B, T] lens, null-padded table rows."""
    rng = np.random.RandomState(0)
    kp, vp = _pool(rng)
    q = jnp.asarray(rng.randn(3, 2, 2, 16), jnp.float32)
    for l in range(kp.shape[0]):
        want = paged_causal_attention(q, kp[l], vp[l], TABLE, kv_len=LENS)
        got = paged_decode_attention(q, kp, vp, TABLE, LENS, layer=l,
                                     interpret=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5)


def test_paged_kernel_flat_lens_t1():
    """[B] kv_len with T=1 — the plain decode tick's mask form."""
    rng = np.random.RandomState(1)
    kp, vp = _pool(rng)
    q = jnp.asarray(rng.randn(3, 1, 2, 16), jnp.float32)
    lens = jnp.asarray([5, 17, 32], jnp.int32)
    want = paged_causal_attention(q, kp[0], vp[0], TABLE, kv_len=lens)
    got = paged_decode_attention(q, kp, vp, TABLE, lens, layer=0,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    with pytest.raises(ValueError, match="ragged"):
        paged_decode_attention(
            jnp.zeros((3, 2, 2, 16), jnp.float32), kp, vp, TABLE, lens,
            interpret=True)


def test_paged_kernel_int8_matches_gather():
    """int8-native: int8 pools stream as bytes, scales post-matmul exactly
    as the gather path's causal_attention_int8kv semantics."""
    rng = np.random.RandomState(2)
    kq, ks, vq, vs = _int8_pool(rng)
    q = jnp.asarray(rng.randn(3, 2, 2, 16), jnp.float32)
    for l in range(kq.shape[0]):
        want = paged_causal_attention_int8kv(
            q, kq[l], ks[l], vq[l], vs[l], TABLE, kv_len=LENS)
        got = paged_decode_attention_int8kv(
            q, kq, ks, vq, vs, TABLE, LENS, layer=l, interpret=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5)


def test_paged_kernel_null_block_garbage_never_observable():
    """Fill the reserved null block 0 with large garbage: a short slot whose
    window is mostly null-padded must produce EXACTLY the output of the
    same window with block 0 zeroed — the kv_len mask, not the data, is
    what keeps padding reads invisible (the engine's contract)."""
    rng = np.random.RandomState(3)
    kp, vp = _pool(rng)
    kp = kp.at[:, 0].set(1e3)  # poison the null block
    vp = vp.at[:, 0].set(-1e3)
    q = jnp.asarray(rng.randn(2, 1, 2, 16), jnp.float32)
    table = jnp.asarray([[2, 0, 0, 0], [7, 3, 0, 0]], jnp.int32)
    lens = jnp.asarray([3, 11], jnp.int32)
    got = paged_decode_attention(q, kp, vp, table, lens, layer=1,
                                 interpret=True)
    clean_k = kp.at[:, 0].set(0.0)
    clean_v = vp.at[:, 0].set(0.0)
    want = paged_decode_attention(q, clean_k, clean_v, table, lens, layer=1,
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    gather = paged_causal_attention(q, kp[1], vp[1], table, kv_len=lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(gather),
                               atol=2e-5)


def test_paged_kernel_cow_boundary_tables():
    """COW-shaped tables: two slots share their leading (prefix) blocks and
    diverge only at the boundary block — the revisit-friendly pattern
    prefix sharing produces. Each row must equal its own gathered window;
    the shared blocks are read-only so neither row perturbs the other."""
    rng = np.random.RandomState(4)
    kp, vp = _pool(rng)
    q = jnp.asarray(rng.randn(2, 1, 2, 16), jnp.float32)
    # rows share blocks 1,2 (the full prefix pages); boundary differs: 3 vs 4
    table = jnp.asarray([[1, 2, 3, 0], [1, 2, 4, 0]], jnp.int32)
    lens = jnp.asarray([21, 23], jnp.int32)
    got = paged_decode_attention(q, kp, vp, table, lens, layer=0,
                                 interpret=True)
    want = paged_causal_attention(q, kp[0], vp[0], table, kv_len=lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_paged_kernel_traced_layer_index():
    """A fori_loop-style TRACED layer index selects the right plane (the
    scalar-prefetch operand carries it; one executable serves every
    layer)."""
    rng = np.random.RandomState(5)
    kp, vp = _pool(rng)
    q = jnp.asarray(rng.randn(3, 1, 2, 16), jnp.float32)
    lens = jnp.asarray([9, 17, 30], jnp.int32)
    f = jax.jit(lambda l: paged_decode_attention(
        q, kp, vp, TABLE, lens, layer=l, interpret=True))
    for l in range(kp.shape[0]):
        want = paged_causal_attention(q, kp[l], vp[l], TABLE, kv_len=lens)
        np.testing.assert_allclose(np.asarray(f(l)), np.asarray(want),
                                   atol=2e-5)


def test_paged_kernel_rejects_layer_slice():
    """A per-layer pool slice is exactly the materialization the kernel
    exists to kill — rejected loudly, never silently accepted."""
    rng = np.random.RandomState(6)
    kp, vp = _pool(rng)
    q = jnp.zeros((3, 1, 2, 16), jnp.float32)
    with pytest.raises(ValueError, match="WHOLE pool"):
        paged_decode_attention(q, kp[0], vp[0], TABLE,
                               jnp.asarray([1, 1, 1], jnp.int32),
                               interpret=True)


@pytest.mark.parametrize("heads,quant", [(6, False), (12, False),
                                         (2, True), (12, True)])
def test_paged_kernel_takes_heads_its_copies_cannot_cut(heads, quant):
    """Mosaic cuts a (page, H, Dh) block out of a plane only at whole tiles
    of heads, and no block at all out of the int8 scale pools: such a call
    takes the window walk (a BlockSpec's window a page, dead steps
    skipped), never an error, and equals the gather route (that these
    counts compile: tests/test_tpu_compile.py)."""
    from vtpu.ops import decode_attn
    assert quant or not decode_attn._copies_cut(heads, 4)
    rng = np.random.RandomState(heads)
    q = jnp.asarray(rng.randn(3, 2, heads, 16), jnp.float32)
    if quant:
        kq, ks, vq, vs = _int8_pool(rng, h=heads)
        got = paged_decode_attention_int8kv(
            q, kq, ks, vq, vs, TABLE, LENS, layer=1, interpret=True)
        want = paged_causal_attention_int8kv(
            q, kq[1], ks[1], vq[1], vs[1], TABLE, kv_len=LENS)
    else:
        kp, vp = _pool(rng, h=heads)
        got = paged_decode_attention(q, kp, vp, TABLE, LENS, layer=1,
                                     interpret=True)
        want = paged_causal_attention(q, kp[1], vp[1], TABLE, kv_len=LENS)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# ------------------------------------------- the walk: live pages, in groups
# ISSUE 29: a slot costs cdiv(kv_len, page) pages, copied a group at a time.
# One table serves every case: a row that ends inside a group, a slot with
# nothing live (kv_len 1, all null) BETWEEN live rows (the copy of a slot's
# first group is started by the slot before it), a row that ends on a page's
# edge, one on the last page's last token, one a token into a page. Every
# entry past a live row's pages names a block full of NaN.

WALK_NAN_BLOCK = 29


def _walk_case(wp: int, t: int):
    """(table [5, wp], kv_len [5, t]) over a pool of 30 blocks of 8."""
    last = np.asarray([2 * PAGE + 3, 1, 2 * PAGE, wp * PAGE, PAGE + 1])
    pages = -(-last // PAGE)
    table = np.full((5, wp), WALK_NAN_BLOCK, np.int32)
    table[1] = 0  # the idle slot maps the null block throughout
    ids = iter(range(1, WALK_NAN_BLOCK))
    for row in (0, 2, 3, 4):
        table[row, :pages[row]] = [next(ids) for _ in range(pages[row])]
    # ragged: query i of a row reads i tokens fewer than the last (never 0)
    lens = np.maximum(last[:, None] - np.arange(t)[::-1][None], 1)
    return jnp.asarray(table), jnp.asarray(lens, jnp.int32), pages


# (tokens a group, window pages): the window smaller than a group (the
# group is clamped to it), equal to it, no multiple of it, and three groups
WALK_GROUPS = [(64, 4), (32, 4), (24, 4), (16, 6)]
WALK_IDS = [f"group{g // PAGE}-wp{w}" for g, w in WALK_GROUPS]


@pytest.mark.parametrize("quant", [False, True], ids=["exact", "int8"])
@pytest.mark.parametrize("t", [1, 4], ids=["t1", "t4ragged"])
@pytest.mark.parametrize("group_tokens,wp", WALK_GROUPS, ids=WALK_IDS)
def test_paged_kernel_walks_live_pages(monkeypatch, group_tokens, wp, t,
                                       quant):
    """Equal to the gather route wherever a row ends, and a block named
    past a row's last page is never read: it holds NaN, which the gather
    route itself could not mask (0 x NaN), so the reference runs on a
    pool with that block zeroed."""
    from vtpu.ops import decode_attn
    monkeypatch.setattr(decode_attn, "_GROUP_TOKENS", group_tokens)
    rng = np.random.RandomState(29)
    table, lens, _ = _walk_case(wp, t)
    q = jnp.asarray(rng.randn(5, t, 2, 16), jnp.float32)
    if quant:
        kq, ks, vq, vs = _int8_pool(rng, nb=30)
        ks_nan = ks.at[:, WALK_NAN_BLOCK].set(jnp.nan)
        vs_nan = vs.at[:, WALK_NAN_BLOCK].set(jnp.nan)
        got = paged_decode_attention_int8kv(
            q, kq, ks_nan, vq, vs_nan, table, lens, layer=1, interpret=True)
        want = paged_causal_attention_int8kv(
            q, kq[1], ks[1], vq[1], vs[1], table, kv_len=lens)
    else:
        kp, vp = _pool(rng, nb=30)
        got = paged_decode_attention(
            q, kp.at[:, WALK_NAN_BLOCK].set(jnp.nan),
            vp.at[:, WALK_NAN_BLOCK].set(jnp.nan), table, lens, layer=1,
            interpret=True)
        want = paged_causal_attention(
            q, kp.at[:, WALK_NAN_BLOCK].set(0.0)[1],
            vp.at[:, WALK_NAN_BLOCK].set(0.0)[1], table, kv_len=lens)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("group_tokens,wp", WALK_GROUPS, ids=WALK_IDS)
def test_paged_kernel_copies_a_rows_live_pages_only(monkeypatch,
                                                    group_tokens, wp):
    """The mechanism, counted under the interpreter: the kernel starts one
    copy a plane for each of a row's cdiv(kv_len, page) pages (one for a
    slot with nothing live), not one for each page of the window."""
    from vtpu.ops import decode_attn
    monkeypatch.setattr(decode_attn, "_GROUP_TOKENS", group_tokens)
    started = []
    real_copy = decode_attn.pltpu.make_async_copy

    class Counted:
        def __init__(self, *args):
            self.copy = real_copy(*args)

        def start(self):
            jax.debug.callback(lambda: started.append(1))
            self.copy.start()

        def wait(self):
            self.copy.wait()

    monkeypatch.setattr(decode_attn.pltpu, "make_async_copy", Counted)
    rng = np.random.RandomState(30)
    kp, vp = _pool(rng, nb=30)
    table, lens, pages = _walk_case(wp, 1)
    q = jnp.asarray(rng.randn(5, 1, 2, 16), jnp.float32)
    out = paged_decode_attention(q, kp, vp, table, lens, layer=0,
                                 interpret=True)
    jax.block_until_ready(out)
    jax.effects_barrier()
    assert len(started) == 2 * int(pages.sum())  # K and V
    assert int(pages.sum()) < 5 * wp


@pytest.mark.parametrize("t", [1, 4], ids=["t1", "t4ragged"])
def test_paged_window_walk_names_a_rows_live_blocks_only(t):
    """The window walk's mechanism, read off its index map: over a row's
    grid steps it names the blocks of the row's cdiv(kv_len, page) pages
    and then the last of them again (no new copy), never an entry past
    them."""
    from vtpu.ops import decode_attn
    wp = 6
    table, lens, pages = _walk_case(wp, t)
    table, lens = np.asarray(table), np.asarray(lens)
    for row in range(5):
        named = [int(decode_attn._window_block(
            row, j, np.asarray([1]), table, lens, t=t, page=PAGE)[1])
            for j in range(wp)]
        live = list(table[row, :pages[row]])
        assert named == live + [live[-1]] * (wp - pages[row])
        assert WALK_NAN_BLOCK not in named


# ----------------------------------------------------------- route resolver


def test_paged_attn_route_resolution():
    """Forced overrides win everywhere; auto keeps the kernel off non-TPU
    backends and off every shape the routing basis measured slower — the
    'never selects the kernel where it measured slower' half of the
    acceptance bar, as a static property of the resolver. The basis
    (DECODE_ATTN_r05.json) wins only at bf16 T=1 from window 1024 and int8
    T=1 from 2048; every T=4 cell lost."""
    assert paged_attn_route("kernel", 8) == "kernel"
    assert paged_attn_route("kernel", 8, t=5, quant=True) == "kernel"
    assert paged_attn_route("gather", 1 << 20, backend="tpu") == "gather"
    # auto off-TPU: interpreted pallas is a correctness rig, never a win
    assert paged_attn_route(None, 1 << 20, backend="cpu") == "gather"
    # auto on TPU: the measured window floor routes per shape
    assert paged_attn_route(None, PAGED_ATTN_MIN_WINDOW,
                            backend="tpu") == "kernel"
    assert paged_attn_route(None, PAGED_ATTN_MIN_WINDOW - 1,
                            backend="tpu") == "gather"
    # int8 carries its own (higher) measured floor: 1024 lost (0.65-0.90x)
    assert paged_attn_route(None, PAGED_ATTN_MIN_WINDOW,
                            backend="tpu", quant=True) == "gather"
    assert paged_attn_route(None, PAGED_ATTN_MIN_WINDOW_INT8,
                            backend="tpu", quant=True) == "kernel"
    # verify chunks (T > 1) never auto-route to the kernel: every measured
    # T=4 cell lost (0.28-0.59x)
    assert paged_attn_route(None, 1 << 20, backend="tpu", t=4) == "gather"
    with pytest.raises(ValueError, match="paged_attn"):
        paged_attn_route("pallas", 1024)


# ------------------------------------------- compiled-HLO gather-free audit


def _decode_hlo(params, cfg, kv_page, paged_attn, mesh=None, slots=2,
                bucket=16):
    model = TransformerSlotModel(params, cfg, mesh=mesh, kv_page=kv_page,
                                 paged_attn=paged_attn)
    state = model.init_state(slots)
    fn = jax.jit(model.decode_step, static_argnames=("kv_bucket", "unroll"))
    return fn.lower(
        model.params, state, jnp.zeros((slots,), jnp.int32),
        jnp.ones((slots,), bool), bucket, unroll=True,
    ).compile().as_text()


def test_kernel_route_hlo_is_gather_free(params, params_int8):
    """The tentpole's compiled evidence: at the pool-window gather size
    (B * window * H * Dh elements per value plane) the kernel route's
    decode step carries ZERO gathers while the gather route carries one
    per plane per layer (2L exact, 4L int8) — the O(window)
    materialization is gone from the executable, not just the source."""
    window = 16
    min_elems = 2 * window * CFG.n_heads * CFG.head_dim
    hlo_g = _decode_hlo(params, CFG, PAGE, "gather", bucket=window)
    hlo_k = _decode_hlo(params, CFG, PAGE, "kernel", bucket=window)
    assert count_pool_gathers(hlo_g, min_elems) == 2 * CFG.n_layers
    assert count_pool_gathers(hlo_k, min_elems) == 0
    # int8: four gathered planes (values + scales) all disappear; the
    # scale planes are H-wide so the value-plane threshold covers the audit
    hlo_g8 = _decode_hlo(params_int8, CFG_INT8, PAGE, "gather",
                         bucket=window)
    hlo_k8 = _decode_hlo(params_int8, CFG_INT8, PAGE, "kernel",
                         bucket=window)
    assert count_pool_gathers(hlo_g8, min_elems) >= 2 * CFG.n_layers
    assert count_pool_gathers(hlo_k8, min_elems) == 0


# -------------------------------------------------- tp=2: shard_map parity


@needs_devices
def test_paged_kernel_tp2_matches_single_chip():
    """The shard_map wrapper: under a ('tp',) mesh each chip walks its own
    head shard — the result equals the single-chip kernel and the gather
    oracle, exact and int8."""
    mesh = make_axis_mesh("tp", 2)
    rng = np.random.RandomState(7)
    kp, vp = _pool(rng)
    q = jnp.asarray(rng.randn(3, 2, 2, 16), jnp.float32)
    want = paged_causal_attention(q, kp[0], vp[0], TABLE, kv_len=LENS)
    got = jax.jit(lambda: paged_decode_attention(
        q, kp, vp, TABLE, LENS, layer=0, mesh=mesh, interpret=True))()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    kq, ks, vq, vs = _int8_pool(rng)
    want8 = paged_causal_attention_int8kv(
        q, kq[1], ks[1], vq[1], vs[1], TABLE, kv_len=LENS)
    got8 = jax.jit(lambda: paged_decode_attention_int8kv(
        q, kq, ks, vq, vs, TABLE, LENS, layer=1, mesh=mesh,
        interpret=True))()
    np.testing.assert_allclose(np.asarray(got8), np.asarray(want8),
                               atol=2e-5)


_COLLECTIVE_KINDS = ("all-reduce", "all-gather", "all-to-all",
                     "collective-permute", "reduce-scatter")


def _collective_counts(hlo: str) -> dict:
    return {k: len(re.findall(rf"\b{k}\b", hlo)) for k in _COLLECTIVE_KINDS}


@needs_devices
def test_kernel_route_collective_parity_tp2(params_int8):
    """PR-5 audit style: the kernel route introduces NO collectives beyond
    the gather route's (which itself matched dense-TP exactly) — per-kind
    compiled-HLO counts are equal under tp=2. int8 pools carry the most
    planes (values + scales), so they are the strongest single exhibit."""
    mesh = make_axis_mesh("tp", 2)
    assert (_collective_counts(_decode_hlo(params_int8, CFG_INT8, PAGE,
                                           "kernel", mesh=mesh))
            == _collective_counts(_decode_hlo(params_int8, CFG_INT8, PAGE,
                                              "gather", mesh=mesh)))


# --------------------------------------------------- engine token equality


def _serving(**kw):
    # one bucket == max_seq: a single decode executable per engine (each
    # kernel-route executable is an interpreted-pallas compile on this rig)
    base = dict(slots=2, prefill_buckets=(16,), max_new_tokens=4,
                kv_page=PAGE)
    base.update(kw)
    return ServingConfig(**base)


def _run(params, serving, prompts, mesh=None, cfg=CFG, steps=4):
    eng = ServingEngine(params, cfg, serving, mesh=mesh)
    eng.start()
    try:
        reqs = [eng.submit(p, max_new_tokens=steps) for p in prompts]
        streams = [list(r.stream()) for r in reqs]
        stats = eng.stats()
    finally:
        eng.stop()
    return streams, stats


def test_engine_streams_kernel_equals_gather_and_dense(params):
    """Acceptance: kernel-route streams == gather-route streams == dense
    streams; route counters attribute every tick (and on this CPU backend
    the AUTO route counts gather everywhere — per-shape routing never
    selects the kernel where it measured slower); the one-fetch-per-tick
    contract holds on both routes."""
    prompts = [_prompt(1, 5), _prompt(2, 7), _prompt(3, 3)]
    dense, ds = _run(params, _serving(kv_page=None), prompts)
    auto, as_ = _run(params, _serving(), prompts)
    gather, gs = _run(params, _serving(paged_attn="gather"), prompts)
    kernel, ks = _run(params, _serving(paged_attn="kernel"), prompts)
    assert kernel == gather == auto == dense
    ticks = ks["decode_ticks"] + ks["spec_ticks"]
    assert ks["paged_attn_kernel_ticks"] == ticks > 0
    assert ks["paged_attn_gather_ticks"] == 0
    assert gs["paged_attn_gather_ticks"] > 0
    assert gs["paged_attn_kernel_ticks"] == 0
    # auto on CPU: interpreted pallas never routes
    assert as_["paged_attn_kernel_ticks"] == 0
    assert as_["paged_attn_gather_ticks"] == \
        as_["decode_ticks"] + as_["spec_ticks"] > 0
    # dense engines route nothing (the counters stay flat, not missing)
    assert ds["paged_attn_kernel_ticks"] == 0
    assert ds["paged_attn_gather_ticks"] == 0
    assert ks["device_gets_per_tick"] == 1.0
    assert gs["device_gets_per_tick"] == 1.0
    assert ks["kv_pool_free"] == ks["kv_pool_blocks"]


def test_engine_int8_streams_kernel_equals_gather(params_int8):
    """int8-KV engines: the kernel's native int8 layout (bytes streamed,
    scales post-matmul in VMEM) stays token-equal with the gather route."""
    prompts = [_prompt(5, 5), _prompt(6, 6)]
    gather, _ = _run(params_int8, _serving(paged_attn="gather"), prompts,
                     cfg=CFG_INT8)
    kernel, stats = _run(params_int8, _serving(paged_attn="kernel"), prompts,
                         cfg=CFG_INT8)
    assert kernel == gather
    assert stats["paged_attn_kernel_ticks"] > 0
    assert stats["kv_pool_free"] == stats["kv_pool_blocks"]


def test_engine_moe_streams_kernel_equals_gather():
    """The MoE family through the shared trunk: routed experts swap the FFN,
    the paged read route swaps underneath them — streams never change."""
    from vtpu.models.moe import MoEConfig, init_moe_params
    from vtpu.serving.adapters import MoeSlotModel

    cfg = MoEConfig(vocab=96, d_model=64, n_heads=2, n_layers=1, d_ff=64,
                    n_experts=4, top_k=2, max_seq=16, head_dim=32,
                    dtype=jnp.float32)
    mparams = init_moe_params(jax.random.key(5), cfg)
    serving = ServingConfig(slots=2, prefill_buckets=(16,), max_new_tokens=4)
    prompts = [[t % cfg.vocab for t in _prompt(21, 5)],
               [t % cfg.vocab for t in _prompt(22, 7)]]

    def run(route):
        eng = ServingEngine(serving=serving, model=MoeSlotModel(
            mparams, cfg, kv_page=PAGE, paged_attn=route))
        eng.start()
        try:
            reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
            return [list(r.stream()) for r in reqs], eng.stats()
        finally:
            eng.stop()

    gather, _ = run("gather")
    kernel, stats = run("kernel")
    assert kernel == gather
    assert stats["paged_attn_kernel_ticks"] > 0
    assert stats["kv_pool_free"] == stats["kv_pool_blocks"]


@needs_devices
def test_engine_tp2_streams_kernel_equals_gather(params):
    """tp=2 engines: the shard_map-wrapped kernel route stays token-equal
    with the gather route — the acceptance bar's tp clause, same contract
    style as tests/test_paged_kv_tp.py (whose suite already pins
    gather-TP == dense-TP == single-chip)."""
    mesh = make_axis_mesh("tp", 2)
    prompts = [_prompt(1, 5), _prompt(2, 7)]
    gather_tp, _ = _run(params, _serving(paged_attn="gather"), prompts,
                        mesh=mesh)
    kernel_tp, stats = _run(params, _serving(paged_attn="kernel"), prompts,
                            mesh=mesh)
    assert kernel_tp == gather_tp
    assert stats["paged_attn_kernel_ticks"] > 0
    assert stats["tp"] == 2
    assert stats["kv_pool_free"] == stats["kv_pool_blocks"]


def test_spec_verify_table_aware_on_kernel_route(params):
    """batched_spec_step runs draft/verify table-aware: on the kernel route
    a repetitive stream still drafts (spec ticks fire, T = K+1 window reads
    walk the table) and emits EXACTLY the gather route's stream."""
    cfg = ModelConfig(
        vocab=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq=32, head_dim=16, dtype=jnp.float32, use_pallas=False)
    p = init_params(jax.random.key(0), cfg)
    prompt = [5, 6, 7, 8, 5, 6, 7, 8, 5, 6]
    steps = 12

    def run(route):
        sv = ServingConfig(slots=1, prefill_buckets=(16,),
                           max_new_tokens=steps, spec_tokens=3,
                           kv_page=PAGE, paged_attn=route)
        eng = ServingEngine(p, cfg, sv)
        eng.start()
        try:
            stream = list(eng.submit(prompt, max_new_tokens=steps).stream())
            return stream, eng.stats()
        finally:
            eng.stop()

    gather, gs = run("gather")
    kernel, ks = run("kernel")
    assert kernel == gather
    assert ks["spec_ticks"] > 0
    # spec ticks route exactly like decode ticks (the counters cover both)
    assert (ks["paged_attn_kernel_ticks"]
            == ks["decode_ticks"] + ks["spec_ticks"])
    assert ks["paged_attn_gather_ticks"] == 0
    assert gs["spec_ticks"] > 0 and gs["paged_attn_kernel_ticks"] == 0


# ------------------------------------------------------- config validation


def test_paged_attn_without_pool_raises(params):
    with pytest.raises(ValueError, match="kv_page"):
        ServingEngine(params, CFG, ServingConfig(
            slots=2, prefill_buckets=(16,), paged_attn="kernel"))
    with pytest.raises(ValueError, match="kv_page"):
        TransformerSlotModel(params, CFG, paged_attn="gather")


def test_paged_attn_bad_value_and_mismatch_raise(params):
    with pytest.raises(ValueError, match="paged_attn"):
        TransformerSlotModel(params, CFG, kv_page=PAGE, paged_attn="pallas")
    # engine/adapter route mismatch is a config contradiction, like kv_page
    model = TransformerSlotModel(params, CFG, kv_page=PAGE,
                                 paged_attn="gather")
    with pytest.raises(ValueError, match="paged_attn"):
        ServingEngine(model=model, serving=ServingConfig(
            slots=2, prefill_buckets=(16,), kv_page=PAGE,
            paged_attn="kernel"))
