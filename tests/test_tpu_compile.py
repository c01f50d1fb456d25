"""The Pallas kernels compile for a v5e — checked on the CPU tier, with no
chip (ISSUE 21 satellite a).

The installed libtpu can describe a TPU topology and run the real TPU
compiler, Mosaic included, on a machine without a TPU. Every kernel the
serving path routes to on the chip is compiled here at its flagship shape
with ``interpret=False``, so a change that breaks Mosaic lowering fails in
this tier instead of on chip time. hack/tpu_compile_probe.py does the same
for whole engines. A compile is not a run: numerics stay with the
interpreted tests, speed with the chip.
"""

import dataclasses
import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from vtpu.models import ModelConfig, init_params
from vtpu.models.moe import MoEConfig, init_moe_params
from vtpu.ops.attention import flash_attention
from vtpu.ops import decode_attn
from vtpu.ops.decode_attn import (
    count_pool_sized_ops,
    paged_decode_attention,
    paged_decode_attention_int8kv,
)
from vtpu.serving import ServingConfig, ServingEngine
from vtpu.serving.adapters import MoeSlotModel

# the compile-only client writes persistent-cache entries it cannot load
# back ("DeserializeLoadedExecutable not implemented"): it recompiles, fine
pytestmark = pytest.mark.filterwarnings(
    "ignore:Error reading persistent compilation cache entry")

B, H, DH = 4, 8, 128          # flagship heads
LAYERS, PAGE, WINDOW = 12, 16, 1024
N_BLOCKS = 1 + B * WINDOW // PAGE


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # no libtpu in this environment
        pytest.skip(f"no compile-only TPU topology: {exc}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return list(topo.devices)


def _custom_calls(fn, *avals) -> int:
    return jax.jit(fn).lower(*avals).compile().as_text().count(
        "tpu_custom_call")


_EXPERT_KERNEL = re.compile(
    r'op_name="[^"]*experts/jit\(grouped_experts_ffn\)/'
    r'experts_(?:gate_up|down)/')


def _expert_kernels(text: str) -> int:
    """Custom calls of a compiled text that are the held experts' grouped
    kernels (vtpu/ops/grouped_ffn.py, PR 41: ``experts_gate_up`` and
    ``experts_down`` under the ``experts`` scope), two a sparse layer."""
    return sum(1 for line in text.splitlines()
               if "tpu_custom_call" in line and _EXPERT_KERNEL.search(line))


def _on(sharding):
    def aval(shape, dtype, spec=None):
        sh = sharding if spec is None else NamedSharding(sharding.mesh, spec)
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)
    return aval


def test_flash_attention_compiles(v5e):
    aval = _on(SingleDeviceSharding(v5e[0]))
    x = aval((B, 1024, H, DH), jnp.bfloat16)
    assert _custom_calls(
        lambda q, k, v: flash_attention(q, k, v, interpret=False),
        x, x, x) == 1


@pytest.mark.parametrize("t", [1, 4])
def test_paged_decode_kernels_compile(v5e, t):
    aval = _on(SingleDeviceSharding(v5e[0]))
    q = aval((B, t, H, DH), jnp.bfloat16)
    table = aval((B, WINDOW // PAGE), jnp.int32)
    kv_len = aval((B, t), jnp.int32)
    pool = (LAYERS, N_BLOCKS, PAGE, H, DH)
    assert _custom_calls(
        lambda q, k, v, tb, ln: paged_decode_attention(
            q, k, v, tb, ln, layer=3, interpret=False),
        q, aval(pool, jnp.bfloat16), aval(pool, jnp.bfloat16), table,
        kv_len) == 1
    assert _custom_calls(
        lambda q, k, ks, v, vs, tb, ln: paged_decode_attention_int8kv(
            q, k, ks, v, vs, tb, ln, layer=3, interpret=False),
        q, aval(pool, jnp.int8), aval(pool[:-1], jnp.float32),
        aval(pool, jnp.int8), aval(pool[:-1], jnp.float32), table,
        kv_len) == 1


# the benchmark's two paged configurations as their decode step calls the
# kernel on the 4096 window: (slots, heads, layers, pool blocks)
CELL_SHAPES = {"dsllm7b": (16, 32, 15, 898), "olmoe": (64, 16, 8, 2048)}


@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_paged_kernel_compiles_at_cell_shapes(v5e, cell):
    """One Mosaic kernel, the pools left where they are: the program around
    it holds no temporary of a plane's size, nor even of the VMEM the
    kernel's page groups were sized for (ISSUE 29)."""
    slots, heads, layers, blocks = CELL_SHAPES[cell]
    aval = _on(SingleDeviceSharding(v5e[0]))
    pool = aval((layers, blocks, PAGE, heads, DH), jnp.bfloat16)
    compiled = jax.jit(
        lambda q, k, v, tb, ln: paged_decode_attention(
            q, k, v, tb, ln, layer=3, interpret=False)
    ).lower(aval((slots, 1, heads, DH), jnp.bfloat16), pool, pool,
            aval((slots, 4096 // PAGE), jnp.int32),
            aval((slots,), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert (compiled.memory_analysis().temp_size_in_bytes
            < decode_attn._GROUP_VMEM_BYTES)


# what the kernel's own copies cannot cut takes the window walk: head counts
# that are no whole tile (a 12-head model; 40 heads over four chips; int8 at
# 8 heads over four), and int8 pools at any window, VMEM a page at a time
@pytest.mark.parametrize("heads,quant,window", [
    (12, False, 1024), (6, False, 1024), (10, False, 4096),
    (2, True, 2048), (12, True, 2048), (32, True, 4096), (32, True, 16384)])
def test_paged_window_walk_compiles(v5e, heads, quant, window):
    aval = _on(SingleDeviceSharding(v5e[0]))
    pool = (LAYERS, 1 + B * window // PAGE, PAGE, heads, DH)
    q = aval((B, 1, heads, DH), jnp.bfloat16)
    table, kv_len = aval((B, window // PAGE), jnp.int32), aval((B,), jnp.int32)
    if quant:
        assert _custom_calls(
            lambda q, k, ks, v, vs, tb, ln: paged_decode_attention_int8kv(
                q, k, ks, v, vs, tb, ln, layer=3, interpret=False),
            q, aval(pool, jnp.int8), aval(pool[:-1], jnp.float32),
            aval(pool, jnp.int8), aval(pool[:-1], jnp.float32), table,
            kv_len) == 1
    else:
        assert not decode_attn._copies_cut(heads, 2)
        assert _custom_calls(
            lambda q, k, v, tb, ln: paged_decode_attention(
                q, k, v, tb, ln, layer=3, interpret=False),
            q, aval(pool, jnp.bfloat16), aval(pool, jnp.bfloat16), table,
            kv_len) == 1


def test_tp4_shard_map_wrappers_compile(v5e):
    """Under a ('tp',) x 4 mesh a Mosaic kernel must sit inside shard_map
    (the SPMD pass cannot partition it): the paged decode kernels and the
    prefill flash kernel, two heads a chip."""
    mesh = Mesh(np.array(v5e), ("tp",))
    aval = _on(NamedSharding(mesh, P()))
    heads = P(None, None, "tp", None)
    pool_heads = P(None, None, None, "tp", None)
    scale_heads = P(None, None, None, "tp")
    q = aval((B, 1, H, DH), jnp.bfloat16, heads)
    table = aval((B, WINDOW // PAGE), jnp.int32)
    kv_len = aval((B,), jnp.int32)
    pool = (LAYERS, N_BLOCKS, PAGE, H, DH)
    assert _custom_calls(
        lambda q, k, v, tb, ln: paged_decode_attention(
            q, k, v, tb, ln, layer=3, mesh=mesh, interpret=False),
        q, aval(pool, jnp.bfloat16, pool_heads),
        aval(pool, jnp.bfloat16, pool_heads), table, kv_len) == 1
    assert _custom_calls(
        lambda q, k, ks, v, vs, tb, ln: paged_decode_attention_int8kv(
            q, k, ks, v, vs, tb, ln, layer=3, mesh=mesh, interpret=False),
        q, aval(pool, jnp.int8, pool_heads),
        aval(pool[:-1], jnp.float32, scale_heads),
        aval(pool, jnp.int8, pool_heads),
        aval(pool[:-1], jnp.float32, scale_heads), table, kv_len) == 1
    x = aval((1, 1024, H, DH), jnp.bfloat16, heads)
    assert _custom_calls(
        lambda q, k, v: flash_attention(q, k, v, interpret=False, mesh=mesh),
        x, x, x) == 1
    # without the wrapper the same kernel under the same mesh is refused
    with pytest.raises(NotImplementedError, match="shard_map"):
        _custom_calls(
            lambda q, k, v: flash_attention(q, k, v, interpret=False),
            x, x, x)


# ------------------------------------------------- the whole decode step
# The engine's decode step on the kernel route, compiled for the v5e: the
# pool reaches the paged kernel as the buffer it is stored in (ISSUE 26).
# Toy widths at TPU-legal head sizes; the pool is abstract and sized like a
# deployment's (128 MiB a bf16 plane a chip), because a plane of a few MiB
# is prefetched whole into the fast memory space and reads as a copy.

STEP_BUCKET, STEP_SLOTS, STEP_BLOCKS = 128, 4, 2048
STEP_DENSE = ModelConfig(
    vocab=128, d_model=64, n_heads=8, n_layers=3, d_ff=64, max_seq=256,
    head_dim=128, dtype=jnp.bfloat16, use_pallas=False)
STEP_MOE = MoEConfig(
    vocab=128, d_model=64, n_heads=8, n_layers=3, d_ff=64, n_experts=4,
    top_k=2, max_seq=256, head_dim=128, dtype=jnp.bfloat16)
PLANES = ("k", "v", "k_scale", "v_scale")
# what may be as large as a plane: the planes, and the scatters that write
# them in place, each inside its fusion
POOL_SIZED_OK = {"parameter", "bitcast", "get-tuple-element", "scatter",
                 "fusion"}


def _step_engine(family: str, int8: bool, tp: int, wide: bool = False):
    """``wide``: the attention widths of the 7B model (hidden 4096, 32
    heads) over zeros, so that a projection stack is of a size the compiler
    will not keep in the fast memory."""
    serving = ServingConfig(
        slots=STEP_SLOTS, prefill_buckets=(STEP_BUCKET,), max_new_tokens=4,
        kv_page=PAGE, kv_pool_blocks=15, paged_attn="kernel",
        prefill_chunk=PAGE)
    widths = {"d_model": 4096, "n_heads": 32} if wide else {}

    def weights(init, cfg):
        if not wide:
            return init(jax.random.key(0), cfg)
        return jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            jax.eval_shape(lambda: init(jax.random.key(0), cfg)))

    if family == "moe":
        cfg = dataclasses.replace(STEP_MOE, kv_int8=int8, **widths)
        model = MoeSlotModel(
            weights(init_moe_params, cfg), cfg, kv_page=PAGE,
            kv_pool_blocks=15, paged_attn="kernel")
        return ServingEngine(serving=serving, model=model)
    cfg = dataclasses.replace(STEP_DENSE, kv_int8=int8, **widths)
    if tp:  # the heads are the 7B model's, 32: eight a chip
        cfg = dataclasses.replace(cfg, n_heads=32)
    mesh = Mesh(np.array(jax.devices()[:tp]), ("tp",)) if tp else None
    return ServingEngine(weights(init_params, cfg), cfg, serving, mesh=mesh)


def _compiled_decode_step(eng, v5e, tp: int, monkeypatch, chunk=False):
    """(the decode step compiled as the engine's warm-up lowers it, its
    pool planes as given to it): on v5e devices, the pool abstract at
    STEP_BLOCKS blocks, eight heads a chip. ``chunk``: the chunk program
    in the step's place."""
    tpu_mesh = Mesh(np.array(v5e[:tp]), ("tp",)) if tp else None
    if tp:  # what the trunk closes over must name the same devices
        eng.model.mesh = tpu_mesh

    def to_tpu(x):
        sharding = getattr(x, "sharding", None)
        if isinstance(sharding, NamedSharding):
            sharding = NamedSharding(tpu_mesh, sharding.spec)
        elif tp:
            sharding = NamedSharding(tpu_mesh, P())
        else:
            sharding = SingleDeviceSharding(v5e[0])
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    state = {
        key: jax.ShapeDtypeStruct(
            val.shape[:1] + (STEP_BLOCKS,) + val.shape[2:], val.dtype,
            sharding=val.sharding) if key in PLANES else val
        for key, val in eng.state.items()}
    b = eng.serving.slots
    args = jax.tree.map(to_tpu, (
        eng.params, state, jnp.zeros((b,), jnp.int32), jnp.zeros((b,), bool),
        eng._rng))
    # the kernel asks the backend whether to interpret: steer it here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if chunk:
        lowered = eng._prefill_chunk.lower(
            *args[:2], *jax.tree.map(to_tpu, (
                jnp.zeros((1, PAGE), jnp.int32), jnp.int32(0), jnp.int32(0),
                jnp.int32(1))),
            kv_bucket=STEP_BUCKET, unroll=eng._unroll,
            block_ids=to_tpu(np.zeros((STEP_BUCKET // PAGE,), np.int32)))
    else:
        lowered = eng._decode_sampled.lower(
            *args, STEP_BUCKET, unroll=eng._unroll)
    return lowered.compile(), {
        k: v for k, v in args[1].items() if k in PLANES}


STEP_CASES = [("dense", False, 0), ("dense", True, 0), ("moe", False, 0),
              ("moe", True, 0), ("dense", False, 4), ("dense", True, 4)]


@pytest.mark.parametrize(
    "family,int8,tp", STEP_CASES,
    ids=[f"{f}-{'int8' if q else 'bf16'}-tp{t or 1}" for f, q, t in STEP_CASES])
def test_decode_step_moves_no_pool_plane(v5e, monkeypatch, family, int8, tp):
    """Between the kv_write scatter and the paged kernel nothing of a pool
    plane's size is computed: no reshape, copy, transpose or slice of K or
    V (a), so the step's temporaries stay under one plane (b), and a plane
    is an argument of exactly its logical bytes (c). The parent of PR 26
    fails (a) and (b): a reshape a layer a plane, both planes in temp."""
    eng = _step_engine(family, int8, tp)
    compiled, planes = _compiled_decode_step(eng, v5e, tp, monkeypatch)
    chips = max(tp, 1)
    layers = eng.model.cfg.n_layers
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == layers
    plane = planes["k"]
    plane_bytes = math.prod(plane.shape) * plane.dtype.itemsize // chips
    # (a) K and V
    ops = count_pool_sized_ops(text, math.prod(plane.shape) // chips)
    assert set(ops) <= POOL_SIZED_OK, ops
    assert ops["scatter"] == ops["fusion"] == 2 * layers, ops
    if int8:
        # the scale pools: written in place too, and converted between the
        # argument's layout and the kernel's twice a step a plane (on entry
        # and on exit, as on the parent), never once a layer
        small = count_pool_sized_ops(
            text, math.prod(planes["k_scale"].shape) // chips)
        assert small["scatter"] == 4 * layers, small
        assert small.get("copy", 0) <= 4, small  # 6 if it were a layer
        assert not {"reshape", "transpose", "slice", "dynamic-slice"} & set(
            small), small
    # (b)
    assert compiled.memory_analysis().temp_size_in_bytes < plane_bytes
    # (c)
    held = jax.jit(lambda k, v: k[0, 0, 0, 0, 0] + v[0, 0, 0, 0, 0]).lower(
        planes["k"], planes["v"]).compile().memory_analysis()
    assert held.argument_size_in_bytes == 2 * plane_bytes


_ENTRY_RESULT = re.compile(
    r"\s*(?:ROOT )?%([\w.\-]+) = (\(?\w+\[.*?) ([\w\-]+)\(")
_ARRAY = re.compile(r"(\w+)\[([0-9,]*)\]\{([0-9,]*)")
_MOVES_NOTHING = {"parameter", "get-tuple-element", "tuple", "bitcast"}


def _projection_relayouts(text: str, layer_elements: int, dtype: str) -> dict:
    """{instruction: results} over the instructions of *text*'s entry
    computation that yield arrays of one layer's projection size laid out
    otherwise than a slice of a stored stack is (row-major, as every weight
    argument): a stack copied into the layout a product wants. The parent
    of PR 31 holds three ``slice_bitcast_fusion``, one a projection, each
    taking a whole stack and yielding every layer's [d, H*Dh] with d
    minor. What is nested in a fusion is no instruction of its own and
    moves nothing; a prefetch keeps the stored layout."""
    found, entry = {}, False
    for line in text.splitlines():
        if line.startswith("ENTRY "):
            entry = True
        elif entry and line.startswith("}"):
            break
        m = _ENTRY_RESULT.match(line) if entry else None
        if m is None or m.group(3) in _MOVES_NOTHING:
            continue
        n = 0
        for dt, dims, order in _ARRAY.findall(m.group(2)):
            shape = [int(d) for d in dims.split(",") if d]
            order = [int(d) for d in order.split(",") if d]
            if (dt == dtype and math.prod(shape) == layer_elements
                    and order != sorted(order, reverse=True)):
                n += 1
        if n:
            found[m.group(1)] = n
    return found


def _holds_projections_as_stored(eng, v5e, tp, monkeypatch, chunk):
    compiled, _ = _compiled_decode_step(eng, v5e, tp, monkeypatch, chunk=chunk)
    cfg = eng.model.cfg
    stack = eng.params["layers"]["wq"]  # whichever form the adapter holds
    layer_elements = math.prod(stack.shape[1:]) // max(tp, 1)
    moved = _projection_relayouts(
        compiled.as_text(), layer_elements, "bf16")
    assert not moved, moved
    stack_bytes = cfg.n_layers * layer_elements * stack.dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < stack_bytes


@pytest.mark.parametrize(
    "family,int8,tp", STEP_CASES,
    ids=[f"{f}-{'int8' if q else 'bf16'}-tp{t or 1}" for f, q, t in STEP_CASES])
def test_decode_step_lays_no_projection_out_anew(
        v5e, monkeypatch, family, int8, tp):
    """At the 7B model's attention widths no instruction of the decode step
    yields a layer's wq, wk or wv in another layout than the stored one
    (a), and the step's temporaries stay under one projection stack (b).
    The parent of PR 31 fails (a) in every case (three fusions, each a
    whole stack in and every layer's projection out) and (b) in every
    case but bf16 over four chips."""
    eng = _step_engine(family, int8, tp, wide=True)
    _holds_projections_as_stored(eng, v5e, tp, monkeypatch, chunk=False)


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_chunk_program_lays_no_projection_out_anew(v5e, monkeypatch, family):
    """The same of the chunk program (16 rows a product where the step has
    4): it calls the same ``_qkv``."""
    eng = _step_engine(family, False, 0, wide=True)
    _holds_projections_as_stored(eng, v5e, 0, monkeypatch, chunk=True)


def test_flash_attention_tp_matches_single_device():
    """The shard_map route of the flash kernel is the same function: heads
    are independent, so a tp=2 run equals the unsharded one bit for bit
    (interpreted here; the compiled form is checked above)."""
    devices = jax.devices()[:2]
    if len(devices) < 2:
        pytest.skip("needs 2 devices")
    mesh = Mesh(np.array(devices), ("tp",))
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.standard_normal((2, 128, 4, 32)), jnp.float32)
               for _ in range(3))
    want = flash_attention(q, k, v)
    got = flash_attention(q, k, v, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _latent_shapes(v5e):
    """The latent family at the published widths (one dense and one
    sparse layer, 4 slots, a pool of STEP_BLOCKS blocks of 64, positions
    to 32768) as shapes on the described chip: (module, cfg, params,
    state, on_chip)."""
    from vtpu.models import latent as M

    cfg = M.LatentConfig(
        vocab=16160, d_model=7168, n_heads=128, n_dense_layers=1,
        n_sparse_layers=1, d_ff=18432, d_ff_expert=2048, q_rank=1536,
        kv_rank=512, nope_dim=128, rope_dim=64, v_dim=128, index_heads=64,
        index_dim=128, index_topk=2048, n_experts=256, held=(0, 16), top_k=8,
        n_group=8, topk_group=4, max_seq=32768)
    assert cfg.stored_width == 640 and cfg.latent_width == 576
    chip = SingleDeviceSharding(v5e[0])

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: M.init_latent_params(jax.random.key(0), cfg)))
    state = on_chip(jax.eval_shape(
        lambda: M.init_latent_cache(cfg, 4, 64, STEP_BLOCKS)))
    return M, cfg, params, state, on_chip


def _pool_plane_ops(text: str, plane) -> dict:
    """{opcode: count} of the instructions whose result is the plane as
    stored or its rows view (blocks and page merged)."""
    layers, blocks, page, row = plane.shape
    shapes = (f"[{layers},{blocks},{page},{row}]",
              f"[{layers},{blocks * page},{row}]")
    ops = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+(\[[0-9,]*\])\S* "
                     r"([\w\-]+)\(", line)
        if m and m.group(1) in shapes:
            ops[m.group(2)] = ops.get(m.group(2), 0) + 1
    return ops


def _expansions(text: str, window: int, width: int = 128) -> list:
    """Results shaped ``[.., window, heads, width]`` in a compiled text: a
    window's latents made into a head's keys or values; [(heads, line)]."""
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[(?:\d+,)*"
                     rf"{window},(\d+),{width}\]\S* convolution\(", line)
        if m:
            found.append((int(m.group(1)), line))
    return found


def test_latent_decode_step_moves_no_pool_plane(v5e):
    """The latent family's decode step at the published widths (one dense
    and one sparse layer, 4 slots, a pool of 256 blocks of 64, window
    4096): nothing of a pool plane's size is computed but the in-place
    scatters and the fusions that wrap them, neither for the latent plane
    nor for the indexer's keys, and the step's temporaries stay under the
    latent plane. With rows of 576 the compiler lays the plane out
    blocks-minor to save the lanes' padding and converts the whole pool
    on the way in and out (2 x 1.84 GB a step at the cell's pool, compiled
    in PR 28): ``LatentConfig.stored_width`` pads the row to 640 instead.
    The step gathers its selected rows and attends in the latent space:
    no head's keys or values are made of the window (PR 34)."""
    M, cfg, params, state, on_chip = _latent_shapes(v5e)
    compiled = jax.jit(
        M.latent_decode_step, static_argnums=(1, 5), donate_argnums=(2,)
    ).lower(params, cfg, state, on_chip(jnp.zeros((4,), jnp.int32)),
            on_chip(jnp.zeros((4,), bool)), 4096).compile()
    text = compiled.as_text()
    for plane in ("ckv", "ik"):
        ops = _pool_plane_ops(text, state[plane])
        assert set(ops) <= POOL_SIZED_OK, (plane, ops)
        assert ops["scatter"] == ops["fusion"] == cfg.n_layers, (plane, ops)
    latent_plane = math.prod(state["ckv"].shape) * 2
    assert compiled.memory_analysis().temp_size_in_bytes < latent_plane
    assert not _expansions(text, 4096)


def _dense_latent_shapes(v5e, slots: int, blocks: int):
    """DeepSeek-V2's block at the published widths (one dense and one
    sparse layer, the 20 held experts, no indexer) as shapes on the
    described chip: (module, cfg, params, state, on_chip)."""
    from vtpu.models import latent as M

    cfg = M.LatentConfig(
        vocab=12800, d_model=5120, n_heads=128, n_dense_layers=1,
        n_sparse_layers=1, d_ff=12288, d_ff_expert=1536, d_ff_shared=3072,
        q_rank=1536, kv_rank=512, nope_dim=128, rope_dim=64, v_dim=128,
        index_heads=0, index_dim=0, index_topk=0, n_experts=160,
        held=(0, 20), top_k=6, n_group=8, topk_group=3, route_scale=16.0,
        topk_method="group_limited_greedy", yarn_mscale=0.707,
        yarn_mscale_all_dim=0.707, max_seq=32768)
    assert cfg.attn_scale == pytest.approx(192 ** -0.5 * 1.5896, rel=1e-4)
    chip = SingleDeviceSharding(v5e[0])

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: M.init_latent_params(jax.random.key(0), cfg)))
    state = on_chip(jax.eval_shape(
        lambda: M.init_latent_cache(cfg, slots, 64, blocks)))
    return M, cfg, params, state, on_chip


@pytest.mark.parametrize("window", [4096, 24576])
def test_dense_latent_decode_step_walks_the_pool_in_place(
        v5e, monkeypatch, window):
    """The decode step of a latent model without an indexer (96 slots as
    `dsv2_longgen` has them, a pool of 2048 blocks of 64): one Mosaic
    kernel a layer walks the plane where it lies. Nothing of the plane's
    size is computed but the in-place scatters and the fusions that wrap
    them; nothing of a read window's size either, gathered, sliced or
    copied a slot (96 slots x the window x 640 is what ``window_rows``
    would make: 3.0 GB a layer at 24 k; an eighth of it is the line);
    the temporaries stay under a hundredth of it beside the held experts'
    weighed activations (since PR 41: the worst routing's 20 tiles of 96
    row slots, 5.9 MB whatever the window); the state holds one plane."""
    slots = 96
    M, cfg, params, state, on_chip = _dense_latent_shapes(v5e, slots, 2048)
    assert sorted(state) == ["ckv", "len", "table"]
    # trace-time routing asks the backend: compiled, not interpreted
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = jax.jit(
        M.latent_decode_step, static_argnums=(1, 5), donate_argnums=(2,)
    ).lower(params, cfg, state, on_chip(jnp.zeros((slots,), jnp.int32)),
            on_chip(jnp.zeros((slots,), bool)), window).compile()
    text = compiled.as_text()
    assert _expert_kernels(text) == 2 * cfg.n_sparse_layers
    assert text.count("tpu_custom_call") == cfg.n_layers + 2
    ops = _pool_plane_ops(text, state["ckv"])
    assert set(ops) <= POOL_SIZED_OK, ops
    assert ops["scatter"] == ops["fusion"] == cfg.n_layers, ops
    a_window = slots * window * cfg.stored_width
    sized = count_pool_sized_ops(text, a_window // 8)
    assert not {"gather", "dynamic-slice", "slice", "reshape", "transpose",
                "concatenate"} & set(sized), sized
    # what else is that large at the 4 k window is a weight on its way (a
    # prefetch, or ``wq_b`` laid out for its product: PERF.md section 7)
    for shape in re.findall(r" = \w+\[([0-9,]+)\]\S* copy\(", text):
        assert slots not in [int(d) for d in shape.split(",")][:1] \
            or math.prod(int(d) for d in shape.split(",")) < a_window // 8
    from vtpu.ops import grouped_ffn

    _, tm, tiles = grouped_ffn.plan(slots, cfg.held[1], cfg.top_k)
    assert (tm, tiles) == (96, 20)
    assert compiled.memory_analysis().temp_size_in_bytes < (
        a_window * 2 // 100 + tiles * tm * cfg.d_ff_expert * 2)


@pytest.mark.parametrize("family", ["selects", "dense"])
def test_latent_chunk_expands_its_window_a_group_of_heads_at_a_time(
        v5e, monkeypatch, family):
    """A 512-token chunk at the published widths over the 32768 window (the
    largest of the five programs `dsv32_longctx` and `dsv2_longgen` each
    warm; the same two layers and pool as the steps above), with the
    indexer's selection and with none: its attention runs in the chunk
    kernel (PR 38), which expands a block of the window into a few heads'
    keys and values in VMEM (the name is from PR 34, when XLA made them a
    group of eight heads at a time).

    - One Mosaic kernel a layer, ``latent_chunk`` under ``latent_attn``.
    - No head's keys or values are made of the window outside it, and no
      scores: nothing shaped ``[g, 512, 32768]`` for more than one head
      leaves a fusion in any dtype (the selection's mask ``[1, 512,
      32768]`` does, as int8 for the kernel).
    - The temporaries stay under 1 GB (XLA's expanded form had 785 MB; the
      cell's peak has to stay under 15.5 GB beside 12 GB of weights and
      pool).
    - Neither pool plane is copied or transposed."""
    shapes = _latent_shapes if family == "selects" else functools.partial(
        _dense_latent_shapes, slots=96, blocks=2048)
    M, cfg, params, state, on_chip = shapes(v5e)
    assert sorted(state) == (["ckv", "ik", "len", "table"]
                             if family == "selects" else
                             ["ckv", "len", "table"])
    window, chunk = 32768, 512
    # trace-time routing asks the backend: compiled, not interpreted
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    scalar = on_chip(jnp.zeros((), jnp.int32))
    compiled = jax.jit(
        M.latent_prefill_chunk, static_argnums=(1, 7), donate_argnums=(2,)
    ).lower(params, cfg, state, on_chip(jnp.zeros((1, chunk), jnp.int32)),
            scalar, scalar, scalar, window,
            on_chip(jnp.zeros((window // 64,), jnp.int32))).compile()
    text = compiled.as_text()
    kernels = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert _expert_kernels(text) == 2 * cfg.n_sparse_layers
    kernels = [line for line in kernels if not _EXPERT_KERNEL.search(line)]
    assert len(kernels) == cfg.n_layers
    assert all(re.search(r'op_name="[^"]*latent_attn/latent_chunk', line)
               for line in kernels), [k[-300:] for k in kernels]
    assert not _expansions(text, window)
    # computations a fusion calls hold what never leaves the chip's cores
    fused = set(re.findall(r"calls=%?([\w.\-]+)", text))
    scores = re.compile(rf"\w+\[(?:\d+,)*(\d+),{chunk},{window}\]")
    name, written = None, []
    for line in text.splitlines():
        m = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if m:
            name = m.group(1)
        elif name not in fused and " = " in line:
            m = scores.match(line.split(" = ", 1)[1].split("(", 1)[0])
            if m and int(m.group(1)) > 1:
                written.append(line.strip()[:160])
    assert not written, written
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    for plane in sorted(set(state) & {"ckv", "ik"}):
        ops = _pool_plane_ops(text, state[plane])
        assert set(ops) <= POOL_SIZED_OK, (plane, ops)


# (H, D, F, top_k) of the three configurations that hold experts, and the
# rows of their decode step; the admission bucket and a chunk are 256 and 512
HELD_EXPERTS = {"mimo-v2.5-7l-ep16": (16, 4096, 2048, 8, 96),
                "deepseek-v3.2-5l-ep16": (16, 7168, 2048, 8, 16),
                "deepseek-v2-5l-ep8": (20, 5120, 1536, 6, 96)}


@pytest.mark.parametrize("program",
                         ["step", "admission", "chunk", "most_rows"])
@pytest.mark.parametrize("config", sorted(HELD_EXPERTS))
def test_held_experts_kernels_compile(v5e, config, program):
    """The grouped kernels of ``vtpu/ops/grouped_ffn.py`` (PR 41) at the
    published widths and the rows of each program, and at the most rows
    ``takes`` lets a launch have (several prompts admitted together, a
    longer chunk: a launch it takes must compile, for nothing falls back
    from Mosaic's refusal), over a stack of two layers read in place: two
    Mosaic kernels, and beside the worst routing's tiles of weighed
    activations no temporary of a layer's stack (a layer sliced out for a
    kernel would be copied)."""
    from vtpu.ops.grouped_ffn import (
        _MOST_ROWS, grouped_experts_ffn, plan, takes)

    h, d, f, top_k, slots = HELD_EXPERTS[config]
    t = {"step": slots, "admission": 256, "chunk": 512,
         "most_rows": _MOST_ROWS}[program]
    assert takes(t, d, f) and not takes(_MOST_ROWS + 1, d, f)
    _, tm, tiles = plan(t, h, top_k)
    aval = _on(SingleDeviceSharding(v5e[0]))
    compiled = jax.jit(
        lambda x, g, a, b, c: grouped_experts_ffn(x, g, a, b, c, 1, top_k)
    ).lower(aval((t, d), jnp.bfloat16), aval((t, h), jnp.float32),
            aval((2, h, d, f), jnp.bfloat16), aval((2, h, d, f), jnp.bfloat16),
            aval((2, h, f, d), jnp.bfloat16)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2
    assert compiled.memory_analysis().temp_size_in_bytes < (
        tiles * tm * f * 2 + h * d * f * 2 // 8)


# -- the hybrid family at its cell's sizes ----------------------------------

HYBRID_SLOTS, HYBRID_BLOCKS = 64, 16385


def _hybrid_shapes(v5e):
    """granite-4.0-h-micro's ``HybridConfig`` at the published widths, with
    the adapter's params and the engine state of `granite4h_sessions` (64
    slots, 16384 blocks of 16) as shapes on the described chip."""
    from vtpu.models import hybrid as M
    from vtpu.models.transformer import hold_projections

    cfg = M.HybridConfig(
        vocab=100352, d_model=2048, n_heads=32, n_kv_heads=8, head_dim=64,
        d_ff=8192, ssm_heads=64, ssm_head_dim=64, ssm_state=128, ssd_chunk=256,
        layer_types=tuple((["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4),
        max_seq=16384)
    chip = SingleDeviceSharding(v5e[0])

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: M.init_hybrid_params(jax.random.key(0), cfg)))
    params["attention"] = hold_projections(params["attention"], cfg.attention)
    state = on_chip(jax.eval_shape(lambda: M.init_hybrid_state(
        cfg, HYBRID_SLOTS, 16, HYBRID_BLOCKS)))
    return M, cfg, params, state, on_chip


@pytest.mark.parametrize("program", ["step", "chunk", "admission"])
def test_hybrid_programs_compile_at_the_cells_sizes(
        v5e, monkeypatch, program):
    """The decode step (window 8192: the kernel route, 8 queries a slot
    over pool rows of [4, 128]), a 512-token chunk at that window and a
    whole-prompt admission of 512 compile for a v5e and fit the chip
    beside the state. The step updates the recurrent state in place: its
    temporaries stay under ONE layer's state (134 MB of the 4.83 GB), so
    there is no second copy of ``h``; and it visits a layer's state once,
    inside the state kernel (PR 36): the kernel takes the stack as the
    loop carries it, so outside the custom calls nothing computes, copies,
    slices or updates an array of a layer's or the stack's shape."""
    # trace-time routing asks the backend: the kernel, compiled, as on a chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    M, cfg, params, state, on_chip = _hybrid_shapes(v5e)
    i32 = lambda *shape: on_chip(jnp.zeros(shape, jnp.int32))  # noqa: E731
    if program == "step":
        compiled = jax.jit(
            M.hybrid_decode_step, static_argnums=(1, 5), donate_argnums=(2,)
        ).lower(params, cfg, state, i32(HYBRID_SLOTS),
                on_chip(jnp.zeros((HYBRID_SLOTS,), bool)), 8192).compile()
    elif program == "chunk":
        compiled = _hybrid_chunk(v5e, 8192)[0]
    else:
        compiled = jax.jit(
            M.hybrid_prefill_rows, static_argnums=(1,), donate_argnums=(2,)
        ).lower(params, cfg, state, i32(1, 512), i32(1), i32(1)).compile()
    mem = compiled.memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert peak < 0.95 * 16 * 2**30, peak
    recurrent = math.prod(state["h"].shape) * 4
    assert mem.alias_size_in_bytes > recurrent   # the state is updated in place
    # and no program lays a pool plane out anew: a plane of four rows a
    # token is tiled by four, and a gather or scatter of its pages as
    # stored made the compiler convert the whole pool both ways
    # (slots._token_rows_merged)
    big = count_pool_sized_ops(compiled.as_text(), math.prod(state["k"].shape))
    assert "copy" not in big and "transpose" not in big, big
    if program == "step":
        text = compiled.as_text()
        # the paged kernel an attention layer (4) and the state kernel a
        # run of Mamba layers (one loop body each: 5)
        runs = [kind for kind, _, _ in M.layer_runs(cfg.layer_types)]
        assert (runs.count("attention"), runs.count("mamba")) == (4, 5)
        assert text.count("tpu_custom_call") == 4 + 5
        one_layer = recurrent // cfg.n_ssm_layers
        assert one_layer == 64 * 64 * 64 * 128 * 4
        assert mem.temp_size_in_bytes < one_layer, mem.temp_size_in_bytes
        assert _state_shaped_ops(text, state["h"].shape) == []


def _state_shaped_ops(text: str, stack: tuple) -> list:
    """The fusions, copies, dynamic slices and dynamic updates of a compiled
    program's text, outside its custom calls, with an operand or a result
    of one layer's state's shape or of the stack's."""
    shapes = tuple("f32[" + ",".join(map(str, dims)) + "]"
                   for dims in (stack, stack[1:]))
    visits = re.compile(
        r" (fusion|copy|dynamic-slice|dynamic-update-slice)\(")
    return [line.strip()[:200] for line in text.splitlines()
            if " = " in line and visits.search(line)
            and any(shape in line for shape in shapes)]


def test_state_shaped_ops_finds_the_parents_three_visits():
    """The reader of the property above, on lines as the v5e compiler wrote
    them for the step before the kernel (a layer sliced out of the stack,
    updated by a fusion, put back) and for the step with it."""
    stack = (36, 64, 64, 64, 128)
    before = """
  %fusion.9 = f32[64,64,64,128]{3,2,1,0:T(8,128)} fusion(f32[36,64,64,64,128]{4,3,2,1,0:T(8,128)} %gte.1, s32[] %i), kind=kLoop
  %dynamic-update-slice.4 = f32[36,64,64,64,128]{4,3,2,1,0:T(8,128)} dynamic-update-slice(%gte.1, %fusion.9, %i, %c, %c, %c, %c)
  %copy.2 = f32[36,64,64,64,128]{4,3,2,1,0:T(8,128)} copy(%gte.1)
"""
    after = """
  %get-tuple-element.3 = f32[36,64,64,64,128]{4,3,2,1,0:T(8,128)} get-tuple-element(%arg), index=3
  %ssm_state_step.35 = (f32[64,64,64]{2,1,0:T(8,128)}, f32[36,64,64,64,128]{4,3,2,1,0:T(8,128)}) custom-call(%a, %b), custom_call_target="tpu_custom_call"
  %fusion.1 = f32[64,64,64]{2,1,0:T(8,128)} fusion(%x), kind=kLoop
"""
    assert len(_state_shaped_ops(before, stack)) == 3
    assert _state_shaped_ops(after, stack) == []


# -- block-sparse attention beside linear attention, at its cell's sizes ------

SALA_SLOTS, SALA_BLOCKS = 96, 30001


def _sala_shapes(v5e):
    """MiniCPM-SALA's ``SparseLinearConfig`` at the published widths in the
    stage `sala_longsessions` serves (S L L L S L L L), with the adapter's
    params and the cell's engine state (96 slots, 30000 blocks of 64) as
    shapes on the described chip."""
    from vtpu.models import sparselinear as M

    cfg = M.SparseLinearConfig(
        vocab=73448, d_model=4096, n_heads=32, n_kv_heads=2, head_dim=128,
        d_ff=16384, lin_heads=32, lin_head_dim=128, ssd_chunk=256,
        layer_types=tuple((["sparse"] + ["linear"] * 3) * 2),
        layer_index=tuple(range(0, 32, 4)),
        kernel_stride=16, block_size=64, window_size=2048, init_blocks=1,
        topk=64, dense_len=8192, dim_model_base=256, max_seq=49152)
    chip = SingleDeviceSharding(v5e[0])

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip), tree)

    params = M.hold_projections(on_chip(jax.eval_shape(
        lambda: M.init_sparselinear_params(jax.random.key(0), cfg))), cfg)
    state = on_chip(jax.eval_shape(lambda: M.init_sparselinear_state(
        cfg, SALA_SLOTS, 64, SALA_BLOCKS)))
    return M, cfg, params, state, on_chip


@pytest.mark.parametrize("program,window", [("step", 32768), ("chunk", 16384)])
def test_sparse_linear_programs_compile_at_the_cells_sizes(
        v5e, monkeypatch, program, window):
    """The decode step (the selection's table a key/value head walked by
    the grouped kernel, 16 queries a slot over pool rows of [1, 128]; the
    linear layers' rows moved by the state kernel with a key and a query a
    head) and a 512-token chunk (the selection's mask over the gathered
    window) compile for a v5e and fit the chip beside the state. Neither
    lays a pool plane out anew nor copies one; the step updates the rows in
    place, inside the state kernel: outside the custom calls nothing
    computes, copies, slices or updates an array of a layer's rows' or the
    stack's shape (what PR 36's test holds for the hybrid family, whose
    kernel this is)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    M, cfg, params, state, on_chip = _sala_shapes(v5e)
    i32 = lambda *shape: on_chip(jnp.zeros(shape, jnp.int32))  # noqa: E731
    if program == "step":
        compiled = jax.jit(
            M.sparselinear_decode_step, static_argnums=(1, 5),
            donate_argnums=(2,)
        ).lower(params, cfg, state, i32(SALA_SLOTS),
                on_chip(jnp.zeros((SALA_SLOTS,), bool)), window).compile()
    else:
        compiled = jax.jit(
            M.sparselinear_prefill_chunk, static_argnums=(1, 7),
            donate_argnums=(2,)
        ).lower(params, cfg, state, i32(1, 512), i32(), i32(), i32(), window,
                i32(window // 64)).compile()
    mem = compiled.memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert peak < 0.8 * 16 * 2**30, peak
    recurrent = math.prod(state["s"].shape) * 4
    assert recurrent == 96 * 6 * 32 * 128 * 128 * 4
    assert mem.alias_size_in_bytes > recurrent   # the state is updated in place
    text = compiled.as_text()
    for plane in ("k", "ck"):
        big = count_pool_sized_ops(text, math.prod(state[plane].shape))
        assert "copy" not in big and "transpose" not in big, (plane, big)
    if program == "step":
        # the grouped walk a sparse layer and key/value head (2 x 2), the
        # state kernel a run of linear layers (one loop body each: 2)
        assert text.count("tpu_custom_call") == 4 + 2
        one_layer = recurrent // cfg.n_linear_layers
        assert mem.temp_size_in_bytes < 5 * one_layer, mem.temp_size_in_bytes
        assert _state_shaped_ops(text, state["s"].shape) == []


@pytest.mark.parametrize("window", [16384, 24576, 32768, 40960, 49152])
def test_sparse_linear_chunks_attend_in_the_kernel_under_the_mask(
        v5e, monkeypatch, window):
    """At every read window of `sala_longsessions` a chunk's sparse layers
    attend in ``chunk_attn`` with the selection's mask as an operand (a
    call a layer and key/value head: 2 x 2), and none falls to the XLA form
    beside it, whose float32 scores [32, 512, window] made the first ramp
    miss its limit (PERF.md section 6, PR 47). The cell reports no
    ``chunk_attn_*`` metric (their lists are pinned), so this is what holds
    the route. Traced at the cell's widths, not compiled: the 16384 window
    is compiled above."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    M, cfg, params, state, on_chip = _sala_shapes(v5e)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    jaxpr = jax.make_jaxpr(
        M.sparselinear_prefill_chunk, static_argnums=(1, 7))(
            params, cfg, state, i32(1, 512), i32(), i32(), i32(), window,
            i32(window // 64))
    calls = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "pallas_call"]
    assert len(calls) == 4
    from vtpu.ops import chunk_attn

    for call in calls:
        assert call.params["name"] == "chunk_attn"
        # [N, Hk (one a call), key blocks, T x G, mask blocks a key block]
        flags = call.invars[-1].aval
        bk = chunk_attn.key_block(window)
        assert flags.shape == (1, 1, window // bk, 512 * 16, bk // 64)
        assert flags.dtype == jnp.bfloat16


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


# -- the window family at its cell's sizes -----------------------------------

SWA_SLOTS, SWA_BLOCKS = 96, 14001


def _swa_shapes(v5e):
    """MiMo-V2.5's ``SwaConfig`` at the published widths in its first
    seven layers (0 1 1 1 1 0 1; the 16 held experts), with the adapter's
    params and the engine state of `mimo_mixedqueue` (96 slots, 14000
    blocks of 64) as shapes on the described chip."""
    from vtpu.models import swa as M

    cfg = M.SwaConfig(
        vocab=19072, d_model=4096, n_heads=64, head_dim=192, v_head_dim=128,
        rope_dim=64,
        layer_types=("full",) + ("window",) * 4 + ("full", "window"),
        ffn_types=("dense",) + ("moe",) * 6, n_kv_heads=4,
        n_kv_heads_window=8, window=128, d_ff=16384, d_ff_expert=2048,
        n_experts=256, held=(0, 16), top_k=8, max_seq=36864)
    chip = SingleDeviceSharding(v5e[0])

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: M.init_swa_params(jax.random.key(0), cfg)))
    params["layers"] = M.hold_projections(params["layers"], cfg)
    state = on_chip(jax.eval_shape(lambda: M.init_swa_state(
        cfg, SWA_SLOTS, 64, SWA_BLOCKS)))
    return M, cfg, params, state, on_chip


@pytest.mark.parametrize("program,window", [
    ("step", 4096), ("step", 32768), ("chunk", 4096), ("chunk", 32768)])
def test_window_family_programs_compile_at_the_cells_sizes(
        v5e, monkeypatch, program, window):
    """The decode step and a 512-token chunk of `mimo_mixedqueue`, at its
    smallest and its largest read window, compile for a v5e and fit the
    chip beside the state. A cached token's row as stored is 4 x 192 = 768
    and 4 x 128 = 512 columns a full layer (2560 B, whole 128-lane tiles,
    no padding) and a ring row 8 x 320 columns (5120 B). The step walks
    the full layers' pool in place, one Mosaic kernel a full layer:
    nothing of a pool plane's size is computed but the in-place scatters
    and the fusions that wrap them, no window is gathered
    (``count_pool_gathers`` 0 at half of one slot-window's keys; the
    embedding's 96 rows are an eighth of it at the 4 k window), and
    the rings are updated in place. Neither program lays a pool plane or a
    stack of projections out anew."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    M, cfg, params, state, on_chip = _swa_shapes(v5e)
    i32 = lambda *shape: on_chip(jnp.zeros(shape, jnp.int32))  # noqa: E731
    assert cfg.kv_bytes_per_token == 2 * 2560
    assert cfg.ring_bytes_per_position == 5 * 5120
    assert state["k"].shape[2:] == (64, 768) and state["v"].shape[3] == 512
    assert state["wk"].shape == (5, 96, 128, 1536)
    if program == "step":
        compiled = jax.jit(
            M.swa_decode_step, static_argnums=(1, 5), donate_argnums=(2,)
        ).lower(params, cfg, state, i32(SWA_SLOTS),
                on_chip(jnp.zeros((SWA_SLOTS,), bool)), window).compile()
    else:
        compiled = _swa_chunk(v5e, window)[0]
    mem = compiled.memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert peak < 0.95 * 16 * 2**30, peak
    pool = sum(math.prod(state[key].shape) * 2 for key in ("k", "v"))
    rings = sum(math.prod(state[key].shape) * 2 for key in ("wk", "wv"))
    assert rings == 96 * 5 * 128 * 5120
    assert mem.alias_size_in_bytes > pool + rings   # updated in place
    text = compiled.as_text()
    for plane in ("k", "v"):
        ops = _pool_plane_ops(text, state[plane])
        assert set(ops) <= POOL_SIZED_OK, (plane, ops)
    big = count_pool_sized_ops(text, math.prod(state["v"].shape))
    assert "copy" not in big and "transpose" not in big, big
    # no stack of projections is laid out anew
    wq = math.prod(params["layers"]["window_moe"]["wq"].shape)
    for shape in re.findall(r" = bf16\[([0-9,]+)\]\S* (?:copy|transpose)\(",
                            text):
        assert math.prod(int(d) for d in shape.split(",")) < wq // 5, shape
    # the six expert layers' two kernels each, the stacks read in place
    # (a layer sliced out of one for a kernel would be a copy of 268 MB)
    assert _expert_kernels(text) == 2 * cfg.ffn_types.count("moe")
    assert mem.temp_size_in_bytes < 3 * wq * 2, mem.temp_size_in_bytes
    if program == "step":
        # beside them a full layer each
        assert text.count("tpu_custom_call") - _expert_kernels(text) == 2
        a_window = window * 768                      # one slot's keys
        assert decode_attn.count_pool_gathers(text, a_window // 2) == 0
        ring_layer = rings // 5
        assert mem.temp_size_in_bytes < ring_layer, mem.temp_size_in_bytes


# -- generation by blocks at its cell's widths --------------------------------

SDAR_SLOTS, SDAR_BLOCKS, SDAR_LAYERS = 96, 10241, 2


def _blockdiff_shapes(v5e):
    """SDAR's ``BlockDiffConfig`` at the published widths (32 query heads
    on 4 key/value heads of 128, 16 of 128 experts 768 wide held, an eighth
    of the vocabulary) in two of the cell's 24 layers (a whole program of 24
    unrolled layers compiles in 40-60 s here: ``python -m vbench.rehearse
    sdar-30b-a3b-24l-ep8`` is that rehearsal), with the adapter's params
    and the engine state of `sdar_blockgen` (96 slots, 10240 blocks of 16)
    as shapes on the described chip."""
    from vtpu.models import blockdiff as M
    from vtpu.models.transformer import hold_projections

    cfg = M.BlockDiffConfig(
        vocab=18992, d_model=2048, n_heads=32, n_kv_heads=4, head_dim=128,
        n_layers=SDAR_LAYERS, d_ff=768, n_experts=128, held=(0, 16), top_k=8,
        max_seq=6144, mask_token_id=18991, denoising_steps=2,
        confidence_threshold=None)
    chip = SingleDeviceSharding(v5e[0])

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: init_moe_params(jax.random.key(0), cfg)))
    params["layers"] = hold_projections(params["layers"], cfg)
    state = on_chip(jax.eval_shape(lambda: M.init_block_state(
        cfg, SDAR_SLOTS, 16, SDAR_BLOCKS)))
    return M, cfg, params, state, on_chip


@pytest.mark.parametrize("program", ["pass", "chunk"])
def test_block_generation_programs_compile_at_the_cells_widths(
        v5e, monkeypatch, program):
    """A pass of 4 rows a slot and a 512-token chunk of `sdar_blockgen`
    compile for a v5e. A cached token's row as stored is 4 heads x 128 a
    plane (2048 B a token a layer both planes). The pass walks the pool in
    place, one Mosaic kernel a layer (``paged_attn`` under the scope
    ``block_attn``: the grouped walk with its log-sum-exp out, named in
    the compiled operation's path as both readers of a trace need it)
    beside the held experts' two: nothing of a pool
    plane's size is computed but the in-place scatters and what wraps them,
    no window is gathered, the pool is updated in place, and no stack of
    projections or experts is laid out anew (a layer sliced out of the
    experts' stack for a kernel would be a copy of 151 MB)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    M, cfg, params, state, on_chip = _blockdiff_shapes(v5e)
    i32 = lambda *shape: on_chip(jnp.zeros(shape, jnp.int32))  # noqa: E731
    assert state["k"].shape == (SDAR_LAYERS, SDAR_BLOCKS, 16, 4, 128)
    window = 4096
    if program == "pass":
        compiled = jax.jit(
            M.block_pass, static_argnums=(1, 4), donate_argnums=(2,)
        ).lower(params, cfg, state,
                on_chip(jnp.zeros((SDAR_SLOTS,), bool)), window).compile()
    else:
        compiled = _blockdiff_chunk(v5e, window)[0]
    mem = compiled.memory_analysis()
    pool = sum(math.prod(state[key].shape) * 2 for key in ("k", "v"))
    assert mem.alias_size_in_bytes > pool               # updated in place
    text = compiled.as_text()
    big = count_pool_sized_ops(text, math.prod(state["k"].shape))
    assert set(big) <= POOL_SIZED_OK, big
    assert _expert_kernels(text) == 2 * SDAR_LAYERS
    experts = math.prod(params["layers"]["w_gate"].shape[1:])
    wq = math.prod(params["layers"]["wq"].shape[1:])
    for shape in re.findall(r" = bf16\[([0-9,]+)\]\S* (?:copy|transpose)\(",
                            text):
        assert math.prod(int(d) for d in shape.split(",")) < wq, shape
    if program == "pass":
        assert text.count("tpu_custom_call") == 3 * SDAR_LAYERS
        walks = re.findall(
            r'custom_call_target="tpu_custom_call"[^\n]*'
            r'op_name="([^"]*/paged_attn/pallas_call)"', text)
        assert len(walks) == SDAR_LAYERS, walks[:3]
        assert all("/attn/block_attn/paged_attn/" in w for w in walks)
        a_window = window * 4 * 128                  # one slot's keys
        assert decode_attn.count_pool_gathers(text, a_window // 2) == 0
        assert mem.temp_size_in_bytes < experts * 2, mem.temp_size_in_bytes


# -- a chunk's attention over its gathered window (PR 45) ---------------------

_CHUNK_KERNEL = re.compile(
    r'custom_call_target="tpu_custom_call"[^\n]*'
    r'op_name="[^"]*/(?:attn|gather_attn)/chunk_attn/jit\(chunk_attention\)/'
    r'chunk_attn/pallas_call"')


def _dense_chunk(v5e, window):
    """A 512-token chunk of `dsllm7b_longprompt` (32 heads of 128, hidden
    4096) in two of its 15 layers over its pool (898 blocks of 16)."""
    from vtpu.models import slots as slot_steps
    from vtpu.models.transformer import hold_projections, init_paged_kv_cache

    cfg = ModelConfig(
        vocab=102400, d_model=4096, n_heads=32, n_layers=2, d_ff=11008,
        max_seq=4096, head_dim=128, dtype=jnp.bfloat16, use_pallas=False)
    aval = _on(SingleDeviceSharding(v5e[0]))
    on_chip = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: aval(x.shape, x.dtype), tree)
    params = on_chip(jax.eval_shape(
        lambda: init_params(jax.random.key(0), cfg)))
    params["layers"] = hold_projections(params["layers"], cfg)
    state = on_chip(jax.eval_shape(
        lambda: init_paged_kv_cache(cfg, 16, 16, 898)))

    def chunk(params, state, tokens, slot, offset, new_len, block_ids):
        return slot_steps.chunked_prefill_into_slot(
            params, cfg, state, tokens, slot, offset, new_len,
            kv_bucket=window, unroll=True, block_ids=block_ids)

    i32 = lambda *shape: aval(shape, jnp.int32)  # noqa: E731
    return jax.jit(chunk, donate_argnums=(1,)).lower(
        params, state, i32(1, 512), i32(), i32(), i32(),
        i32(window // 16)).compile(), cfg.n_layers, 32 * 128


def _hybrid_chunk(v5e, window):
    M, cfg, params, state, on_chip = _hybrid_shapes(v5e)
    i32 = lambda *shape: on_chip(jnp.zeros(shape, jnp.int32))  # noqa: E731
    return jax.jit(
        M.hybrid_prefill_chunk, static_argnums=(1, 7), donate_argnums=(2,)
    ).lower(params, cfg, state, i32(1, 512), i32(), i32(), i32(), window,
            i32(window // 16)).compile(), cfg.n_attn_layers, 8 * 64


def _swa_chunk(v5e, window):
    M, cfg, params, state, on_chip = _swa_shapes(v5e)
    i32 = lambda *shape: on_chip(jnp.zeros(shape, jnp.int32))  # noqa: E731
    return jax.jit(
        M.swa_prefill_chunk, static_argnums=(1, 7), donate_argnums=(2,)
    ).lower(params, cfg, state, i32(1, 512), i32(), i32(), i32(), window,
            i32(window // 64)).compile(), cfg.layer_types.count("full"), 4 * 192


def _blockdiff_chunk(v5e, window):
    from vtpu.models import slots as slot_steps
    from vtpu.models.latent import LayerOfStack
    from vtpu.models.moe import held_moe_ffn

    M, cfg, params, state, on_chip = _blockdiff_shapes(v5e)
    i32 = lambda *shape: on_chip(jnp.zeros(shape, jnp.int32))  # noqa: E731

    def chunk(params, state, tokens, slot, offset, new_len, block_ids):
        return slot_steps.chunked_prefill_into_slot(
            params, cfg, state, tokens, slot, offset, new_len,
            kv_bucket=window, unroll=True, ffn_fn=held_moe_ffn(cfg),
            block_ids=block_ids, layer_of=LayerOfStack)

    return jax.jit(chunk, donate_argnums=(1,)).lower(
        params, state, i32(1, 512), i32(), i32(), i32(),
        i32(window // 16)).compile(), SDAR_LAYERS, 4 * 128


# the four wired configurations' chunk programs at a read window of their
# cell's (the dense one's 4096 is also its hidden width: a float32 ``[1, 512,
# 4096]`` of the norms is a quarter of the line the scores are held to)
CHUNK_PROGRAMS = {
    "dsllm7b_longprompt": (_dense_chunk, 4096),
    "granite4h_sessions": (_hybrid_chunk, 16384),
    "mimo_mixedqueue": (_swa_chunk, 24576),
    "sdar_blockgen": (_blockdiff_chunk, 4096),
}


@pytest.mark.parametrize("cell", sorted(CHUNK_PROGRAMS))
def test_chunk_programs_attend_their_window_in_the_chunk_kernel(
        v5e, monkeypatch, cell):
    """The chunk program of each wired configuration, compiled for a v5e at
    its cell's widths: one ``chunk_attn`` kernel an attention layer, under
    the scope the trace's reader looks for; no float32 tensor of the
    chunk's scores (``[heads, T x G, window]``, nor four heads' worth of
    it: XLA's form wrote and read it three times a layer); and the
    gathered window goes into the kernel as it lies: nothing copies or
    transposes an array of a layer's window."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    build, window = CHUNK_PROGRAMS[cell]
    compiled, layers, row_width = build(v5e, window)
    text = compiled.as_text()
    assert len(_CHUNK_KERNEL.findall(text)) == layers
    for dims in re.findall(r" = f32\[([0-9,]+)\]", text):
        dims = [int(d) for d in dims.split(",")]
        assert not (dims[-1] == window
                    and math.prod(dims) >= 4 * 512 * window), dims
    # an array of a layer's window that is copied or transposed is the
    # gather's own or the write-back's (slots._chunk_window and
    # _chunk_write_back, swa's window_rows: PERF.md section 7), as in the
    # parent; what the kernel's call prepares is the queries' fold and back,
    # arrays of the chunk's 512 rows (the parent also copied the window a
    # layer under ``attn``: the einsums' transposes)
    for line in text.splitlines():
        moved = re.search(r" = bf16\[([0-9,]+)\]\S* (?:copy|transpose)\(", line)
        if not moved:
            continue
        dims = [int(d) for d in moved.group(1).split(",")]
        if math.prod(dims) < window * row_width or 512 in dims:
            continue
        assert re.search(r'op_name="[^"]*/(gather_attn/gather|kv_write/)',
                         line), line[:300]
    # nor is a layer's window sliced out of the stacked view for the
    # kernel's operand: the kernel takes the stack and the layer's index
    for line in text.splitlines():
        cut = re.search(r" = bf16\[([0-9,]+)\]\S* slice\(", line)
        if cut and math.prod(int(d) for d in cut.group(1).split(",")) \
                >= window * row_width:
            assert not re.search(
                r'op_name="[^"]*/(attn|gather_attn)/(chunk_attn/)?slice"',
                line), line[:300]
