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

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from vtpu.ops.attention import flash_attention
from vtpu.ops.decode_attn import (
    paged_decode_attention,
    paged_decode_attention_int8kv,
)

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
