"""A prefill chunk's attention over its gathered window alone, XLA's form
beside the kernel, at the benchmark's head shapes.

    python benchmarks/chunk_attn_bench.py [--tiny] [--shapes dense,hybrid,sdar,mimo]
        [--windows 1024,4096,8192] [--tiles 1024x512,2048x512] [--out f.json]

One layer's attention of a 512-token chunk at each head-cached cell's
published widths, the window in the form the cell's chunk program holds it:

- ``dense`` (`dsllm7b_longprompt`): 32 heads of 128, G 1, ``[1, W, 32, 128]``;
- ``hybrid`` (`granite4h_sessions`): 8 key/value heads of 64 under 32 query
  heads (G 4), two a stored row, ``[1, W, 4, 128]`` (the 16384 window too);
- ``sdar`` (`sdar_blockgen`): 4 key/value heads of 128 under 32 (G 8),
  ``[1, W, 4, 128]``, a query reading to the end of its block of 4;
- ``mimo`` (`mimo_mixedqueue`): 4 key/value heads 192 wide for keys and 128
  for values under 64 (G 16), side by side, ``[1, W, 768]`` / ``[1, W, 512]``
  (windows 16384 and 24576 too).

XLA's form is what the chunk program runs off the kernel
(``ops.attention.causal_attention`` under the ragged ``kv_len``; for
``mimo`` ``ops.window_attn.full_attention`` over the rows read as heads);
the kernel is ``ops.chunk_attn.chunk_attention`` at each of ``--tiles``
(window positions x query rows a grid step; default: what ships). Each at
the chunk at the window's end (every key block live) and at half of it (the
kernel stops there; XLA's form attends the whole window whatever the
chunk's offset): milliseconds a layer, how far the kernel reads under XLA's
form, the two outputs' distance. A timed program attends ``LAYERS`` windows
of their own, as a chunk program attends a window a layer, so that a launch
of a third of a millisecond is not timed by the host's dispatch.
``chunk_attn.takes`` wires a shape in where the kernel reads at least 30 %
under XLA's form at its cell's windows (PERF.md, section 6, PR 45 has the
table). On a TPU the numbers are device times; ``--tiny`` runs a cut-down shape on the CPU, the kernel interpreted,
and proves only that the script runs: never a speed.
"""

import argparse
import functools
import json
import os
import sys
import time

ap = argparse.ArgumentParser()
ap.add_argument("--tiny", action="store_true")
ap.add_argument("--shapes", default="dense,hybrid,sdar,mimo")
ap.add_argument("--windows", default="1024,4096,8192")
ap.add_argument("--tiles", default="")
ap.add_argument("--out", default="chiprun_out/chunk_attn_bench.json")
args = ap.parse_args()
if args.tiny:
    os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from vtpu.ops import chunk_attn as K  # noqa: E402
from vtpu.ops.attention import causal_attention  # noqa: E402
from vtpu.ops.window_attn import full_attention  # noqa: E402

T = 32 if args.tiny else 512
LAYERS = 2 if args.tiny else 4
# name: (query heads, key/value heads, Dk, Dv, the window's stored form,
# the block a query reads to the end of, further windows)
SHAPES = {
    "dense": (32, 32, 128, 128, "heads", 1, ()),
    "hybrid": (32, 8, 64, 64, "packed", 1, (16384,)),
    "sdar": (32, 4, 128, 128, "heads", 4, ()),
    "mimo": (64, 4, 192, 128, "flat", 1, (16384, 24576)),
}
if args.tiny:
    SHAPES = {"dense": (4, 4, 128, 128, "heads", 1, ()),
              "hybrid": (16, 4, 64, 64, "packed", 1, ()),
              "sdar": (16, 2, 128, 128, "heads", 4, ()),
              "mimo": (32, 2, 192, 128, "flat", 1, ())}
WINDOWS = [256] if args.tiny else [int(w) for w in args.windows.split(",")]
TILES = ([(128, 128)] if args.tiny else
         [tuple(map(int, x.split("x"))) for x in args.tiles.split(",") if x]
         or [(K._KEYS, K._ROWS)])


def inputs(key, name, w):
    """A chunk's queries and ``LAYERS`` windows of ``w`` in ``name``'s
    stored form (a tuple of keys, a tuple of values)."""
    hq, hk, dk, dv, stored, _, _ = SHAPES[name]
    ks = jax.random.split(key, 1 + 2 * LAYERS)
    q = jax.random.normal(ks[0], (1, T, hq, dk), jnp.bfloat16)

    def window(key, d):
        x = jax.random.normal(key, (1, w, hk, d), jnp.bfloat16)
        if stored == "packed":
            return x.reshape(1, w, -1, 128)
        return x.reshape(1, w, -1) if stored == "flat" else x

    return (q, tuple(window(k, dk) for k in ks[1:1 + LAYERS]),
            tuple(window(k, dv) for k in ks[1 + LAYERS:]))


def layered(fn):
    """``fn`` over each of the windows, one program: ``[LAYERS, ...]``."""
    return jax.jit(lambda q, ks, vs, reach: jnp.stack(
        [fn(q, k, v, reach) for k, v in zip(ks, vs)]))


def reach_of(name, end):
    """``reach [1, T]`` of a chunk whose last query sits at ``end - 1``."""
    block = SHAPES[name][5]
    at = end - T + jnp.arange(T)
    return ((at // block + 1) * block)[None].astype(jnp.int32)


def xla_form(name):
    hq, hk, dk, dv, stored, _, _ = SHAPES[name]
    scale = dk ** -0.5
    if stored == "flat":  # the rows read as heads, as swa._full_layer does
        return lambda q, k, v, reach: full_attention(
            q, k.reshape(k.shape[:2] + (hk, dk)),
            v.reshape(v.shape[:2] + (hk, dv)), reach - 1, scale)
    return lambda q, k, v, reach: causal_attention(
        q, k, v, kv_len=reach, scale=scale)


def timed(fn, xs, runs=8):
    """Milliseconds a layer of ``fn``'s program, and its outputs."""
    jax.block_until_ready(fn(*xs))
    jax.block_until_ready(fn(*xs))
    t0 = time.perf_counter()
    for _ in range(runs):
        out = fn(*xs)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / runs / LAYERS * 1e3, out


device = jax.devices()[0]
rows = []
for name in args.shapes.split(","):
    hq, hk, dk, dv, stored, _, more = SHAPES[name]
    for w in WINDOWS + [x for x in more if not args.tiny]:
        q, k, v = inputs(jax.random.key(w), name, w)
        fits = K.fits(q.shape, k[0].shape, v[0].shape, 2)
        xla = layered(xla_form(name))
        kernels = {tile: layered(functools.partial(
            K.chunk_attention, scale=dk ** -0.5, interpret=args.tiny,
            keys_a_step=tile[0], rows_a_step=tile[1])) for tile in TILES}
        for end in (w, max(w // 2, T)):
            reach = reach_of(name, end)
            row = {"shape": name, "window": w, "chunk_end": end, "fits": fits}
            ms, ref = timed(xla, (q, k, v, reach))
            row["xla_ms_layer"] = round(ms, 3)
            ref = np.asarray(ref.astype(jnp.float32))
            row["mean_abs_out"] = float(np.abs(ref).mean())
            for (keys, qrows), fn in kernels.items():
                try:
                    ms, out = timed(fn, (q, k, v, reach))
                except Exception as exc:  # a tile Mosaic refuses
                    row[f"kernel_{keys}x{qrows}"] = {
                        "error": str(exc).splitlines()[0][:200]}
                    continue
                gap = np.abs(np.asarray(out.astype(jnp.float32)) - ref)
                row[f"kernel_{keys}x{qrows}"] = {
                    "ms_layer": round(ms, 3),
                    "under_xla_pct": round(
                        100 * (1 - ms / row["xla_ms_layer"]), 1),
                    "max_abs_diff": float(gap.max()),
                    "mean_abs_diff": float(gap.mean())}
            rows.append(row)
            print(json.dumps(row), flush=True)

result = {"device": {"platform": device.platform, "kind": device.device_kind},
          "queries": T, "layers_a_program": LAYERS, "rows": rows}
print(json.dumps(result["device"]))
os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
with open(args.out, "w") as f:
    json.dump(result, f, indent=1)
