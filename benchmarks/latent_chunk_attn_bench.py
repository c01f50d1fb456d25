"""A prefill chunk's masked attention alone, in its two forms, at the
benchmark's shapes.

    python benchmarks/latent_chunk_attn_bench.py [--tiny]
        [--windows 4096,16384,24576] [--tiles 1024x4,2048x4] [--out f.json]

One layer's attention of a 512-token chunk of the latent family at the
published widths (128 heads, rank 512 + 64 rotated, 128 + 128 a head,
bfloat16) over a read window, under one mask that keeps 2048 visible
positions a query: the absorbed form (every query through ``w_uk``,
``masked_latent_attention`` in the latent space, the mix through ``w_uv``)
against the expanded form (``vtpu.ops.latent._expanded``: the window through
``w_uk`` and ``w_uv`` once, the scores made twice). Milliseconds a layer and
five layers' worth (`dsv32_longctx` has five), the two outputs' distance,
and, in float32 at the highest matmul precision over the first window, the
two forms against each other. PERF.md, section 6, PR 34 chose the form with
this table.

Then the kernel that ships on a TPU since PR 38
(``vtpu.ops.latent_chunk.chunk_attention``: the expanded form with a
block's scores kept on the chip, the scores made once) against the expanded
form, under the selection's mask and under the causal one, with the chunk
at the window's end (every key block live) and at three quarters of it (the
kernel stops there; XLA's form attends the whole window whatever the
chunk's offset), at each of ``--tiles`` (window positions x heads a grid
step; default: what ships). On a TPU the numbers are device times;
``--tiny`` runs a cut-down shape on the CPU, the kernel interpreted, and
proves only that the script runs: never a speed.
"""

import argparse
import functools
import json
import os
import sys
import time

ap = argparse.ArgumentParser()
ap.add_argument("--tiny", action="store_true")
ap.add_argument("--windows", default="4096,16384,24576")
ap.add_argument("--tiles", default="")
ap.add_argument("--out", default="chiprun_out/latent_chunk_attn_bench.json")
args = ap.parse_args()
if args.tiny:
    os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from vtpu.ops import latent as L  # noqa: E402
from vtpu.ops import latent_chunk as K  # noqa: E402

SCALE, LAYERS = 0.1352, 5
# queries, heads, rank, rotated, nope, values, kept
SHAPE = (64, 8, 64, 16, 16, 16, 32) if args.tiny else (
    512, 128, 512, 64, 128, 128, 2048)
WINDOWS = [256] if args.tiny else [int(w) for w in args.windows.split(",")]


def absorbed(q_nope, q_pe, window, keep, w_uk, w_uv):
    q_abs = jnp.einsum("nthd,hdr->nthr", q_nope, w_uk)
    mixed = L._absorbed(q_abs, q_pe, window, keep, SCALE)
    return jnp.einsum("nthr,hrv->nthv", mixed, w_uv)


def expanded(q_nope, q_pe, window, keep, w_uk, w_uv):
    return L._expanded(q_nope, q_pe, window, keep, w_uk, w_uv, SCALE)


def inputs(key, w, dtype, end=None):
    """The queries of a chunk that ends at ``end`` (the window's end unless
    told) of a window of ``w``, a mask of the best ``kept`` of uniform
    scores a query."""
    t, h, rank, dr, dn, dv, kept = SHAPE
    ks = jax.random.split(key, 6)
    up = rank ** -0.5
    positions = ((end or w) - t + jnp.arange(t))[None]
    return (jax.random.normal(ks[0], (1, t, h, dn), dtype),
            jax.random.normal(ks[1], (1, t, h, dr), dtype),
            jax.random.normal(ks[2], (1, w, rank + dr), dtype),
            L.select_mask(jax.random.uniform(ks[3], (1, t, w)), positions,
                          kept),
            (jax.random.normal(ks[4], (h, dn, rank)) * up).astype(dtype),
            (jax.random.normal(ks[5], (h, rank, dv)) * up).astype(dtype))


def timed(fn, xs, runs=8):
    jax.block_until_ready(fn(*xs))
    jax.block_until_ready(fn(*xs))
    t0 = time.perf_counter()
    for _ in range(runs):
        out = fn(*xs)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / runs * 1e3, out


device = jax.devices()[0]
rows = []
for w in WINDOWS:
    xs = inputs(jax.random.key(w), w, jnp.bfloat16)
    row = {"window": w, "expanded_tiles": L._expanded_tiles(
        1, SHAPE[0], SHAPE[1], w)}
    outs = {}
    for name, fn in (("absorbed", absorbed), ("expanded", expanded)):
        ms, out = timed(jax.jit(fn), xs)
        outs[name] = np.asarray(out.astype(jnp.float32))
        row[name + "_ms_layer"] = round(ms, 3)
        row[name + f"_ms_{LAYERS}_layers"] = round(LAYERS * ms, 2)
    gap = np.abs(outs["expanded"] - outs["absorbed"])
    row.update(max_abs_diff=float(gap.max()), mean_abs_diff=float(gap.mean()),
               mean_abs_out=float(np.abs(outs["absorbed"]).mean()))
    rows.append(row)
    print(json.dumps(row), flush=True)

TILES = ([(64, 2)] if args.tiny else
         [tuple(map(int, x.split("x"))) for x in args.tiles.split(",") if x]
         or [(K._KEYS, K._HEADS)])
# one jitted function a tile: the kernel reads its tile when it is traced
KERNELS = {tile: jax.jit(functools.partial(
    K.chunk_attention, scale=SCALE, interpret=args.tiny)) for tile in TILES}
for w in WINDOWS:
    for share in (1.0, 0.75):
        end = int(w * share) // 64 * 64
        q_nope, q_pe, window, keep, w_uk, w_uv = inputs(
            jax.random.key(w), w, jnp.bfloat16, end)
        positions = (end - SHAPE[0] + jnp.arange(SHAPE[0]))[None]
        causal = jnp.arange(w) <= positions[..., None]
        for mask, kp in (("selection", keep), ("causal", causal)):
            row = {"window": w, "chunk_end": end, "mask": mask}
            ms, ref = timed(jax.jit(expanded),
                            (q_nope, q_pe, window, kp, w_uk, w_uv))
            row["expanded_ms_layer"] = round(ms, 3)
            ref = np.asarray(ref.astype(jnp.float32))
            for (keys, heads), fn in KERNELS.items():
                K._KEYS, K._HEADS = keys, heads
                ms, out = timed(fn, (
                    q_nope, q_pe, window, None if mask == "causal" else kp,
                    positions, w_uk, w_uv))
                gap = np.abs(np.asarray(out.astype(jnp.float32)) - ref)
                row[f"kernel_{keys}x{heads}"] = {
                    "ms_layer": round(ms, 3),
                    f"ms_{LAYERS}_layers": round(LAYERS * ms, 2),
                    "under_expanded_pct": round(100 * (1 - ms / row[
                        "expanded_ms_layer"]), 1),
                    "max_abs_diff": float(gap.max()),
                    "mean_abs_diff": float(gap.mean())}
            rows.append(row)
            print(json.dumps(row), flush=True)

with jax.default_matmul_precision("highest"):
    xs = inputs(jax.random.key(7), WINDOWS[0], jnp.float32)
    gap = float(jnp.abs(jax.jit(expanded)(*xs) - jax.jit(absorbed)(*xs)).max())
result = {"device": {"platform": device.platform, "kind": device.device_kind},
          "shape": dict(zip(("queries", "heads", "rank", "rotated", "nope",
                             "values", "kept"), SHAPE)),
          "rows": rows, "float32_highest_max_abs_diff": gap}
print(json.dumps(result["device"] | {"float32_highest_max_abs_diff": gap}))
if gap > 1e-4:
    raise SystemExit(f"the two forms differ by {gap} in float32")
os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
with open(args.out, "w") as f:
    json.dump(result, f, indent=1)
