"""A decode step's recurrent-state update alone, at the benchmark's shapes.

    python benchmarks/ssm_state_bench.py [--tiny] [--heads 8,16,32,64]
        [--out f.json]

``vtpu.ops.ssm_step.ssm_state_step`` over the stacked state of
`granite4h_sessions` (``h [36, 64, 64, 64, 128]`` float32, 4.83 GB, donated
and updated in place) in a loop over its 36 layers, as a decode step's
``_walk`` runs it: milliseconds for the 36 layers at each number of heads a
tile (``_TILE_HEADS``), and ``vtpu.models.hybrid._ssd_step`` in the same
loop the way the CPU route runs it (a layer sliced out of the stack and put
back), each against the least time (every slot's state read once and
written once at the v5e's 819 GB/s) and, once a row, the distance between
the kernel's ``y`` and ``h`` and ``_ssd_step``'s on the first layers. On a
TPU the numbers are device times; ``--tiny`` interprets a cut-down shape on
the CPU and proves only that the script runs: never a speed.
"""

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from vtpu.models.hybrid import _ssd_step, _step_operands  # noqa: E402
from vtpu.ops import ssm_step  # noqa: E402

REPEATS = 8
BANDWIDTH = 819e9  # vbench/peaks/TPU_v5_lite.json


def inputs(shape):
    """A step's operands a layer, [Lm, ...] each: steps of 0.004-0.03 as the
    cell's weights give them, one slot in eight idle (dt = 0)."""
    lm, b, nh, p, n = shape
    ks = jax.random.split(jax.random.key(36), 5)
    dt = jnp.exp(jax.random.uniform(
        ks[0], (lm, b, 1, nh), jnp.float32, jnp.log(0.004), jnp.log(0.03)))
    dt = dt * (jnp.arange(b) % 8 != 7)[None, :, None, None]
    a = -jnp.exp(jax.random.uniform(ks[1], (lm, nh), jnp.float32, 0.0, 2.77))
    xs = jax.random.normal(ks[2], (lm, b, 1, nh, p), jnp.bfloat16)
    bm = jax.random.normal(ks[3], (lm, b, 1, n), jnp.bfloat16)
    cm = jax.random.normal(ks[4], (lm, b, 1, n), jnp.bfloat16)
    return dt, a, xs, bm, cm


def through_the_kernel(h, ops, interpret):
    def layer(l, carry):
        h, ys = carry
        dt, a, xs, bm, cm = (z[l] for z in ops)
        y, h = ssm_step.ssm_state_step(
            h, l, *_step_operands(xs, dt, a, bm, cm), interpret=interpret)
        return h, ys.at[l].set(y)

    lm, b, nh, p, _ = h.shape
    return jax.lax.fori_loop(
        0, lm, layer, (h, jnp.zeros((lm, b, nh, p), jnp.float32)))


def through_xla(h, ops):
    def layer(l, carry):
        h, ys = carry
        dt, a, xs, bm, cm = (z[l] for z in ops)
        y, s = _ssd_step(xs, dt, a, bm, cm,
                         jax.lax.dynamic_index_in_dim(h, l, 0, False))
        return (jax.lax.dynamic_update_index_in_dim(h, s, l, 0),
                ys.at[l].set(y[:, 0]))

    lm, b, nh, p, _ = h.shape
    return jax.lax.fori_loop(
        0, lm, layer, (h, jnp.zeros((lm, b, nh, p), jnp.float32)))


def timed(fn, h, ops):
    """(ms a call, the state, the last call's readouts)."""
    h, ys = jax.block_until_ready(fn(h, ops))
    t0 = time.perf_counter()
    for _ in range(REPEATS):
        h, ys = fn(h, ops)
    jax.block_until_ready(ys)
    return 1e3 * (time.perf_counter() - t0) / REPEATS, h, ys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--heads", default="8,16,32,64")
    ap.add_argument("--out", default="chiprun_out/ssm_state_bench.json")
    args = ap.parse_args(argv)
    if args.tiny:
        jax.config.update("jax_platforms", "cpu")
    # layers, slots, heads, head width, state
    shape = (3, 4, 8, 32, 16) if args.tiny else (36, 64, 64, 64, 128)
    tiles = [4] if args.tiny else [int(g) for g in args.heads.split(",")]
    lm, b, nh, p, n = shape
    ops = inputs(shape)
    moved = 2 * lm * b * nh * p * n * 4
    least_ms = 1e3 * moved / BANDWIDTH

    # the two routes on the first layers of a small stack of their own
    few = (min(lm, 2),) + shape[1:]
    small = jax.random.normal(jax.random.key(1), few, jnp.float32)
    few_ops = tuple(z[:few[0]] for z in ops)
    want_h, want_y = jax.jit(through_xla)(small, few_ops)

    h = jax.random.normal(jax.random.key(0), shape, jnp.float32)
    rows = []

    def row(name, ms, **more):
        rows.append({"route": name, "ms_all_layers": round(ms, 3),
                     "gb_per_s": round(moved / ms / 1e6, 1),
                     "of_least_pct": round(100 * least_ms / ms, 1), **more})
        print(json.dumps(rows[-1]), flush=True)

    for heads in tiles:
        ssm_step._TILE_HEADS = heads
        kernel = functools.partial(through_the_kernel, interpret=args.tiny)
        got_h, got_y = jax.jit(kernel)(small, few_ops)
        ms, h, _ = timed(jax.jit(kernel, donate_argnums=(0,)), h, ops)
        row("kernel", ms, tile_heads=heads,
            y_distance=float(jnp.max(jnp.abs(got_y - want_y))),
            h_distance=float(jnp.max(jnp.abs(got_h - want_h))))
    ms, h, _ = timed(jax.jit(through_xla, donate_argnums=(0,)), h, ops)
    row("_ssd_step", ms)
    result = {"device": jax.devices()[0].device_kind, "shape": shape,
              "bytes": moved, "least_ms": round(least_ms, 3), "rows": rows}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f)
    print(json.dumps({k: v for k, v in result.items() if k != "rows"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
