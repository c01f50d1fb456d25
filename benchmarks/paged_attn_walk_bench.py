"""Kernel-only timing of the paged decode walk at the benchmark's shapes.

    python benchmarks/paged_attn_walk_bench.py [--tiny] [--int8]
        [--other path/to/another/decode_attn.py] [--heads N] [--shapes a,b]
        [--out f.json]

One decode step's worth of `paged_decode_attention` calls (one a layer, as
the engine unrolls them) on a pool of the cell's size, tables and lengths
drawn as the cells fill them (most of a read window is padding, the pool is
full), against the gather route's output on the last layer. `--other` times
another checkout's `vtpu/ops/decode_attn.py` beside this one (PERF.md, PR 29,
chose the kernel's form with this table). On a TPU the numbers are device
times; `--tiny` interprets a cut-down shape on the CPU and proves only that
the script runs: never a speed.
"""

import argparse
import importlib.util
import json
import os
import statistics
import sys
import time

ap = argparse.ArgumentParser()
ap.add_argument("--tiny", action="store_true")
ap.add_argument("--int8", action="store_true", help="int8 pools with scales")
ap.add_argument("--other", help="another decode_attn.py to time beside")
ap.add_argument("--heads", type=int, help="heads a chip, not the shape's")
ap.add_argument("--shapes",
                default="dense1024,dense4096,longprompt4096,olmoe4096")
ap.add_argument("--out", default="chiprun_out/paged_attn_walk_bench.json")
args = ap.parse_args()
if args.tiny:
    os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from vtpu.ops import decode_attn  # noqa: E402
from vtpu.ops.attention import (  # noqa: E402
    paged_causal_attention, paged_causal_attention_int8kv)

PAGE, DH = 16, 128
# slots, window pages, heads, layers, pool blocks, and the slots' lengths
SHAPES = {
    # dsllm7b_decode under its 1024 window: 14 resident of 16
    "dense1024": (16, 64, 32, 15, 898, lambda r: [*r.randint(400, 1000, 14), 1, 1]),
    # dsllm7b_decode once a stream has passed 1024: the pool full
    "dense4096": (16, 256, 32, 15, 898, lambda r: [*r.randint(600, 1450, 14), 1, 1]),
    # dsllm7b_longprompt: five long residents
    "longprompt4096": (16, 256, 32, 15, 898, lambda r: [*r.randint(1800, 3400, 5), *[1] * 11]),
    # olmoe_chat: 40 of 64 slots live, one past 1024
    "olmoe4096": (64, 256, 16, 8, 2048, lambda r: [*r.randint(100, 1000, 39), 1300, *[1] * 24]),
    # granite4h_sessions: 64 resident; 8 key/value heads of 64 stored 4 rows
    # of 128 lanes a token (the "heads" here), 32 query heads over them
    "granite4096": (64, 256, 4, 4, 16384, lambda r: r.randint(1536, 4088, 64)),
}
# (query heads, head size, softmax scale) where they are not the pool's rows
GROUPED = {"granite4096": (32, 64, 1 / 64)}


def fill(name, rng):
    b, wp, h, layers, nb, draw = SHAPES[name]
    h = args.heads or h
    lens = np.asarray(draw(rng), np.int32)
    if args.tiny:
        layers, nb, wp = 2, max(64, nb // 16), max(8, wp // 16)
        lens = np.maximum(1, lens // 16)
    rng.shuffle(lens)
    pages = -(-lens // PAGE)
    while pages.sum() > nb - 1:  # the pool refuses what does not fit
        lens[np.argmax(lens)] -= 64
        pages = -(-lens // PAGE)
    table = np.zeros((b, wp), np.int32)
    free = list(rng.permutation(np.arange(1, nb)))
    for i in np.flatnonzero(lens > 1):
        table[i, :pages[i]] = [free.pop() for _ in range(pages[i])]
    return (b, wp, h, layers, nb), lens, table, int(pages.sum())


def load(path):
    spec = importlib.util.spec_from_file_location("other_decode_attn", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main():
    rng = np.random.RandomState(0)
    mods = {"this": decode_attn}
    if args.other:
        mods["other"] = load(args.other)
    dtype = jnp.float32 if args.tiny else jnp.bfloat16
    rows = []
    for name in args.shapes.split(","):
        (b, wp, h, layers, nb), lens, table, live = fill(name, rng)
        key = jax.random.key(1)
        plane = jax.jit(lambda k: jax.random.normal(
            k, (layers, nb, PAGE, h, DH), jnp.float32).astype(dtype))
        kp, vp = plane(jax.random.fold_in(key, 1)), plane(jax.random.fold_in(key, 2))
        hq, dq, scale = GROUPED.get(name, (h, DH, None))
        q = jax.random.normal(jax.random.fold_in(key, 3), (b, 1, hq, dq), dtype)
        tb, ln = jnp.asarray(table), jnp.asarray(lens)
        kw = {} if scale is None else {"scale": scale}
        if args.int8:
            quant = jax.jit(lambda p: (
                jnp.round(p.astype(jnp.float32) * 32).clip(-127, 127).astype(jnp.int8),
                jnp.full(p.shape[:-1], 1 / 32, jnp.float32)))
            (kp, ks), (vp, vs) = quant(kp), quant(vp)
            pools = (kp, ks, vp, vs)
            want = paged_causal_attention_int8kv(
                q, kp[-1], ks[-1], vp[-1], vs[-1], tb, kv_len=ln)
        else:
            pools = (kp, vp)
            want = paged_causal_attention(
                q, kp[-1], vp[-1], tb, kv_len=ln[:, None], **kw)
        want = np.asarray(want.astype(jnp.float32))
        for tag, mod in mods.items():
            fn = (mod.paged_decode_attention_int8kv if args.int8
                  else mod.paged_decode_attention)

            def step(q, tb, ln, *pools):
                outs = [fn(q, *pools, tb, ln, layer=i,
                           interpret=True if args.tiny else None, **kw)
                        for i in range(layers)]
                return sum(outs[1:], outs[0]), outs[-1]

            row = dict(shape=name, kernel=tag, int8=args.int8, heads=h,
                       layers=layers,
                       live_pages=live, window_pages=b * wp,
                       device=jax.devices()[0].device_kind)
            try:
                f = jax.jit(step)
                t0 = time.perf_counter()
                _, last = jax.block_until_ready(f(q, tb, ln, *pools))
                row["first_call_s"] = round(time.perf_counter() - t0, 2)
                row["max_abs_err"] = float(np.max(np.abs(
                    np.asarray(last.astype(jnp.float32)) - want)))
                times = []
                for _ in range(2 if args.tiny else 20):
                    t0 = time.perf_counter()
                    jax.block_until_ready(f(q, tb, ln, *pools)[0])
                    times.append(1e3 * (time.perf_counter() - t0))
                # the median: one stall of the machine's is not the kernel's
                row["ms_per_step"] = round(statistics.median(times), 3)
                row["ms_min"], row["ms_max"] = round(min(times), 3), round(max(times), 3)
                row["us_per_live_page"] = round(
                    1e3 * row["ms_per_step"] / (layers * live), 4)
                row["live_bytes_over_819GBps_ms"] = round(
                    1e3 * layers * live * 2 * PAGE * h * DH
                    * kp.dtype.itemsize / 819e9, 3)
            except Exception as exc:  # a form that does not compile is a row
                row["error"] = str(exc)[:600]
            print(json.dumps(row), flush=True)
            rows.append(row)
    out = os.path.join(ROOT, args.out)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as fh:
        json.dump(rows, fh, indent=1)


if __name__ == "__main__":
    main()
