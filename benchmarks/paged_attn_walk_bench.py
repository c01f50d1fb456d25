"""Kernel-only timing of the paged decode walk at the benchmark's shapes.

    python benchmarks/paged_attn_walk_bench.py [--tiny] [--int8]
        [--other [tag=]path/to/another/decode_attn.py[,tag=path...]]
        [--groups N,M] [--heads N] [--shapes a,b] [--out f.json]

One decode step's worth of `paged_decode_attention` calls (one a layer, as
the engine unrolls them) on a pool of the cell's size, tables and lengths
drawn as the cells fill them (most of a read window is padding, the pool is
full), against the gather route's output on the last layer (and, where the
shape takes the walk's second output, each query head's log-sum-exp over the
gathered window). `--other` times other copies of `vtpu/ops/decode_attn.py`
beside this one: another checkout's, or a candidate form's (PERF.md, PR 29
and PR 44, chose the kernels' forms with this table); `--groups` times each
copy at those tokens a group of pages, by its module's constant (the grouped
walk's own where the copy has one). On a TPU the numbers
are host times around whole steps that end on the device; `--tiny` interprets a cut-down shape on the CPU and proves only that
the script runs: never a speed.
"""

import argparse
import importlib.util
import itertools
import json
import os
import statistics
import sys
import time

ap = argparse.ArgumentParser()
ap.add_argument("--tiny", action="store_true")
ap.add_argument("--int8", action="store_true", help="int8 pools with scales")
ap.add_argument("--other", help="other decode_attn.py files to time beside, "
                "[tag=]path, comma-separated")
ap.add_argument("--groups", help="tokens a group of pages, comma-separated: "
                "each copy's constant set to each (as it stands if not given)")
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
    gather_kv_pages, paged_causal_attention, paged_causal_attention_int8kv)

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
    # sdar_blockgen: 79 resident of 96 holding about 107 k tokens, the rest
    # idle; 4 key/value heads of 128, a row of the pool each, 32 query heads
    # over them, a pass of 4 rows a slot that all read the slot's cache
    "sdar4096": (96, 256, 4, 24, 10240, lambda r: [
        *(300 + 3700 * r.rand(79) ** 2.4).astype(int), *[0] * 17]),
}
# (query heads, head size, softmax scale, queries a slot, the log-sum-exp
# too) where they are not the pool's rows
GROUPED = {"granite4096": (32, 64, 1 / 64, 1, False),
           "sdar4096": (32, 128, 128 ** -0.5, 4, True)}


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


def window_lse(q, k_plane, v_plane, table, lens, scale):
    """Each query head's log-sum-exp over its slot's gathered window, in
    float32: what the grouped walk's second output is held to."""
    b, t, hq, dq = q.shape
    win = gather_kv_pages(k_plane, table).astype(jnp.float32)
    win = win.reshape(b, win.shape[1], -1, dq)            # [B, S, Hk, Dh]
    qg = q.astype(jnp.float32).reshape(b, t, win.shape[2], -1, dq)
    s = jnp.einsum("btkgd,bskd->btkgs", qg, win,
                   precision="highest") * scale
    s = jnp.where(jnp.arange(win.shape[1]) < lens[:, None, None, None, None],
                  s, -jnp.inf)
    return jax.nn.logsumexp(s, axis=-1).reshape(b, t, hq)


def load(path):
    spec = importlib.util.spec_from_file_location("other_decode_attn", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main():
    rng = np.random.RandomState(0)
    mods = {"this": decode_attn}
    for item in filter(None, (args.other or "").split(",")):
        tag, _, path = item.rpartition("=")
        mods[tag or "other"] = load(path)
    dtype = jnp.float32 if args.tiny else jnp.bfloat16
    rows = []
    for name in args.shapes.split(","):
        (b, wp, h, layers, nb), lens, table, live = fill(name, rng)
        key = jax.random.key(1)
        plane = jax.jit(lambda k: jax.random.normal(
            k, (layers, nb, PAGE, h, DH), jnp.float32).astype(dtype))
        kp, vp = plane(jax.random.fold_in(key, 1)), plane(jax.random.fold_in(key, 2))
        hq, dq, scale, t, lse = GROUPED.get(name, (h, DH, None, 1, False))
        q = jax.random.normal(jax.random.fold_in(key, 3), (b, t, hq, dq), dtype)
        tb = jnp.asarray(table)
        ln = jnp.asarray(lens if t == 1 else np.repeat(lens[:, None], t, 1))
        kw = {} if scale is None else {"scale": scale}
        groups = int(np.maximum(-(-lens // 128), 1).sum())
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
                q, kp[-1], vp[-1], tb, kv_len=ln.reshape(b, t), **kw)
        want = np.asarray(want.astype(jnp.float32))
        read = lens > 0  # a slot that reads nothing has no answer
        want_lse = lse and np.asarray(
            window_lse(q, kp[-1], vp[-1], tb, jnp.asarray(lens), scale))
        if lse:
            kw["lse"] = True
        lengths = [int(n) for n in (args.groups or "0").split(",")]
        for (tag, mod), tokens in itertools.product(mods.items(), lengths):
            fn = (mod.paged_decode_attention_int8kv if args.int8
                  else mod.paged_decode_attention)
            const = ("_GROUPED_GROUP_TOKENS" if name in GROUPED and hasattr(
                mod, "_GROUPED_GROUP_TOKENS") else "_GROUP_TOKENS")
            if tokens:  # read when the step below is traced
                setattr(mod, const, tokens)

            def step(q, tb, ln, *pools):
                outs = [fn(q, *pools, tb, ln, layer=i,
                           interpret=True if args.tiny else None, **kw)
                        for i in range(layers)]
                if lse:  # both outputs of every layer are made
                    return (sum(o.astype(jnp.float32) + e[..., None]
                                for o, e in outs), outs[-1])
                return sum(outs[1:], outs[0]), outs[-1]

            row = dict(shape=name, kernel=tag, int8=args.int8, heads=h,
                       group_tokens=getattr(mod, const),
                       layers=layers, queries_a_slot=t,
                       live_pages=live, window_pages=b * wp,
                       groups_of_128=groups,
                       device=jax.devices()[0].device_kind)
            try:
                f = jax.jit(step)
                t0 = time.perf_counter()
                _, last = jax.block_until_ready(f(q, tb, ln, *pools))
                row["first_call_s"] = round(time.perf_counter() - t0, 2)
                if lse:
                    last, sums = last
                    row["max_abs_err_lse"] = float(np.max(np.abs(
                        np.asarray(sums) - want_lse)[read]))
                row["max_abs_err"] = float(np.max(np.abs(
                    np.asarray(last.astype(jnp.float32)) - want)[read]))
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
                row["us_per_group_of_128"] = round(
                    1e3 * row["ms_per_step"] / (layers * groups), 4)
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
