"""A decode step's walk over a latent plane alone, at the benchmark's
shapes.

    python benchmarks/latent_walk_bench.py [--tiny] [--groups 256,512,1024]
        [--tokens 7400] [--out f.json]

``vtpu.ops.decode_attn.latent_decode_attention`` over five layers of a pool
``[5, 14000, 64, 640]`` (bfloat16, `dsv2_longgen`'s) for 96 slots whose
lengths are spread about ``--tokens`` (0.5 to 1.5 times it, ending inside
pages), 128 absorbed queries a slot, through a page table of scattered
blocks on the 32 k window: milliseconds for the five layers at each number
of tokens a group (``_LATENT_GROUP_TOKENS``), the share of the roofline
(``vbench/reference/mla.py latent_attn_step_cost`` over the v5e's peaks)
and, once, the distance to ``masked_latent_attention`` over the gathered
window of the first eight slots. On a TPU the numbers are device times;
``--tiny`` interprets a cut-down shape on the CPU and proves only that the
script runs: never a speed.
"""

import argparse
import json
import os
import sys
import time

ap = argparse.ArgumentParser()
ap.add_argument("--tiny", action="store_true")
ap.add_argument("--groups", default="256,512,1024")
ap.add_argument("--tokens", type=int, default=7400)
ap.add_argument("--out", default="chiprun_out/latent_walk_bench.json")
args = ap.parse_args()
if args.tiny:
    os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from vbench.reference import mla as ref  # noqa: E402
from vtpu.ops import decode_attn  # noqa: E402
from vtpu.ops import latent as L  # noqa: E402

SCALE, REPEATS = 0.1147, 8
# slots, heads, rank, rotated, stored, page, blocks, layers, window, tokens
SHAPE = (4, 8, 32, 8, 128, 8, 64, 2, 128, 60) if args.tiny else (
    96, 128, 512, 64, 640, 64, 14000, 5, 32768, args.tokens)
GROUPS = [16] if args.tiny else [int(g) for g in args.groups.split(",")]
PEAKS = (197e12, 819e9)  # vbench/peaks/TPU_v5_lite.json


def main() -> int:
    slots, heads, rank, dr, stored, page, blocks, layers, window, mean = SHAPE
    rng = np.random.default_rng(35)
    dtype = jnp.float32 if args.tiny else jnp.bfloat16
    unit = min(blocks, 1000)  # one draw of blocks, repeated: 5.7 GB in all
    assert blocks % unit == 0
    base = jax.random.normal(jax.random.key(0), (1, unit, page, stored),
                             dtype) * 0.5
    pool = jnp.tile(base.at[..., rank + dr:].set(0),
                    (layers, blocks // unit, 1, 1))
    q = jax.random.normal(jax.random.key(1), (slots, heads, stored), dtype)
    q = q.at[..., rank + dr:].set(0)
    lens = np.minimum(rng.integers(mean // 2, mean * 3 // 2, slots) | 1,
                      window).astype(np.int32)
    wp = window // page
    need = -(-lens // page)
    assert need.sum() < blocks, "the lengths do not fit the pool"
    table = np.zeros((slots, wp), np.int32)
    ids = rng.permutation(np.arange(1, blocks))
    at = 0
    for b in range(slots):
        table[b, :need[b]] = ids[at:at + need[b]]
        at += need[b]
    table, lens_d = jnp.asarray(table), jnp.asarray(lens)
    live = int(lens.sum())
    cfg = dict(num_attention_heads=heads, kv_lora_rank=rank,
               qk_rope_head_dim=dr, num_hidden_layers=layers, hidden_size=0,
               q_lora_rank=0, qk_nope_head_dim=0, v_head_dim=0,
               intermediate_size=0, moe_intermediate_size=0,
               n_routed_experts_published=1, n_routed_experts=1,
               held_experts_first=0, num_experts_per_tok=1,
               n_shared_experts=1)
    flops, byts = ref.latent_attn_step_cost(cfg, slots, live)
    least_ms = 1e3 * max(flops / PEAKS[0], byts / PEAKS[1])
    rows = []
    for group in GROUPS:
        decode_attn._LATENT_GROUP_TOKENS = group

        @jax.jit
        def walk(q, pool, table, lens):
            return [decode_attn.latent_decode_attention(
                q, pool, table, lens, l, rank, SCALE) for l in range(layers)]

        out = jax.block_until_ready(walk(q, pool, table, lens_d))
        t0 = time.perf_counter()
        for _ in range(REPEATS):
            out = walk(q, pool, table, lens_d)
        jax.block_until_ready(out)
        ms = 1e3 * (time.perf_counter() - t0) / REPEATS
        row = {"group_tokens": group, "ms_five_layers": round(ms, 3),
               "roofline_pct": round(100 * least_ms / ms, 1)}
        if not rows:  # against the gathered window, the first slots
            n = min(8, slots)
            win = L.window_rows(pool, 0, table[:n])[..., :rank + dr]
            keep = jnp.arange(win.shape[1])[None, None] < lens_d[:n, None, None]
            want = L.masked_latent_attention(
                q[:n, None, :, :rank], q[:n, None, :, rank:rank + dr], win,
                keep, SCALE)[:, 0]
            row["distance"] = float(jnp.max(jnp.abs(
                out[0][:n].astype(jnp.float32) - want.astype(jnp.float32))))
        rows.append(row)
        print(json.dumps(row), flush=True)
    result = {"device": jax.devices()[0].device_kind, "slots": slots,
              "live_tokens": live, "least_ms": round(least_ms, 3),
              "flops": flops, "bytes": byts, "rows": rows}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f)
    print(json.dumps({k: v for k, v in result.items() if k != "rows"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
