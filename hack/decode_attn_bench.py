"""Decode-attention kernel bench: Pallas decode_attention vs the XLA op
sequence, bf16 and int8 KV, at serving decode/verify shapes.

Int8 KV lost at batch 8 / kv 2048 through the XLA path (the
fused-convert formulation still bottoms out at ~33% HBM BW — decode
attention there is dispatch-bound: M=1 batched matmuls + a materialized
[B,H,T,S] mask/score chain). This measures whether the fused Pallas kernel
(benchmarks/decode_attn_kernel.py decode_attention — the standalone
study; no in-trunk route since r6) moves the needle at every target cell
{batch 8, 32} x {window 1024, 2048}, bf16 AND int8, T=1 (decode tick) and
T=4 (verify tick).

Timing uses the two-chain-length difference: each variant runs as a scan of
K1 and K2 dependent iterations inside one executable, and the per-call cost
is (t_K2 - t_K1) / (K2 - K1) — the fixed per-call cost (dispatch + the
syncing fetch, which dwarfs a 40-300 us kernel) cancels exactly instead of
being amortized.

Usage: python hack/decode_attn_bench.py  (needs a TPU; writes
build/DECODE_ATTN.json — DECODE_ATTN_r05.json at the repo root is the
round-5 run of this script, the basis of PAGED_ATTN_T_FLOORS)
"""

from __future__ import annotations

import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from benchmarks.decode_attn_kernel import decode_attention  # noqa: E402
from vtpu.ops.attention import (  # noqa: E402
    causal_attention, causal_attention_int8kv)

H, DH = 8, 128
CHAIN_LO, CHAIN_HI = 32, 288


def timed(fn, *args, iters: int = 7) -> float:
    fn(*args)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        np.asarray(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def bench_cell(b: int, s: int, t: int) -> dict:
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, t, H, DH), jnp.bfloat16)
    k = jnp.asarray(rng.randn(b, s, H, DH), jnp.bfloat16)
    v = jnp.asarray(rng.randn(b, s, H, DH), jnp.bfloat16)
    kq = jnp.asarray(rng.randint(-127, 128, (b, s, H, DH)), jnp.int8)
    vq = jnp.asarray(rng.randint(-127, 128, (b, s, H, DH)), jnp.int8)
    ks = jnp.asarray(rng.rand(b, s, H).astype(np.float32) * 0.02 + 1e-3)
    vs = jnp.asarray(rng.rand(b, s, H).astype(np.float32) * 0.02 + 1e-3)
    lens = jnp.asarray(
        rng.randint(s // 2, s + 1, (b, 1)) + np.arange(t)[None, :], jnp.int32)
    lens = jnp.minimum(lens, s)

    def chain(fn, length):
        @jax.jit
        def run(q, *kv):
            def body(carry, _):
                out = fn(carry, *kv)
                # feed the output back as the next q: a real data dependency
                # so XLA cannot collapse or overlap the iterations
                return out.astype(carry.dtype), None
            out, _ = jax.lax.scan(body, q, None, length=length)
            return out
        return run

    cell = {"batch": b, "window": s, "t": t}
    variants = {
        "xla_bf16": (lambda q, k, v: causal_attention(q, k, v, kv_len=lens),
                     (q, k, v)),
        "pallas_bf16": (lambda q, k, v: decode_attention(q, k, v, lens),
                        (q, k, v)),
        "xla_int8": (lambda q, kq, ks, vq, vs: causal_attention_int8kv(
            q, kq, ks, vq, vs, kv_len=lens), (q, kq, ks, vq, vs)),
        "pallas_int8": (lambda q, kq, ks, vq, vs: decode_attention(
            q, kq, vq, lens, ks, vs), (q, kq, ks, vq, vs)),
    }
    for name, (fn, args) in variants.items():
        t_lo = timed(chain(fn, CHAIN_LO), *args)
        t_hi = timed(chain(fn, CHAIN_HI), *args)
        cell[f"{name}_us"] = round(
            (t_hi - t_lo) / (CHAIN_HI - CHAIN_LO) * 1e6, 1)
    # bytes streamed per call (window reads; q/out negligible)
    bf16_bytes = 2 * b * s * H * DH * 2
    int8_bytes = 2 * b * s * H * DH + 2 * b * s * H * 4
    cell["bf16_window_mb"] = round(bf16_bytes / 1e6, 1)
    cell["int8_window_mb"] = round(int8_bytes / 1e6, 1)
    cell["pallas_bf16_gbps"] = round(bf16_bytes / (cell["pallas_bf16_us"] / 1e6) / 1e9, 1)
    cell["pallas_int8_gbps"] = round(int8_bytes / (cell["pallas_int8_us"] / 1e6) / 1e9, 1)
    cell["pallas_vs_xla_bf16"] = round(cell["xla_bf16_us"] / cell["pallas_bf16_us"], 2)
    cell["pallas_vs_xla_int8"] = round(cell["xla_int8_us"] / cell["pallas_int8_us"], 2)
    cell["pallas_int8_vs_best_bf16"] = round(
        min(cell["xla_bf16_us"], cell["pallas_bf16_us"]) / cell["pallas_int8_us"], 2)
    return cell


def main() -> None:
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit("decode_attn_bench.py needs a TPU")
    cells = []
    for b, s in [(8, 1024), (8, 2048), (32, 1024), (32, 2048)]:
        for t in (1, 4):
            cell = bench_cell(b, s, t)
            cells.append(cell)
            print(json.dumps(cell))
    out = {"backend": backend, "chain": [CHAIN_LO, CHAIN_HI], "cells": cells}
    (ROOT / "build").mkdir(exist_ok=True)
    (ROOT / "build" / "DECODE_ATTN.json").write_text(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
