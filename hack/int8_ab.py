"""int8-KV vs bf16-KV decode A/B at the target cells
({batch 8, 32} x {window 1024, 2048}), with INTERLEAVED repeats so the
verdict per cell is a median with a visible spread, not one draw.

Both arms run the dense XLA trunk path with two-chain-difference timing
(benchmarks/mfu_bench.py timed_per_iter: the fixed per-call cost cancels).
Needs a TPU; writes build/INT8_AB.json (INT8_AB_r05.json at the repo root
is the round-5 run of this script, the basis of choose_kv_int8).
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
import statistics
import sys

import jax
import jax.numpy as jnp

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from benchmarks.mfu_bench import bench_decode  # noqa: E402
from vtpu.models import ModelConfig  # noqa: E402

REPEATS = 5


def main() -> None:
    if jax.default_backend() != "tpu":
        raise SystemExit("int8_ab.py needs a TPU")
    cfg = ModelConfig(
        vocab=8192, d_model=1024, n_heads=8, n_layers=12, d_ff=4096,
        max_seq=2048, head_dim=128, dtype=jnp.bfloat16, use_pallas=True,
    )
    cfg_q = dataclasses.replace(cfg, kv_int8=True)
    cells = []
    for b, bkt in ((8, 1024), (8, 0), (32, 1024), (32, 0)):
        bf16_ms: list[float] = []
        int8_ms: list[float] = []
        for r in range(REPEATS):
            # interleave arms so drift lands on both equally
            for base, out in ((cfg, bf16_ms), (cfg_q, int8_ms)):
                row = bench_decode(base, b, 128, 64, kv_bucket=bkt)
                out.append(row["ms_per_step"])
        cell = {
            "batch": b, "window": bkt or cfg.max_seq,
            "bf16_ms_per_step": sorted(round(x, 3) for x in bf16_ms),
            "int8_ms_per_step": sorted(round(x, 3) for x in int8_ms),
            "bf16_median_ms": round(statistics.median(bf16_ms), 3),
            "int8_median_ms": round(statistics.median(int8_ms), 3),
        }
        cell["int8_speedup"] = round(
            cell["bf16_median_ms"] / cell["int8_median_ms"], 3)
        cell["int8_wins_or_ties"] = (
            cell["int8_median_ms"]
            <= cell["bf16_median_ms"] * 1.03)  # ties within run noise
        cells.append(cell)
        print(json.dumps(cell), flush=True)
    out = {
        "what": "int8-KV vs bf16-KV decode, default trunk path, "
                f"{REPEATS} interleaved repeats per arm per cell, "
                "two-chain-difference timing",
        "cells": cells,
        "all_cells_win_or_tie": all(c["int8_wins_or_ties"] for c in cells),
    }
    (ROOT / "build").mkdir(exist_ok=True)
    (ROOT / "build" / "INT8_AB.json").write_text(
        json.dumps(out, indent=1) + "\n")
    print(json.dumps({"all_cells_win_or_tie": out["all_cells_win_or_tie"]}))


if __name__ == "__main__":
    main()
