"""Speculative decoding measured END TO END through the live ServingEngine: tokens/s, spec vs plain, on workloads with REAL
acceptance profiles — repetition-heavy (prompt-lookup drafts verify),
non-repetitive random (drafts rarely verify; the adaptive gate must shut
drafting off), and a 50/50 mix.

Batch rows: 8 AND 32 are both first-class. ``--quick`` is the CI mode: the
tiny model at the requested batches with short streams, so the batch-32
path is exercised end to end on every build even without a chip —
wall-clock claims come only from chip runs. Without ``--quick`` the script
needs a TPU and says so otherwise.

The artifact reports wall tokens/s AND device tick counts.

Writes build/SPEC_SERVING.json on TPU (or wherever --out points).
Run on the chip (one process a chip).
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

PHRASE = [17, 93, 210, 467, 31, 88, 1500, 72]  # repeated -> lookup-hit heaven


def build_prompt(kind: str, rng, vocab: int, n: int) -> list[int]:
    if kind == "rep":
        return (PHRASE * (n // len(PHRASE) + 1))[:n]
    return [int(x) for x in rng.randint(0, vocab, (n,))]


def run_workload(eng, prompts, max_new: int) -> dict:
    t0 = time.perf_counter()
    reqs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    streams = [list(r.stream()) for r in reqs]
    wall = time.perf_counter() - t0
    toks = sum(len(s) for s in streams)
    return {"wall_s": round(wall, 2), "tokens": toks,
            "tokens_per_sec": round(toks / wall, 1), "streams": streams}


def parse_args() -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="CI mode: tiny model, short streams, but the real "
                         "engine at the requested batches (incl. 32)")
    ap.add_argument("--batches", default=None,
                    help="comma-separated batch rows (default: 8,32)")
    ap.add_argument("--workloads", default=None,
                    help="comma-separated subset of rep,rand,mix")
    ap.add_argument("--max-new", type=int, default=None,
                    help="decode tokens per request")
    ap.add_argument("--out", default=None,
                    help="artifact path (default build/SPEC_SERVING.json "
                         "on TPU; quick runs only write when set)")
    return ap.parse_args()


def main() -> None:
    a = parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from vtpu.models import ModelConfig, init_params
    from vtpu.serving.engine import ServingConfig, ServingEngine

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not a.quick:
        raise SystemExit(
            f"spec_serving_bench.py needs a TPU, JAX found "
            f"{jax.default_backend()!r}; --quick runs the tiny-model "
            "exerciser")
    if not a.quick:
        cfg = ModelConfig(
            vocab=8192, d_model=1024, n_heads=8, n_layers=12, d_ff=4096,
            max_seq=1280, head_dim=128, dtype=jnp.bfloat16, use_pallas=True,
        )
        batches = (8, 32)
        plen, max_new = 256, 96
        workloads = ("rep", "rand", "mix")
    else:
        # quick: the tiny model, but REAL batch rows — a 32-slot engine
        # admits, speculates, and retires 32 concurrent streams end to end
        cfg = ModelConfig(
            vocab=512, d_model=128, n_heads=4, n_layers=2, d_ff=256,
            max_seq=160, head_dim=32, dtype=jnp.float32, use_pallas=False,
        )
        batches = (8, 32)
        plen, max_new = 32, 12
        workloads = ("mix",)  # quick keeps one mixed row per batch
    if a.batches:
        batches = tuple(int(b) for b in a.batches.split(","))
    if a.workloads:
        workloads = tuple(a.workloads.split(","))
    if a.max_new:
        max_new = a.max_new

    params = jax.jit(lambda k: init_params(k, cfg))(jax.random.key(0))
    jax.block_until_ready(params)
    rng = np.random.RandomState(0)
    out = {"backend": jax.default_backend(),
           "device_kind": jax.devices()[0].device_kind,
           "model": "tiny" if a.quick else "d1024 L12 h8 bf16",
           "quick": bool(a.quick), "cells": []}
    if a.quick:
        out["scope_note"] = (
            "quick mode: real engine + real batch rows (incl. 32) at "
            "tiny-model scale — an end-to-end exerciser of the batch-32 "
            "speculation path, not a chip-throughput claim")

    for b in batches:
        for workload in workloads:
            kinds = ({"rep": ["rep"] * b, "rand": ["rand"] * b,
                      "mix": (["rep", "rand"] * b)[:b]}[workload])
            prompts = [build_prompt(k, rng, cfg.vocab, plen) for k in kinds]
            cell = {"batch": b, "workload": workload,
                    "prompt_len": plen, "max_new": max_new}
            for spec in (0, 4):
                scfg = ServingConfig(
                    slots=b, prefill_buckets=(plen,), max_new_tokens=max_new,
                    spec_tokens=spec)
                # warm the executables on a THROWAWAY engine so
                # the measured engine's tick counters describe only the
                # measured workload (jax's compile cache is process-global)
                warm = ServingEngine(params, cfg, scfg)
                warm.start()
                try:
                    run_workload(warm, prompts[:2], 8)
                finally:
                    warm.stop()
                eng = ServingEngine(params, cfg, scfg)
                eng.start()
                try:
                    r = run_workload(eng, prompts, max_new)
                    stats = eng.stats()
                finally:
                    eng.stop()
                key = "spec" if spec else "plain"
                cell[key] = {
                    "wall_s": r["wall_s"], "tokens": r["tokens"],
                    "tokens_per_sec": r["tokens_per_sec"],
                    "device_ticks": stats["decode_ticks"] + stats["spec_ticks"],
                    "decode_ticks": stats["decode_ticks"],
                    "spec_ticks": stats["spec_ticks"],
                    "mean_emitted_per_spec_tick":
                        stats.get("mean_emitted_per_spec_tick"),
                    "spec_emitted_hist": stats.get("spec_emitted_hist"),
                }
                if spec:
                    plain_streams = cell.pop("_plain_streams")
                    cell["streams_identical_to_plain"] = (
                        r["streams"] == plain_streams)
                    # On bf16 the verify matmul (width k+1) and the decode
                    # matmul (width 1) reduce in different orders, so argmax
                    # near-ties can flip; once one token flips the
                    # continuations legitimately differ, so the meaningful
                    # stats are how many streams diverged and where — not a
                    # bare boolean. Exactness under deterministic f32 is
                    # tests/test_serving.py::
                    # test_spec_decode_stream_identical_to_plain.
                    first_div = []
                    for s, p in zip(r["streams"], plain_streams):
                        d = next((i for i in range(min(len(s), len(p)))
                                  if s[i] != p[i]), None)
                        if d is not None:
                            first_div.append(d)
                    cell["diverged_streams"] = (
                        f"{len(first_div)}/{len(plain_streams)}")
                    cell["first_divergence_median"] = (
                        sorted(first_div)[len(first_div) // 2]
                        if first_div else None)
                else:
                    cell["_plain_streams"] = r["streams"]
            cell["measured_wall_speedup"] = round(
                cell["spec"]["tokens_per_sec"]
                / max(cell["plain"]["tokens_per_sec"], 1e-9), 2)
            cell["measured_tick_reduction"] = round(
                cell["plain"]["device_ticks"]
                / max(cell["spec"]["device_ticks"], 1), 2)
            out["cells"].append(cell)
            print(json.dumps(cell), flush=True)

    out_path = a.out
    if out_path is None and not a.quick:
        (REPO / "build").mkdir(exist_ok=True)
        out_path = str(REPO / "build" / "SPEC_SERVING.json")
    if out_path:
        pathlib.Path(out_path).write_text(json.dumps(out, indent=1))
    print(json.dumps({"cells": len(out["cells"]),
                      "batches": list(batches), "quick": bool(a.quick)}))


if __name__ == "__main__":
    main()
