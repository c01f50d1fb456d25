"""Admission data-plane A/B: sync-serial vs batched-async admission under a
mixed load — steady decode on live slots plus a Poisson burst of bucketed
prompts (ISSUE 2 tentpole).

Both arms run the SAME ServingEngine, weights, and seeded traffic trace;
only the admission configuration differs:

  sync arm:   async_admission=False, prefill_batch_sizes=(1,) — every
              admission is one serial [1, bucket] prefill dispatch PLUS a
              blocking per-admission first-token sync inserted between
              decode ticks (the PR-1 data plane). A K-prompt burst injects
              K dispatch+sync pairs into the pipelined loop.
  async arm:  default batched/async admission with a per-tick prefill
              budget — same-bucket waiting prompts coalesce into one
              [N, bucket] dispatch that samples first tokens ON DEVICE;
              admission performs zero blocking host syncs and the budget
              bounds per-tick prefill work (Sarathi-style co-scheduling).

Per arm: background-stream ITL p50/p99 during the burst window (per-token
delivery gaps observed by client threads), burst TTFT p50/p99, and the
engine's own admission telemetry (the admission phase's mean ms a loop
pass, admission_syncs, prefill_batch_hist). Headline: sync/async background ITL p99 ratio. A
deterministic same-bucket K-burst drain phase also asserts the coalescing
contract: K prompts drain in <= ceil(K/Nmax) prefill dispatches.

Usage:  python benchmarks/prefill_bench.py [--quick] [--slots 8] [--bg 4]
            [--burst 16] [--bg-steps 192] [--prompt-len 40]
Emits:  full artifact JSON on stdout line 1, then the compact one-line
        headline summary (metric/value/verdict — the PR-3 driver-artifact
        convention, shared helper vtpu/obs/summary.py) as the FINAL stdout
        line; human notes on stderr. --quick trims the load for CI while
        keeping the A/B shape.
"""

from __future__ import annotations

import argparse
import json
import random
import statistics
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

BUCKET = 64


def pct(sorted_vals, q):
    if not sorted_vals:
        return None
    return sorted_vals[min(len(sorted_vals) - 1, int(len(sorted_vals) * q))]


def run_mixed_arm(params, cfg, serving, a, name: str,
                  drain: bool = True) -> dict:
    """One mixed-load arm: warmup wave, steady background streams (ITL
    measured by client threads), a seeded Poisson burst (TTFT measured per
    request), and — when ``drain`` — the deterministic same-bucket
    coalescing phase. Shared by this bench's sync/async A/B and by
    benchmarks/disagg_bench.py's co-scheduled/disagg A/B (which skips the
    drain phase: the disagg worker admits through handoffs, not batched
    prefill dispatches, so the dispatch-count bound doesn't apply)."""
    import jax
    import jax.numpy as jnp

    from vtpu.serving import ServingEngine

    bg_free = a.slots - a.bg

    def prompt(seed: int):
        return [int(t) for t in jax.random.randint(
            jax.random.key(seed), (a.prompt_len,), 1, cfg.vocab, jnp.int32)]

    eng = ServingEngine(params, cfg, serving)
    eng.start()
    try:
        # warmup wave: every executable compiled, thread steady state
        for r in [eng.submit(prompt(1 + i), max_new_tokens=4)
                  for i in range(a.slots)]:
            for _ in r.stream():
                pass
        # background streams: client threads record per-token stamps
        bg_reqs = [eng.submit(prompt(100 + i), max_new_tokens=a.bg_steps)
                   for i in range(a.bg)]
        gap_log: list[tuple[float, float]] = []
        lock = threading.Lock()

        def consume_bg(req):
            last = None
            for _ in req.stream():
                now = time.perf_counter()
                if last is not None:
                    with lock:
                        gap_log.append((now, now - last))
                last = now

        bg_threads = [threading.Thread(target=consume_bg, args=(r,))
                      for r in bg_reqs]
        for t in bg_threads:
            t.start()
        time.sleep(0.05)  # let the pool reach steady decode
        # Poisson burst: seeded arrivals, TTFT measured per request
        rng = random.Random(a.seed)
        ttfts: list[float] = []
        burst_threads = []

        def consume_burst(req, t0):
            first = True
            for _ in req.stream():
                if first:
                    with lock:
                        ttfts.append(time.perf_counter() - t0)
                    first = False

        t_burst0 = time.perf_counter()
        for i in range(a.burst):
            t0 = time.perf_counter()
            req = eng.submit(prompt(1000 + i),
                             max_new_tokens=a.burst_steps)
            th = threading.Thread(target=consume_burst, args=(req, t0))
            th.start()
            burst_threads.append(th)
            time.sleep(rng.expovariate(1000.0 / a.mean_gap_ms) / 1000.0)
        for th in burst_threads:
            th.join()
        t_burst1 = time.perf_counter()
        drain_dispatches = None
        if drain:
            # deterministic coalescing phase: occupy every non-background
            # slot with blockers, queue K same-bucket prompts behind them,
            # then cancel the blockers — all K wait together and the freed
            # slots return in ONE retire sweep, so the burst must drain in
            # <= ceil(K/Nmax) prefill dispatches (Nmax = the largest
            # warmed batch the per-tick budget admits while decoding)
            blockers = [eng.submit(prompt(3000 + i), max_new_tokens=256)
                        for i in range(bg_free)]
            blocker_streams = [iter(r.stream()) for r in blockers]
            for s in blocker_streams:
                next(s)  # every blocker slot admitted and streaming
            hist0 = eng.stats()["prefill_batch_hist"]
            drain_reqs = [eng.submit(prompt(2000 + i), max_new_tokens=2)
                          for i in range(bg_free)]
            for r in blockers:
                r.cancel()
            for r in drain_reqs:
                for _ in r.stream():
                    pass
            hist1 = eng.stats()["prefill_batch_hist"]
            drain_dispatches = sum(b1 - b0 for b0, b1 in zip(hist0, hist1))
        for r in bg_reqs:
            r.cancel()
        for t in bg_threads:
            t.join()
        stats = eng.stats()
    finally:
        eng.stop()
    burst_gaps = sorted(g * 1e3 for ts, g in gap_log
                        if t_burst0 <= ts <= t_burst1)
    all_gaps = sorted(g * 1e3 for _, g in gap_log)
    ttfts_ms = sorted(t * 1e3 for t in ttfts)
    # largest batch a single dispatch may carry while decoding: warmed
    # sizes capped by the free slots and by the per-tick prefill budget
    budget = serving.prefill_budget
    fit = [s for s in eng._admit_sizes
           if s <= bg_free and (not budget or s * BUCKET <= budget)]
    nmax = max(fit) if fit else 1
    out = {
        "arm": name,
        "bg_itl_p50_ms": round(pct(burst_gaps, 0.50) or 0.0, 3),
        "bg_itl_p99_ms": round(pct(burst_gaps, 0.99) or 0.0, 3),
        "bg_itl_p99_ms_full_run": round(pct(all_gaps, 0.99) or 0.0, 3),
        "ttft_p50_ms": round(pct(ttfts_ms, 0.50) or 0.0, 3),
        "ttft_p99_ms": round(pct(ttfts_ms, 0.99) or 0.0, 3),
        "ttft_runs": len(ttfts_ms),
        "drain_prompts": bg_free if drain else None,
        "drain_dispatches": drain_dispatches,
        "drain_dispatch_bound": -(-bg_free // nmax) if drain else None,
        "admission_syncs": stats["admission_syncs"],
        "admission_ms_per_pass": stats["tick_phase_ms"]["admission"]["mean_ms"],
        "prefill_batch_hist": stats["prefill_batch_hist"],
        "batched_admission": stats["batched_admission"],
        # TTFT attribution (the trace-substrate split) + the disagg
        # handoff contract counters — zero / None on co-scheduled arms
        "queue_wait_p99_ms": stats["queue_wait_p99_ms"],
        "prefill_exec_p99_ms": stats["prefill_exec_p99_ms"],
        "disagg": stats["disagg"],
        "handoffs": stats["handoffs"],
        "handoff_copies": stats["handoff_copies"],
        "repartitions": stats["repartitions"],
        "device_gets_per_tick": stats["device_gets_per_tick"],
    }
    print(f"{name:>7}: bg ITL p99 {out['bg_itl_p99_ms']:8.2f} ms, "
          f"TTFT p50 {out['ttft_p50_ms']:7.2f} ms, p99 "
          f"{out['ttft_p99_ms']:7.2f} ms, "
          f"{out['admission_syncs']} admission syncs, "
          f"hist {out['prefill_batch_hist']}", file=sys.stderr)
    return out


def main() -> None:
    ap = argparse.ArgumentParser("prefill-bench")
    ap.add_argument("--quick", action="store_true",
                    help="CI mode: lighter load, same A/B shape")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--bg", type=int, default=4,
                    help="steady background streams (ITL is measured here)")
    ap.add_argument("--burst", type=int, default=16,
                    help="Poisson burst arrivals (TTFT is measured here)")
    ap.add_argument("--bg-steps", type=int, default=192,
                    help="background stream length in tokens")
    ap.add_argument("--burst-steps", type=int, default=4,
                    help="tokens per burst request (short: slots recycle)")
    ap.add_argument("--prompt-len", type=int, default=40)
    ap.add_argument("--mean-gap-ms", type=float, default=4.0,
                    help="mean Poisson inter-arrival gap for the burst")
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()
    if a.quick:
        a.burst, a.bg_steps = min(a.burst, 12), min(a.bg_steps, 160)

    import jax

    if jax.default_backend() != "cpu":
        # the A/B isolates host-side admission stalls; CPU-calibrated
        print("note: running on", jax.default_backend(), file=sys.stderr)
    import jax.numpy as jnp

    from vtpu.models import ModelConfig, init_params
    from vtpu.serving import ServingConfig

    # Tiny on purpose (same scale as decode_bench): per-tick device compute
    # is small, so the A/B isolates what ADMISSION costs the tick loop —
    # serial dispatch+sync pairs vs one batched async dispatch.
    cfg = ModelConfig(
        vocab=256, d_model=64, n_heads=2, n_layers=2, d_ff=128,
        max_seq=a.bg_steps + BUCKET + 8, head_dim=32, dtype=jnp.float32,
        use_pallas=False,
    )
    params = init_params(jax.random.key(0), cfg)
    bg_free = a.slots - a.bg
    if bg_free < 1:
        sys.exit("--bg must leave at least one free slot for the burst")

    common = dict(slots=a.slots, prefill_buckets=(BUCKET,),
                  max_new_tokens=a.bg_steps)
    sync = run_mixed_arm(params, cfg, ServingConfig(
        **common, async_admission=False, prefill_batch_sizes=(1,)), a, "sync")
    async_ = run_mixed_arm(params, cfg, ServingConfig(
        **common, prefill_budget=2 * BUCKET), a, "async")
    ratio = (sync["bg_itl_p99_ms"] / async_["bg_itl_p99_ms"]
             if async_["bg_itl_p99_ms"] else None)
    coalesced = async_["drain_dispatches"] <= async_["drain_dispatch_bound"]
    print(f"batched-async admission ITL p99 speedup: "
          f"{ratio and round(ratio, 2)}x  (coalescing bound "
          f"{async_['drain_dispatches']} <= {async_['drain_dispatch_bound']}: "
          f"{coalesced})", file=sys.stderr)
    artifact = {
        "metric": "batched_async_admission_itl_p99_speedup",
        "value": ratio and round(ratio, 3),
        "unit": "x_bg_itl_p99_vs_sync_serial",
        "pass": bool(ratio and ratio >= 1.5 and coalesced
                     and async_["admission_syncs"] == 0),
        "slots": a.slots, "bg": a.bg, "burst": a.burst,
        "bucket": BUCKET, "quick": a.quick,
        "model": {"vocab": cfg.vocab, "d_model": cfg.d_model,
                  "n_layers": cfg.n_layers},
        "arms": [sync, async_],
    }
    # artifact on stdout line 1, then the compact headline as the FINAL
    # line (the PR-3 convention, shared implementation in
    # vtpu/obs/summary.py) — this bench predates the convention and used
    # to emit a bare multi-line artifact
    print(json.dumps(artifact))
    from vtpu.obs.summary import print_summary

    print_summary(
        artifact["metric"], artifact["value"],
        "pass" if artifact["pass"] else "fail", unit=artifact["unit"],
        coalescing_bound_held=coalesced,
        admission_syncs_async=async_["admission_syncs"],
    )


if __name__ == "__main__":
    from vtpu.util.jaxcache import place_compile_cache

    place_compile_cache()
    main()
