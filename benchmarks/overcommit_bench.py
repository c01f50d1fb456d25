"""KV overcommit oversubscription sweep (ISSUE 6 tentpole).

The paged pool (PR 4/5) virtualized sequence memory but admission still
hard-capped residency: a full pool parks new work until a retire. The
overcommit subsystem (ServingConfig.kv_swap) turns that wall into a
hierarchy — parked conversations' private pages evict to a pinned host
pool (async D2H), resume swaps them back (async H2D) or rebuilds short /
dropped sequences through the prefill path — so one engine holds MANY
times more parked sessions than its HBM pool has blocks.

This bench drives that loop end to end and answers the ROADMAP question:
**live:parked ratio vs resume latency**. For each oversubscription ratio R
(total parked pages = R x pool blocks):

  1. sessions admit in waves of `slots`, stream a few tokens, and park;
     pool pressure from the next wave evicts the parked pages (the host
     tier is sized to hold ~half of them, so the sweep exercises BOTH
     restore paths: swap-in for spilled pages, recompute-on-fault for
     dropped ones);
  2. every session is resumed; the time from resume() to its next token
     is the resume latency (p50/p99 reported per ratio);
  3. every stream must be TOKEN-EQUAL to an unconstrained reference run —
     oversubscription must never change what a session says, only when.

Deterministic gates (exit code): token equality at every ratio; at the
top ratio nonzero swap-out bytes AND nonzero fault recomputes (both
restore paths actually ran); the decode tick transfer contract intact
(device_gets_per_tick == 1.0 — the swap path performs no blocking fetch
on the tick path). Full runs additionally gate a bounded resume p99.

Usage:  python benchmarks/overcommit_bench.py [--quick] [--ratios 2,4,8]
            [--page P] [--slots S] [--prompt-len N] [--max-new N] [--out F]
Emits:  full artifact JSON on stdout line 1, then the compact one-line
        summary (metric/value/verdict — the PR-3 driver-artifact
        convention) as the FINAL stdout line; human notes on stderr.
"""

from __future__ import annotations

import argparse
import json
import queue as _queue
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> None:
    ap = argparse.ArgumentParser("overcommit-bench")
    ap.add_argument("--quick", action="store_true",
                    help="CI mode: single 4x ratio, lighter trace")
    ap.add_argument("--ratios", default=None,
                    help="comma-separated oversubscription ratios "
                         "(parked pages : pool blocks); default 2,4,8 "
                         "(quick: 4)")
    ap.add_argument("--page", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4,
                    help="live decode slots (one wave's concurrency)")
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=24,
                    help="decode tokens per session")
    ap.add_argument("--park-after", type=int, default=2,
                    help="tokens a session streams before parking")
    ap.add_argument("--resume-p99-bar-ms", type=float, default=1000.0,
                    help="full runs gate resume p99 under this bound")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None,
                    help="artifact path (default OVERCOMMIT_r09.json on "
                         "full runs; quick runs only write when set)")
    a = ap.parse_args()
    if a.quick:
        a.max_new = min(a.max_new, 12)
    ratios = [int(x) for x in a.ratios.split(",")] if a.ratios else (
        [4] if a.quick else [2, 4, 8])

    import jax
    import jax.numpy as jnp

    from vtpu.models import ModelConfig, init_params
    from vtpu.obs.tickprof import host_ms_per_tick
    from vtpu.serving import ServingConfig, ServingEngine

    # tiny on purpose (see paged_kv_bench): a CPU tick is dominated by
    # fixed dispatch overhead, the regime where a TPU's latency-bound
    # decode tick also lives — resume latency then measures the overcommit
    # machinery, not model FLOPs
    cfg = ModelConfig(
        vocab=128, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq=a.max_seq, head_dim=16, dtype=jnp.float32, use_pallas=False,
    )
    params = init_params(jax.random.key(0), cfg)
    bucket = max(16, a.prompt_len, a.page)
    pages_per = -(-(a.prompt_len + a.max_new) // a.page)
    pool_blocks = a.slots * pages_per  # exactly one live wave fits

    def prompt(seed: int):
        return [int(t) for t in jax.random.randint(
            jax.random.key(seed), (a.prompt_len,), 1, cfg.vocab, jnp.int32)]

    def reference(n_sessions: int) -> list[list[int]]:
        eng = ServingEngine(params, cfg, ServingConfig(
            slots=a.slots, prefill_buckets=(bucket,),
            max_new_tokens=a.max_new, prefill_chunk=bucket,
            kv_page=a.page))
        eng.start()
        try:
            reqs = [eng.submit(prompt(100 + i), max_new_tokens=a.max_new)
                    for i in range(n_sessions)]
            return [list(r.stream()) for r in reqs]
        finally:
            eng.stop()

    def drain_nowait(req, out: list) -> None:
        while True:
            try:
                tok = req.out.get_nowait()
            except _queue.Empty:
                return
            assert tok is not None, "session ended while parked"
            out.append(tok)

    def run_ratio(ratio: int) -> dict:
        n_sessions = ratio * pool_blocks // pages_per
        # host tier sized to ~half the parked pages: evictions beyond it
        # DROP and resume recomputes — both restore paths in one sweep
        host_blocks = max((n_sessions * pages_per) // 2, 1)
        serving = ServingConfig(
            slots=a.slots, prefill_buckets=(bucket,),
            max_new_tokens=a.max_new, prefill_chunk=bucket,
            kv_page=a.page, kv_pool_blocks=pool_blocks,
            kv_swap=host_blocks)
        eng = ServingEngine(params, cfg, serving)
        eng.start()
        sessions = [{"req": None, "tokens": []} for _ in range(n_sessions)]
        t_start = time.perf_counter()
        try:
            parked = 0
            for w0 in range(0, n_sessions, a.slots):
                wave = sessions[w0:w0 + a.slots]
                for i, s in enumerate(wave):
                    s["req"] = eng.submit(prompt(100 + w0 + i),
                                          max_new_tokens=a.max_new)
                for s in wave:
                    while len(s["tokens"]) < a.park_after:
                        s["tokens"].append(s["req"].out.get(timeout=60))
                for s in wave:
                    eng.park(s["req"])
                parked += len(wave)
                t0 = time.perf_counter()
                while eng.stats()["parked_sessions"] < parked:
                    # deadlock guard, not a latency gate: the first park
                    # compiles the swap executables, and under the smoke
                    # tier this bench shares a wave with the UNCACHED
                    # tp2 compile — 60s has been seen exceeded by
                    # scheduler starvation alone on a loaded 2-core box
                    assert time.perf_counter() - t0 < 180, "park stalled"
                    time.sleep(0.002)
            # production stopped: collect whatever was delivered pre-park
            for s in sessions:
                drain_nowait(s["req"], s["tokens"])
            mid = eng.stats()
            resume_ms = []
            for s in sessions:
                t0 = time.perf_counter()
                eng.resume(s["req"])
                tok = s["req"].out.get(timeout=120)  # first post-resume token
                resume_ms.append((time.perf_counter() - t0) * 1e3)
                assert tok is not None, "stream ended at resume"
                s["tokens"].append(tok)
                for tok in s["req"].stream():
                    s["tokens"].append(tok)
            wall = time.perf_counter() - t_start
            stats = eng.stats()
        finally:
            eng.stop()
        refs = reference(n_sessions)
        token_equal = all(
            s["tokens"] == ref for s, ref in zip(sessions, refs))
        complete = all(len(s["tokens"]) == a.max_new for s in sessions)
        resume_ms.sort()
        row = {
            "ratio": ratio,
            "sessions": n_sessions,
            "pool_blocks": pool_blocks,
            "parked_pages_total": n_sessions * pages_per,
            "swap_host_blocks": host_blocks,
            "wall_s": round(wall, 3),
            "token_equal_vs_unconstrained": token_equal,
            "all_sessions_complete": complete,
            "resume_p50_ms": round(resume_ms[len(resume_ms) // 2], 2),
            "resume_p99_ms": round(
                resume_ms[min(len(resume_ms) - 1,
                              int(len(resume_ms) * 0.99))], 2),
            "parks": stats["parks"],
            "resumes": stats["resumes"],
            "evicted_blocks": stats["evicted_blocks"],
            "swap_out_bytes": stats["swap_out_bytes"],
            "swap_in_bytes": stats["swap_in_bytes"],
            "swap_faults": stats["swap_faults"],
            "fault_recomputes": stats["fault_recomputes"],
            "pool_blocked_admissions": stats["pool_blocked_admissions"],
            "pool_blocked_resumes": stats["pool_blocked_resumes"],
            "kv_pool_used_hwm": stats["kv_pool_used_hwm"],
            "parked_peak_vs_pool": round(
                n_sessions * pages_per / pool_blocks, 2),
            "device_gets_per_tick": stats["device_gets_per_tick"],
            "host_ms_per_tick": host_ms_per_tick(stats["tick_phase_ms"]),
        }
        print(f"ratio {ratio}x: {n_sessions} sessions over "
              f"{pool_blocks} blocks — resume p50 {row['resume_p50_ms']}ms "
              f"p99 {row['resume_p99_ms']}ms, "
              f"{row['evicted_blocks']} evicted, "
              f"{row['swap_faults']} faults "
              f"({row['fault_recomputes']} recomputed), "
              f"equal={token_equal}", file=sys.stderr)
        return row

    rows = [run_ratio(r) for r in ratios]
    top = rows[-1]
    ok = (
        all(r["token_equal_vs_unconstrained"]
            and r["all_sessions_complete"] for r in rows)
        and top["swap_out_bytes"] > 0
        and top["fault_recomputes"] > 0
        and all(r["device_gets_per_tick"] == 1.0 for r in rows)
    )
    p99_ok = top["resume_p99_ms"] <= a.resume_p99_bar_ms
    artifact = {
        "metric": "kv_overcommit_resume_p99_ms_at_top_ratio",
        "value": top["resume_p99_ms"],
        "unit": f"ms_at_{top['ratio']}x_oversubscription",
        "pass": bool(ok and (a.quick or p99_ok)),
        "resume_p99_bar_ms": a.resume_p99_bar_ms,
        "page": a.page,
        "slots": a.slots,
        "prompt_len": a.prompt_len,
        "max_new": a.max_new,
        "park_after": a.park_after,
        "quick": a.quick,
        "model": {"vocab": cfg.vocab, "d_model": cfg.d_model,
                  "n_heads": cfg.n_heads, "n_layers": cfg.n_layers,
                  "max_seq": cfg.max_seq},
        "sweep": rows,
    }
    out_path = a.out or (None if a.quick else "OVERCOMMIT_r09.json")
    if out_path:
        Path(out_path).write_text(json.dumps(artifact, indent=2) + "\n")
    print(json.dumps(artifact))
    # compact headline as the FINAL stdout line (PR-3 convention, shared
    # implementation in vtpu/obs/summary.py)
    from vtpu.obs.summary import print_summary

    print_summary(
        artifact["metric"], artifact["value"],
        "pass" if artifact["pass"] else "fail", unit=artifact["unit"],
        top_ratio=top["ratio"],
        sessions_vs_pool_blocks=f"{top['sessions']}x{top['pool_blocks']}",
        token_equal=top["token_equal_vs_unconstrained"],
        swap_out_bytes=top["swap_out_bytes"],
        fault_recomputes=top["fault_recomputes"],
        device_gets_per_tick=top["device_gets_per_tick"],
    )
    # token equality + both-restore-paths + tick contract gate ALWAYS
    # (deterministic); the resume-p99 bound gates full runs only (quick CI
    # boxes are too noisy for a latency bar)
    if not ok or (not a.quick and not p99_ok):
        sys.exit(1)


if __name__ == "__main__":
    from vtpu.util.jaxcache import place_compile_cache

    place_compile_cache()
    main()
