"""Disaggregated prefill/decode A/B: the co-scheduled budgeted loop vs the
role-split engine under a mixed load (ISSUE 9 tentpole).

Both arms run the SAME ServingEngine, weights, paged pool and seeded
traffic trace (the prefill_bench mixed load: steady background decode
streams plus a seeded Poisson burst of prompts); only the role
configuration differs:

  cosched arm:  the PR-2 data plane with a per-tick prefill budget —
                prefill and decode co-scheduled on one loop, admission
                gated on a free decode slot (a burst past the free slots
                queues until retires).
  disagg arm:   ServingConfig.disagg — dedicated PrefillWorker thread(s)
                drain the waiting line, chunk-prefill into slot-less pool
                blocks, deliver first tokens WITHOUT waiting for a slot,
                and hand decode a filled page-table row (zero-copy
                install); the DisaggController re-partitions prefill
                capacity with backlog.

Headline: burst TTFT p99 speedup (cosched/disagg), gated on NOT regressing
background ITL p99 past --itl-slack. Deterministic gates run in every mode
(exit code): the disagg arm hands off (handoffs > 0) with ZERO handoff
copies, the co-scheduled arm stays dormant (handoffs == 0), and BOTH arms
hold the decode-side transfer contract (device_gets_per_tick == 1.0). The
perf gates apply to full runs only (CI boxes are too noisy; --quick keeps
the A/B shape).

Usage:  python benchmarks/disagg_bench.py [--quick] [--slots 8] [--bg 4]
            [--burst 16] [--out DISAGG_r11.json]
Emits:  full artifact JSON on stdout line 1, then the compact one-line
        summary (vtpu/obs/summary.print_summary) as the FINAL stdout line;
        human notes on stderr.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from prefill_bench import BUCKET, run_mixed_arm  # noqa: E402

PAGE = 8


def main() -> None:
    ap = argparse.ArgumentParser("disagg-bench")
    ap.add_argument("--quick", action="store_true",
                    help="CI mode: lighter load, same A/B shape, perf "
                         "gates skipped (deterministic gates still apply)")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--bg", type=int, default=4,
                    help="steady background streams (ITL is measured here)")
    ap.add_argument("--burst", type=int, default=16,
                    help="Poisson burst arrivals (TTFT is measured here)")
    ap.add_argument("--bg-steps", type=int, default=192)
    # burst streams long enough to OCCUPY their slots: the co-scheduled
    # arm's later arrivals then wait for retires (TTFT = slot wait) while
    # the disagg arm prefills ahead and delivers first tokens slot-free —
    # the architectural difference under test, not a prefill-speed race
    ap.add_argument("--burst-steps", type=int, default=32)
    ap.add_argument("--prompt-len", type=int, default=40)
    ap.add_argument("--mean-gap-ms", type=float, default=4.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--itl-slack", type=float, default=1.25,
                    help="background ITL p99 regression bound: disagg must "
                         "stay within this factor of the co-scheduled arm")
    ap.add_argument("--out", default=None,
                    help="also write the artifact JSON to this path")
    a = ap.parse_args()
    if a.quick:
        a.burst, a.bg_steps = min(a.burst, 12), min(a.bg_steps, 160)

    import jax

    if jax.default_backend() != "cpu":
        print("note: running on", jax.default_backend(), file=sys.stderr)
    import jax.numpy as jnp

    from vtpu.models import ModelConfig, init_params
    from vtpu.serving import DisaggConfig, ServingConfig

    # same tiny-model discipline as prefill_bench: per-tick device compute
    # is small, so the A/B isolates what the ROLE SPLIT buys — slot-free
    # prefill-ahead and first-token-before-slot vs slot-gated admission
    # rounded up to a BUCKET multiple: the prefill chunk must divide the
    # context (and BUCKET is a PAGE multiple, so the pool divides too)
    max_seq = -(-(a.bg_steps + BUCKET + 8) // BUCKET) * BUCKET
    cfg = ModelConfig(
        vocab=256, d_model=64, n_heads=2, n_layers=2, d_ff=128,
        max_seq=max_seq, head_dim=32, dtype=jnp.float32, use_pallas=False,
    )
    params = init_params(jax.random.key(0), cfg)
    if a.slots - a.bg < 1:
        sys.exit("--bg must leave at least one free slot for the burst")

    # equal resources in both arms: same paged pool (the dense-equivalent
    # default), same buckets, same chunk — the disagg arm differs only in
    # WHO runs prefill and when
    common = dict(slots=a.slots, prefill_buckets=(BUCKET,),
                  max_new_tokens=a.bg_steps, prefill_chunk=BUCKET,
                  kv_page=PAGE)
    cosched = run_mixed_arm(params, cfg, ServingConfig(
        **common, prefill_budget=2 * BUCKET), a, "cosched", drain=False)
    # the disagg ceiling equals the co-scheduled budget: both arms may
    # inject at most 2*BUCKET prompt tokens between two decode ticks, so
    # the A/B isolates the ROLE SPLIT (slot-free prefill-ahead +
    # first-token-before-slot), not a bigger prefill ration
    disagg = run_mixed_arm(params, cfg, ServingConfig(
        **common,
        disagg=DisaggConfig(min_prefill_tokens=BUCKET,
                            max_prefill_tokens=2 * BUCKET,
                            backlog_high=4, burst_ticks=1)), a, "disagg",
        drain=False)

    ttft_speedup = (cosched["ttft_p99_ms"] / disagg["ttft_p99_ms"]
                    if disagg["ttft_p99_ms"] else None)
    itl_ratio = (disagg["bg_itl_p99_ms"] / cosched["bg_itl_p99_ms"]
                 if cosched["bg_itl_p99_ms"] else None)
    # deterministic gates: always enforced, any mode
    det = {
        "disagg_handed_off": disagg["handoffs"] > 0,
        "handoff_copies_zero": disagg["handoff_copies"] == 0,
        "cosched_dormant": cosched["handoffs"] == 0
        and not cosched["disagg"],
        "device_gets_per_tick_contract":
            cosched["device_gets_per_tick"] == 1.0
            and disagg["device_gets_per_tick"] == 1.0,
    }
    det_ok = all(det.values())
    # perf gates: full runs only (the disagg win must show under burst
    # WITHOUT regressing background ITL past the slack)
    perf = {
        "ttft_p99_improves": bool(ttft_speedup and ttft_speedup > 1.0),
        "bg_itl_p99_within_slack": bool(
            itl_ratio is not None and itl_ratio <= a.itl_slack),
    }
    perf_ok = all(perf.values())
    ok = det_ok and (a.quick or perf_ok)
    print(f"disagg TTFT p99 speedup {ttft_speedup and round(ttft_speedup, 2)}x"
          f"  (bg ITL p99 ratio {itl_ratio and round(itl_ratio, 2)} <= "
          f"{a.itl_slack}: {perf['bg_itl_p99_within_slack']}; "
          f"handoffs {disagg['handoffs']}, copies "
          f"{disagg['handoff_copies']}, repartitions "
          f"{disagg['repartitions']})", file=sys.stderr)
    artifact = {
        "metric": "disagg_burst_ttft_p99_speedup_vs_cosched",
        "value": ttft_speedup and round(ttft_speedup, 3),
        "unit": "x_burst_ttft_p99_vs_cosched_budgeted_loop",
        "pass": bool(ok),
        "deterministic_gates": det,
        "perf_gates": perf,
        "bg_itl_p99_ratio": itl_ratio and round(itl_ratio, 3),
        "itl_slack": a.itl_slack,
        "slots": a.slots, "bg": a.bg, "burst": a.burst,
        "bucket": BUCKET, "kv_page": PAGE, "quick": a.quick,
        "model": {"vocab": cfg.vocab, "d_model": cfg.d_model,
                  "n_layers": cfg.n_layers},
        "arms": [cosched, disagg],
    }
    print(json.dumps(artifact))
    if a.out:
        Path(a.out).write_text(json.dumps(artifact, indent=1))
    from vtpu.obs.summary import print_summary

    print_summary(
        artifact["metric"], artifact["value"],
        "pass" if artifact["pass"] else "fail", unit=artifact["unit"],
        handoff_copies=disagg["handoff_copies"],
        bg_itl_p99_ratio=artifact["bg_itl_p99_ratio"],
        repartitions=disagg["repartitions"],
    )
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    from vtpu.util.jaxcache import place_compile_cache

    place_compile_cache()
    main()
