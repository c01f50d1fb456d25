"""The rate sweep that finds an open-loop cell's knee, run once by the PR
that defines the cell (the rate then stands as a number in the mix's file).

    python -m vbench.sweep --workload olmoe_chat --seed 3 --seconds 30 \
        --rates 2,3,4,5,6

One engine, built once; for each rate the mix's own schedule at that rate,
a window of ``--seconds``, then a drain until the engine is idle. Prints a
JSON line a rate: time to first token (median, 95th percentile, and the
95th percentile of each half of the window: a backlog that grows shows as
the second half above the first), gaps, tokens a second, and the requests
still without a first token when the window closed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from vbench import manifest, run, stamps, traffic  # noqa: E402
from vbench.client import Client  # noqa: E402


def one_rate(eng, mix: dict, rate: float, seed: int, vocab: int,
             seconds: float) -> dict:
    mix = dict(mix, rate_per_s=rate)
    client = Client(eng)
    t0 = run.now() + mix["ramp_s"]
    sender = client.run_open(
        traffic.open_schedule(mix, seed, vocab, seconds), t0)
    run.sleep_until(t0 + seconds)
    owed = client.first_tokens_owed(t0 + seconds)
    sender.join(timeout=5)
    end = run.now() + 120
    while run.now() < end:
        s = eng.stats()
        if not s["active_slots"] and not s["queued"] and \
                not s["admitting_slots"]:
            break
        time.sleep(0.1)
    drained_s = run.now() - (t0 + seconds)
    client.close()
    client.join(10)
    recs = client.records()
    tt = stamps.ttfts(recs, seconds + drained_s)
    half = [[r.stamps[0] - r.due_s for r in recs if r.in_window and r.stamps
             and lo <= r.due_s < hi]
            for lo, hi in ((0, seconds / 2), (seconds / 2, seconds))]
    gaps = stamps.window_gaps(recs, 0.0, seconds)
    return {
        "rate_per_s": rate, "due": len(tt),
        "ttft_p50_ms": 1e3 * stamps.percentile(tt, 0.5),
        "ttft_p95_ms": 1e3 * stamps.percentile(tt, 0.95),
        "ttft_p95_ms_halves": [1e3 * (stamps.percentile(h, 0.95) or 0)
                               for h in half],
        "itl_mean_ms": 1e3 * sum(gaps) / max(1, len(gaps)),
        "itl_p95_ms": 1e3 * (stamps.percentile(gaps, 0.95) or 0),
        "out_tokens_per_s": stamps.window_tokens(recs, 0, seconds) / seconds,
        "owed_first_tokens_at_close": owed,
        "drain_s": drained_s,
        "not_ok": sum(1 for r in recs if r.status != "OK"),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    run.place_cache()
    man = manifest.load(ROOT)
    cell = manifest.cell(man, args.workload)
    run.find_devices(cell["chips"])
    cfg = manifest.config(man, ROOT, cell["config"])
    mix = traffic.load_mix(cell["traffic"], ROOT)
    eng, _ = run.build_engine(cfg, args.seed)
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            row = one_rate(eng, mix, rate, args.seed, cfg["vocab_size"],
                           args.seconds)
            print(json.dumps(row), flush=True)
            if row["owed_first_tokens_at_close"] > 0.1 * row["due"]:
                break  # past the knee: the backlog grew through the window
    finally:
        eng.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
