"""Streaming TTFT benchmark client.

Parity: reference benchmarks/ai-benchmark/benchmark.py — N warmup requests,
then M timed requests against a streaming endpoint; per-request TTFT is the
wall time from request start to the first streamed token, per-token latency
the mean gap between subsequent tokens. One JSON object per timed request is
appended to --out (JSONL), which report.py aggregates.

A request that streams no first token, is refused, or whose server-reported
terminal is not OK is a FAILURE: it carries ``"failed"`` and no ``ttft_ms``
(so it never enters a percentile as a 0 ms sample), is counted in the
summary, and makes the run exit non-zero.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
import urllib.request

# one percentile convention for the whole benchmark pair: report.py owns it
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from report import pct  # noqa: E402


def one_request(url: str, prompt_len: int, max_tokens: int,
                timeout: float = 120.0) -> dict:
    """One streamed request. On failure the record has ``"failed"`` (the
    reason) and no ``ttft_ms``; ``http`` and ``status`` are what the server
    said (``status`` None from a server that sends no terminal line)."""
    body = json.dumps({"prompt_len": prompt_len, "max_tokens": max_tokens}).encode()
    req = urllib.request.Request(
        f"{url}/generate", data=body, headers={"Content-Type": "application/json"}
    )
    start = time.monotonic()
    stamps: list[float] = []
    http = status = None
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            http = resp.status
            for raw in resp:
                if raw.startswith(b"data: "):
                    stamps.append(time.monotonic())
                elif raw.startswith(b"status: "):
                    status = raw[len(b"status: "):].strip().decode()
    except OSError as exc:  # refused, HTTP error status, timeout
        http = getattr(exc, "code", http)
        failed = repr(exc)
    else:
        failed = ("no first token" if not stamps
                  else f"status {status}" if status not in (None, "OK")
                  else None)
    if failed:
        return {"failed": failed, "http": http, "status": status,
                "tokens": len(stamps), "ts": time.time()}
    gaps = [b - a for a, b in zip(stamps, stamps[1:])]
    return {
        "ttft_ms": (stamps[0] - start) * 1e3,
        "http": http,
        "status": status,
        "tokens": len(stamps),
        "per_token_ms": statistics.mean(gaps) * 1e3 if gaps else 0.0,
        # raw inter-token gaps: report.py aggregates run-level ITL
        # percentiles from these (a per-request mean hides tail stalls —
        # exactly what admission bursts inflict)
        "gaps_ms": [round(g * 1e3, 3) for g in gaps],
        "total_ms": (stamps[-1] - start) * 1e3,
        "ts": time.time(),
    }


def main() -> None:
    parser = argparse.ArgumentParser("ttft-benchmark")
    parser.add_argument("--url", default="http://127.0.0.1:8100")
    parser.add_argument("--warmup", type=int, default=30)
    parser.add_argument("--runs", type=int, default=200)
    parser.add_argument("--prompt-len", type=int, default=1024)
    parser.add_argument("--max-tokens", type=int, default=16)
    parser.add_argument("--interval", type=float, default=0.0,
                        help="seconds between request starts (0 = back to back)")
    parser.add_argument("--out", default="ttft.jsonl")
    parser.add_argument("--label", default="")
    args = parser.parse_args()

    for i in range(args.warmup):
        one_request(args.url, args.prompt_len, args.max_tokens)
        print(f"warmup {i + 1}/{args.warmup}", end="\r", file=sys.stderr)
    print(file=sys.stderr)

    samples = []
    with open(args.out, "a") as out:
        for i in range(args.runs):
            t0 = time.monotonic()
            sample = one_request(args.url, args.prompt_len, args.max_tokens)
            sample["label"] = args.label
            samples.append(sample)
            out.write(json.dumps(sample) + "\n")
            out.flush()
            shown = (f"FAILED ({sample['failed']})" if "failed" in sample
                     else f"ttft={sample['ttft_ms']:.1f}ms")
            print(f"run {i + 1}/{args.runs}: {shown}", end="\r",
                  file=sys.stderr)
            if args.interval:
                time.sleep(max(0.0, args.interval - (time.monotonic() - t0)))
    print(file=sys.stderr)

    # server-side span telemetry, re-derived from the engine's trace
    # substrate (vtpu/obs): the same percentiles as the engine measured
    # them (submit -> first delivery), printed next to the client's
    # wall-clock view so the HTTP hop's share of TTFT is visible. Older
    # servers without GET /stats degrade to null.
    server_trace = None
    try:
        with urllib.request.urlopen(f"{args.url}/stats", timeout=10) as resp:
            server_trace = json.loads(resp.read().decode())
    except (OSError, ValueError):
        pass
    if server_trace is not None:
        # persist the engine-side view next to the samples so report.py
        # can split TTFT into queue-wait vs prefill-execution per arm
        # (records without ttft_ms are ignored by legacy aggregation)
        with open(args.out, "a") as out:
            out.write(json.dumps({"server_trace": server_trace,
                                  "label": args.label,
                                  "ts": time.time()}) + "\n")

    failures = [s for s in samples if "failed" in s]
    samples = [s for s in samples if "failed" not in s]
    ttfts = sorted(s["ttft_ms"] for s in samples)
    itl = sorted(g for s in samples for g in s["gaps_ms"])

    def rpct(vals: list, q: float):
        # no sample is "no number", never a 0 ms percentile
        return round(pct(vals, q), 2) if vals else None

    print(json.dumps({
        "runs": len(samples),
        "failed": len(failures),
        "failures": sorted({s["failed"] for s in failures}),
        "p50_ttft_ms": round(statistics.median(ttfts), 2) if ttfts else None,
        "p95_ttft_ms": rpct(ttfts, 0.95),
        "p99_ttft_ms": rpct(ttfts, 0.99),
        "p50_itl_ms": rpct(itl, 0.50),
        "p95_itl_ms": rpct(itl, 0.95),
        "p99_itl_ms": rpct(itl, 0.99),
        "server_trace": server_trace,
        "out": args.out,
    }))
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
