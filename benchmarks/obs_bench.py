"""Observability overhead A/B + trace round-trip (ISSUE 7 tentpole gate).

The vtpu/obs subsystem promises first-class telemetry — request-lifecycle
tracing, tick-phase histograms, the vtpu_serving_* exporter — at a price
of approximately nothing: recording is host-only (a counter bump, a
monotonic stamp, a tuple into a preallocated ring), so turning tracing on
must add ZERO host syncs and cost at most 2% tokens/sec. This bench is
that contract's exit-code gate, in two parts:

  1. Overhead A/B: identical decode-heavy request waves through two
     LONG-LIVED engines differing ONLY in ``ServingConfig.trace_events``
     (0 = ring off vs the ring on), warmed once so compiles never enter a
     timed window. Measurement is built for a noisy shared box (measured:
     raw run-to-run throughput swings 2x on seconds-scale CPU
     contention): waves alternate off/on/off/on within each pair, each
     arm's pair estimate is its best-of-2 wave (contention only ever
     SLOWS a wave, so best-of estimates the uncontended rate and both
     arms get a clean-window chance), and the overhead claim is the
     MEDIAN pair's on/off ratio — drift between pairs cancels instead of
     landing on one arm. Deterministic gates (always): the tracing-on
     arm's ``device_gets_per_tick == 1.0`` (no fetch was added anywhere),
     ``admission_syncs`` identical across arms (zero added blocking
     syncs), and the on arm actually recorded events while the off arm
     recorded none. Perf gate (full runs only; --quick CI boxes are too
     noisy for a 2% bar): the median pair ratio within
     ``--overhead-bar-pct`` of 1.

  2. Trace round-trip: a park -> evict -> swap-out -> swap-in -> resume
     lifecycle (plus a parallel drop -> recompute-on-fault session) driven
     through a small overcommit engine with tracing on. Gates
     (deterministic): each session's JSONL events reconstruct the exact
     expected span sequence, the derived spans carry the parked/resume
     attribution, and the Chrome dump is valid ``trace_event`` JSON
     (loads in Perfetto).

``--fleet`` (ISSUE 15) runs the FLEET arm of the same contract instead:
the whole fleet observability plane (per-engine rings + the FleetTrace
control ring, journey stitching, flight recorder) priced by an identical
on/off A/B over two 3-engine fleets behind ``EngineFleet.submit`` —
≤2% tokens/sec and zero added syncs with everything on (full runs gate
it; --quick reports it) — followed by a deterministic scenario: one
migrate and one kill through the ON fleet, gating stitched journeys
(exact hop kinds, token conservation), a blackout window per move, a
JSON-parseable post-mortem bundle for the dead engine, and the
fleet-stats exporter coverage check. Artifact: OBS_r17.json.

Usage:  python benchmarks/obs_bench.py [--fleet] [--quick] [--slots N]
            [--repeats R] [--max-new N] [--overhead-bar-pct 2.0] [--out F]
Emits:  full artifact JSON on stdout line 1, then the compact one-line
        headline summary (metric/value/verdict — the PR-3 driver-artifact
        convention, shared helper vtpu/obs/summary.py) as the FINAL stdout
        line; human notes on stderr.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> None:
    ap = argparse.ArgumentParser("obs-bench")
    ap.add_argument("--quick", action="store_true",
                    help="CI mode: one A/B pair, short streams; the perf "
                         "bar is reported but not gated")
    ap.add_argument("--fleet", action="store_true",
                    help="run the FLEET observability arm (ISSUE 15): "
                         "3-engine fleet on/off overhead A/B + one-kill/"
                         "one-migrate journey-stitching scenario -> "
                         "OBS_r17.json")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=64,
                    help="decode tokens per request/wave (quick: capped "
                         "at 16)")
    ap.add_argument("--requests", type=int, default=None,
                    help="requests per wave (default 4x slots)")
    ap.add_argument("--repeats", type=int, default=7,
                    help="interleaved measurement pairs (quick: 1)")
    ap.add_argument("--waves-per-arm", type=int, default=4,
                    help="waves per arm per pair; each arm scores its "
                         "best-of (quick: 1)")
    ap.add_argument("--overhead-bar-pct", type=float, default=2.0,
                    help="full runs gate tracing-on tokens/sec within this "
                         "percent of tracing-off")
    ap.add_argument("--out", default=None,
                    help="artifact path (default OBS_r10.json on full "
                         "runs; quick runs only write when set)")
    a = ap.parse_args()
    if a.quick:
        a.max_new = min(a.max_new, 16)
        a.repeats = 1
        a.waves_per_arm = 1
    n_requests = a.requests or 4 * a.slots
    if a.fleet:
        fleet_arm(a, n_requests)
        return

    import jax
    import jax.numpy as jnp

    from vtpu.models import ModelConfig, init_params
    from vtpu.serving import ServingConfig, ServingEngine
    from vtpu.obs.summary import print_summary
    from vtpu.obs.tickprof import host_ms_per_tick
    from vtpu.obs.trace import (
        DROP_RESTORE_SEQUENCE, SWAP_RESTORE_SEQUENCE, subsequence)

    # tiny on purpose (see paged_kv_bench): a CPU tick is dominated by
    # fixed dispatch overhead — the regime where a TPU's latency-bound
    # decode tick also lives, and the regime where per-tick host-side
    # tracing cost would show if it existed
    cfg = ModelConfig(
        vocab=128, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq=max(128, a.prompt_len + a.max_new + 1), head_dim=16,
        dtype=jnp.float32, use_pallas=False,
    )
    params = init_params(jax.random.key(0), cfg)
    bucket = max(16, a.prompt_len)

    def prompt(seed: int, n: int = None):
        return [int(t) for t in jax.random.randint(
            jax.random.key(seed), (n or a.prompt_len,), 1, cfg.vocab,
            jnp.int32)]

    prompts = [prompt(100 + i) for i in range(n_requests)]

    import gc

    def make_engine(trace_events: int) -> ServingEngine:
        eng = ServingEngine(params, cfg, ServingConfig(
            slots=a.slots, prefill_buckets=(bucket,),
            max_new_tokens=a.max_new, trace_events=trace_events))
        eng.start()
        # warm pass: compiles and first-dispatch costs happen HERE, never
        # inside a timed wave
        for r in [eng.submit(p, max_new_tokens=2)
                  for p in prompts[:a.slots]]:
            list(r.stream())
        return eng

    def wave(eng: ServingEngine) -> float:
        """One measured wave: submit the request set, drain every stream,
        return tokens/sec."""
        gc.collect()  # a GC pause inside a ~0.5 s wave is real noise
        t0 = time.perf_counter()
        reqs = [eng.submit(p, max_new_tokens=a.max_new) for p in prompts]
        total = sum(len(list(r.stream())) for r in reqs)
        return total / (time.perf_counter() - t0)

    eng_off = make_engine(0)
    eng_on = make_engine(16384)
    pair_rows = []
    try:
        for rep in range(a.repeats):
            # finest-grain interleave: off/on waves alternate inside the
            # pair, and the pair's arm order flips per repeat, so neither
            # a contention spike nor a one-time process cost lands on one
            # arm systematically
            arms = ([(eng_off, "off"), (eng_on, "on")] if rep % 2 == 0
                    else [(eng_on, "on"), (eng_off, "off")])
            scores = {"off": [], "on": []}
            for _ in range(a.waves_per_arm):
                for eng, name in arms:
                    scores[name].append(wave(eng))
            row = {"off": round(max(scores["off"]), 2),
                   "on": round(max(scores["on"]), 2)}
            row["ratio"] = round(row["on"] / row["off"], 4)
            pair_rows.append(row)
            print(f"pair {rep + 1}/{a.repeats}: off {row['off']} tok/s, "
                  f"on {row['on']} tok/s (ratio {row['ratio']})",
                  file=sys.stderr)
        off_stats = eng_off.stats()
        on_stats = eng_on.stats()
    finally:
        eng_off.stop()
        eng_on.stop()

    def arm_row(stats, trace_events):
        return {
            "trace_events": trace_events,
            "device_gets_per_tick": stats["device_gets_per_tick"],
            "admission_syncs": stats["admission_syncs"],
            "trace_events_recorded": stats["trace_events_recorded"],
            "trace_events_dropped": stats["trace_events_dropped"],
            "host_ms_per_tick": host_ms_per_tick(stats["tick_phase_ms"]),
            "tick_phase_ms": stats["tick_phase_ms"],
            "itl_p50_ms": stats["itl_p50_ms"],
            "ttft_p50_ms": stats["ttft_p50_ms"],
        }

    med = lambda vals: sorted(vals)[len(vals) // 2]  # noqa: E731
    off_tps = med([r["off"] for r in pair_rows])
    on_tps = med([r["on"] for r in pair_rows])
    pair_ratios = [r["ratio"] for r in pair_rows]
    overhead_pct = (1.0 - med(pair_ratios)) * 100.0
    off, on = arm_row(off_stats, 0), arm_row(on_stats, 16384)
    # zero ADDED host syncs: both engines served identical traffic, so
    # their blocking-sync counters must be identical (and 0 on the
    # default device-sampled path) and the tick transfer contract must
    # hold on both — tracing touched neither
    syncs_equal = off["admission_syncs"] == on["admission_syncs"]
    tick_contract = (off["device_gets_per_tick"] == 1.0
                     and on["device_gets_per_tick"] == 1.0)
    recorded = (on["trace_events_recorded"] > 0
                and off["trace_events_recorded"] == 0)

    # ---- part 2: the lifecycle round-trip through the trace ------------
    # streams long enough (24 tokens, parked after 2) that the park
    # settles many ticks before the budget would retire the slot
    page = 8
    lc_prompt, lc_new = 8, 24
    pages_per = -(-(lc_prompt + lc_new) // page)  # blocks per session
    eng = ServingEngine(params, cfg, ServingConfig(
        slots=2, prefill_buckets=(16,), max_new_tokens=lc_new,
        prefill_chunk=16, kv_page=page, kv_pool_blocks=2 * pages_per,
        kv_swap=pages_per))  # host tier holds ONE session: the other drops
    eng.start()
    try:
        wave1 = [eng.submit(prompt(900 + i, lc_prompt),
                            max_new_tokens=lc_new) for i in range(2)]
        for r in wave1:
            for _ in range(2):
                assert r.out.get(timeout=60) is not None
        # park ONE AT A TIME so park order (the eviction LRU axis) is
        # deterministic: wave1[0] parks first, so it is evicted first and
        # takes the host-tier slot; wave1[1] finds the tier full and drops
        for i, r in enumerate(wave1):
            eng.park(r)
            t0 = time.perf_counter()
            while eng.stats()["parked_sessions"] < i + 1:
                assert time.perf_counter() - t0 < 60, "park stalled"
                time.sleep(0.002)
        # pool pressure: the second wave's admissions evict both parked
        # sessions — the first-parked spills to the host tier, the second
        # finds it full and drops (recompute-on-fault at resume)
        wave2 = [eng.submit(prompt(910 + i, lc_prompt),
                            max_new_tokens=lc_new) for i in range(2)]
        for r in wave2:
            list(r.stream())
        for r in wave1:
            eng.resume(r)
            list(r.stream())
        stats = eng.stats()
        spans = eng.trace.spans()
        by_rid = {r.rid: [] for r in wave1}
        for e in eng.trace.events():
            if e["rid"] in by_rid:
                by_rid[e["rid"]].append(e["event"])
        swap_ok = subsequence(SWAP_RESTORE_SEQUENCE, by_rid[wave1[0].rid])
        drop_ok = subsequence(DROP_RESTORE_SEQUENCE, by_rid[wave1[1].rid])
        span_ok = all(
            spans[r.rid]["parks"] == 1
            and spans[r.rid]["parked_ms"] > 0
            and len(spans[r.rid]["resume_latency_ms"]) == 1
            and spans[r.rid]["tokens"] == lc_new
            for r in wave1)
        chrome = eng.trace.chrome_trace()
        chrome_ok = (
            isinstance(chrome.get("traceEvents"), list)
            and len(chrome["traceEvents"]) > 0
            and all(isinstance(e, dict) and "ph" in e and "name" in e
                    for e in chrome["traceEvents"])
            and json.loads(json.dumps(chrome)) == chrome)
        lifecycle = {
            "swap_path_events_ok": swap_ok,
            "drop_path_events_ok": drop_ok,
            "spans_ok": span_ok,
            "chrome_trace_valid": chrome_ok,
            "chrome_trace_events": len(chrome["traceEvents"]),
            "swap_out_bytes": stats["swap_out_bytes"],
            "swap_in_bytes": stats["swap_in_bytes"],
            "fault_recomputes": stats["fault_recomputes"],
            "events": {str(r.rid): by_rid[r.rid] for r in wave1},
        }
        if not (swap_ok and drop_ok):
            print(f"lifecycle events: {lifecycle['events']}", file=sys.stderr)
    finally:
        eng.stop()

    ok = (tick_contract and syncs_equal and recorded
          and swap_ok and drop_ok and span_ok and chrome_ok
          and stats["swap_out_bytes"] > 0 and stats["fault_recomputes"] > 0)
    perf_ok = overhead_pct <= a.overhead_bar_pct
    artifact = {
        "metric": "tracing_on_tokens_per_sec_overhead_pct",
        "value": round(overhead_pct, 3),
        "unit": f"percent_vs_tracing_off_bar_{a.overhead_bar_pct}",
        "pass": bool(ok and (a.quick or perf_ok)),
        "overhead_bar_pct": a.overhead_bar_pct,
        "overhead_estimator":
            "median_of_pair_ratios_best_of_waves_per_arm",
        "pairs": pair_rows,
        "tokens_per_sec_off_median": round(off_tps, 2),
        "tokens_per_sec_on_median": round(on_tps, 2),
        "device_gets_per_tick_contract": tick_contract,
        "admission_syncs_equal": syncs_equal,
        "trace_recording_asymmetry_ok": recorded,
        "slots": a.slots,
        "requests": n_requests,
        "max_new": a.max_new,
        "repeats": a.repeats,
        "waves_per_arm": a.waves_per_arm,
        "quick": a.quick,
        "model": {"vocab": cfg.vocab, "d_model": cfg.d_model,
                  "n_heads": cfg.n_heads, "n_layers": cfg.n_layers,
                  "max_seq": cfg.max_seq},
        "arms": [off, on],
        "lifecycle": lifecycle,
    }
    out_path = a.out or (None if a.quick else "OBS_r10.json")
    if out_path:
        Path(out_path).write_text(json.dumps(artifact, indent=2) + "\n")
    print(json.dumps(artifact))
    print_summary(
        artifact["metric"], artifact["value"],
        "pass" if artifact["pass"] else "fail", unit=artifact["unit"],
        tokens_per_sec_off=round(off_tps, 2),
        tokens_per_sec_on=round(on_tps, 2),
        device_gets_per_tick=on["device_gets_per_tick"],
        added_host_syncs=0 if syncs_equal else "NONZERO",
        lifecycle_round_trip=bool(swap_ok and drop_ok and chrome_ok),
    )
    # the structural gates (tick contract, zero added syncs, lifecycle
    # round-trip) are deterministic and gate ALWAYS; the 2% tokens/sec
    # envelope gates full runs only (quick CI boxes are too noisy)
    if not ok or (not a.quick and not perf_ok):
        sys.exit(1)


def fleet_arm(a, n_requests: int) -> None:
    """The ISSUE 15 fleet arm: price the WHOLE fleet observability plane
    (engine rings + FleetTrace control ring/journeys/flight recorder)
    with an on/off A/B over two identical 3-engine fleets, then drive a
    deterministic one-migrate + one-kill scenario through the ON fleet
    and gate the stitched-journey contracts."""
    import gc
    import time as _time

    import jax
    import jax.numpy as jnp

    from vtpu.models import ModelConfig, init_params
    from vtpu.obs.export import (
        FLEET_ALLOWLIST, FLEET_COUNTERS, FLEET_GAUGES, FLEET_SPECIAL)
    from vtpu.obs.summary import print_summary
    from vtpu.serving import (
        EngineFleet, FaultPlan, FleetConfig, ServingConfig, ServingEngine,
        Status)

    kill_new = 24  # the kill must land mid-stream (see fleet_bench)
    page = 8
    need = max(64, 8 + max(a.max_new, kill_new) + 1)
    cfg = ModelConfig(
        vocab=128, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq=-(-need // page) * page, head_dim=16,
        dtype=jnp.float32, use_pallas=False,
    )
    params = init_params(jax.random.key(0), cfg)

    def prompt(seed: int, n: int = 8):
        return [int(t) for t in jax.random.randint(
            jax.random.key(seed), (n,), 1, cfg.vocab, jnp.int32)]

    prompts = [prompt(100 + i) for i in range(n_requests)]

    def make_fleet(on: bool, faults_for=None):
        """A 3-engine fleet differing ONLY in whether the obs plane is on
        (engine rings + the fleet control ring/journeys/recorder)."""
        faults_for = faults_for or {}
        engines = {
            n: ServingEngine(params, cfg, ServingConfig(
                slots=a.slots, prefill_buckets=(16,),
                max_new_tokens=max(a.max_new, kill_new), prefill_chunk=16,
                kv_page=page, kv_swap=16,
                trace_events=16384 if on else 0,
                faults=faults_for.get(n)))
            for n in ("a", "b", "c")
        }
        # wide miss window: concurrent smoke benches starve live loops
        # for over a second (the fleet_bench FC note)
        fleet = EngineFleet(engines, FleetConfig(
            probe_interval_ms=20.0, miss_ms=2000.0, suspect_misses=2,
            dead_misses=4, trace_events=4096 if on else 0))
        fleet.start()
        for r in [fleet.submit(p, max_new_tokens=2)
                  for p in prompts[:3 * a.slots]]:
            list(r.stream())  # warm every engine's executables
        return fleet

    def wave(fleet) -> tuple:
        gc.collect()
        t0 = _time.perf_counter()
        reqs = [fleet.submit(p, max_new_tokens=a.max_new) for p in prompts]
        total = sum(len(list(r.stream())) for r in reqs)
        return total, _time.perf_counter() - t0

    plans = {n: FaultPlan() for n in ("a", "b", "c")}
    fleet_off = make_fleet(False)
    fleet_on = make_fleet(True, faults_for=plans)
    pair_rows = []
    agg = {"off": [0, 0.0], "on": [0, 0.0]}  # [tokens, seconds]
    try:
        # estimator: AGGREGATE tokens/sec per arm over all interleaved
        # waves. The engine arm's best-of/median-of-pairs assumes an
        # uncontended window exists for best-of to find — with six
        # engine loop threads plus two monitors on a 2-core rig it never
        # does (measured pair ratios swing ±25%, so a median of 7 lands
        # anywhere in ±8%). Interleaving still cancels drift; summing
        # ~40s of measurement per arm tightens the estimate to the
        # envelope the 2% bar needs. Pair rows stay as diagnostics.
        for rep in range(a.repeats):
            arms = ([(fleet_off, "off"), (fleet_on, "on")] if rep % 2 == 0
                    else [(fleet_on, "on"), (fleet_off, "off")])
            scores = {"off": [], "on": []}
            for _ in range(a.waves_per_arm):
                for f, name in arms:
                    toks, secs = wave(f)
                    agg[name][0] += toks
                    agg[name][1] += secs
                    scores[name].append(toks / secs)
            row = {"off": round(max(scores["off"]), 2),
                   "on": round(max(scores["on"]), 2)}
            row["ratio"] = round(row["on"] / row["off"], 4)
            pair_rows.append(row)
            print(f"fleet pair {rep + 1}/{a.repeats}: off {row['off']} "
                  f"tok/s, on {row['on']} tok/s (best-of ratio "
                  f"{row['ratio']})", file=sys.stderr)

        def arm_stats(fleet):
            fs = fleet.stats()
            engs = fs["engines"]
            return {
                "device_gets_per_tick_ok": all(
                    s["device_gets_per_tick"] in (None, 1.0)
                    for s in engs.values()),
                "admission_syncs": sum(
                    s["admission_syncs"] for s in engs.values()),
                "events_recorded": sum(
                    s["trace_events_recorded"] for s in engs.values()),
                "fleet_events_recorded": fs["fleet_trace_events_recorded"],
                "journeys_ended": fs["journeys_ended"],
                "journeys_conserved": fs["journeys_conserved"],
            }

        # journeys close on the monitor's prune cadence: let the drained
        # waves' journeys settle before auditing the stitch accounting
        t_w = _time.perf_counter()
        while (fleet_on.stats()["journeys_open"] > 0
               and _time.perf_counter() - t_w < 30):
            _time.sleep(0.005)
        off_s, on_s = arm_stats(fleet_off), arm_stats(fleet_on)
        tick_contract = (off_s["device_gets_per_tick_ok"]
                         and on_s["device_gets_per_tick_ok"])
        syncs_equal = off_s["admission_syncs"] == on_s["admission_syncs"]
        recorded = (on_s["events_recorded"] > 0
                    and on_s["fleet_events_recorded"] > 0
                    and off_s["events_recorded"] == 0
                    and off_s["fleet_events_recorded"] == 0)
        # every measured request yields a stitched journey (hops=1) and
        # the conserved count tracks the ended count exactly
        journeys_ok = (on_s["journeys_ended"] >= n_requests
                       and on_s["journeys_conserved"]
                       == on_s["journeys_ended"])

        # ---- scenario: one migrate + one kill through the ON fleet ----
        ref = ServingEngine(params, cfg, ServingConfig(
            slots=2, prefill_buckets=(16,), max_new_tokens=kill_new,
            prefill_chunk=16, kv_page=page, kv_swap=16))
        ref.start()
        try:
            want = [list(ref.submit(prompt(900 + j),
                                    max_new_tokens=kill_new).stream())
                    for j in range(2)]
        finally:
            ref.stop()
        # throttle every engine's decode (~10ms/token) BEFORE the
        # scenario submits: the engine streams whether or not the client
        # reads, and on a fast rig the whole 24-token stream can drain
        # in the submit→park window — the kill would then land on an
        # idle engine (1-hop journey, no bundle). The A/B waves above
        # are fully drained, so the perf estimate never sees the seam.
        for p in plans.values():
            p.arm("delayed_fetch", count=100000, arg=0.01)
        reqs = [fleet_on.submit(prompt(900 + j), max_new_tokens=kill_new)
                for j in range(2)]
        its = [r.stream() for r in reqs]
        heads = [[next(it), next(it)] for it in its]

        def owner_of(r):
            # _assigned holds every LIVE request; the journey's immutable
            # hop 0 is the fallback should the stream somehow already be
            # terminal (the monitor prunes finished requests)
            name = fleet_on._assigned.get(r)
            if name is None:
                name = fleet_on.trace.journeys()[r.jid]["hops"][0]["engine"]
            return name

        owner0, owner1 = owner_of(reqs[0]), owner_of(reqs[1])
        # PARK both scenario sessions before anything slow happens: the
        # engine decodes whether or not the client reads, so an unparked
        # 24-token stream can fully drain during the steps below — the
        # kill would land on an idle engine (no failover, 1-hop journey)
        # and the migrate would find a completed session. A parked
        # session cannot complete: the kill deterministically catches
        # r0 (failover resumes it on the survivor — the ledger covers
        # parked sessions) and the migrate moves r1's parked entry
        # (resume on arrival is migrate()'s contract).
        for r, owner in zip(reqs, (owner0, owner1)):
            fleet_on.engines[owner].park(r)
            t_p = _time.perf_counter()
            while (r not in fleet_on.engines[owner]._parked
                   and r.status is None):
                if _time.perf_counter() - t_p > 30:
                    break
                _time.sleep(0.002)
        # migrate r1 onto an engine that is neither its own nor the one
        # about to die, so the kill fails over exactly one session
        dst = next(n for n in ("a", "b", "c") if n not in (owner0, owner1))
        rep_m = fleet_on.migrate_session(reqs[1], dst)
        plans[owner0].arm("engine_death")
        streams = [h + list(it) for h, it in zip(heads, its)]
    finally:
        fleet_off.stop()
        fleet_on.stop()

    # read AFTER stop: the final journey-end pass has run, so the SLO
    # percentiles and stitched spans are settled
    scenario_stats = fleet_on.stats()
    from vtpu.obs.fleettrace import validate_bundle

    journeys = fleet_on.trace.journeys()
    j_kill = journeys.get(reqs[0].jid, {})
    j_mig = journeys.get(reqs[1].jid, {})
    bundle = fleet_on.trace.bundles().get(owner0)
    unmapped = sorted(
        k for k in scenario_stats
        if k not in set(FLEET_COUNTERS) | set(FLEET_GAUGES)
        | FLEET_SPECIAL | FLEET_ALLOWLIST)
    gates = {
        "scenario_token_equal": streams == want
                                 and all(r.status == Status.OK
                                         for r in reqs),
        "migrate_path_ok": rep_m["path"] in ("resident", "host",
                                             "recompute"),
        "kill_journey_stitched": (
            j_kill.get("n_hops") == 2
            and [h["kind"] for h in j_kill.get("hops", [])]
            == ["route", "failover"]),
        "kill_journey_conserved": j_kill.get("conserved") is True,
        "migrate_journey_stitched": (
            j_mig.get("n_hops") == 2
            and [h["kind"] for h in j_mig.get("hops", [])]
            == ["route", "migrate"]),
        "migrate_journey_conserved": j_mig.get("conserved") is True,
        "blackout_windows": (
            all(b["ms"] is not None and b["ms"] >= 0
                for j in (j_kill, j_mig)
                for b in j.get("blackouts", []))
            and any(b["kind"] == "failover" and b["ms"] > 0
                    for b in j_kill.get("blackouts", []))
            and any(b["kind"] == "migration"
                    for b in j_mig.get("blackouts", []))),
        "postmortem_bundle": validate_bundle(bundle),
        "fleet_stats_coverage": not unmapped,
        "tick_contract_both_arms": tick_contract,
        "zero_added_syncs": syncs_equal,
        "recording_asymmetry": recorded,
        "ab_journeys_stitched": journeys_ok,
    }
    ok = all(gates.values())
    if not ok:
        print(f"fleet gates: {gates}"
              + (f" unmapped={unmapped}" if unmapped else ""),
              file=sys.stderr)

    off_tps = agg["off"][0] / agg["off"][1] if agg["off"][1] else 0.0
    on_tps = agg["on"][0] / agg["on"][1] if agg["on"][1] else 0.0
    overhead_pct = (1.0 - on_tps / off_tps) * 100.0 if off_tps else 0.0
    perf_ok = overhead_pct <= a.overhead_bar_pct
    artifact = {
        "metric": "fleet_obs_on_tokens_per_sec_overhead_pct",
        "value": round(overhead_pct, 3),
        "unit": f"percent_vs_obs_off_bar_{a.overhead_bar_pct}",
        "pass": bool(ok and (a.quick or perf_ok)),
        "overhead_bar_pct": a.overhead_bar_pct,
        "overhead_estimator":
            "aggregate_tokens_per_sec_over_interleaved_waves",
        "pairs": pair_rows,
        "tokens_per_sec_off": round(off_tps, 2),
        "tokens_per_sec_on": round(on_tps, 2),
        "gates": gates,
        "arms": {"off": off_s, "on": on_s},
        "scenario": {
            "kill_engine": owner0,
            "migrate_dst": dst,
            "kill_journey": {k: j_kill.get(k) for k in
                             ("n_hops", "tokens", "delivered", "conserved",
                              "truncated", "terminal")},
            "migrate_journey": {k: j_mig.get(k) for k in
                                ("n_hops", "tokens", "delivered",
                                 "conserved", "truncated", "terminal")},
            "blackouts": {"kill": j_kill.get("blackouts"),
                          "migrate": j_mig.get("blackouts")},
            "failover_blackout_p50_ms":
                scenario_stats["failover_blackout_p50_ms"],
            "rebuild_p50_ms": scenario_stats["rebuild_p50_ms"],
            "postmortem_bundle_events":
                len(bundle["events"]) if bundle else 0,
        },
        "slots": a.slots,
        "requests": n_requests,
        "max_new": a.max_new,
        "repeats": a.repeats,
        "waves_per_arm": a.waves_per_arm,
        "quick": a.quick,
    }
    out_path = a.out or (None if a.quick else "OBS_r17.json")
    if out_path:
        Path(out_path).write_text(json.dumps(artifact, indent=2) + "\n")
    print(json.dumps(artifact))
    print_summary(
        artifact["metric"], artifact["value"],
        "pass" if artifact["pass"] else "fail", unit=artifact["unit"],
        journeys_conserved=gates["kill_journey_conserved"]
        and gates["migrate_journey_conserved"],
        bundle=gates["postmortem_bundle"],
        coverage=gates["fleet_stats_coverage"],
        added_host_syncs=0 if syncs_equal else "NONZERO",
    )
    if not ok or (not a.quick and not perf_ok):
        sys.exit(1)


if __name__ == "__main__":
    from vtpu.util.jaxcache import place_compile_cache

    place_compile_cache()
    main()
