"""Engine fleet A/B: kill-and-failover vs a single-engine reference (ISSUE 14).

The tentpole claim under measurement: an engine of a fleet can die WITHOUT
SAYING GOODBYE — loop thread gone mid-stream, no cleanup, no extract — and
every stream it held still finishes token-equal on a survivor, rebuilt
from the fleet's flush-boundary session ledger through the existing
recompute-on-fault prefill path. Deterministic gates, every run:

  1. TOKEN EQUALITY THROUGH KILL-AND-FAILOVER: every stream on the dead
     engine (live slots AND a still-waiting request) finishes token-equal
     to the single-engine reference — for the exact and int8 pools;
  2. FAILOVER ACCOUNTING: ``failover_sessions`` equals the dead engine's
     session count, with zero failover_faulted;
  3. ZERO LEAKS ON ALL ENGINES after drain-to-empty: the reaped corpse
     and every survivor end pool free == capacity, nothing parked, no
     slots, host tier free;
  4. EVERY CONFIGURED SEAM FIRED: engine_death on each kill plan,
     probe_loss on the hysteresis scenario (FaultPlan.snapshot());
  5. HYSTERESIS: a SUSPECT-but-alive engine (probe_loss misses under the
     dead threshold) is NEVER failed over and its stream is untouched;
  6. BLACKOUT: per-stream failover blackout (kill -> first post-failover
     token) p50/p99 ms reported, p99 under --blackout-ms.

Usage:  python benchmarks/fleet_bench.py [--quick] [--sessions N]
            [--max-new N] [--page P] [--blackout-ms MS] [--out F]
Emits:  full artifact JSON on stdout line 1, then the compact one-line
        summary (metric/value/verdict — the PR-3 driver-artifact
        convention) as the FINAL stdout line; human notes on stderr.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main() -> None:
    ap = argparse.ArgumentParser("fleet-bench")
    ap.add_argument("--quick", action="store_true",
                    help="CI mode: smaller traffic, same gates")
    ap.add_argument("--remote", action="store_true",
                    help="cross-host arm (ISSUE 18): the fleet's members "
                         "live in three spawned engine-host processes "
                         "behind TCP; SIGKILL one and gate the same "
                         "failover claims across the fabric")
    ap.add_argument("--sessions", type=int, default=None,
                    help="sessions on the doomed engine (default 3: two "
                         "live at slots=2 plus one waiting; quick 3)")
    ap.add_argument("--max-new", type=int, default=12,
                    help="decode tokens per session")
    ap.add_argument("--page", type=int, default=8)
    ap.add_argument("--blackout-ms", type=float, default=10000.0,
                    help="failover blackout p99 bound (generous: the CI "
                         "rig's blackout is miss-ladder latency plus "
                         "recompute dispatch — the gate catches hangs)")
    ap.add_argument("--out", default=None,
                    help="artifact path (default FLEET_r16.json on full "
                         "runs; quick runs only write when set)")
    a = ap.parse_args()
    sessions = a.sessions or 3
    if a.quick:
        a.max_new = min(a.max_new, 10)

    import jax
    import jax.numpy as jnp

    from vtpu.models import ModelConfig, init_params
    from vtpu.serving import (
        EngineFleet, FaultPlan, FaultSpec, FleetConfig, RoutePolicy,
        ServingConfig, ServingEngine, Status)

    # tiny on purpose (the chaos/migrate bench discipline): the CPU rig's
    # tick is dispatch-dominated, so the bench measures the supervision
    # and failover machinery, not model FLOPs
    mk = dict(vocab=128, d_model=32, n_heads=2, head_dim=16, n_layers=1,
              d_ff=64, max_seq=64, dtype=jnp.float32, use_pallas=False)
    cfg = ModelConfig(**mk)
    cfg_int8 = ModelConfig(kv_int8=True, **mk)
    prompt_len = 8

    def prompt(seed: int, vocab: int):
        return [int(t) for t in jax.random.randint(
            jax.random.key(seed), (prompt_len,), 1, vocab, jnp.int32)]

    def base_serving(**kw):
        base = dict(slots=2, prefill_buckets=(16,), max_new_tokens=a.max_new,
                    prefill_chunk=16, kv_page=a.page, kv_swap=16)
        base.update(kw)
        return ServingConfig(**base)

    class PinPolicy(RoutePolicy):
        """Deterministic placement: everything lands on one engine while
        it lives; survivors rank by name once it is gone/draining."""

        def __init__(self, name="a"):
            self.name = name

        def score(self, name, signals):
            if signals.draining:
                return None
            return 1.0 if name == self.name else 0.0

    # supervision tuned for the bench: probes every 20 ms, a beat older
    # than 2 s is a miss, 4 misses declare DEAD. The window is WIDE on
    # purpose: the smoke tier runs several benches concurrently on
    # 2-core runners, where a LIVE engine's loop can be starved for
    # over a second at a stretch — a tighter window false-positives,
    # and a fenced-alive engine degrades its streams to CANCELLED (the
    # designed safe failure, but not this bench's scenario). The kill
    # scenarios' blackout floor is therefore ~2 s of deliberate
    # detection latency — the hysteresis price, reported, not hidden.
    FC = dict(probe_interval_ms=20.0, miss_ms=2000.0,
              suspect_misses=2, dead_misses=4)

    artifact: dict = {
        "metric": ("crosshost_deterministic_gates" if a.remote
                   else "fleet_deterministic_gates"),
        "quick": bool(a.quick),
        "sessions": sessions,
        "max_new": a.max_new,
        "blackout_bound_ms": a.blackout_ms,
        "scenarios": [],
    }
    all_pass = True
    blackouts_ms: list = []

    def pools_clean(eng) -> bool:
        s = eng.stats()
        ok = (s["kv_pool_free"] == s["kv_pool_blocks"]
              and s["parked_sessions"] == 0 and s["active_slots"] == 0)
        if s["swap_host_blocks"]:
            ok = ok and s["swap_host_free"] == s["swap_host_blocks"]
        return ok

    # ------------------------------------------------- kill-and-failover
    # the kill must land while the slotted streams are still LIVE: the
    # client takes two head tokens then arms the seam, and the engine
    # keeps producing in the meantime — on a loaded smoke rig a short
    # budget can fully drain first, leaving the death nothing to catch.
    # 24 tokens cannot (prompt 8 + 24 < max_seq 64).
    kill_new = max(a.max_new, 24)

    def pct(vals, q):
        return (vals[min(len(vals) - 1, int(len(vals) * q))]
                if vals else None)

    def finish(out_default: str) -> None:
        """The shared artifact tail: blackout percentiles off the
        client-side samples, artifact JSON + one-line summary, exit."""
        nonlocal all_pass
        blackouts_ms.sort()
        p50, p99 = pct(blackouts_ms, 0.5), pct(blackouts_ms, 0.99)
        blackout_ok = p99 is not None and p99 <= a.blackout_ms
        all_pass &= blackout_ok
        artifact["blackout_ms"] = {
            "samples": len(blackouts_ms),
            "p50": round(p50, 3) if p50 is not None else None,
            "p99": round(p99, 3) if p99 is not None else None,
            "bound": a.blackout_ms,
            "pass": blackout_ok,
        }
        log(f"blackout: p50={p50} p99={p99} bound={a.blackout_ms} "
            f"pass={blackout_ok}")
        artifact["pass"] = bool(all_pass)
        out_path = a.out or (None if a.quick else out_default)
        if out_path:
            Path(out_path).write_text(json.dumps(artifact, indent=2) + "\n")
            log(f"artifact -> {out_path}")
        print(json.dumps(artifact))

        from vtpu.obs.summary import print_summary

        print_summary(
            artifact["metric"],
            round(p99, 3) if p99 is not None else -1,
            "pass" if all_pass else "FAIL",
            unit="failover_blackout_p99_ms",
            scenarios={sc["name"]: sc["pass"]
                       for sc in artifact["scenarios"]},
        )
        sys.exit(0 if all_pass else 1)

    # ---------------------------------------------- cross-host (--remote)
    # ISSUE 18: the same kill-and-failover claim with the fleet's members
    # behind REAL process + TCP boundaries — three spawned engine-host
    # children (one engine each, identical params by shared seed),
    # everything pinned on r0@h0, SIGKILL that child mid-stream. The
    # in-proc gates apply unchanged, plus the fabric's own: journeys
    # conserved with HOST-tagged hops, survivors leak-clean read over
    # the wire, the rebuilds landing on REMOTE destinations.
    if a.remote:
        import os
        import signal

        from vtpu.serving.fabric import (
            connect_host, spawn_host, tcp_connect)

        log("=== scenario: crosshost kill_failover (SIGKILL a host) ===")
        buckets = (16, 64)
        params = init_params(jax.random.key(0), cfg)
        prompts = [prompt(300 + j, cfg.vocab) for j in range(sessions)]
        ref = ServingEngine(params, cfg, base_serving(
            slots=sessions, prefill_buckets=buckets))
        ref.start()
        try:
            want = [list(ref.submit(p, max_new_tokens=kill_new).stream())
                    for p in prompts]
        finally:
            ref.stop()
        sv = dict(slots=2, prefill_buckets=list(buckets),
                  max_new_tokens=kill_new, prefill_chunk=16,
                  kv_page=a.page, kv_swap=16)
        # throttle the doomed engine's decode (~10ms/token): the tiny
        # model would otherwise finish the whole stream into the socket
        # buffer before the SIGKILL lands — the kill must be MID-stream
        # for the failover to have work to do
        doomed = dict(sv, faults=[dict(seam="delayed_fetch", at=0,
                                       count=100000, arg=0.01)])
        specs = {"r0": doomed, "r1": dict(sv), "r2": dict(sv)}
        mk_json = {**mk, "dtype": "float32"}
        procs, clients, members = {}, {}, {}
        fleet = None
        try:
            spawned = {n: spawn_host({"model": mk_json, "seed": 0,
                                      "engines": {n: s}})
                       for n, s in specs.items()}
            for i, (n, (proc, port)) in enumerate(spawned.items()):
                procs[n] = proc
                chan = tcp_connect("127.0.0.1", port)
                client, engines = connect_host(chan, host=f"h{i}",
                                               proc=proc)
                clients[n] = client
                members[n] = engines[n]
            fleet = EngineFleet(dict(members), FleetConfig(
                **FC, route_policy=PinPolicy("r0")))
            fleet.start()
            deadline = time.perf_counter() + 300
            while any(m._beat_ns == 0 for m in members.values()):
                if time.perf_counter() > deadline:
                    raise SystemExit("child engines never warmed up")
                time.sleep(0.05)
            reqs = [fleet.submit(p, max_new_tokens=kill_new)
                    for p in prompts]
            its = [r.stream() for r in reqs]
            heads = [[next(its[j]), next(its[j])] for j in range(2)]
            heads += [[] for _ in range(sessions - 2)]
            t_kill = time.perf_counter()
            os.kill(procs["r0"].pid, signal.SIGKILL)
            post = [next(its[j]) for j in range(sessions)]
            blackouts_ms.append((time.perf_counter() - t_kill) * 1e3)
            streams = [heads[j] + [post[j]] + list(its[j])
                       for j in range(sessions)]
            # journeys close on the monitor's prune pass and survivor
            # slots retire over the wire — wait for both to settle
            deadline = time.perf_counter() + 30
            while time.perf_counter() < deadline:
                fs = fleet.stats(include_engines=False)
                if (fs["journeys_ended"] >= sessions
                        and all(pools_clean(members[n])
                                for n in ("r1", "r2"))):
                    break
                time.sleep(0.05)
            fs = fleet.stats(include_engines=False)
            journeys = fleet.trace.journeys()
            clean = all(pools_clean(members[n]) for n in ("r1", "r2"))
        finally:
            if fleet is not None:
                fleet.stop()
            for client in clients.values():
                client.close()
            for proc in procs.values():
                if proc.poll() is None:
                    proc.kill()
                proc.wait()
        gates = {
            "token_equal": streams == want,
            "all_ok": all(r.status == Status.OK for r in reqs),
            "failover_sessions": fs["failover_sessions"] == sessions
                                  and fs["failovers"] == 1
                                  and fs["failover_faulted"] == 0,
            "dead_declared": fs["engine_states"]["r0"] == "DEAD",
            "zero_leaks_survivors": clean,
            # every session ONE journey, route@h0 -> failover on a
            # SURVIVOR host, per-hop tokens conserving the delivery
            "journeys_host_tagged": all(
                journeys.get(r.jid, {}).get("n_hops") == 2
                and [h["kind"] for h in journeys[r.jid]["hops"]]
                == ["route", "failover"]
                and journeys[r.jid]["conserved"] is True
                and journeys[r.jid]["hops"][0]["host"] == "h0"
                and journeys[r.jid]["hops"][1]["host"] in ("h1", "h2")
                for r in reqs),
            "fabric_counters": fs["remote_engines"] == 3
                                and fs["fabric_msgs_sent"] > 0
                                and fs["fabric_msgs_recv"] > 0,
        }
        ok = all(gates.values())
        all_pass &= ok
        artifact["scenarios"].append({
            "name": "crosshost_kill_failover", "pass": ok, "gates": gates,
            "failover_sessions": fs["failover_sessions"],
            "stitched_blackout_p99_ms": fs["failover_blackout_p99_ms"],
            "fabric": {k: fs[k] for k in (
                "fabric_msgs_sent", "fabric_msgs_recv",
                "fabric_bytes_sent", "fabric_bytes_recv",
                "fabric_payload_bytes", "fabric_retries",
                "fabric_timeouts", "fabric_resends",
                "fabric_checksum_faults")},
        })
        log(f"crosshost_kill_failover: pass={ok} gates={gates}")
        finish("CROSSHOST_r18.json")

    def run_kill(name, layout_cfg):
        nonlocal all_pass
        log(f"=== scenario: kill_failover[{name}] ===")
        params = init_params(jax.random.key(0), layout_cfg)
        prompts = [prompt(100 + j, layout_cfg.vocab)
                   for j in range(sessions)]
        ref = ServingEngine(params, layout_cfg,
                            base_serving(slots=sessions))
        ref.start()
        try:
            want = [list(ref.submit(p, max_new_tokens=kill_new).stream())
                    for p in prompts]
        finally:
            ref.stop()
        plan = FaultPlan()
        engines = {
            "a": ServingEngine(params, layout_cfg,
                               base_serving(faults=plan)),
            "b": ServingEngine(params, layout_cfg, base_serving()),
            "c": ServingEngine(params, layout_cfg, base_serving()),
        }
        fleet = EngineFleet(engines, FleetConfig(
            **FC, route_policy=PinPolicy("a")))
        fleet.start()
        try:
            reqs = [fleet.submit(p, max_new_tokens=kill_new)
                    for p in prompts]
            its = [r.stream() for r in reqs]
            # slots=2: the first two stream a couple of tokens, the rest
            # wait — a live-slot AND waiting-line failover in one kill
            heads = [[next(its[j]), next(its[j])] for j in range(2)]
            heads += [[] for _ in range(sessions - 2)]
            t_kill = time.perf_counter()
            plan.arm("engine_death")  # die at the very next flush
            post = [next(its[j]) for j in range(sessions)]
            blackouts_ms.append((time.perf_counter() - t_kill) * 1e3)
            streams = [heads[j] + [post[j]] + list(its[j])
                       for j in range(sessions)]
            fs = fleet.stats()
            clean = all(pools_clean(e) for e in engines.values())
        finally:
            fleet.stop()
        # ISSUE 15: the flight recorder's black box and the stitched
        # journeys, audited after stop (the final journey-end pass ran)
        from vtpu.obs.fleettrace import validate_bundle

        journeys = fleet.trace.journeys()
        bundle_ok = validate_bundle(fleet.trace.bundles().get("a"))
        gates = {
            "token_equal": streams == want,
            "all_ok": all(r.status == Status.OK for r in reqs),
            "failover_sessions": fs["failover_sessions"] == sessions
                                  and fs["failovers"] == 1
                                  and fs["failover_faulted"] == 0,
            "dead_declared": fs["engine_states"]["a"] == "DEAD",
            "zero_leaks_all_engines": clean,
            "seams_fired":
                plan.snapshot()["injected"]["engine_death"] == 1,
            "survivors_rebuilt": sum(
                fs["engines"][n]["migrations_in"]
                for n in ("b", "c")) == sessions,
            # every session ONE journey: route -> failover, per-hop
            # tokens summing to exactly the delivered stream
            "journeys_conserved": all(
                journeys.get(r.jid, {}).get("n_hops") == 2
                and [h["kind"] for h in journeys[r.jid]["hops"]]
                == ["route", "failover"]
                and journeys[r.jid]["conserved"] is True
                for r in reqs),
            "postmortem_bundle": bundle_ok,
        }
        ok = all(gates.values())
        all_pass &= ok
        artifact["scenarios"].append({
            "name": f"kill_failover[{name}]", "pass": ok, "gates": gates,
            "failover_sessions": fs["failover_sessions"],
            "probe_misses": fs["probe_misses"],
            "stitched_blackout_p50_ms":
                fleet.stats()["failover_blackout_p50_ms"],
        })
        log(f"kill_failover[{name}]: pass={ok} gates={gates}")

    run_kill("exact", cfg)
    run_kill("int8", cfg_int8)

    # ------------------------------------------------------------- drain
    log("=== scenario: drain (router-driven rolling evacuation) ===")
    params = init_params(jax.random.key(0), cfg)
    prompts = [prompt(200 + j, cfg.vocab) for j in range(sessions)]
    ref = ServingEngine(params, cfg, base_serving(slots=sessions))
    ref.start()
    try:
        want = [list(ref.submit(p, max_new_tokens=a.max_new).stream())
                for p in prompts]
    finally:
        ref.stop()
    engines = {n: ServingEngine(params, cfg, base_serving())
               for n in ("a", "b", "c")}
    fleet = EngineFleet(engines, FleetConfig(
        **FC, route_policy=PinPolicy("a")))
    fleet.start()
    try:
        reqs = [fleet.submit(p, max_new_tokens=a.max_new) for p in prompts]
        its = [r.stream() for r in reqs]
        heads = [[next(its[0])], [next(its[1])]] + [[] for _ in
                                                    range(sessions - 2)]
        report = fleet.drain("a")
        refused = False
        try:
            engines["a"].submit(prompts[0])
        except RuntimeError:
            refused = True
        streams = [h + list(it) for h, it in zip(heads, its)]
        sa = engines["a"].stats()
        clean = all(pools_clean(e) for e in engines.values())
        fs = fleet.stats()
    finally:
        fleet.stop()
    gates = {
        "token_equal": streams == want,
        "all_ok": all(r.status == Status.OK for r in reqs),
        "src_empty": (sa["active_slots"] == 0 and sa["parked_sessions"] == 0
                      and sa["queued"] == 0
                      and sa["kv_pool_free"] == sa["kv_pool_blocks"]),
        "admission_refused": refused,
        "no_failover": fs["failovers"] == 0,
        "zero_leaks_all_engines": clean,
    }
    drain_pass = all(gates.values())
    all_pass &= drain_pass
    artifact["scenarios"].append({
        "name": "drain", "pass": drain_pass, "gates": gates,
        "report": {k: report[k] for k in ("migrated", "completed",
                                          "faulted")},
    })
    log(f"drain: pass={drain_pass} gates={gates} report={report}")

    # --------------------------------------------------------- hysteresis
    log("=== scenario: suspect (SUSPECT-but-alive is never failed over) ===")
    # probes walk sorted names each round: arrivals 0,3,6,... are 'a',
    # 1,4,7 'b', 2,5,8 'c' — eat b's probes in rounds 0 and 1 only
    # (2 misses = SUSPECT < 4 = DEAD), then let it recover
    fleet_plan = FaultPlan([FaultSpec("probe_loss", at=1),
                            FaultSpec("probe_loss", at=4)])
    engines = {n: ServingEngine(params, cfg, base_serving())
               for n in ("a", "b", "c")}
    fleet = EngineFleet(engines, FleetConfig(
        **FC, route_policy=PinPolicy("b"), faults=fleet_plan))
    fleet.start()
    try:
        req = fleet.submit(prompts[0], max_new_tokens=a.max_new)
        it = req.stream()
        head = [next(it)]
        t0 = time.perf_counter()
        seen_suspect = False
        while time.perf_counter() - t0 < 30:
            s = fleet.stats()
            seen_suspect |= s["suspects"] >= 1
            if seen_suspect and s["engine_states"]["b"] == "HEALTHY":
                break
            time.sleep(0.005)
        stream = head + list(it)
        fs = fleet.stats()
    finally:
        fleet.stop()
    gates = {
        "stream_untouched": stream == want[0]
                             and req.status == Status.OK,
        "went_suspect": seen_suspect and fs["suspects"] >= 1,
        "recovered": fs["engine_states"]["b"] == "HEALTHY",
        "never_failed_over": fs["failovers"] == 0
                              and fs["failover_sessions"] == 0,
        "seams_fired":
            fleet_plan.snapshot()["injected"]["probe_loss"] == 2,
    }
    sus_pass = all(gates.values())
    all_pass &= sus_pass
    artifact["scenarios"].append({
        "name": "suspect", "pass": sus_pass, "gates": gates,
        "probe_misses": fs["probe_misses"],
    })
    log(f"suspect: pass={sus_pass} gates={gates}")

    # ------------------------------------------------ blackout + artifact
    finish("FLEET_r16.json")


if __name__ == "__main__":
    from vtpu.util.jaxcache import place_compile_cache

    place_compile_cache()
    main()
