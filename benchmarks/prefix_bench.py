"""Prefix gravity A/B: a zipfian shared-prefix trace ON vs OFF (ISSUE 20).

The tentpole claim under measurement: making the prefix cache a FLEET
resource — content-addressed pids, prefix-aware routing with the
avoided-prefill bonus, hot replication by rebuild — turns shared-prefix
traffic into suffix-only work without a single staged per-admission
copy. Two arms over the SAME trace and the same three-member fleet (two
local engines plus one loopback-fabric remote): ON registers the
distinct prefixes once and submits suffix-only with ``prefix_tokens``;
OFF submits the full prompt every time. Deterministic gates, every run:

  1. TOKEN EQUALITY: every ON stream equals its OFF stream (greedy
     decode; the prefix path is token-invisible);
  2. ZERO-COPY ADMISSION: ``prefix_install_copies`` stays 0 on every
     engine in both arms — admission shares blocks, never copies;
  3. EXACT ACCOUNTING: directory hits + misses == prefix-aware submits,
     with the routed-to-resident fraction above the pressure baseline
     (max_replicas / engines — what residency-blind routing could hit);
  4. HOT REPLICATION: the zipf-head prefix ends with a second resident,
     rebuilt through the chunked-prefill path (zero tier installs);
  5. KILL + PREFIX REUSE: a pinned engine dies mid-stream holding every
     session; the survivor already resident rebuilds each session
     AROUND its registered prefix (``failover_prefix_reuses``, shared
     blocks > 0) and the streams finish token-equal;
  6. ZERO LEAKS on every engine of every arm after unregister + drain —
     the reaped corpse included.

Full runs add the perf gates (quick CI boxes share cores across
benches, so quick only reports): tokens/sec ON >= --speedup x OFF, and
client-side TTFT p99 ON <= 1.10 x OFF.

Usage:  python benchmarks/prefix_bench.py [--quick] [--requests N]
            [--decode N] [--kill-new N] [--speedup X] [--out F]
Emits:  full artifact JSON on stdout line 1, then the compact one-line
        summary (metric/value/verdict — the PR-3 driver-artifact
        convention) as the FINAL stdout line; human notes on stderr.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main() -> None:
    ap = argparse.ArgumentParser("prefix-bench")
    ap.add_argument("--quick", action="store_true",
                    help="CI mode: smaller trace, deterministic gates "
                         "only (perf reported, not gated)")
    ap.add_argument("--requests", type=int, default=None,
                    help="trace length (default 96; quick 12)")
    ap.add_argument("--decode", type=int, default=4,
                    help="decode tokens per request in the A/B arms")
    ap.add_argument("--kill-new", type=int, default=10,
                    help="decode budget in the kill scenario (long "
                         "enough that the armed death lands mid-stream)")
    ap.add_argument("--speedup", type=float, default=1.3,
                    help="full-run tokens/sec gate: ON >= this x OFF")
    ap.add_argument("--repeats", type=int, default=None,
                    help="repeats per arm; perf gates use the best wall "
                         "(OS scheduling noise dominates sub-second "
                         "walls), deterministic gates must hold on "
                         "EVERY repeat (default 3; quick 1)")
    ap.add_argument("--out", default=None,
                    help="artifact path (default PREFIX_r20.json on "
                         "full runs; quick runs only write when set)")
    a = ap.parse_args()
    n_requests = a.requests or (12 if a.quick else 96)
    repeats = a.repeats or (1 if a.quick else 3)

    import numpy as np

    import jax
    import jax.numpy as jnp

    from vtpu.models import ModelConfig, init_params
    from vtpu.serving import (
        EngineFleet, FaultPlan, FleetConfig, RoutePolicy, ServingConfig,
        ServingEngine, Status)
    from vtpu.serving.fabric import EngineHost, connect_host, loopback_pair

    # tiny on purpose (the fleet/chaos bench discipline): the CPU rig's
    # tick is dispatch-dominated, so the A/B measures exactly what the
    # prefix tier removes — whole prefill CHUNK dispatches. max_seq 128
    # leaves room for the longest registrable prefix (max_seq - chunk)
    mk = dict(vocab=128, d_model=32, n_heads=2, head_dim=16, n_layers=1,
              d_ff=64, max_seq=128, dtype=jnp.float32, use_pallas=False)
    cfg = ModelConfig(**mk)
    params = init_params(jax.random.key(0), cfg)

    # geometry: the prefix is page-ALIGNED (112 = 14 pages of 8) so
    # admission shares whole pages (no COW boundary) and the resident
    # hit fires; 112 + 4 suffix + 4 decode = 120 <= 128. The pool is
    # sized for up to three pinned prefixes plus two live slots.
    PREFIX_LEN = 112
    SUF_LEN = 4
    PAGE = 8
    POOL = 64

    def serving(max_new: int, faults=None) -> ServingConfig:
        return ServingConfig(
            slots=2, prefill_buckets=(16,), max_new_tokens=max_new,
            prefill_chunk=16, kv_page=PAGE, kv_swap=16,
            kv_pool_blocks=POOL, faults=faults)

    # supervision: fleet_bench's wide window (smoke runners starve live
    # loops for seconds), plus the tiny queue-slot denominator — the
    # route bonus 0.25 * plen * ms_per_token / queue_slot_ms must
    # dominate the resident's own pinned-block pool handicap (up to
    # 0.25 score units) on any machine, however fast the tiny model
    FC = dict(probe_interval_ms=20.0, miss_ms=2000.0, suspect_misses=2,
              dead_misses=4, prefix_queue_slot_ms=0.01)

    # ------------------------------------------------- the zipfian trace
    # 4 distinct prefixes, zipf(1.2) popularity (~.53/.23/.14/.10), a
    # unique suffix per request. Seeded: both arms replay the SAME trace.
    NPREFIX = 4
    rng = np.random.default_rng(7)
    prefixes = [[int(t) for t in rng.integers(1, cfg.vocab, PREFIX_LEN)]
                for _ in range(NPREFIX)]
    weights = 1.0 / (np.arange(1, NPREFIX + 1) ** 1.2)
    weights /= weights.sum()
    trace = [int(i) for i in rng.choice(NPREFIX, size=n_requests,
                                        p=weights)]
    suffixes = [[int(t) for t in rng.integers(1, cfg.vocab, SUF_LEN)]
                for _ in range(n_requests)]

    # pre-placement spreads expected LOAD, not prefix count: hottest
    # first, each onto the least-loaded member (greedy bin pack — the
    # HAMi spread-mode binpack analog at prefix granularity)
    MEMBERS = ("e0", "e1", "r0")
    placement: dict = {}
    load = {n: 0.0 for n in MEMBERS}
    for i in sorted(range(NPREFIX), key=lambda i: -weights[i]):
        tgt = min(MEMBERS, key=lambda n: (load[n], n))
        placement[i] = tgt
        load[tgt] += float(weights[i])
    log(f"trace: {n_requests} requests over {NPREFIX} prefixes "
        f"(zipf weights {[round(float(w), 3) for w in weights]}), "
        f"placement {placement}")

    artifact: dict = {
        "metric": "prefix_gravity_gates",
        "quick": bool(a.quick),
        "requests": n_requests,
        "prefix_len": PREFIX_LEN,
        "decode": a.decode,
        "scenarios": [],
    }
    all_pass = True

    def build_fleet(fc_extra=None):
        """Two local engines + one loopback-fabric remote ("r0"): the
        prefix tier's claims are fleet-wide INCLUDING the wire, so the
        A/B routes real traffic through a remote proxy too."""
        host_eng = ServingEngine(params, cfg, serving(a.decode))
        host_eng.start()
        srv = EngineHost({"r0": host_eng})
        ch_a, ch_b, _link = loopback_pair(delay_s=0.0)
        threading.Thread(target=srv.serve_channel, args=(ch_b,),
                         daemon=True).start()
        client, engines = connect_host(ch_a, host="h0")
        members = {
            "e0": ServingEngine(params, cfg, serving(a.decode)),
            "e1": ServingEngine(params, cfg, serving(a.decode)),
            "r0": engines["r0"],
        }
        fleet = EngineFleet(members, FleetConfig(
            **{**FC, **(fc_extra or {})}))
        fleet.start()
        deadline = time.perf_counter() + 120
        while members["r0"]._beat_ns == 0:
            if time.perf_counter() > deadline:
                raise SystemExit("loopback remote never warmed up")
            time.sleep(0.01)
        return fleet, members, (host_eng, srv, client)

    def consume(req, out, idx, t_sub):
        toks = []
        t_first = None
        for t in req.stream():
            if t_first is None:
                t_first = time.perf_counter()
            toks.append(t)
        out[idx] = {"toks": toks, "status": req.status,
                    "ttft_ms": ((t_first - t_sub) * 1e3
                                if t_first is not None else None)}

    def drain_and_settle(fleet, members, pids, timeout=120.0):
        """Retire every slot, sweep every residency (looped: a probe-
        thread replication landing mid-sweep is caught next pass; once
        no donors remain the monitor cannot mint more), then wait for
        every pool to read fully free."""
        deadline = time.perf_counter() + timeout
        while True:
            busy = any(m.stats()["active_slots"] or m.stats()["queued"]
                       for m in members.values())
            if not busy:
                break
            if time.perf_counter() > deadline:
                raise SystemExit("fleet never drained")
            time.sleep(0.02)
        for _ in range(100):
            lids = [(n, pid, lid)
                    for pid in pids
                    for n, lid in fleet.prefixdir.residents(pid).items()]
            if not lids:
                break
            for n, pid, lid in lids:
                try:
                    members[n].unregister_prefix(lid)
                except Exception:
                    pass  # already dropped (or the engine is a corpse)
                if getattr(members[n], "is_remote", False):
                    # a remote has no loop-thread listener: mirror the
                    # unregister into the directory, the spill-path way
                    fleet.prefixdir.on_event(n, "unregister", pid,
                                             lid=lid)
            time.sleep(0.02)
        clean = {}
        while True:
            clean = {n: pools_clean(m) for n, m in members.items()}
            if all(clean.values()) or time.perf_counter() > deadline:
                break
            time.sleep(0.02)
        return clean

    def pools_clean(eng) -> bool:
        s = eng.stats()
        ok = (s["kv_pool_free"] == s["kv_pool_blocks"]
              and s["parked_sessions"] == 0 and s["active_slots"] == 0)
        if s["swap_host_blocks"]:
            ok = ok and s["swap_host_free"] == s["swap_host_blocks"]
        return ok

    # ------------------------------------------------------ the two arms

    def run_arm(prefix_on: bool) -> dict:
        fc_extra = ({"prefix_replicate_hits": 3, "prefix_max_replicas": 2}
                    if prefix_on else {})
        fleet, members, (host_eng, srv, client) = build_fleet(fc_extra)
        res: dict = {}
        cpids = []
        try:
            t0 = time.perf_counter()
            if prefix_on:
                # registration is INSIDE the wall: the ON arm pays its
                # one-time builds up front, honestly — but per-engine
                # in parallel, the way independent tenants would
                by_tgt: dict = {}
                for i, tgt in placement.items():
                    by_tgt.setdefault(tgt, []).append(i)
                got = {}

                def reg(tgt, idxs):
                    for i in idxs:
                        got[i] = fleet.register_prefix(prefixes[i],
                                                       engine=tgt)

                regs = [threading.Thread(target=reg, args=(tgt, idxs))
                        for tgt, idxs in by_tgt.items()]
                for th in regs:
                    th.start()
                for th in regs:
                    th.join(120)
                cpids.extend(got[i] for i in sorted(got))
                if len(cpids) != NPREFIX:
                    raise SystemExit("prefix registration failed")
            out: list = [None] * n_requests
            threads = []
            for j in range(n_requests):
                pre = prefixes[trace[j]]
                t_sub = time.perf_counter()
                if prefix_on:
                    req = fleet.submit(suffixes[j], prefix_tokens=pre,
                                       max_new_tokens=a.decode)
                else:
                    req = fleet.submit(pre + suffixes[j],
                                       max_new_tokens=a.decode)
                th = threading.Thread(target=consume,
                                      args=(req, out, j, t_sub))
                th.start()
                threads.append(th)
            for th in threads:
                th.join(300)
            wall_s = time.perf_counter() - t0

            if prefix_on:
                # the zipf head crossed the hit threshold during the
                # trace; the monitor WILL replicate it — wait for the
                # second resident (deterministic: hits persist, the
                # probe loop keeps running)
                head_pid = fleet.register_prefix(prefixes[0])
                deadline = time.perf_counter() + 90
                while len(fleet.prefixdir.residents(head_pid)) < 2:
                    if time.perf_counter() > deadline:
                        break
                    time.sleep(0.02)
                res["head_replicas"] = len(
                    fleet.prefixdir.residents(head_pid))
            fstats = fleet.stats()
            res["stats"] = {k: v for k, v in fstats.items()
                            if k != "engines"}
            res["engines"] = {
                n: {k: es[k] for k in
                    ("prefix_hits", "prefix_misses",
                     "prefix_install_copies", "prefix_tier_installs",
                     "prefix_blocks_shared", "prefix_exports")}
                for n, es in fstats["engines"].items()}
            clean = drain_and_settle(fleet, members, cpids)
            res["pools_clean"] = clean
            res["streams"] = [r["toks"] if r else None for r in out]
            res["statuses"] = [r["status"] if r else None for r in out]
            res["ttft_ms"] = sorted(
                r["ttft_ms"] for r in out if r and r["ttft_ms"])
            res["wall_s"] = wall_s
            gen = sum(len(s) for s in res["streams"] if s)
            res["tokens_per_s"] = gen / wall_s if wall_s else 0.0
        finally:
            fleet.stop()
            client.close()
            srv.stop()
        return res

    def pct(vals, q):
        return (vals[min(len(vals) - 1, int(len(vals) * q))]
                if vals else None)

    offs, ons = [], []
    for r in range(repeats):
        log(f"=== arm: prefix OFF, repeat {r + 1}/{repeats} ===")
        offs.append(run_arm(False))
        log(f"off[{r}]: wall={offs[-1]['wall_s']:.2f}s "
            f"tok/s={offs[-1]['tokens_per_s']:.1f}")
        log(f"=== arm: prefix ON, repeat {r + 1}/{repeats} ===")
        ons.append(run_arm(True))
        log(f"on[{r}]: wall={ons[-1]['wall_s']:.2f}s "
            f"tok/s={ons[-1]['tokens_per_s']:.1f} "
            f"head_replicas={ons[-1].get('head_replicas')}")

    # perf from the best repeat of each arm (sub-second walls, OS noise);
    # every DETERMINISTIC gate must hold on every repeat
    on = max(ons, key=lambda r: r["tokens_per_s"])
    off = max(offs, key=lambda r: r["tokens_per_s"])
    hits = on["stats"]["prefix_directory_hits"]
    misses = on["stats"]["prefix_directory_misses"]
    routed_frac = on["stats"]["prefix_routes"] / n_requests
    baseline = 2 / len(MEMBERS)  # prefix_max_replicas / fleet size
    speedup = (on["tokens_per_s"] / off["tokens_per_s"]
               if off["tokens_per_s"] else 0.0)
    ttft_on, ttft_off = (min(pct(r["ttft_ms"], 0.99) for r in ons),
                         min(pct(r["ttft_ms"], 0.99) for r in offs))

    gates = {
        "token_equal": all(
            r["streams"] == offs[0]["streams"]
            and all(s == Status.OK for s in r["statuses"])
            and None not in r["streams"]
            for r in ons + offs),
        "zero_install_copies": all(
            e["prefix_install_copies"] == 0
            for r in ons + offs for e in r["engines"].values()),
        "accounting_exact": all(
            r["stats"]["prefix_directory_hits"]
            + r["stats"]["prefix_directory_misses"] == n_requests
            for r in ons),
        "routed_to_resident": all(
            r["stats"]["prefix_routes"] / n_requests > 2.0 / 3.0
            for r in ons),
        "hot_replicated": all(
            r.get("head_replicas", 0) >= 2
            and r["stats"]["prefix_replications"] >= 1
            and all(e["prefix_tier_installs"] == 0
                    for e in r["engines"].values())
            for r in ons),
        "zero_leaks_all_engines": all(
            all(r["pools_clean"].values()) for r in ons + offs),
    }
    if not a.quick:
        gates["speedup"] = speedup >= a.speedup
        gates["ttft_p99"] = (ttft_on is not None and ttft_off is not None
                             and ttft_on <= 1.10 * ttft_off)
    sc = {
        "name": "zipf_routing[on_vs_off]",
        "gates": gates,
        "speedup": round(speedup, 3),
        "tokens_per_s": {"on": round(on["tokens_per_s"], 1),
                         "off": round(off["tokens_per_s"], 1)},
        "ttft_p99_ms": {"on": ttft_on and round(ttft_on, 2),
                        "off": ttft_off and round(ttft_off, 2)},
        "directory": {"hits": hits, "misses": misses,
                      "routed_frac": round(routed_frac, 3),
                      "pressure_baseline": round(baseline, 3)},
        "repeats": repeats,
        "replications": on["stats"]["prefix_replications"],
        "pass": all(gates.values()),
    }
    artifact["scenarios"].append(sc)
    all_pass &= sc["pass"]
    log(f"zipf_routing: speedup={speedup:.2f}x routed={routed_frac:.2f} "
        f"hits/misses={hits}/{misses} gates={gates}")

    # ------------------------------------- kill + failover prefix reuse
    # everything pinned to a throttled engine that dies mid-stream; the
    # survivor ALREADY resident rebuilds each session around its
    # registered prefix — sharing the pinned pages, recomputing only
    # the private tail
    log("=== scenario: kill + failover prefix reuse ===")
    kpre, ksuf = prefixes[0], [suffixes[0], suffixes[1]]
    ref = ServingEngine(params, cfg, serving(a.kill_new))
    ref.start()
    try:
        want = [list(ref.submit(kpre + s,
                                max_new_tokens=a.kill_new).stream())
                for s in ksuf]
    finally:
        ref.stop()

    class PinPolicy(RoutePolicy):
        def __init__(self, name):
            self.name = name

        def score(self, name, signals):
            if signals.draining:
                return None
            return 1.0 if name == self.name else 0.0

    plan = FaultPlan()
    # throttle the doomed engine's decode (~10ms/token) so the armed
    # death lands MID-stream, not after a free run to completion
    plan.arm("delayed_fetch", count=100000, arg=0.01)
    kmembers = {
        "a": ServingEngine(params, cfg, serving(a.kill_new, faults=plan)),
        "b": ServingEngine(params, cfg, serving(a.kill_new)),
        "c": ServingEngine(params, cfg, serving(a.kill_new)),
    }
    kfleet = EngineFleet(kmembers, FleetConfig(
        **FC, route_policy=PinPolicy("a")))
    kfleet.start()
    try:
        cpid = None
        for n in ("a", "b", "c"):
            cpid = kfleet.register_prefix(kpre, engine=n)
        corpse_lid = kfleet.prefixdir.residents(cpid)["a"]
        reqs = [kfleet.submit(s, prefix_tokens=kpre,
                              max_new_tokens=a.kill_new) for s in ksuf]
        its = [r.stream() for r in reqs]
        heads = [[next(it), next(it)] for it in its]
        plan.arm("engine_death")  # die at the very next flush boundary
        streams = [heads[j] + list(its[j]) for j in range(len(reqs))]
        ks = kfleet.stats()
        reuses = sum(ks["engines"][n]["failover_prefix_reuses"]
                     for n in ("b", "c"))
        shared = sum(ks["engines"][n]["prefix_blocks_shared"]
                     for n in ("b", "c"))
        # the fence swept the corpse's residency; its local pin remains
        # and is released by name so the corpse audits clean too
        try:
            kmembers["a"].unregister_prefix(corpse_lid)
        except (ValueError, KeyError):
            pass
        kclean = drain_and_settle(kfleet, kmembers, [cpid])
        kgates = {
            "token_equal": (streams == want
                            and all(r.status == Status.OK for r in reqs)),
            "death_fired":
                plan.snapshot()["injected"]["engine_death"] == 1,
            "failover_counted": (ks["failovers"] == 1
                                 and ks["failover_sessions"] == len(reqs)),
            "prefix_reused": reuses >= 1 and shared >= 1,
            "corpse_swept": "a" not in kfleet.prefixdir.residents(cpid),
            "zero_leaks_all_engines": all(kclean.values()),
        }
        ksc = {
            "name": "kill_prefix_reuse",
            "gates": kgates,
            "failover_sessions": ks["failover_sessions"],
            "failover_prefix_reuses": reuses,
            "prefix_blocks_shared": shared,
            "pass": all(kgates.values()),
        }
        artifact["scenarios"].append(ksc)
        all_pass &= ksc["pass"]
        log(f"kill_prefix_reuse: reuses={reuses} shared={shared} "
            f"gates={kgates}")
    finally:
        kfleet.stop()

    # ------------------------------------------------------ artifact tail
    artifact["speedup"] = round(speedup, 3)
    artifact["pass"] = bool(all_pass)
    out_path = a.out or (None if a.quick else "PREFIX_r20.json")
    if out_path:
        Path(out_path).write_text(json.dumps(artifact, indent=2) + "\n")
        log(f"artifact -> {out_path}")
    print(json.dumps(artifact))

    from vtpu.obs.summary import print_summary

    print_summary(
        artifact["metric"],
        round(speedup, 3),
        "pass" if all_pass else "FAIL",
        unit="tokens_per_sec_speedup",
        scenarios={sc["name"]: sc["pass"]
                   for sc in artifact["scenarios"]},
    )
    sys.exit(0 if all_pass else 1)


if __name__ == "__main__":
    from vtpu.util.jaxcache import place_compile_cache

    place_compile_cache()
    main()
