"""Chaos soak: a seeded fault schedule over mixed serving traffic (ISSUE 12).

Every recovery path PR 12 added — deadline/overload shedding, crash
containment, swap-loss recompute, worker restart, watchdog degradation —
is exercised here IN COMBINATION, over the traffic mixes that stress the
seams: paged + int8 + overcommit park/evict/resume pressure (co-scheduled),
disaggregated prefill/decode with a dying worker, the multi-tick
device loop under a stalling fetch, (ISSUE 13) live cross-engine
migration whose source dies mid-transfer — the destination rebuilds the
session from token history via recompute-on-fault — and (ISSUE 14) a
FLEET engine killed without saying goodbye (engine_death: the loop thread
vanishes with no cleanup), every stream it held rebuilt on survivors from
the session ledger. The schedule is deterministic (a
seeded FaultPlan / explicit FaultSpecs — see vtpu/serving/faults), so the
gates are exact, not statistical:

  1. TYPED TERMINALS: every request ends with a status — OK, CANCELLED,
     SHED_DEADLINE, SHED_OVERLOAD or FAULTED — never a silent close;
  2. BLAST RADIUS: every stream that ended OK is TOKEN-EQUAL to the same
     request in a fault-free reference run (a fault changes WHEN and
     WHO, never WHAT an unaffected stream says);
  3. ZERO LEAKS: after the soak drains, the allocator free count, the
     host swap pool and slot occupancy all read exactly their initial
     values (stats(): kv_pool_free / swap_host_free / active_slots /
     parked_sessions);
  4. TICK CONTRACT: device_gets_per_tick holds throughout — 1.0 on the
     classic loops, 1/k under the device loop — i.e. NO recovery path
     added a host sync;
  5. COVERAGE: the seams each scenario configured actually injected
     (FaultPlan.snapshot()).

Usage:  python benchmarks/chaos_bench.py [--quick] [--seed N]
            [--sessions N] [--max-new N] [--out F]
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


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main() -> None:
    ap = argparse.ArgumentParser("chaos-bench")
    ap.add_argument("--quick", action="store_true",
                    help="CI mode: smaller traffic, same gates")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for the core scenario's FaultPlan.seeded "
                         "schedule")
    ap.add_argument("--sessions", type=int, default=None,
                    help="core-scenario sessions per wave (default 4; "
                         "quick 2)")
    ap.add_argument("--max-new", type=int, default=16,
                    help="decode tokens per session")
    ap.add_argument("--page", type=int, default=8)
    ap.add_argument("--out", default=None,
                    help="artifact path (default FAULTS_r15.json on full "
                         "runs; quick runs only write when set)")
    a = ap.parse_args()
    waves = a.sessions or (2 if a.quick else 4)
    if a.quick:
        a.max_new = min(a.max_new, 10)

    import jax
    import jax.numpy as jnp

    from vtpu.serving import (
        DisaggConfig, FaultPlan, FaultSpec, ServingConfig, ServingEngine,
        Status, Terminal, migrate)
    from vtpu.models import ModelConfig, init_params

    # tiny on purpose (the overcommit/paged bench discipline): the CPU
    # rig's tick is dispatch-dominated, so the soak measures the failure
    # machinery, not model FLOPs — and int8 KV rides the core scenario so
    # the swap/recompute paths move a quantized pool
    mk = dict(vocab=128, d_model=32, n_heads=2, n_layers=1, d_ff=64,
              max_seq=64, head_dim=16, dtype=jnp.float32, use_pallas=False)
    cfg = ModelConfig(kv_int8=True, **mk)
    cfg_bf16 = ModelConfig(**mk)
    params = init_params(jax.random.key(0), cfg)
    prompt_len = 8
    # core wave-1 budget gets the SAME floor the migrate/fleet scenarios
    # use for their kills: the park (and the eviction pressure the swap
    # seams need) must land while wave 1 is still live — on a starved
    # smoke runner a 10-token budget can fully drain between take(2) and
    # park (the engine keeps decoding whether or not the client reads),
    # leaving nothing to park, no eviction, and the gated at=0 spill seam
    # never consulted (prompt 8 + 24 < max_seq 64)
    core_new = max(a.max_new, 24)
    pages_per = -(-(prompt_len + core_new) // a.page)

    def prompt(seed: int):
        return [int(t) for t in jax.random.randint(
            jax.random.key(seed), (prompt_len,), 1, cfg.vocab, jnp.int32)]

    def take(req, n: int) -> list:
        """Up to n tokens off the raw queue; stops early at the typed
        terminal (an injected fault — or a shed, under chaos — may have
        ended the stream before its n-th token)."""
        got = []
        while len(got) < n:
            item = req.out.get(timeout=120)
            if item is None or isinstance(item, Terminal):
                break
            got.append(item)
        return got

    def drain(req) -> list:
        """Consume the rest of the stream. Status-aware, NOT stream():
        take() above may already have consumed the Terminal sentinel of a
        request that ended early, and a second blocking get() would then
        wait forever — the terminal is delivered exactly once."""
        got = []
        while req.status is None:
            try:
                item = req.out.get(timeout=0.05)
            except _queue.Empty:  # re-check the status
                continue
            if item is None or isinstance(item, Terminal):
                break
            got.append(item)
        # terminal reached (or consumed earlier): empty what remains —
        # tokens always precede finish(), so nothing can arrive after
        while True:
            try:
                item = req.out.get_nowait()
            except _queue.Empty:
                return got
            if item is not None and not isinstance(item, Terminal):
                got.append(item)

    def wait_drained(eng, timeout: float = 60.0) -> dict:
        """Poll until the engine is idle (nothing active, parked, queued
        or mid-swap) and return the settled stats snapshot — the state
        the zero-leak gate is judged on."""
        t0 = time.perf_counter()
        while True:
            s = eng.stats()
            if (s["active_slots"] == 0 and s["parked_sessions"] == 0
                    and s["queued"] == 0 and s["admitting_slots"] == 0):
                return s
            if time.perf_counter() - t0 > timeout:
                return s
            time.sleep(0.01)

    def run_traffic(eng, *, deadlines: bool, expect_shed: int) -> dict:
        """The shared core-scenario schedule — identical submit order in
        both arms (the chaos arm adds deadline submits up front and an
        overload config; neither changes any OK stream's tokens):

          [deadline probes] -> wave 1 fills every slot and streams 2
          tokens -> low-priority burst overflows the line (chaos arm:
          shed to depth while the slots are still busy) -> wave 1 parks
          -> wave 2 + the burst remnant pressure the pool (evictions ->
          the swap seams) -> wave 1 resumes -> everything drains.
        """
        out = {"reqs": [], "streams": [], "deadline_idx": [],
               "burst_idx": []}

        def submit(seed, **kw):
            req = eng.submit(prompt(seed), max_new_tokens=core_new, **kw)
            out["reqs"].append(req)
            out["streams"].append([])
            return len(out["reqs"]) - 1, req

        if deadlines:
            for j in range(2):
                i, _ = submit(500 + j, deadline_ms=0)
                out["deadline_idx"].append(i)
        wave1 = [submit(100 + j, priority=5) for j in range(waves)]
        for i, req in wave1:
            out["streams"][i] += take(req, 2)
        # the burst goes in while every slot is busy: in the chaos arm
        # the line overflows shed_queue_depth and the policy sheds the
        # excess (lowest priority = these) at the next tick head —
        # waited on below so the shed deterministically lands BEFORE the
        # park frees slots
        for j in range(2 + waves):
            i, _ = submit(600 + j, priority=0)
            out["burst_idx"].append(i)
        if expect_shed:
            # wait for the FIRST shed only (the full excess may shrink if
            # a fault frees a slot mid-burst): the point is that the shed
            # lands while wave 1 still has most of its budget, so the
            # parks below still create the eviction pressure
            t0 = time.perf_counter()
            while eng.stats()["shed_overload"] < 1:
                if time.perf_counter() - t0 > 5:
                    break
                time.sleep(0.002)
        for i, req in wave1:
            if req.status is None:
                eng.park(req)
        t0 = time.perf_counter()
        want = sum(1 for i, r in wave1 if r.status is None)
        while eng.stats()["parked_sessions"] < want:
            if time.perf_counter() - t0 > 60:
                break
            time.sleep(0.002)
        # pool pressure: wave 2 plus the burst remnant force the parked
        # pages out (spill or injected-loss drop)
        for j in range(waves):
            submit(200 + j, priority=5)
        for i, req in wave1:
            if req.status is None:
                eng.resume(req)
        for i, req in enumerate(out["reqs"]):
            out["streams"][i] += drain(req)
        return out

    artifact: dict = {
        "metric": "chaos_soak_deterministic_gates",
        "seed": a.seed,
        "quick": bool(a.quick),
        "sessions_per_wave": waves,
        "max_new": a.max_new,
        "scenarios": [],
    }
    all_pass = True

    # ---------------------------------------------------------------- core
    log("=== scenario: core (paged+int8+swap, seeded schedule) ===")
    shed_depth = 2

    def core_serving(faults=None, shed=False):
        return ServingConfig(
            slots=waves, prefill_buckets=(16,), max_new_tokens=core_new,
            prefill_chunk=16, kv_page=a.page,
            kv_pool_blocks=waves * pages_per + 1,
            kv_swap=max(waves * pages_per // 2, 1),
            shed_queue_depth=(shed_depth if shed else 0), faults=faults)

    ref_eng = ServingEngine(params, cfg, core_serving())
    ref_eng.start()
    try:
        ref = run_traffic(ref_eng, deadlines=False, expect_shed=0)
    finally:
        ref_eng.stop()

    # the GATED seams are pinned to arrivals that exist at every traffic
    # scale and under any box load (arrival COUNTS at a seam shift with
    # timing — a pure seeded rate can legitimately draw all its firings
    # past the soak's horizon on a loaded CI runner); the seeded portion
    # layers reproducible extra chaos on top (ungated — whatever it hits
    # must still satisfy the typed/token-equal/leak gates)
    plan = FaultPlan(
        [FaultSpec("alloc_exhaust", at=0),   # first reservation blocks
         FaultSpec("swap_d2h_loss", at=0),   # first eviction's spill lost
         FaultSpec("dispatch_exc", at=9)]    # one mid-wave emit faults
        + list(FaultPlan.seeded(a.seed, rates={
            "alloc_exhaust": 0.05, "swap_d2h_loss": 0.3,
            "swap_h2d_loss": 0.5}).specs))
    eng = ServingEngine(params, cfg, core_serving(faults=plan, shed=True))
    eng.start()
    try:
        chaos = run_traffic(eng, deadlines=True, expect_shed=1)
        settled = wait_drained(eng)
        stats = eng.stats()
    finally:
        eng.stop()

    # chaos submit order = [2 deadline probes] + the reference order
    shift = len(chaos["deadline_idx"])
    terminals = [r.status for r in chaos["reqs"]]
    gates = {}
    gates["all_terminal"] = all(s is not None for s in terminals)
    gates["deadline_typed"] = all(
        chaos["reqs"][i].status == Status.SHED_DEADLINE
        for i in chaos["deadline_idx"])
    gates["affected_typed"] = all(s in Status.ALL for s in terminals)
    gates["some_overload_shed"] = stats["shed_overload"] >= 1
    token_equal, compared = True, 0
    for i, req in enumerate(chaos["reqs"]):
        if req.status != Status.OK:
            continue
        j = i - shift
        if j < 0:
            continue
        compared += 1
        if chaos["streams"][i] != ref["streams"][j]:
            token_equal = False
            log(f"core: OK stream {i} diverged from reference {j}")
    gates["unaffected_token_equal"] = token_equal and compared > 0
    gates["zero_leaks"] = (
        settled["kv_pool_free"] == settled["kv_pool_blocks"]
        and settled["swap_host_free"] == settled["swap_host_blocks"]
        and settled["active_slots"] == 0
        and settled["parked_sessions"] == 0)
    gates["tick_contract"] = stats["device_gets_per_tick"] == 1.0
    snap = plan.snapshot()
    gates["seams_fired"] = all(
        snap["injected"][s] >= 1
        for s in ("swap_d2h_loss", "dispatch_exc", "alloc_exhaust"))
    core_pass = all(gates.values())
    all_pass &= core_pass
    artifact["scenarios"].append({
        "name": "core", "pass": core_pass, "gates": gates,
        "terminals": {s or "None": terminals.count(s)
                      for s in set(terminals)},
        "streams_compared": compared,
        "fault_plan": snap,
        "stats": {k: stats[k] for k in (
            "shed_deadline", "shed_overload", "faulted_requests",
            "faults_injected", "fault_recomputes", "swap_out_bytes",
            "swap_in_bytes", "evicted_blocks", "parks", "resumes",
            "pool_blocked_admissions", "pool_blocked_resumes",
            "device_gets_per_tick", "decode_ticks", "generated_tokens")},
    })
    log(f"core: pass={core_pass} gates={gates}")

    # -------------------------------------------------------------- disagg
    log("=== scenario: disagg (worker death + restart) ===")

    def disagg_serving(faults=None):
        return ServingConfig(
            slots=2, prefill_buckets=(16,), max_new_tokens=a.max_new,
            prefill_chunk=16, kv_page=a.page,
            disagg=DisaggConfig(prefill_workers=1),
            worker_retry_backoff_ms=5.0, faults=faults)

    params16 = init_params(jax.random.key(0), cfg_bf16)
    n_disagg = 2 if a.quick else 4
    ref_eng = ServingEngine(params16, cfg_bf16, disagg_serving())
    ref_eng.start()
    try:
        ref_reqs = [ref_eng.submit(prompt(300 + j),
                                   max_new_tokens=a.max_new)
                    for j in range(n_disagg)]
        ref_streams = [drain(r) for r in ref_reqs]
    finally:
        ref_eng.stop()
    plan_d = FaultPlan([FaultSpec("worker_death", at=0)])
    eng = ServingEngine(params16, cfg_bf16, disagg_serving(faults=plan_d))
    eng.start()
    try:
        reqs = [eng.submit(prompt(300 + j), max_new_tokens=a.max_new)
                for j in range(n_disagg)]
        streams = [drain(r) for r in reqs]
        settled = wait_drained(eng)
        stats = eng.stats()
    finally:
        eng.stop()
    gates = {
        "all_terminal": all(r.status is not None for r in reqs),
        "all_ok": all(r.status == Status.OK for r in reqs),
        "token_equal": streams == ref_streams,
        "worker_restarted": stats["worker_restarts"] == 1,
        "seams_fired": plan_d.snapshot()["injected"]["worker_death"] == 1,
        "zero_leaks": (
            settled["kv_pool_free"] == settled["kv_pool_blocks"]
            and settled["active_slots"] == 0),
        "tick_contract": stats["device_gets_per_tick"] == 1.0,
        "no_faulted": stats["faulted_requests"] == 0,
    }
    disagg_pass = all(gates.values())
    all_pass &= disagg_pass
    artifact["scenarios"].append({
        "name": "disagg", "pass": disagg_pass, "gates": gates,
        "fault_plan": plan_d.snapshot(),
        "stats": {k: stats[k] for k in (
            "worker_restarts", "faulted_requests", "faults_injected",
            "handoffs", "handoff_copies", "device_gets_per_tick",
            "decode_ticks", "generated_tokens")},
    })
    log(f"disagg: pass={disagg_pass} gates={gates}")

    # --------------------------------------------------------- device loop
    log("=== scenario: device_loop (watchdog degrade under k>1) ===")
    k = 2
    n_loop = 2 if a.quick else 4

    def loop_serving(faults=None, wd=0.0):
        return ServingConfig(
            slots=2, prefill_buckets=(16,), max_new_tokens=a.max_new,
            decode_loop_k=k, fetch_watchdog_ms=wd, faults=faults)

    ref_eng = ServingEngine(params16, cfg_bf16, loop_serving())
    ref_eng.start()
    try:
        ref_reqs = [ref_eng.submit(prompt(400 + j),
                                   max_new_tokens=a.max_new)
                    for j in range(n_loop)]
        ref_streams = [drain(r) for r in ref_reqs]
    finally:
        ref_eng.stop()
    plan_l = FaultPlan([FaultSpec("delayed_fetch", at=2, arg=0.03),
                        FaultSpec("dispatch_exc", at=5)])
    eng = ServingEngine(params16, cfg_bf16,
                        loop_serving(faults=plan_l, wd=8.0))
    eng.start()
    try:
        reqs = [eng.submit(prompt(400 + j), max_new_tokens=a.max_new)
                for j in range(n_loop)]
        streams = [drain(r) for r in reqs]
        settled = wait_drained(eng)
        stats = eng.stats()
    finally:
        eng.stop()
    ok_equal = all(
        streams[i] == ref_streams[i]
        for i, r in enumerate(reqs) if r.status == Status.OK)
    n_ok = sum(r.status == Status.OK for r in reqs)
    gates = {
        "all_terminal": all(r.status is not None for r in reqs),
        "affected_typed": all(
            r.status in (Status.OK, Status.FAULTED) for r in reqs),
        "one_faulted": sum(
            r.status == Status.FAULTED for r in reqs) == 1,
        "unaffected_token_equal": ok_equal and n_ok >= 1,
        "watchdog_degraded": stats["watchdog_degrades"] >= 1,
        # decode_ticks counts INNER ticks even after the degrade clamps
        # the per-flush cap, so the fetch contract stays exactly 1/k
        "tick_contract": stats["device_gets_per_tick"] == round(1 / k, 4),
        "zero_leaks": settled["active_slots"] == 0,
        "seams_fired": (
            plan_l.snapshot()["injected"]["delayed_fetch"] == 1
            and plan_l.snapshot()["injected"]["dispatch_exc"] == 1),
    }
    loop_pass = all(gates.values())
    all_pass &= loop_pass
    artifact["scenarios"].append({
        "name": "device_loop", "pass": loop_pass, "gates": gates,
        "fault_plan": plan_l.snapshot(),
        "stats": {key: stats[key] for key in (
            "watchdog_degrades", "faulted_requests", "faults_injected",
            "loop_flushes", "loop_early_exits", "device_gets_per_tick",
            "device_gets_per_token", "decode_ticks", "generated_tokens")},
    })
    log(f"device_loop: pass={loop_pass} gates={gates}")

    # -------------------------------------------------------------- migrate
    log("=== scenario: migrate (source dies mid-transfer) ===")
    n_mig = 2 if a.quick else 3
    # a budget comfortably past what the park round trip can outrun: the
    # client takes 2 tokens then parks, and the engine keeps producing in
    # the meantime — on a loaded smoke rig a 10-token budget can DRAIN
    # before the park lands, turning the parked-first determinism into
    # "completed" paths. 24 tokens cannot (prompt 8 + 24 < max_seq 64).
    mig_new = max(a.max_new, 24)

    def migrate_serving(faults=None):
        return ServingConfig(
            slots=n_mig, prefill_buckets=(16,), max_new_tokens=mig_new,
            prefill_chunk=16, kv_page=a.page, kv_swap=8, faults=faults)

    ref_eng = ServingEngine(params16, cfg_bf16, migrate_serving())
    ref_eng.start()
    try:
        ref_reqs = [ref_eng.submit(prompt(700 + j),
                                   max_new_tokens=mig_new)
                    for j in range(n_mig)]
        ref_streams = [drain(r) for r in ref_reqs]
    finally:
        ref_eng.stop()
    # the FIRST migration's source dies after the metadata handshake (the
    # kill-source-mid-migration case): the destination rebuilds that
    # session from token history; the rest transfer resident
    plan_m = FaultPlan([FaultSpec("migrate_src_death", at=0)])
    src = ServingEngine(params16, cfg_bf16, migrate_serving(faults=plan_m))
    dst = ServingEngine(params16, cfg_bf16, migrate_serving())
    src.start()
    dst.start()
    try:
        reqs, streams, paths = [], [], []
        for j in range(n_mig):
            req = src.submit(prompt(700 + j), max_new_tokens=mig_new)
            reqs.append(req)
            streams.append(take(req, 2))
        # park everyone FIRST: a parked session cannot finish, so the
        # extraction order (and which session the src-death seam hits)
        # is deterministic regardless of box speed vs the tiny budgets
        for req in reqs:
            src.park(req)
        t0 = time.perf_counter()
        while src.stats()["parked_sessions"] < n_mig:
            if time.perf_counter() - t0 > 60:
                break
            time.sleep(0.002)
        for j, req in enumerate(reqs):
            rep = migrate(req, src, dst)
            paths.append(rep["path"])
        for j, req in enumerate(reqs):
            streams[j] += drain(req)
        settled_src = wait_drained(src)
        settled_dst = wait_drained(dst)
        stats_src, stats_dst = src.stats(), dst.stats()
    finally:
        src.stop()
        dst.stop()
    gates = {
        "all_terminal": all(r.status is not None for r in reqs),
        "all_ok": all(r.status == Status.OK for r in reqs),
        "token_equal": streams == ref_streams,
        "src_death_recovered": paths[0] == "recompute"
                                and stats_dst["migrate_recomputes"] >= 1,
        # everyone parked before the first transfer, so the rest move
        # resident deterministically (a parked session cannot finish)
        "rest_resident": all(p == "resident" for p in paths[1:]),
        "zero_extra_copies": stats_src["migration_copies"] == 0
                              and stats_dst["migration_copies"] == 0,
        "zero_leaks": (
            settled_src["kv_pool_free"] == settled_src["kv_pool_blocks"]
            and settled_src["swap_host_free"]
            == settled_src["swap_host_blocks"]
            and settled_src["active_slots"] == 0
            and settled_src["parked_sessions"] == 0
            and settled_dst["kv_pool_free"] == settled_dst["kv_pool_blocks"]
            and settled_dst["active_slots"] == 0
            and settled_dst["parked_sessions"] == 0),
        "tick_contract": (
            stats_src["device_gets_per_tick"] in (None, 1.0)
            and stats_dst["device_gets_per_tick"] == 1.0),
        "seams_fired": (
            plan_m.snapshot()["injected"]["migrate_src_death"] == 1),
    }
    mig_pass = all(gates.values())
    all_pass &= mig_pass
    artifact["scenarios"].append({
        "name": "migrate", "pass": mig_pass, "gates": gates,
        "paths": paths,
        "fault_plan": plan_m.snapshot(),
        "stats": {key: stats_src[key] for key in (
            "migrations_out", "migrate_out_bytes", "migration_copies",
            "faults_injected")} | {
            "dst_" + key: stats_dst[key] for key in (
                "migrations_in", "migrate_in_bytes", "migrate_recomputes",
                "fault_recomputes", "generated_tokens")},
    })
    log(f"migrate: pass={mig_pass} gates={gates}")

    # ------------------------------------------------------------ fleet
    log("=== scenario: fleet (kill one engine of three, ledger failover) ===")
    from vtpu.serving import EngineFleet, FleetConfig, RoutePolicy

    class PinA(RoutePolicy):
        def score(self, name, signals):
            if signals.draining:
                return None
            return 1.0 if name == "a" else 0.0

    n_fleet = 2 if a.quick else 3
    ref_eng = ServingEngine(params16, cfg_bf16,
                            migrate_serving())  # same geometry family
    ref_eng.start()
    try:
        # mig_new, not a.max_new: the kill must land while streams are
        # still live (same early-completion hazard as the park above)
        ref_reqs = [ref_eng.submit(prompt(800 + j),
                                   max_new_tokens=mig_new)
                    for j in range(n_fleet)]
        ref_streams = [drain(r) for r in ref_reqs]
    finally:
        ref_eng.stop()
    plan_f = FaultPlan()
    engines = {"a": ServingEngine(params16, cfg_bf16,
                                  migrate_serving(faults=plan_f)),
               "b": ServingEngine(params16, cfg_bf16, migrate_serving()),
               "c": ServingEngine(params16, cfg_bf16, migrate_serving())}
    # wide miss window: the smoke tier runs benches concurrently on
    # starved runners, and a live-but-stalled loop must never be
    # declared dead here (see fleet_bench's FC note)
    fleet = EngineFleet(engines, FleetConfig(
        probe_interval_ms=20.0, miss_ms=2000.0, suspect_misses=2,
        dead_misses=4, route_policy=PinA))
    fleet.start()
    try:
        reqs = [fleet.submit(prompt(800 + j), max_new_tokens=mig_new)
                for j in range(n_fleet)]
        streams = [take(r, 2) for r in reqs]
        plan_f.arm("engine_death")  # the next flush boundary kills 'a'
        for j, req in enumerate(reqs):
            streams[j] += drain(req)
        fs = fleet.stats()
        settled = [wait_drained(e) for e in
                   (engines["b"], engines["c"])]
        stats_a = engines["a"].stats()
    finally:
        fleet.stop()
    # ISSUE 15: every DEAD engine yields a loadable black box, and the
    # killed sessions' journeys stitch token-conserved across the hop
    from vtpu.obs.fleettrace import validate_bundle

    journeys = fleet.trace.journeys()
    bundle_ok = validate_bundle(fleet.trace.bundles().get("a"))
    gates = {
        "postmortem_bundle": bundle_ok,
        "journeys_conserved": all(
            journeys.get(r.jid, {}).get("conserved") is True
            and journeys.get(r.jid, {}).get("n_hops") == 2
            for r in reqs),
        "all_terminal": all(r.status is not None for r in reqs),
        "all_ok": all(r.status == Status.OK for r in reqs),
        "token_equal": streams == ref_streams,
        "failover_counted": fs["failovers"] == 1
                             and fs["failover_sessions"] == n_fleet
                             and fs["failover_faulted"] == 0,
        "dead_declared": fs["engine_states"]["a"] == "DEAD",
        "corpse_reaped": (
            stats_a["kv_pool_free"] == stats_a["kv_pool_blocks"]
            and stats_a["active_slots"] == 0
            and stats_a["parked_sessions"] == 0),
        "zero_leaks_survivors": all(
            s["kv_pool_free"] == s["kv_pool_blocks"]
            and s["active_slots"] == 0 and s["parked_sessions"] == 0
            for s in settled),
        # survivors only: the corpse died with a dispatched-but-never-
        # fetched tick in flight (exactly what a crash loses), so its own
        # ratio legitimately under-reads — no recovery path may add a
        # sync on the engines still serving, though
        "tick_contract": all(
            fs["engines"][n]["device_gets_per_tick"] in (None, 1.0)
            for n in ("b", "c")),
        "seams_fired":
            plan_f.snapshot()["injected"]["engine_death"] == 1,
    }
    fleet_pass = all(gates.values())
    all_pass &= fleet_pass
    artifact["scenarios"].append({
        "name": "fleet", "pass": fleet_pass, "gates": gates,
        "fault_plan": plan_f.snapshot(),
        "stats": {
            "faults_injected": stats_a["faults_injected"],
            "failovers": fs["failovers"],
            "failover_sessions": fs["failover_sessions"],
            "probe_misses": fs["probe_misses"],
            "survivor_migrations_in": sum(
                fs["engines"][n]["migrations_in"] for n in ("b", "c")),
        },
    })
    log(f"fleet: pass={fleet_pass} gates={gates}")

    # ------------------------------------------------------------ artifact
    artifact["pass"] = bool(all_pass)
    injected_total = sum(
        sc["stats"]["faults_injected"] for sc in artifact["scenarios"])
    artifact["faults_injected_total"] = injected_total
    out_path = a.out or (None if a.quick else "FAULTS_r16.json")
    if out_path:
        Path(out_path).write_text(json.dumps(artifact, indent=2) + "\n")
        log(f"artifact -> {out_path}")
    print(json.dumps(artifact))

    from vtpu.obs.summary import print_summary

    print_summary(
        "chaos_soak_deterministic_gates",
        injected_total, "pass" if all_pass else "FAIL",
        unit="faults_injected",
        scenarios={sc["name"]: sc["pass"] for sc in artifact["scenarios"]},
    )
    sys.exit(0 if all_pass else 1)


if __name__ == "__main__":
    from vtpu.util.jaxcache import place_compile_cache

    place_compile_cache()
    main()
