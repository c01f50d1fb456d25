"""Live session migration A/B: migrated streams vs stay-put (ISSUE 13).

The tentpole claim under measurement: a session moved between engines
mid-stream resumes at exactly its next token, pays ZERO device copies
beyond the one D2H/H2D each side already pays for swap, and the migration
blackout (last token on the source -> first token on the destination) is
bounded. Deterministic gates, every run:

  1. TOKEN EQUALITY: every migrated stream equals the stay-put reference
     — for the exact and int8 pools, and under a ('tp',) head-sharded
     mesh (the staging pair moves per-chip shards);
  2. ZERO COPIES: stats()["migration_copies"] == 0 on source AND
     destination in every scenario (the handoff_copies bar applied
     across engines); payload bytes show up on migrate_{out,in}_bytes;
  3. DRAIN: ServingEngine.drain(dst) leaves the source EMPTY — pool free
     == capacity, no slots, nothing parked/queued/admitting, admission
     refused — with every evacuated stream completing on the destination
     token-equal;
  4. BLACKOUT: per-migration blackout p50/p99 ms reported, p99 under the
     --blackout-ms bound;
  5. CRASH RECOVERY: the migrate_src_death and migrate_payload_loss
     seams fire (FaultPlan.snapshot()), recoverable sessions rebuild
     token-equal via the recompute-on-fault prefill path, and ONLY the
     configured-unrebuildable session ends with a typed FAULTED terminal.

Usage:  python benchmarks/migrate_bench.py [--quick] [--sessions N]
            [--max-new N] [--page P] [--tp N] [--blackout-ms MS] [--out F]
Emits:  full artifact JSON on stdout line 1, then the compact one-line
        summary (metric/value/verdict — the PR-3 driver-artifact
        convention) as the FINAL stdout line; human notes on stderr.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main() -> None:
    ap = argparse.ArgumentParser("migrate-bench")
    ap.add_argument("--quick", action="store_true",
                    help="CI mode: smaller traffic, same gates")
    ap.add_argument("--sessions", type=int, default=None,
                    help="sessions per arm (default 4; quick 2)")
    ap.add_argument("--max-new", type=int, default=12,
                    help="decode tokens per session")
    ap.add_argument("--page", type=int, default=8)
    ap.add_argument("--tp", type=int, default=2,
                    help="tensor-parallel degree for the tp arm (0 skips)")
    ap.add_argument("--blackout-ms", type=float, default=5000.0,
                    help="migration blackout p99 bound (generous: the CI "
                         "rig's blackout is compile/dispatch noise, the "
                         "gate catches hangs, not microseconds)")
    ap.add_argument("--out", default=None,
                    help="artifact path (default MIGRATE_r15.json on full "
                         "runs; quick runs only write when set)")
    a = ap.parse_args()
    sessions = a.sessions or (2 if a.quick else 4)
    if a.quick:
        a.max_new = min(a.max_new, 10)
    if a.tp > 1 and "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={max(a.tp, 2)}"
        ).strip()

    import jax
    import jax.numpy as jnp

    from vtpu.models import ModelConfig, init_params
    from vtpu.serving import (
        FaultPlan, FaultSpec, ServingConfig, ServingEngine, Status, migrate)

    # tiny on purpose (the chaos-bench discipline): the CPU rig's tick is
    # dispatch-dominated, so the bench measures the migration machinery,
    # not model FLOPs
    mk = dict(vocab=128, d_model=32, n_layers=1, d_ff=64,
              max_seq=64, dtype=jnp.float32, use_pallas=False)
    cfg = ModelConfig(n_heads=2, head_dim=16, **mk)
    cfg_int8 = ModelConfig(n_heads=2, head_dim=16, kv_int8=True, **mk)
    cfg_tp = ModelConfig(n_heads=4, head_dim=8, **mk)
    prompt_len = 8

    def prompt(seed: int, vocab: int):
        return [int(t) for t in jax.random.randint(
            jax.random.key(seed), (prompt_len,), 1, vocab, jnp.int32)]

    def base_serving(**kw):
        base = dict(slots=2, prefill_buckets=(16,), max_new_tokens=a.max_new,
                    prefill_chunk=16, kv_page=a.page, kv_swap=16)
        base.update(kw)
        return ServingConfig(**base)

    artifact: dict = {
        "metric": "migrate_deterministic_gates",
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

    # ---------------------------------------------------- token-equal arms
    def run_layout(name, layout_cfg, mesh=None):
        nonlocal all_pass
        log(f"=== scenario: token_equal[{name}] ===")
        params = init_params(jax.random.key(0), layout_cfg)
        prompts = [prompt(100 + j, layout_cfg.vocab)
                   for j in range(sessions)]
        ref = ServingEngine(params, layout_cfg,
                            base_serving(slots=sessions), mesh=mesh)
        ref.start()
        try:
            want = [list(ref.submit(p, max_new_tokens=a.max_new).stream())
                    for p in prompts]
        finally:
            ref.stop()
        # decode-throttled source (~10ms/token): the migrate must catch
        # every session MID-stream — an unthrottled engine on a loaded
        # box can finish the whole --max-new stream between the two
        # head reads and the migrate() call (all_migrated would fail)
        src = ServingEngine(params, layout_cfg,
                            base_serving(slots=sessions, faults=FaultPlan(
                                [FaultSpec("delayed_fetch", at=0,
                                           count=100000, arg=0.01)])),
                            mesh=mesh)
        dst = ServingEngine(params, layout_cfg,
                            base_serving(slots=sessions), mesh=mesh)
        src.start()
        dst.start()
        try:
            got, paths = [], []
            for j, p in enumerate(prompts):
                req = src.submit(p, max_new_tokens=a.max_new)
                it = req.stream()
                head = [next(it), next(it)]
                t_last = time.perf_counter()
                rep = migrate(req, src, dst)
                head.append(next(it))
                blackouts_ms.append((time.perf_counter() - t_last) * 1e3)
                paths.append(rep["path"])
                got.append(head + list(it))
            ss, ds = src.stats(), dst.stats()
        finally:
            src.stop()
            dst.stop()
        gates = {
            "token_equal": got == want,
            "all_migrated": ss["migrations_out"] == sessions
                             and ds["migrations_in"] == sessions,
            "zero_extra_copies": ss["migration_copies"] == 0
                                  and ds["migration_copies"] == 0,
            "payload_moved": ss["migrate_out_bytes"] > 0
                              and ss["migrate_out_bytes"]
                              == ds["migrate_in_bytes"],
            "pools_clean": pools_clean(src) and pools_clean(dst),
            "src_empty": ss["parked_sessions"] == 0
                          and ss["active_slots"] == 0,
        }
        ok = all(gates.values())
        all_pass &= ok
        artifact["scenarios"].append({
            "name": f"token_equal[{name}]", "pass": ok, "gates": gates,
            "paths": paths,
            "migrate_out_bytes": ss["migrate_out_bytes"],
            "migrate_in_bytes": ds["migrate_in_bytes"],
        })
        log(f"token_equal[{name}]: pass={ok} gates={gates}")

    run_layout("exact", cfg)
    run_layout("int8", cfg_int8)
    if a.tp > 1 and len(jax.devices()) >= a.tp:
        from vtpu.parallel.mesh import make_axis_mesh

        run_layout(f"tp{a.tp}", cfg_tp, mesh=make_axis_mesh("tp", a.tp))
    elif a.tp > 1:
        log(f"tp arm skipped: {len(jax.devices())} devices < tp={a.tp}")

    # ------------------------------------------------------------- drain
    log("=== scenario: drain ===")
    params = init_params(jax.random.key(0), cfg)
    prompts = [prompt(200 + j, cfg.vocab) for j in range(sessions + 2)]
    ref = ServingEngine(params, cfg, base_serving(slots=sessions + 2))
    ref.start()
    try:
        want = [list(ref.submit(p, max_new_tokens=a.max_new).stream())
                for p in prompts]
    finally:
        ref.stop()
    src = ServingEngine(params, cfg, base_serving(slots=2))
    dst = ServingEngine(params, cfg, base_serving(slots=sessions + 2))
    src.start()
    dst.start()
    try:
        reqs, its, streams = [], [], []
        for j, p in enumerate(prompts):
            req = src.submit(p, max_new_tokens=a.max_new)
            reqs.append(req)
            its.append(req.stream())
            streams.append([])
        # first two stream a little (live slots); one parks; the rest wait
        for j in (0, 1):
            streams[j].append(next(its[j]))
        src.park(reqs[0])
        t0 = time.perf_counter()
        while reqs[0] not in src._parked and reqs[0].status is None:
            if time.perf_counter() - t0 > 30:
                break
            time.sleep(0.002)
        report = src.drain(dst)
        refused = False
        try:
            src.submit(prompts[0])
        except RuntimeError:
            refused = True
        for j in range(len(reqs)):
            streams[j] += list(its[j])
        ss, ds = src.stats(), dst.stats()
    finally:
        src.stop()
        dst.stop()
    gates = {
        "token_equal": streams == want,
        "all_completed": all(r.status == Status.OK for r in reqs),
        "src_empty": (ss["active_slots"] == 0 and ss["parked_sessions"] == 0
                      and ss["queued"] == 0 and ss["admitting_slots"] == 0
                      and ss["kv_pool_free"] == ss["kv_pool_blocks"]
                      and ss["swap_host_free"] == ss["swap_host_blocks"]),
        "admission_refused": refused,
        "zero_extra_copies": ss["migration_copies"] == 0
                              and ds["migration_copies"] == 0,
        "dst_clean": pools_clean(dst),
    }
    drain_pass = all(gates.values())
    all_pass &= drain_pass
    artifact["scenarios"].append({
        "name": "drain", "pass": drain_pass, "gates": gates,
        "report": report,
        "migrated": report["migrated"], "completed": report["completed"],
    })
    log(f"drain: pass={drain_pass} gates={gates} report={report}")

    # ------------------------------------------------------ crash recovery
    log("=== scenario: crash_recovery (migrate_* fault seams) ===")
    # every crash-recovery SOURCE is decode-throttled (~10ms/token):
    # the rebuild path needs the sequence still inside the destination's
    # prefill bucket when migrate() runs, and an unthrottled engine on a
    # loaded 1-core box free-runs past it between the head reads and
    # the call (scenario (c) inverts this — it must NOT complete early)
    throttle = FaultSpec("delayed_fetch", at=0, count=100000, arg=0.01)
    plan_src = FaultPlan([FaultSpec("migrate_src_death", at=0), throttle])
    plan_dst = FaultPlan([FaultSpec("migrate_payload_loss", at=0)])
    p1, p2, p3 = (prompt(300, cfg.vocab), prompt(301, cfg.vocab),
                  prompt(302, cfg.vocab))
    budget_c = 12  # scenario (c) needs the sequence to outgrow bucket 16
    ref = ServingEngine(params, cfg, base_serving())
    ref.start()
    try:
        want = [list(ref.submit(p, max_new_tokens=a.max_new).stream())
                for p in (p1, p2)]
        want_c = list(ref.submit(p3, max_new_tokens=budget_c).stream())
    finally:
        ref.stop()
    # (a) source dies after the handshake -> destination rebuilds
    src = ServingEngine(params, cfg, base_serving(faults=plan_src))
    dst = ServingEngine(params, cfg, base_serving())
    src.start()
    dst.start()
    try:
        r = src.submit(p1, max_new_tokens=a.max_new)
        it = r.stream()
        got1 = [next(it), next(it)]
        rep1 = migrate(r, src, dst)
        got1 += list(it)
        recompute_stats = dst.stats()
    finally:
        src.stop()
        dst.stop()
    # (b) payload lost in transit -> destination rebuilds
    src = ServingEngine(params, cfg, base_serving(
        faults=FaultPlan([throttle])))
    dst = ServingEngine(params, cfg, base_serving(faults=plan_dst))
    src.start()
    dst.start()
    try:
        r2 = src.submit(p2, max_new_tokens=a.max_new)
        it2 = r2.stream()
        got2 = [next(it2)]
        rep2 = migrate(r2, src, dst)
        got2 += list(it2)
    finally:
        src.stop()
        dst.stop()
    # (c) payload lost AND unrebuildable (no prefill route on the
    # destination for a grown sequence) -> the ONE configured typed
    # FAULTED terminal of the whole bench
    plan_dst2 = FaultPlan([FaultSpec("migrate_payload_loss", at=0)])
    src = ServingEngine(params, cfg, base_serving(
        faults=FaultPlan([throttle])))
    dst = ServingEngine(params, cfg, ServingConfig(
        slots=2, prefill_buckets=(16,), max_new_tokens=a.max_new,
        kv_page=a.page, kv_swap=0, faults=plan_dst2))
    src.start()
    dst.start()
    try:
        # fixed budget independent of --max-new: the sequence must GROW
        # past the destination's only bucket (16) while still mid-stream,
        # or the "unrebuildable" arm would quietly turn into "completed"
        r3 = src.submit(p3, max_new_tokens=budget_c)
        it3 = r3.stream()
        got3 = [next(it3) for _ in range(9)]  # seq = 8 + 9 > bucket 16
        rep3 = migrate(r3, src, dst)
        got3 += list(it3)
    finally:
        src.stop()
        dst.stop()
    gates = {
        "src_death_recovered": rep1["path"] == "recompute"
                                and rep1["src_died"] and got1 == want[0]
                                and r.status == Status.OK,
        "src_death_recomputed": recompute_stats["migrate_recomputes"] == 1
                                 and recompute_stats["fault_recomputes"] == 1,
        "payload_loss_recovered": rep2["path"] == "recompute"
                                   and got2 == want[1]
                                   and r2.status == Status.OK,
        "unrebuildable_typed_faulted": rep3["path"] == "faulted"
                                        and r3.status == Status.FAULTED
                                        and got3 == want_c[:len(got3)],
        "seams_fired": (
            plan_src.snapshot()["injected"]["migrate_src_death"] == 1
            and plan_dst.snapshot()["injected"]["migrate_payload_loss"] == 1
            and plan_dst2.snapshot()["injected"]["migrate_payload_loss"] == 1),
    }
    fault_pass = all(gates.values())
    all_pass &= fault_pass
    artifact["scenarios"].append({
        "name": "crash_recovery", "pass": fault_pass, "gates": gates,
        "paths": [rep1["path"], rep2["path"], rep3["path"]],
    })
    log(f"crash_recovery: pass={fault_pass} gates={gates}")

    # ---------------------------------------------------------- blackout
    blackouts_ms.sort()

    def pct(vals, q):
        return (vals[min(len(vals) - 1, int(len(vals) * q))]
                if vals else None)

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

    # ---------------------------------------------------------- artifact
    artifact["pass"] = bool(all_pass)
    out_path = a.out or (None if a.quick else "MIGRATE_r15.json")
    if out_path:
        Path(out_path).write_text(json.dumps(artifact, indent=2) + "\n")
        log(f"artifact -> {out_path}")
    print(json.dumps(artifact))

    from vtpu.obs.summary import print_summary

    print_summary(
        "migrate_deterministic_gates",
        round(p99, 3) if p99 is not None else -1,
        "pass" if all_pass else "FAIL",
        unit="blackout_p99_ms",
        scenarios={sc["name"]: sc["pass"] for sc in artifact["scenarios"]},
    )
    sys.exit(0 if all_pass else 1)


if __name__ == "__main__":
    from vtpu.util.jaxcache import place_compile_cache

    place_compile_cache()
    main()
