"""Decode data-plane A/B: host sampling + synchronous tick loop vs on-device
batched sampling + one-tick-deep pipelined loop (ISSUE 1 tentpole).

Both arms run the SAME ServingEngine over the same weights and prompts; only
the sampling/pipelining configuration differs:

  host arm:    ``sample=`` callable configured -> the engine's fallback path.
               Every tick fetches the full [B, vocab] logits to the host and
               argmaxes per slot in Python — the seed repo's hot path, and
               what any custom sampler still gets today.
  device arm:  default config -> sampling fused into the jitted decode step
               (B*4 token bytes per tick instead of B*vocab*4 logit bytes),
               tick t+1 dispatched from the device-resident sampled tokens
               while the host delivers tick t (one-tick lookahead).

Reports tokens/sec and host-overhead µs/tick per arm (from the engine's own
stats() telemetry: device_gets_per_tick, bytes_fetched_per_tick, the
tick_phase_ms totals) plus the device/host speedup. Timed windows exclude
compiles: each arm runs one full warmup wave before measurement.

--loop-k (ISSUE 11) switches to the multi-tick device-loop sweep: k in
{1, 2, 4, 8} decode ticks per compiled flush across slot counts, reporting
host-ms-per-token amortization and tokens/sec -> DEVICE_LOOP_r13.json.
Deterministic gates run EVERY time (streams token-equal to k=1 for
exact/int8/MoE/tp=2, the 1/k fetch contract, early-exit slots stopping at
exactly their budget); the tokens/sec bar (>= 1.3x at the highest slot
count, k=8 vs k=1, host ms/token strictly decreasing in k) gates FULL runs
only — quick CI boxes are too noisy for perf claims (house discipline).

--fused-spec (ISSUE 19) sweeps the FUSED speculation grid: decode_loop_k in
--ks x spec_tokens in --spec-ks, draft+verify running INSIDE the device
loop with one [B, k, K+1] fetch per flush, against the k=1 no-spec classic
loop. Deterministic gates run EVERY time (every cell's measured streams
token-equal to the baseline; the one-fetch-per-flush accounting honest
against delivered tokens; staggered budgets truncating exactly); the perf
bar (>= 1.8x tokens/sec at the top cell AND fetches per delivered token
strictly below the plain loop's 1/k) gates FULL runs only
-> FUSED_SPEC_r19.json. The workload prompts are REPETITIVE on purpose:
token equality holds for any drafts by construction, but the perf claim
needs the n-gram drafter to actually accept.

Usage:  python benchmarks/decode_bench.py [--quick] [--slots 8]
            [--steps 96] [--waves 3] [--repeats 3]
        python benchmarks/decode_bench.py --loop-k [--quick]
            [--ks 1,2,4,8] [--loop-slots 8,32] [--out DEVICE_LOOP_r13.json]
        python benchmarks/decode_bench.py --fused-spec [--quick]
            [--ks 4,8] [--spec-ks 3,7] [--out FUSED_SPEC_r19.json]
Emits:  one JSON object on stdout (human summary on stderr); --loop-k and
        --fused-spec modes emit the artifact as one line followed by the
        shared print_summary line. --quick trims shapes for CI.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def _host_us(stats: dict):
    """Host microseconds per inner decode tick outside the device fetch
    (the stats()["tick_phase_ms"] totals), or None before any tick."""
    from vtpu.obs.tickprof import host_ms_per_tick

    ms = host_ms_per_tick(stats["tick_phase_ms"])
    return None if ms is None else round(ms * 1e3, 2)


def main() -> None:
    ap = argparse.ArgumentParser("decode-bench")
    ap.add_argument("--quick", action="store_true",
                    help="CI mode: fewer steps/waves/repeats, same A/B shape")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--steps", type=int, default=96,
                    help="decode tokens per request")
    ap.add_argument("--waves", type=int, default=3,
                    help="request waves per measurement (waves*slots requests;"
                    " >1 exercises retire->re-admit slot reuse)")
    ap.add_argument("--repeats", type=int, default=3,
                    help="timed measurements per arm (median reported)")
    ap.add_argument("--loop-k", action="store_true",
                    help="multi-tick device-loop sweep (ISSUE 11): host-ms-"
                    "per-token amortization across k and slot counts")
    ap.add_argument("--ks", default="1,2,4,8",
                    help="comma-separated decode_loop_k sweep (loop-k mode)")
    ap.add_argument("--loop-slots", default=None,
                    help="comma-separated slot counts for the loop-k sweep "
                    "(default 8,32; quick 2,4)")
    ap.add_argument("--fused-spec", action="store_true",
                    help="fused device-side speculation sweep (ISSUE 19): "
                    "decode_loop_k x spec_tokens grid vs the k=1 no-spec "
                    "classic loop")
    ap.add_argument("--spec-ks", default="3,7",
                    help="comma-separated spec_tokens sweep (fused-spec "
                    "mode)")
    ap.add_argument("--out", default=None,
                    help="also write the artifact JSON to this file "
                    "(loop-k / fused-spec modes)")
    a = ap.parse_args()
    if a.loop_k:
        # the tp=2 token-equality gate needs >= 2 virtual devices, forced
        # BEFORE jax imports (the paged_kv_bench --tp discipline)
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=2").strip()
        run_loop_k(a)
        return
    if a.fused_spec:
        run_fused_spec(a)
        return
    if a.quick:
        a.steps, a.waves, a.repeats = 32, 1, 2

    import jax

    if jax.default_backend() != "cpu":
        # the A/B is a host-overhead experiment; numbers are CPU-calibrated
        print("note: running on", jax.default_backend(), file=sys.stderr)
    import jax.numpy as jnp

    from vtpu.models import ModelConfig, init_params
    from vtpu.serving import ServingConfig, ServingEngine

    # Tiny on purpose: per-tick device compute is small, so the A/B isolates
    # what the tick LOOP costs — per-slot host argmax round-trips and the
    # host/device serialization the pipelined arm hides.
    cfg = ModelConfig(
        vocab=256, d_model=64, n_heads=2, n_layers=2, d_ff=128,
        max_seq=a.steps + 24, head_dim=32, dtype=jnp.float32, use_pallas=False,
    )
    params = init_params(jax.random.key(0), cfg)
    serving = ServingConfig(slots=a.slots, prefill_buckets=(16,),
                            max_new_tokens=a.steps)
    prompts = [
        [int(t) for t in jax.random.randint(
            jax.random.key(100 + i), (12,), 0, cfg.vocab, jnp.int32)]
        for i in range(a.slots * a.waves)
    ]

    def run_arm(name: str, **engine_kw) -> dict:
        eng = ServingEngine(params, cfg, serving, **engine_kw)
        eng.start()
        try:
            # warmup wave: prefill + decode compiles, thread steady state
            for r in [eng.submit(p, max_new_tokens=4)
                      for p in prompts[: a.slots]]:
                for _ in r.stream():
                    pass
            rates = []
            for _ in range(a.repeats):
                t0 = time.perf_counter()
                reqs = [eng.submit(p, max_new_tokens=a.steps)
                        for p in prompts]
                total = sum(
                    sum(1 for _ in r.stream()) for r in reqs)
                rates.append(total / (time.perf_counter() - t0))
            stats = eng.stats()
        finally:
            eng.stop()
        out = {
            "arm": name,
            "tokens_per_sec": round(statistics.median(rates), 1),
            "tokens_per_sec_runs": [round(r, 1) for r in rates],
            "host_overhead_us_per_tick": _host_us(stats),
            "device_gets_per_tick": stats["device_gets_per_tick"],
            "bytes_fetched_per_tick": stats["bytes_fetched_per_tick"],
            "device_sampling": stats["device_sampling"],
            "pipelined": stats["pipelined"],
        }
        print(f"{name:>6}: {out['tokens_per_sec']:8.1f} tok/s, host "
              f"{out['host_overhead_us_per_tick']} µs/tick, "
              f"{out['bytes_fetched_per_tick']} B/tick "
              f"({stats['device_gets_per_tick']} fetch/tick, "
              f"pipelined={out['pipelined']})", file=sys.stderr)
        return out

    # host arm first so its (larger) compile set never shares a timed
    # window with the device arm's
    host = run_arm("host", sample=lambda logits: int(jnp.argmax(logits)))
    device = run_arm("device")
    speedup = device["tokens_per_sec"] / host["tokens_per_sec"]
    print(f"device-sampled pipelined speedup: {speedup:.2f}x",
          file=sys.stderr)
    json.dump({
        "metric": "device_pipelined_decode_speedup",
        "value": round(speedup, 3),
        "unit": "x_tokens_per_sec_vs_host_sync",
        "slots": a.slots,
        "steps": a.steps,
        "waves": a.waves,
        "quick": a.quick,
        "model": {"vocab": cfg.vocab, "d_model": cfg.d_model,
                  "n_layers": cfg.n_layers},
        "arms": [host, device],
    }, sys.stdout, indent=2)
    print()


def run_loop_k(a) -> None:
    """The ISSUE 11 sweep: amortize the host tick tax over k tokens.

    Every cell runs the SAME engine config except decode_loop_k — k=1 is
    the classic pipelined loop (decode_loop_k=1 resolves to it, pinned
    bit-identical in tests), k>1 runs k ticks per compiled flush. The
    timed workload captures its streams, so "every k arm token-equal to
    k=1" is asserted on the measured traffic itself, not a side run."""
    import jax

    if a.quick:
        # trim only the knobs the caller left at their defaults: the smoke
        # tier passes explicit --repeats/--loop-slots with --quick and a
        # blanket reset would silently clobber them
        if a.steps == 96:
            a.steps = 32
        if a.waves == 3:
            a.waves = 1
        if a.repeats == 3:
            a.repeats = 2
    ks = [int(x) for x in str(a.ks).split(",") if x]
    slot_counts = ([int(x) for x in a.loop_slots.split(",")]
                   if a.loop_slots else ([2, 4] if a.quick else [8, 32]))
    import jax.numpy as jnp

    from vtpu.models import ModelConfig, init_params
    from vtpu.obs.summary import print_summary
    from vtpu.serving import ServingConfig, ServingEngine

    log = lambda *x: print(*x, file=sys.stderr)  # noqa: E731
    # Tinier than the ISSUE-1 A/B on purpose: the sweep isolates the host
    # tick tax the loop amortizes, so per-tick device compute must stay
    # SMALL relative to it even at the highest slot count — on the 2-core
    # CI rig the device IS the host CPU, and a bigger trunk flips the
    # high-slot cell into device-bound territory (the opposite of the
    # regime a real accelerator sits in at high slots, where the device
    # is fast and the Python tick is the ceiling).
    cfg = ModelConfig(
        vocab=128, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq=a.steps + 24, head_dim=16, dtype=jnp.float32,
        use_pallas=False,
    )
    params = init_params(jax.random.key(0), cfg)

    def prompts_for(n, seed0=100):
        return [
            [int(t) for t in jax.random.randint(
                jax.random.key(seed0 + i), (12,), 0, cfg.vocab, jnp.int32)]
            for i in range(n)
        ]

    def sweep_slot_count(slots):
        """One slot-count row: all k arms built up front, repeats
        INTERLEAVED across arms (the INT8_AB discipline) so slow drift on
        a shared/throttled box — exactly the rig class this runs on in CI
        — lands evenly on every arm instead of biasing whichever cell ran
        last. The host amortization figure comes from the tick-phase
        profiler's WHOLE-RUN totals per inner tick (admission + dispatch
        + deliver + swap_drain; fetch excluded — that phase is the
        device-bound wait), not the EMA tail: on a 2-core rig one noisy
        flush can dominate an EMA, while the totals average the cell."""
        prompts = prompts_for(slots * a.waves)
        engines = {}
        for k in ks:
            eng = ServingEngine(params, cfg, ServingConfig(
                slots=slots, prefill_buckets=(16,),
                max_new_tokens=a.steps, decode_loop_k=k))
            eng.start()
            for r in [eng.submit(p, max_new_tokens=4)
                      for p in prompts[:slots]]:
                for _ in r.stream():
                    pass
            engines[k] = eng
        rates = {k: [] for k in ks}
        streams0 = {}
        try:
            for rep in range(a.repeats):
                for k in ks:
                    t0 = time.perf_counter()
                    reqs = [engines[k].submit(p, max_new_tokens=a.steps)
                            for p in prompts]
                    got = [list(r.stream()) for r in reqs]
                    rates[k].append(sum(len(s) for s in got)
                                    / (time.perf_counter() - t0))
                    if rep == 0:
                        streams0[k] = got
            stats = {k: engines[k].stats() for k in ks}
        finally:
            for eng in engines.values():
                eng.stop()
        cells = []
        for k in ks:
            st = stats[k]
            ph = st["tick_phase_ms"]
            ticks = max(st["decode_ticks"], 1)
            cells.append({
                "slots": slots, "k": k,
                "tokens_per_sec": round(statistics.median(rates[k]), 1),
                "tokens_per_sec_runs": [round(r, 1) for r in rates[k]],
                "host_us_per_token": _host_us(st),
                "fetch_us_per_token": round(
                    ph["fetch"]["total_ms"] / ticks * 1e3, 2),
                "device_gets_per_token": st["device_gets_per_token"],
                "loop_flushes": st["loop_flushes"],
                "loop_early_exits": st["loop_early_exits"],
                "decode_loop_k": st["decode_loop_k"],
                "tick_fetches": st["tick_fetches"],
                "decode_ticks": st["decode_ticks"],
                "stream_token_equal_k1": streams0[k] == streams0[min(ks)],
            })
        return cells

    # ---------------------------------------------------------- the sweep
    sweep, equal_flags, fetch_flags = [], [], []
    for slots in slot_counts:
        for cell in sweep_slot_count(slots):
            equal_flags.append(cell["stream_token_equal_k1"])
            # the generalized transfer contract: exactly one batched fetch
            # per k inner ticks
            cell["fetch_contract"] = (
                cell["tick_fetches"] * cell["decode_loop_k"]
                == cell["decode_ticks"])
            fetch_flags.append(cell["fetch_contract"])
            sweep.append(cell)
            log(f"slots={cell['slots']:>3} k={cell['k']}: "
                f"{cell['tokens_per_sec']:8.1f} "
                f"tok/s, host {cell['host_us_per_token']} µs/token, "
                f"{cell['device_gets_per_token']} fetch/token, "
                f"early_exits={cell['loop_early_exits']}, "
                f"token_equal_k1={cell['stream_token_equal_k1']}")

    # ------------------------------------ deterministic layout equalities
    def layout_equal(tag, mk_engine, vocab, steps=6):
        prompts = [[t % vocab for t in p] for p in prompts_for(2, 900)]

        def one(k):
            eng = mk_engine(k)
            eng.start()
            try:
                reqs = [eng.submit(p[:7], max_new_tokens=steps)
                        for p in prompts]
                return [list(r.stream()) for r in reqs]
            finally:
                eng.stop()

        ok = one(4) == one(None)
        log(f"layout token-equality [{tag}]: {'ok' if ok else 'DIVERGED'}")
        return ok

    page = 8
    # one layer and a single bucket == max_seq: each gate engine warms one
    # decode window, keeping the eight equality builds cheap in CI
    small = ModelConfig(vocab=64, d_model=32, n_heads=4, n_layers=1,
                        d_ff=64, max_seq=32, head_dim=8, dtype=jnp.float32,
                        use_pallas=False)
    small_int8 = ModelConfig(vocab=64, d_model=32, n_heads=4, n_layers=1,
                             d_ff=64, max_seq=32, head_dim=8,
                             dtype=jnp.float32, use_pallas=False,
                             kv_int8=True)
    sp = init_params(jax.random.key(1), small)
    sp8 = init_params(jax.random.key(1), small_int8)

    def mk(params_, cfg_, mesh=None, **kw):
        return lambda k: ServingEngine(params_, cfg_, ServingConfig(
            slots=2, prefill_buckets=(32,), max_new_tokens=6,
            decode_loop_k=k, **kw), mesh=mesh)

    layouts = {
        "exact": layout_equal("exact", mk(sp, small), small.vocab),
        "int8": layout_equal(
            "int8", mk(sp8, small_int8, kv_page=page), small_int8.vocab),
    }
    from vtpu.models.moe import MoEConfig, init_moe_params
    from vtpu.serving.adapters import MoeSlotModel

    mcfg = MoEConfig(vocab=96, d_model=64, n_heads=2, n_layers=1, d_ff=64,
                     n_experts=4, top_k=2, max_seq=32, head_dim=32,
                     dtype=jnp.float32)
    mparams = init_moe_params(jax.random.key(5), mcfg)
    layouts["moe"] = layout_equal(
        "moe",
        lambda k: ServingEngine(
            serving=ServingConfig(slots=2, prefill_buckets=(32,),
                                  max_new_tokens=6, decode_loop_k=k),
            model=MoeSlotModel(mparams, mcfg)),
        mcfg.vocab)
    if len(jax.devices()) >= 2:
        from vtpu.parallel.mesh import make_axis_mesh

        layouts["tp2"] = layout_equal(
            "tp2", mk(sp, small, mesh=make_axis_mesh("tp", 2),
                      kv_page=page), small.vocab)
    else:  # a real-TPU single-chip box: the tp gate lives in the tests
        layouts["tp2"] = None
        log("layout token-equality [tp2]: skipped (single device)")

    # ---------------------------------------- early-exit deterministic gate
    def early_exit_exact():
        eng = ServingEngine(params, cfg, ServingConfig(
            slots=2, prefill_buckets=(16,), max_new_tokens=16,
            decode_loop_k=4))
        eng.start()
        try:
            budgets = [5, 7]  # both % 4 != 0: the wall lands mid-flush
            reqs = [eng.submit(p, max_new_tokens=b) for p, b in
                    zip(prompts_for(2, 500), budgets)]
            lens = [len(list(r.stream())) for r in reqs]
            stats = eng.stats()
        finally:
            eng.stop()
        ok = lens == budgets and stats["loop_early_exits"] > 0
        log(f"early-exit exact-budget gate: lens={lens} vs {budgets}, "
            f"early_exits={stats['loop_early_exits']} -> "
            f"{'ok' if ok else 'FAIL'}")
        return ok

    gates = {
        "streams_token_equal_k1": all(equal_flags),
        "fetch_contract_one_per_k": all(fetch_flags),
        "layouts_token_equal": layouts,
        "early_exit_exact_budget": early_exit_exact(),
    }
    det_ok = (gates["streams_token_equal_k1"]
              and gates["fetch_contract_one_per_k"]
              and gates["early_exit_exact_budget"]
              and all(v for v in layouts.values() if v is not None))

    # ------------------------------------------------- perf (full runs only)
    top_slots = max(slot_counts)
    top = {c["k"]: c for c in sweep if c["slots"] == top_slots}
    kmin, kmax = min(ks), max(ks)
    speedup = (round(top[kmax]["tokens_per_sec"]
                     / top[kmin]["tokens_per_sec"], 3)
               if kmin in top and kmax in top else None)
    host_series = [top[k]["host_us_per_token"] for k in sorted(top)]
    host_decreasing = (
        all(x is not None for x in host_series)
        and all(b < x for x, b in zip(host_series, host_series[1:])))
    perf_gated = not a.quick
    perf_ok = (speedup is not None and speedup >= 1.3 and host_decreasing)
    verdict = "pass" if det_ok and (perf_ok or not perf_gated) else "fail"
    log(f"k={kmax} vs k={kmin} at slots={top_slots}: {speedup}x tokens/sec, "
        f"host µs/token {host_series} "
        f"({'strictly decreasing' if host_decreasing else 'NOT decreasing'})"
        f"; perf {'gated' if perf_gated else 'recorded only (quick)'}")

    artifact = {
        "metric": "device_loop_tokens_per_sec_speedup_k8_vs_k1",
        "value": speedup,
        "unit": f"x_tokens_per_sec_at_slots_{top_slots}",
        "ks": ks, "slot_counts": slot_counts,
        "steps": a.steps, "waves": a.waves, "repeats": a.repeats,
        "quick": a.quick,
        "host_us_per_token_at_top_slots": host_series,
        "host_us_per_token_strictly_decreasing": host_decreasing,
        "sweep": sweep,
        "deterministic_gates": gates,
        "perf_gated": perf_gated,
        "model": {"vocab": cfg.vocab, "d_model": cfg.d_model,
                  "n_layers": cfg.n_layers},
    }
    print(json.dumps(artifact), flush=True)
    if a.out:
        with open(a.out, "w") as fh:
            json.dump(artifact, fh, indent=2)
    print_summary(
        "device_loop_tokens_per_sec_speedup_k8_vs_k1", speedup, verdict,
        unit=artifact["unit"],
        host_us_per_token=host_series,
        host_amortization_decreasing=host_decreasing,
        deterministic_gates_ok=det_ok, perf_gated=perf_gated)
    if verdict != "pass":
        sys.exit(1)


def run_fused_spec(a) -> None:
    """The ISSUE 19 grid: draft+verify fused inside the multi-tick loop.

    Every (k, K) cell runs decode_loop_k=k, spec_tokens=K — the fused
    executable, one [B, k, K+1] fetch per flush — against the k=1 no-spec
    classic pipelined loop as baseline. Repeats are INTERLEAVED across all
    arms (the loop-k discipline) so drift on a throttled CI box lands
    evenly. The timed workload captures its streams, so token equality to
    the baseline is asserted on the measured traffic itself."""
    import jax

    if a.quick:
        if a.steps == 96:
            a.steps = 32
        if a.waves == 3:
            a.waves = 1
        if a.repeats == 3:
            a.repeats = 2
    else:
        # the regime speculation serves in production: SMALL batch, LONG
        # streams — host tax per delivered token is highest at low slot
        # counts (the plain loop pays it per tick for 2 tokens), and long
        # streams let the n-gram drafter's acceptance establish. Only
        # applied to knobs the caller left at their mode-agnostic defaults.
        if a.steps == 96:
            a.steps = 384
    if a.slots == 8:
        a.slots = 2
    ks = [int(x) for x in str(a.ks).split(",") if x]
    if ks == [1, 2, 4, 8]:  # the --loop-k default: fusion needs k >= 2
        ks = [2] if a.quick else [4, 8]
    spec_ks = [int(x) for x in str(a.spec_ks).split(",") if x]
    if a.quick and spec_ks == [3, 7]:
        spec_ks = [3]
    import jax.numpy as jnp

    from vtpu.models import ModelConfig, init_params
    from vtpu.obs.summary import print_summary
    from vtpu.serving import ServingConfig, ServingEngine

    log = lambda *x: print(*x, file=sys.stderr)  # noqa: E731
    # Same tiny trunk as the loop-k sweep: the grid isolates the host tick
    # tax speculation amortizes further, so device compute stays small.
    cfg = ModelConfig(
        vocab=128, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq=a.steps + 24, head_dim=16, dtype=jnp.float32,
        use_pallas=False,
    )
    params = init_params(jax.random.key(0), cfg)

    # Repetitive prompts: a short motif repeated, so the generated stream
    # falls into n-gram-predictable cycles and the device drafter earns
    # real acceptance. Token equality NEVER depends on this choice — the
    # gate would hold on pure noise too — but the perf bar does.
    def prompts_for(n, seed0=100):
        out = []
        for i in range(n):
            motif = [int(t) for t in jax.random.randint(
                jax.random.key(seed0 + i), (4,), 1, cfg.vocab, jnp.int32)]
            out.append((motif * 3)[:12])
        return out

    arms = [("plain", 1, 0)] + [
        (f"k{k}xK{K}", k, K) for k in ks for K in spec_ks]
    prompts = prompts_for(a.slots * a.waves)
    engines = {}
    for name, k, K in arms:
        eng = ServingEngine(params, cfg, ServingConfig(
            slots=a.slots, prefill_buckets=(16,), max_new_tokens=a.steps,
            decode_loop_k=(k if k > 1 else None),
            spec_tokens=(K if k > 1 else 0)))
        eng.start()
        for r in [eng.submit(p, max_new_tokens=4)
                  for p in prompts[: a.slots]]:
            for _ in r.stream():
                pass
        engines[name] = eng
    rates = {name: [] for name, _, _ in arms}
    streams0 = {}
    try:
        for rep in range(a.repeats):
            for name, _, _ in arms:
                t0 = time.perf_counter()
                reqs = [engines[name].submit(p, max_new_tokens=a.steps)
                        for p in prompts]
                got = [list(r.stream()) for r in reqs]
                rates[name].append(sum(len(s) for s in got)
                                   / (time.perf_counter() - t0))
                if rep == 0:
                    streams0[name] = got
        stats = {name: engines[name].stats() for name, _, _ in arms}
    finally:
        for eng in engines.values():
            eng.stop()

    cells, equal_flags, honest_flags = [], [], []
    for name, k, K in arms:
        st = stats[name]
        fused = k > 1
        # fetches per DELIVERED token per lane: the engine's per-inner-tick
        # fetch rate (1/k by the transfer contract) divided by the mean
        # tokens a verify tick delivers — the same per-lane basis the plain
        # loop's 1/k is denominated in (one token per lane per tick)
        mean_acc = st["mean_emitted_per_spec_tick"] if fused else None
        fetch_per_token = (
            round(st["device_gets_per_token"] / mean_acc, 4)
            if fused and mean_acc else st["device_gets_per_token"])
        # accounting honest: one fetch per flush, the dispatched window
        # fully counted, and the delivered-token ledger consistent with
        # the acceptance telemetry (>= 1 token per participating tick)
        honest = (not fused) or (
            st["tick_fetches"] == st["loop_flushes"]
            and st["fused_flushes"] > 0
            and st["spec_ticks"] + st["decode_ticks"] > 0
            and st["spec_emitted"] >= st["spec_slot_ticks"])
        cell = {
            "arm": name, "k": k, "spec_tokens": K,
            "tokens_per_sec": round(statistics.median(rates[name]), 1),
            "tokens_per_sec_runs": [round(r, 1) for r in rates[name]],
            "fetch_per_delivered_token": fetch_per_token,
            "mean_accepted_per_verify_tick": (
                st["mean_emitted_per_spec_tick"] if fused else None),
            "tick_fetches": st["tick_fetches"],
            "loop_flushes": st["loop_flushes"] if fused else None,
            "fused_flushes": st["fused_flushes"] if fused else None,
            "fused_k_hist": st["fused_k_hist"] if fused else None,
            "spec_ticks": st["spec_ticks"],
            "decode_ticks": st["decode_ticks"],
            "stream_token_equal_plain": streams0[name] == streams0["plain"],
            "accounting_honest": bool(honest),
        }
        equal_flags.append(cell["stream_token_equal_plain"])
        honest_flags.append(cell["accounting_honest"])
        cells.append(cell)
        log(f"{name:>7}: {cell['tokens_per_sec']:8.1f} tok/s, "
            f"{fetch_per_token} fetch/token, "
            f"accept/tick={cell['mean_accepted_per_verify_tick']}, "
            f"token_equal={cell['stream_token_equal_plain']}, "
            f"honest={cell['accounting_honest']}")

    # ------------------------------------- early-exit deterministic gate
    def early_exit_exact():
        eng = ServingEngine(params, cfg, ServingConfig(
            slots=2, prefill_buckets=(16,), max_new_tokens=16,
            decode_loop_k=max(ks), spec_tokens=max(spec_ks)))
        eng.start()
        try:
            # a budget < k GUARANTEES a mid-flush freeze (each
            # participating tick emits >= 1 token); 11 stops off-edge deep
            budgets = [max(ks) - 1, 11]
            reqs = [eng.submit(p, max_new_tokens=b) for p, b in
                    zip(prompts_for(2, 500), budgets)]
            lens = [len(list(r.stream())) for r in reqs]
            st = eng.stats()
        finally:
            eng.stop()
        ok = lens == budgets and st["loop_early_exits"] > 0
        log(f"early-exit exact-budget gate: lens={lens} vs {budgets}, "
            f"early_exits={st['loop_early_exits']} -> "
            f"{'ok' if ok else 'FAIL'}")
        return ok

    gates = {
        "streams_token_equal_plain": all(equal_flags),
        "accounting_honest": all(honest_flags),
        "early_exit_exact_budget": early_exit_exact(),
    }
    det_ok = all(gates.values())

    # ---------------------------------------------- perf (full runs only)
    top_name = f"k{max(ks)}xK{max(spec_ks)}"
    top = next(c for c in cells if c["arm"] == top_name)
    plain = next(c for c in cells if c["arm"] == "plain")
    speedup = round(top["tokens_per_sec"] / plain["tokens_per_sec"], 3)
    # the headline inequality: fetches per delivered token strictly below
    # the plain k-loop's 1/k at the top cell
    fetch_below = (top["fetch_per_delivered_token"] is not None
                   and top["fetch_per_delivered_token"] < 1 / max(ks))
    perf_gated = not a.quick
    perf_ok = speedup >= 1.8 and fetch_below
    verdict = "pass" if det_ok and (perf_ok or not perf_gated) else "fail"
    log(f"{top_name} vs plain k=1: {speedup}x tokens/sec, "
        f"fetch/token {top['fetch_per_delivered_token']} "
        f"({'<' if fetch_below else 'NOT <'} 1/{max(ks)})"
        f"; perf {'gated' if perf_gated else 'recorded only (quick)'}")

    artifact = {
        "metric": "fused_spec_tokens_per_sec_speedup_vs_plain_k1",
        "value": speedup,
        "unit": "x_tokens_per_sec_vs_k1_no_spec",
        "ks": ks, "spec_ks": spec_ks, "slots": a.slots,
        "steps": a.steps, "waves": a.waves, "repeats": a.repeats,
        "quick": a.quick,
        "top_cell": top_name,
        "fetch_per_delivered_token_top": top["fetch_per_delivered_token"],
        "fetch_per_token_below_plain_1_over_k": fetch_below,
        "sweep": cells,
        "deterministic_gates": gates,
        "perf_gated": perf_gated,
        "model": {"vocab": cfg.vocab, "d_model": cfg.d_model,
                  "n_layers": cfg.n_layers},
    }
    print(json.dumps(artifact), flush=True)
    if a.out:
        with open(a.out, "w") as fh:
            json.dump(artifact, fh, indent=2)
    print_summary(
        "fused_spec_tokens_per_sec_speedup_vs_plain_k1", speedup, verdict,
        unit=artifact["unit"],
        fetch_per_delivered_token=top["fetch_per_delivered_token"],
        deterministic_gates_ok=det_ok, perf_gated=perf_gated)
    if verdict != "pass":
        sys.exit(1)


if __name__ == "__main__":
    from vtpu.util.jaxcache import place_compile_cache

    place_compile_cache()
    main()
