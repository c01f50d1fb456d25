"""vTPU headline benchmark: p50 TTFT degradation under 4-way chip sharing,
measured THROUGH the product stack.

North star (BASELINE.json): 4 concurrent JAX inference tenants sharing one
TPU host must see < 5% p50 time-to-first-token degradation vs exclusive use.
Tenants are separate PROCESSES, each holding its own PJRT client, its own
weight copy, and its own continuous-batching serving engine (vtpu/serving).
libvtpu goes under a tenant the way the chart delivers it — LD_PRELOADed
over the installed libtpu.so — enforcing a per-tenant HBM cap (chip/4) and a
25% core duty-cycle: the exact env contract the device plugin's Allocate
writes into a pod. This mirrors the reference's harness shape (vLLM server +
timed streaming client, HAMi stack vs native plugin — reference
benchmarks/README.md:1-100).

THE PROCESS MODEL IS THE PRECONDITION. Six tenant processes (one native, one
stack-exclusive, four sharing) each open their own PJRT client on the one
chip. The installed libtpu is an exclusive-attach runtime: a chip belongs to
one process at a time and a second client fails at start-up (measured in PR
21: "ABORTED: ... libtpu multi-process lockfile"). On such a runtime this
script cannot run; it says so and exits non-zero — it never measures fewer
tenants, or without libvtpu, and prints a headline anyway. Redefining the
experiment for one process a chip (one tenant a chip, or time-sliced attach)
is ROADMAP Speed #8.

Request latency drifts over a run, so measurements are interleaved at the
finest grain the process model allows:

  overhead rounds:   micro-pairs of [native burst] <-> [stack burst], order
                     alternated per pair, each burst followed by the
                     process's OWN dispatch round-trip probes (a trivial
                     jitted matmul + fetch through the same PJRT client its
                     TTFTs ride). The corrected estimator subtracts each
                     arm's own probe median, so a per-process latency offset
                     cancels; drift within a round is bounded by the
                     micro-pair span.
  sharing rounds:    sub-cycles of [each stacked tenant solo] <-> [all four
                     at once on open-loop arrival clocks (~1/8 duty each)]
                     interleaved INSIDE the round, so the exclusive baseline
                     is sampled across the same wall-clock window as the
                     shared traffic it normalizes.
  drift rejection:   a round whose exclusive-baseline samples disagree with
                     each other (intra-round spread) or with the session
                     median (inter-round drift) is discarded AND re-measured
                     (bounded budget). The criteria read ONLY baseline data,
                     never the degradation, so rejection cannot bias the
                     sharing signal. Rejected rounds are published alongside
                     the accepted ones.

Prints exactly TWO JSON lines on stdout. First the full artifact:
  {"metric": ..., "value": <p90 of accepted per-round shared-vs-exclusive
   degradations % — a robust "every round passes" bar, not a median-lucky
   one>, "unit": "percent", "vs_baseline": <value / 5.0>,
   "degradation_p90_ci95": <bootstrap 95% CI on that p90>,
   "libvtpu_attribution": <per-execute wrapper-cost breakdown>, ...}
then, as the FINAL stdout line, a compact headline summary (metric, value,
CI, verdict) — drivers that truncate or last-line-parse long artifacts
always get the headline intact.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import statistics
import subprocess
import sys
import threading
import time

ROOT = pathlib.Path(__file__).resolve().parent
LIBVTPU = ROOT / "libvtpu" / "build" / "libvtpu.so"
# how long one tenant may take from spawn to READY (attach + compile + warm)
READY_TIMEOUT_S = 900.0

TENANTS = 4
# Tenant arrival interval = DUTY_FACTOR x exclusive request time: each
# tenant runs a 1/DUTY_FACTOR duty cycle. At 1/6 the four service windows
# overlap often enough that queueing delay swings the measured degradation
# by >10pp between runs purely on phase alignment; at 1/10 the shared
# window grows long enough for within-round drift to dominate instead. 8
# balances contention realism against window length.
DUTY_FACTOR = 8.0
NEW_TOKENS = 4  # decode tokens streamed per request after the first
# Shared tenants run the FULL libvtpu stack (HBM/4 hard cap, shared region,
# priority gate, accounting) WITH core pacing at 25%. At first attach the
# shim probes its own idle round trip and floors every sync-wall duty charge
# at that minimum, so the cap paces chip busy plus only what a loaded
# dispatch costs above the idle floor; shared_tenant_throttle in the
# artifact audits the residual admit waits.
SHARE_CORE_LIMIT = 25


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# --------------------------------------------------------------------- tenant


def bench_scale():
    """(cfg, prompt_len, warmup): a ~200M-param serving model so TTFT is in
    the milliseconds. The one size there is — no CPU variant."""
    import jax.numpy as jnp

    from vtpu.models import ModelConfig

    cfg = ModelConfig(
        vocab=8192, d_model=1024, n_heads=8, n_layers=12, d_ff=4096,
        max_seq=1280, head_dim=128, dtype=jnp.bfloat16, use_pallas=True,
    )
    return cfg, 1024, 6


def tenant_main(a: argparse.Namespace) -> None:
    # A wrapped tenant was started with LD_PRELOAD=libvtpu.so over the
    # installed libtpu (the chart's delivery; Tenant sets it) — nothing to
    # do here: JAX boots as in any pod.
    wrapped = os.environ.get("VTPU_BENCH_WRAPPED") == "1"

    import jax
    import numpy as np

    from vtpu.models import init_params
    from vtpu.serving.engine import ServingConfig, ServingEngine
    from vtpu.util.jaxcache import place_compile_cache

    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(
            f"bench.py tenants need a TPU, JAX found {backend!r}")
    place_compile_cache()
    cfg, plen, warmup = bench_scale()
    params = jax.jit(lambda k: init_params(k, cfg))(jax.random.key(a.rank))
    jax.block_until_ready(params)
    eng = ServingEngine(
        params, cfg,
        ServingConfig(slots=4, prefill_buckets=(plen,), max_new_tokens=NEW_TOKENS),
    )
    eng.start()
    prompt = np.random.RandomState(a.rank).randint(0, cfg.vocab, (plen,)).astype(np.int32)

    def one_request() -> tuple[float, float]:
        """-> (ttft, total): first-token latency + full-stream wall time.
        The first token arrives via a D2H fetch (engine sample()), which is
        what a streaming client observes as first-token arrival."""
        t0 = time.perf_counter()
        req = eng.submit(prompt)
        first = req.out.get(timeout=300)
        ttft = time.perf_counter() - t0
        assert first is not None, "engine retired the request before a token"
        for _ in req.stream():
            pass
        return ttft, time.perf_counter() - t0

    # Own-process dispatch round-trip probe: a trivial jitted matmul + D2H
    # fetch through THIS process's PJRT client, the one its TTFTs ride. The
    # parent subtracts each arm's own probe median from its TTFT median so a
    # per-process latency offset cancels out of the native-vs-stack overhead
    # estimate.
    import jax.numpy as jnp

    probe_x = jax.device_put(jnp.ones((256, 256), jnp.bfloat16))
    probe_f = jax.jit(lambda t: (t @ t).sum())
    np.asarray(probe_f(probe_x))  # compile + warm

    def probe_block(n: int) -> list[float]:
        out = []
        for _ in range(n):
            t0 = time.perf_counter()
            np.asarray(probe_f(probe_x))
            out.append((time.perf_counter() - t0) * 1e3)
        return out

    for _ in range(warmup):
        one_request()
    if wrapped:
        # Zero the shim counters so the attribution reflects steady state,
        # not warmup's cold-path size queries and compile traffic.
        try:
            import ctypes

            ctypes.CDLL(str(LIBVTPU)).vtpu_stats_reset()
        except Exception as exc:
            log(f"stats reset failed: {exc}")
    print("READY", flush=True)

    # Block protocol: "RUN <n> <interval_ms> <stagger_ms>" -> n requests
    # (open-loop arrival clock when interval_ms > 0) -> "BLOCK {json}";
    # "PROBE <n>" -> n dispatch-RTT probes -> "BLOCK {json}";
    # "BYE" -> drain and exit.
    for line in sys.stdin:
        parts = line.split()
        if not parts or parts[0] == "BYE":
            break
        if parts[0] == "PROBE":
            print("BLOCK " + json.dumps(
                {"rank": a.rank, "probe_ms": probe_block(int(parts[1]))}),
                flush=True)
            continue
        _, n_s, interval_s, stagger_s = parts
        n, interval_ms, stagger_ms = int(n_s), float(interval_s), float(stagger_s)
        ttfts: list[float] = []
        totals: list[float] = []
        if interval_ms > 0:
            # TRUE open-loop: arrivals fire on the clock regardless of
            # whether earlier requests finished (submit is async; a worker
            # thread per in-flight request collects its TTFT), so queueing
            # delay under contention is sampled instead of backed off from.
            lock = threading.Lock()
            workers = []
            errors: list[BaseException] = []

            def worker():
                try:
                    ttft, total = one_request()
                except BaseException as exc:  # re-raised after join
                    with lock:
                        errors.append(exc)
                    return
                with lock:
                    ttfts.append(ttft)
                    totals.append(total)

            start = time.perf_counter() + stagger_ms / 1000.0
            for i in range(n):
                t_next = start + i * interval_ms / 1000.0
                now = time.perf_counter()
                if t_next > now:
                    time.sleep(t_next - now)
                th = threading.Thread(target=worker)
                th.start()
                workers.append(th)
            for th in workers:
                th.join()
            if errors:
                # A silently dropped sample would overstate the results;
                # fail the block loudly instead (the parent sees the crash).
                raise errors[0]
        else:
            for _ in range(n):
                ttft, total = one_request()
                ttfts.append(ttft)
                totals.append(total)
        # Decode data-plane telemetry rides every block: proves the
        # one-device_get-per-tick transfer contract held under this
        # tenant's real traffic and shows the host bookkeeping the
        # pipelined loop hides under the next dispatch. Cumulative over
        # the engine's lifetime — the parent keeps the last block's view.
        es = eng.stats()
        print("BLOCK " + json.dumps({
            "rank": a.rank, "backend": backend, "ttfts": ttfts, "totals": totals,
            "engine": {k: es[k] for k in (
                "device_gets_per_tick", "bytes_fetched_per_tick",
                "device_sampling", "pipelined",
                "pipelined_ticks", "decode_ticks", "generated_tokens",
                # admission data plane: batched prefill dispatch sizes,
                # blocking admission syncs (0 on the batched-async path),
                # and this engine's own inter-token-latency percentiles
                # (the admission head's host time is tick_phase_ms below)
                "prefill_batch_hist",
                "admission_syncs", "batched_admission",
                # multi-tick device loop: the configured k, flush/early-
                # exit counters, and the per-token amortization of the
                # fetch contract (1/k with the loop on; the host's share
                # per inner tick is tick_phase_ms' mean_ms_per_tick)
                "decode_loop_k", "loop_flushes", "loop_early_exits",
                "device_gets_per_token",
                # span telemetry is re-derived from the trace substrate
                # (vtpu/obs): the ITL reservoir is a view over the trace,
                # and TTFT/queue-wait percentiles come from the same
                # submit->first-token spans the Chrome dump renders —
                # comparable against the client-side wall-clock TTFTs
                # above (trace TTFT excludes only the client's own queue
                # hop into submit())
                "itl_p50_ms", "itl_p99_ms",
                "ttft_p50_ms", "ttft_p95_ms", "ttft_p99_ms",
                "queue_wait_p50_ms", "queue_wait_p99_ms",
                # tick-phase attribution (obs tickprof): where the host's
                # time per tick goes under this tenant's traffic
                "tick_phase_ms", "trace_events_recorded",
                # KV-memory data plane: the per-tick read-window histogram
                # (the dense path's global longest-sequence read tax made
                # visible), the dense-vs-paged HBM estimate whose ratio is
                # the oversubscription headroom — PER CHIP under a tp mesh
                # (kv_hbm_bytes_per_chip is the figure that maps onto the
                # per-container TPU_DEVICE_MEMORY_LIMIT_<i> cap) — and,
                # when paging is on, pool occupancy, blocked-on-pool
                # admissions, and the zero-copy prefix counters
                "kv_bucket_hist", "kv_hbm_bytes", "kv_hbm_bytes_per_chip",
                "tp", "paged",
                "kv_pool_occupancy", "pool_blocked_admissions",
                # paged decode-attention routing: which read route each
                # dispatched tick compiled to (fused table-walking kernel
                # vs gather-then-dense) — the measured-routing audit trail
                "paged_attn_kernel_ticks", "paged_attn_gather_ticks",
                "prefix_blocks_shared", "prefix_install_copies",
                # prefix gravity: per-tenant attach hits/misses and the
                # blocks currently pinned read-only by registrations —
                # the fleet directory's engine-side ledger
                "prefix_hits", "prefix_misses", "prefix_shared_blocks",
                # KV overcommit: pool high-water vs capacity, parked
                # population, host-tier swap traffic, and the faults the
                # recompute path absorbed — the counters the ROADMAP's
                # oversubscription story is audited by
                "kv_pool_used_hwm", "parked_sessions", "kv_swap",
                "parks", "resumes", "evicted_blocks",
                "swap_out_bytes", "swap_in_bytes",
                "swap_faults", "fault_recomputes",
                "pool_blocked_resumes",
                "swap_host_blocks", "swap_host_free",
                # failure domains: typed sheds (deadline / overload
                # policy), contained per-request faults, prefill-worker
                # restarts, watchdog degradation steps, and FaultPlan
                # injections — the blast-radius audit per tenant
                "shed_deadline", "shed_overload", "faulted_requests",
                "worker_restarts", "watchdog_degrades",
                "faults_injected")},
        }), flush=True)
    eng.stop()
    if wrapped:
        # Interception cost attribution: the same libvtpu.so this process
        # was preloaded with (CDLL on the loaded path returns the live handle).
        try:
            import ctypes

            lib = ctypes.CDLL(str(LIBVTPU))
            lib.vtpu_stats_json.restype = ctypes.c_size_t
            buf = ctypes.create_string_buffer(2048)
            if lib.vtpu_stats_json(buf, ctypes.c_size_t(len(buf))):
                print("STATS " + buf.value.decode(), flush=True)
        except Exception as exc:  # stats are best-effort telemetry
            log(f"stats export failed: {exc}")


# --------------------------------------------------------------------- parent


def build_libvtpu() -> None:
    """libvtpu.so from libvtpu/src, every run: never a .so already lying in
    the ignored build directory. No library, no benchmark."""
    r = subprocess.run(["make", "-B", "-C", str(ROOT / "libvtpu")],
                       capture_output=True, text=True)
    if r.returncode != 0 or not LIBVTPU.exists():
        raise SystemExit(
            f"libvtpu build failed; bench.py has no unwrapped mode:\n"
            f"{r.stderr[-2000:]}")


class AttachError(RuntimeError):
    """A tenant process did not reach READY."""


class Tenant:
    def __init__(self, rank: int, wrap: bool, tag: str, core_limit: int = 25):
        self.rank = rank
        self.tag = tag
        # last-seen serving-engine decode telemetry from this tenant's
        # BLOCK lines (cumulative; the final block's view is the report)
        self.engine_stats: dict | None = None
        env = dict(os.environ)
        (ROOT / "build").mkdir(exist_ok=True)
        # stderr to a file, not a pipe: a chatty runtime would fill a 64KB
        # pipe nobody drains mid-run and deadlock the whole benchmark.
        self.errpath = ROOT / "build" / f"bench_{tag}{rank}.err"
        self.errfile = open(self.errpath, "w")
        if wrap:
            # the chart's delivery: ld.so.preload the shim, let JAX dlopen
            # the installed libtpu.so itself
            env["LD_PRELOAD"] = str(LIBVTPU)
            env["VTPU_BENCH_WRAPPED"] = "1"
            # The device plugin's env contract: HBM/4 per tenant;
            # core_limit per tenant role (SHARE_CORE_LIMIT for the sharing
            # tenants, 100 for the interception-overhead tenant — a cap
            # would throttle its back-to-back blocks and the overhead
            # number would measure enforcement, not interception).
            env["TPU_DEVICE_MEMORY_LIMIT_0"] = "4g"
            env["TPU_CORE_LIMIT"] = str(core_limit)  # see SHARE_CORE_LIMIT
            region = ROOT / "build" / f"bench_{tag}{rank}.cache"
            region.parent.mkdir(exist_ok=True)
            if region.exists():
                region.unlink()
            env["VTPU_SHARED_REGION"] = str(region)
        self.proc = subprocess.Popen(
            [sys.executable, __file__, "--tenant", "--rank", str(rank)],
            env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=self.errfile, text=True, bufsize=1,
        )

    def _stderr_tail(self) -> str:
        self.errfile.flush()
        return self.errpath.read_text()[-4000:]

    def wait_ready(self, timeout: float = READY_TIMEOUT_S) -> None:
        """Block until the tenant prints READY. A tenant that dies, or says
        nothing for *timeout* seconds (a second client on an exclusive-attach
        runtime fails OR hangs), raises AttachError with its stderr."""
        box: list = []

        def read():
            line = self.proc.stdout.readline()
            while line and line.strip() != "READY":
                line = self.proc.stdout.readline()
            box.append(bool(line))

        th = threading.Thread(target=read, daemon=True)
        th.start()
        th.join(timeout)
        if not box or not box[0]:
            how = ("exited before READY" if box
                   else f"not READY after {timeout:.0f}s")
            raise AttachError(
                f"tenant {self.tag}{self.rank} {how}:\n{self._stderr_tail()}")

    def start_block(self, n: int, interval_ms: float = 0.0, stagger_ms: float = 0.0):
        self.proc.stdin.write(f"RUN {n} {interval_ms} {stagger_ms}\n")
        self.proc.stdin.flush()

    def read_block(self) -> dict:
        line = self.proc.stdout.readline()
        while line and not line.startswith("BLOCK "):
            line = self.proc.stdout.readline()
        if not line:
            raise RuntimeError(f"tenant died mid-block:\n{self._stderr_tail()}")
        blk = json.loads(line[len("BLOCK "):])
        if "engine" in blk:
            self.engine_stats = blk["engine"]
        return blk

    def run_block(self, n: int, interval_ms: float = 0.0, stagger_ms: float = 0.0) -> dict:
        self.start_block(n, interval_ms, stagger_ms)
        return self.read_block()

    def probe(self, n: int) -> list[float]:
        """n dispatch round-trip samples (ms) through this tenant's own
        PJRT client."""
        self.proc.stdin.write(f"PROBE {n}\n")
        self.proc.stdin.flush()
        return self.read_block()["probe_ms"]

    def close(self) -> None:
        self.stats: dict | None = None
        try:
            if self.proc.poll() is None:
                self.proc.stdin.write("BYE\n")
                self.proc.stdin.flush()
            # Drain stdout on a side thread even if the tenant already
            # exited (its STATS line may sit in the pipe buffer); the join
            # bounds a wedged teardown and finally kills the process.
            def drain():
                for line in self.proc.stdout:
                    if line.startswith("STATS "):
                        self.stats = json.loads(line[len("STATS "):])

            th = threading.Thread(target=drain, daemon=True)
            th.start()
            th.join(timeout=30)
            if self.proc.poll() is None:
                self.proc.wait(timeout=5)
        except Exception:
            pass
        finally:
            if self.proc.poll() is None:
                self.proc.kill()
            self.errfile.close()


def pooled_inflation(solo: list[float], shared: list[float]) -> float:
    """Shared-vs-solo inflation of a control tenant, in percent. The single
    implementation all three consumers (point estimate, per-round
    diagnostic, bootstrap) call, so they cannot drift."""
    if not solo or not shared:
        return 0.0
    return ((statistics.median(shared) - statistics.median(solo))
            / statistics.median(solo) * 100.0)


def bootstrap_p90_ci(rounds: list[float], n_boot: int = 10000,
                     seed: int = 20260731,
                     control: list[tuple[list[float], list[float]]] | None = None,
                     ) -> tuple[float, float]:
    """Percentile-bootstrap 95% CI on the p90-of-rounds statistic (resample
    rounds with replacement, recompute the same order-statistic estimator).
    With `control` — per-round (solo_samples, shared_samples) aligned with
    `rounds` — each iteration reuses the SAME resampled round indices for
    the control pools before dividing the control inflation out of the p90:
    control TTFTs within a round share that round's conditions, so
    resampling them at round granularity (not iid per sample) keeps the
    attributed CI honest about that correlation.
    Deterministic seed: the CI must be a property of the data, not the run."""
    import random

    rng = random.Random(seed)
    n = len(rounds)
    stats_: list[float] = []
    for _ in range(n_boot):
        idxs = [rng.randrange(n) for _ in range(n)]
        sample = sorted(rounds[i] for i in idxs)
        p90 = sample[max(0, min(n - 1, round(0.9 * n) - 1))]
        if control is not None:
            solo = [t for i in idxs for t in control[i][0]]
            shared = [t for i in idxs for t in control[i][1]]
            infl = pooled_inflation(solo, shared)
            p90 = ((1.0 + p90 / 100.0) / (1.0 + infl / 100.0) - 1.0) * 100.0
        stats_.append(p90)
    stats_.sort()
    return (stats_[int(0.025 * n_boot)], stats_[min(n_boot - 1, int(0.975 * n_boot))])


def main() -> None:
    build_libvtpu()  # the stack is always in the loop: no plain mode
    # The headline is the p90 of per-round degradations (max also
    # published) — a pass means essentially EVERY round under 5%, not a
    # median-lucky one; p90 rather than max because one spiked round is not
    # chip contention. Rounds that fail the BASELINE-only drift checks are
    # rejected and re-measured, and the headline carries a bootstrap CI.
    overhead_target, overhead_extra = 10, 4
    micro_pairs, micro_block, micro_probes = 4, 4, 5
    share_target, share_extra = 14, 8
    subcycles, solo_per_tenant, shared_per_tenant = 3, 2, 2
    # Baseline-drift acceptance thresholds (see sharing_round below).
    INTRA_SPREAD_MAX = 1.25
    INTER_DRIFT_MAX = 0.20

    # The first tenant alone, then a second beside it: whether this runtime
    # lets two processes hold the chip is settled here, before four more
    # processes are thrown at it.
    native = Tenant(rank=0, wrap=False, tag="native")
    tenants = [native]
    try:
        native.wait_ready()
    except AttachError as exc:
        native.close()
        raise SystemExit(f"bench.py: the first tenant could not start: {exc}")
    # overhead windows use the exclusive-contract tenant (core=100); the
    # four sharing tenants run the sharing contract (SHARE_CORE_LIMIT)
    stack_x = Tenant(rank=0, wrap=True, tag="stackx", core_limit=100)
    tenants.append(stack_x)
    try:
        stack_x.wait_ready()
    except AttachError as exc:
        for t in tenants:
            t.close()
        raise SystemExit(
            "bench.py: a second tenant process could not attach while the "
            "first holds the chip — this runtime gives a chip to one process "
            "at a time, and the 4-way share experiment needs six concurrent "
            "clients. Not measured; the redesign is ROADMAP Speed #8.\n"
            f"{exc}")
    stacks = [Tenant(rank=r, wrap=True, tag="stack", core_limit=SHARE_CORE_LIMIT)
              for r in range(TENANTS)]
    tenants += stacks
    try:
        for t in stacks:  # compile + warm everywhere before any window
            t.wait_ready()

        # ---- Overhead rounds: interleaved native<->stack micro-pairs. ----
        # Each micro-pair runs a small burst on one arm then the other
        # (order alternating per pair AND per round), each burst followed by
        # that arm's OWN dispatch-RTT probes. Two estimators per pair:
        #   raw:            (stk - nat) / nat on burst medians — includes
        #                   whatever fixed latency offset separates the two
        #                   processes;
        #   rtt-corrected:  subtract each arm's own probe median from its
        #                   burst median first, cancelling a per-process
        #                   offset to first order. This is the wrapper-cost
        #                   estimate; raw is published so the correction is
        #                   auditable.
        nat_ttfts: list[float] = []
        nat_totals: list[float] = []
        stk_ttfts: list[float] = []
        # every measured round, accepted or not — the all-rejected fallback below
        # publishes these rather than placeholders
        all_nat_ttfts: list[float] = []
        all_nat_totals: list[float] = []
        all_stk_ttfts: list[float] = []
        round_overheads: list[float] = []
        round_overheads_corrected: list[float] = []
        overhead_rejected: list[dict] = []
        measured = 0
        while (len(round_overheads) < overhead_target
               and measured < overhead_target + overhead_extra):
            measured += 1
            pair_raw: list[float] = []
            pair_cor: list[float] = []
            pair_nat_meds: list[float] = []
            round_nat_ttfts: list[float] = []
            round_nat_totals: list[float] = []
            round_stk_ttfts: list[float] = []
            for p in range(micro_pairs):
                first_native = (p + measured) % 2 == 0
                arms = []
                for arm_native in ([True, False] if first_native else [False, True]):
                    ten = native if arm_native else stack_x
                    b = ten.run_block(micro_block)
                    pr = ten.probe(micro_probes)
                    arms.append((arm_native, b, statistics.median(pr)))
                for arm_native, b, probe_med in arms:
                    if arm_native:
                        nat_med = statistics.median(b["ttfts"])
                        nat_probe = probe_med
                        round_nat_ttfts += b["ttfts"]
                        round_nat_totals += b["totals"]
                        backend = b["backend"]
                    else:
                        stk_med = statistics.median(b["ttfts"])
                        stk_probe = probe_med
                        round_stk_ttfts += b["ttfts"]
                pair_nat_meds.append(nat_med)
                pair_raw.append((stk_med - nat_med) / nat_med * 100.0)
                pair_cor.append(
                    ((stk_med - stk_probe / 1e3) - (nat_med - nat_probe / 1e3))
                    / nat_med * 100.0)
            all_nat_ttfts += round_nat_ttfts
            all_nat_totals += round_nat_totals
            all_stk_ttfts += round_stk_ttfts
            spread = max(pair_nat_meds) / max(min(pair_nat_meds), 1e-9)
            if spread > INTRA_SPREAD_MAX:
                # the native arm's own medians disagree across the round —
                # drift mid-round; re-measure (criterion reads
                # only native data, never the A/B delta). The round's
                # samples stay OUT of the published pools so the pooled
                # p50s describe exactly the rounds the estimator used.
                overhead_rejected.append({
                    "native_medians_ms": [round(m * 1e3, 2) for m in pair_nat_meds],
                    "spread": round(spread, 3),
                    "raw_median": round(statistics.median(pair_raw), 2),
                    "corrected_median": round(statistics.median(pair_cor), 2),
                })
                log(f"overhead round rejected (native spread {spread:.2f}x)")
                continue
            nat_ttfts += round_nat_ttfts
            nat_totals += round_nat_totals
            stk_ttfts += round_stk_ttfts
            round_overheads.append(statistics.median(pair_raw))
            round_overheads_corrected.append(statistics.median(pair_cor))
        overhead_rejection_exhausted = False
        if not round_overheads:
            # same fallback as the sharing phase: publish the rejected
            # rounds' estimates, flagged, rather than crash with no artifact
            log("overhead drift rejection exhausted; publishing all rounds")
            overhead_rejection_exhausted = True
            round_overheads = [r["raw_median"] for r in overhead_rejected]
            round_overheads_corrected = [
                r["corrected_median"] for r in overhead_rejected]
            nat_ttfts, nat_totals = all_nat_ttfts, all_nat_totals
            stk_ttfts = all_stk_ttfts
        p50_nat = statistics.median(nat_ttfts)
        p50_stk = statistics.median(stk_ttfts)
        overhead = statistics.median(round_overheads)
        overhead_corrected = statistics.median(round_overheads_corrected)
        log(f"[{backend}] exclusive p50 TTFT: native {p50_nat * 1e3:.2f} ms, "
            f"through-libvtpu {p50_stk * 1e3:.2f} ms (overhead raw "
            f"{overhead:+.2f}% / rtt-corrected {overhead_corrected:+.2f}%, "
            f"per-round raw {[round(o, 2) for o in round_overheads]}, "
            f"corrected {[round(o, 2) for o in round_overheads_corrected]})")

        # ---- Sharing rounds: solo<->shared interleaved INSIDE the round. --
        # The exclusive baseline comes from the SAME four stack tenants
        # running SOLO (one at a time), not from the native tenant: every
        # process has its own client with its own latency offset, so only a
        # same-process baseline isolates SHARING from process pairing. Each
        # round is S sub-cycles of [4 tenants solo] then
        # [all 4 shared, open-loop staggered arrivals], so baseline and
        # shared samples cover the same wall-clock window — drift between
        # them is bounded by a sub-cycle (~4 s), not a whole flanking block.
        interval_ms = DUTY_FACTOR * statistics.fmean(nat_totals) * 1000.0

        # One UNMEASURED warm-up shared window: the first concurrent window
        # pays one-off costs no later round sees (four processes' first
        # simultaneous dispatches re-priming the transport; observed as a
        # single +775% round 0 with every later round under 5%). All
        # MEASURED rounds are published. The controls join the warm-up too,
        # so their first-ever concurrent window is not measured round 1.
        for i, s in enumerate(stacks):
            s.start_block(2, interval_ms, i * interval_ms / TENANTS)
        native.start_block(2, interval_ms, interval_ms / (2 * TENANTS))
        stack_x.start_block(2, interval_ms, 3 * interval_ms / (2 * TENANTS))
        for s in stacks:
            s.read_block()
        native.read_block()
        stack_x.read_block()

        def sharing_round() -> dict:
            # Two controls ride the same windows as the sharing tenants, so
            # whatever hits a window hits them symmetrically (no clamping —
            # a negative control inflation raises the attributed number too):
            #  - native (no libvtpu at all): a zero-stack reference — its
            #    shared-window inflation is what a stack-free process pays
            #    for window concurrency alone.
            #  - stack_x (WRAPPED, uncapped, exclusive contract): the cost
            #    of concurrency through the wrapper WITHOUT enforcement, so
            #    dividing it out isolates what the CAPPED contract itself
            #    costs — the product behavior under test.
            # The STACK-ATTRIBUTED degradation is the raw degradation with
            # the control's inflation divided out; both are published.
            # Caveat: the two controls are a 5th and 6th concurrent process,
            # so shared windows carry two more clients than the 4-way name
            # implies.
            solo: list[float] = []
            shared: list[float] = []
            sub_solo_medians: list[float] = []
            nat_solo: list[float] = []
            nat_shared: list[float] = []
            wrp_solo: list[float] = []
            wrp_shared: list[float] = []
            for _ in range(subcycles):
                sub: list[float] = []
                for s in stacks:  # each tenant alone on the chip
                    sub += s.run_block(solo_per_tenant)["ttfts"]
                nat_solo += native.run_block(solo_per_tenant)["ttfts"]
                wrp_solo += stack_x.run_block(solo_per_tenant)["ttfts"]
                solo += sub
                sub_solo_medians.append(statistics.median(sub))
                for i, s in enumerate(stacks):  # all 4 at once, staggered
                    s.start_block(shared_per_tenant, interval_ms,
                                  i * interval_ms / TENANTS)
                # the controls join the SAME concurrent window, offset to
                # land between the stack tenants' arrivals
                native.start_block(shared_per_tenant, interval_ms,
                                   interval_ms / (2 * TENANTS))
                stack_x.start_block(shared_per_tenant, interval_ms,
                                    3 * interval_ms / (2 * TENANTS))
                for s in stacks:
                    shared += s.read_block()["ttfts"]
                nat_shared += native.read_block()["ttfts"]
                wrp_shared += stack_x.read_block()["ttfts"]
            base_med = statistics.median(solo)
            shared_med = statistics.median(shared)
            degradation = (shared_med - base_med) / base_med * 100.0
            # Per-round control inflation is published for audit, but the
            # attribution divides by the POOLED control (computed after
            # acceptance): a round's control rests on ~6 TTFTs and a
            # per-round division amplifies its noise into +-15 pp swings;
            # the pooled estimate is stable.
            native_infl = pooled_inflation(nat_solo, nat_shared)
            return {
                "solo": solo, "shared": shared,
                "nat_solo": nat_solo, "nat_shared": nat_shared,
                "wrp_solo": wrp_solo, "wrp_shared": wrp_shared,
                "base_median": base_med, "shared_median": shared_med,
                "sub_solo_medians": sub_solo_medians,
                "degradation": degradation,
                "native_inflation": native_infl,
            }

        accepted: list[dict] = []
        rejected: list[dict] = []
        measured = 0
        while (len(accepted) < share_target
               and measured < share_target + share_extra):
            measured += 1
            r = sharing_round()
            # Acceptance reads ONLY exclusive-baseline data (rejecting on
            # the degradation itself would be cherry-picking):
            #  (a) intra-round: the solo sub-cycle medians must agree within
            #      INTRA_SPREAD_MAX (drift mid-round pollutes the pairing);
            #  (b) inter-round: the round baseline must sit within
            #      INTER_DRIFT_MAX of the running median of every baseline
            #      measured so far (a wandering baseline produces
            #      phantom rounds of either sign).
            spread = (max(r["sub_solo_medians"])
                      / max(min(r["sub_solo_medians"]), 1e-9))
            all_bases = [x["base_median"] for x in accepted + rejected] \
                + [r["base_median"]]
            session_base = statistics.median(all_bases)
            drift = abs(r["base_median"] - session_base) / session_base
            reason = None
            if spread > INTRA_SPREAD_MAX:
                reason = f"intra-round solo spread {spread:.2f}x"
            elif len(all_bases) >= 4 and drift > INTER_DRIFT_MAX:
                reason = (f"baseline {r['base_median'] * 1e3:.1f} ms drifted "
                          f"{drift * 100:.0f}% off session median "
                          f"{session_base * 1e3:.1f} ms")
            if reason:
                rejected.append({**r, "reason": reason})
                log(f"sharing round rejected: {reason}")
            else:
                accepted.append(r)
                log(f"sharing round {len(accepted)}: degradation "
                    f"{r['degradation']:+.2f}% (base "
                    f"{r['base_median'] * 1e3:.1f} ms, native control "
                    f"{r['native_inflation']:+.2f}%)")
        # Final pass of criterion (b) against the COMPLETE session: early
        # rounds were judged against a partial median. Still baseline-only.
        final_base = statistics.median(
            [x["base_median"] for x in accepted + rejected])
        kept: list[dict] = []
        for r in accepted:
            drift = abs(r["base_median"] - final_base) / final_base
            if drift > INTER_DRIFT_MAX:
                rejected.append({**r, "reason":
                                 f"final-pass baseline drift {drift * 100:.0f}%"})
                log(f"sharing round dropped in final pass (drift {drift * 100:.0f}%)")
            else:
                kept.append(r)
        accepted = kept
        rejection_exhausted = False
        if not accepted:
            # Every round failed the baseline checks: publish ALL rounds
            # rather than nothing, flagged — a missing artifact hides the
            # instability, a flagged one reports it.
            log("drift rejection exhausted its budget; publishing all rounds")
            rejection_exhausted = True
            accepted = [dict(r) for r in rejected]

        round_degradations = [r["degradation"] for r in accepted]
        base_ttfts = [t for r in accepted for t in r["solo"]]
        shared_ttfts = [t for r in accepted for t in r["shared"]]
        base_medians = [r["base_median"] for r in accepted]
        p50_base = statistics.median(base_ttfts)
        p50_shared = statistics.median(shared_ttfts)
        log(f"sharing windows: exclusive p50 {p50_base * 1e3:.2f} ms, "
            f"{TENANTS}-way shared p50 {p50_shared * 1e3:.2f} ms over "
            f"{len(shared_ttfts)} requests at {interval_ms:.0f} ms arrival interval; "
            f"accepted {len(accepted)} rounds, rejected {len(rejected)}; "
            f"per-round degradation {[round(d, 2) for d in round_degradations]}")
    finally:
        for t in tenants:
            t.close()

    # Serving-engine decode data plane, per tenant (the last block's
    # cumulative view): with device-side sampling + pipelining on (the
    # default) every tenant must read device_gets_per_tick == 1.0 at
    # slots*4 bytes/tick; a host-sampler fallback or a disabled pipeline
    # is immediately visible here, not buried in TTFT noise.
    tenant_engine = [
        {"tenant": f"{t.tag}{t.rank}", **t.engine_stats}
        for t in tenants if t.engine_stats] or None
    from vtpu.obs.tickprof import host_ms_per_tick

    for e in tenant_engine or []:
        host_ms = host_ms_per_tick(e["tick_phase_ms"])
        log(f"engine[{e['tenant']}]: {e['device_gets_per_tick']} "
            f"device_gets/tick, {e['bytes_fetched_per_tick']} B/tick, "
            f"host {None if host_ms is None else round(host_ms, 4)} ms/tick, "
            f"pipelined={e['pipelined']} "
            f"({e['pipelined_ticks']}/{e['decode_ticks']} decode ticks)")

    # Interception cost attribution: per-execute /
    # per-upload breakdown of where libvtpu's time goes, from the shim's own
    # counters in the stack-exclusive tenant. The derived *_ms fields are the
    # added wrapper cost — real plugin time (enqueue/upload_real) excluded.
    # Caveat: stack_x also serves as the sharing windows' wrapped control,
    # so its cumulative counters include contended-window activity; the
    # attribution is an UPPER bound on solo wrapper cost.
    # Shared-tenant throttle introspection: nonzero admit waits mean core
    # pacing fired during the sharing windows and polluted the sharing
    # signal (must be 0 under the SHARE_CORE_LIMIT contract; the field
    # exists to keep that auditable).
    shared_throttle = [
        {
            "rank": i,
            "admit_wait_ms": round(s.stats["admit_ns"] / 1e6, 1),
            "gate_wait_ms": round(s.stats["gate_ns"] / 1e6, 1),
            "executes": s.stats["executes"],
            # charge-cap gate audit, per SHARING tenant (the paced
            # ones — stack_x's attribution block is the unpaced
            # exclusive tenant): which leg failed, and how much wall
            # time was actually charged into this tenant's limiter.
            "d2h_capped": s.stats.get("d2h_capped"),
            "d2h_floored": s.stats.get("d2h_floored"),
            "d2h_uncapped": s.stats.get("d2h_uncapped"),
            "d2h_gate_inflight": s.stats.get("d2h_gate_inflight"),
            "d2h_gate_size": s.stats.get("d2h_gate_size"),
            "d2h_gate_multichip": s.stats.get("d2h_gate_multichip"),
            "d2h_errors": s.stats.get("d2h_errors"),
            # None-propagating like the d2h_* fields: absence (old shim)
            # must stay distinguishable from a genuine zero
            "sync_charged_ms": None if "sync_charged_ns" not in s.stats
            else round(s.stats["sync_charged_ns"] / 1e6, 1),
            "settled_busy_ms": None if "settled_busy_ns" not in s.stats
            else round(s.stats["settled_busy_ns"] / 1e6, 1),
            "rtt_floor_ms": None if "rtt_floor_ns" not in s.stats
            else round(s.stats["rtt_floor_ns"] / 1e6, 1),
            # calibration oracle: whether THIS tenant's runtime passed
            # event attestation (verdict 1 = faithful -> walls never
            # charged, tower disengaged), the calibrated scale/baseline,
            # and how many walls the attestation skipped outright.
            "calib_verdict": s.stats.get("calib_verdict"),
            "calib_fallback": s.stats.get("calib_fallback"),
            "calib_ratio_ppm": s.stats.get("calib_ratio_ppm"),
            "calib_baseline_ms": None
            if "calib_baseline_ns" not in s.stats
            else round(s.stats["calib_baseline_ns"] / 1e6, 1),
            "calib_recalibs": s.stats.get("calib_recalibs"),
            "d2h_attested": s.stats.get("d2h_attested"),
        }
        for i, s in enumerate(stacks) if s.stats
    ] or None

    attribution = None
    st = stack_x.stats
    if not st:
        log("no STATS line from the stack tenant — attribution unavailable")
    if st and st.get("executes"):
        ex = st["executes"]
        # region_ns is NOT added: output-row region writes already run under
        # the acct_ns timer (upload-path ones under upload_ns); it is
        # published inside the raw counters for reference only.
        wrap_ns = (st["gate_ns"] + st["admit_ns"] + st["acct_ns"]
                   + st["onready_ns"])
        attribution = {
            **st,
            "wrap_cost_per_execute_ms": round(wrap_ns / ex / 1e6, 4),
            "acct_per_execute_ms": round(st["acct_ns"] / ex / 1e6, 4),
            "size_rpc_total_ms": round(st["size_rpc_ns"] / 1e6, 3),
            "upload_wrap_per_call_ms": round(
                (st["upload_ns"] - st["upload_real_ns"])
                / max(st["uploads"], 1) / 1e6, 4),
        }
        log(f"libvtpu attribution: {attribution['wrap_cost_per_execute_ms']:.4f} ms/"
            f"execute wrapper cost, {st['size_rpcs']} size RPCs over "
            f"{ex} executes ({st['size_cache_hits']} cache hits)")

    def p90_of(vals: list[float]) -> float:
        srt = sorted(vals)
        return srt[max(0, min(len(srt) - 1, round(0.9 * len(srt)) - 1))]

    round_native_infl = [r.get("native_inflation", 0.0) for r in accepted]
    pooled_nat_solo = [t for r in accepted for t in r.get("nat_solo", [])]
    pooled_nat_shared = [t for r in accepted for t in r.get("nat_shared", [])]
    native_pooled_infl = pooled_inflation(pooled_nat_solo, pooled_nat_shared)
    # Attribution control: the WRAPPED-uncapped tenant (see sharing_round) —
    # its inflation is the concurrency cost through the wrapper without
    # enforcement.
    control_kind = "wrapped_uncapped"
    round_control = [(r["wrp_solo"], r["wrp_shared"]) for r in accepted]
    ctrl_solo = [t for solo, _ in round_control for t in solo]
    ctrl_shared = [t for _, shared in round_control for t in shared]
    pooled_infl = pooled_inflation(ctrl_solo, ctrl_shared)
    round_attributed = [
        ((1.0 + d / 100.0) / (1.0 + pooled_infl / 100.0) - 1.0) * 100.0
        for d in round_degradations]
    degradation = p90_of(round_attributed)
    raw_degradation = p90_of(round_degradations)
    raw_ci = bootstrap_p90_ci(round_degradations)
    # The attributed CI jointly resamples rounds AND the per-round control
    # pools (same indices), so it carries the control's own sampling
    # uncertainty at round granularity.
    ci_lo, ci_hi = bootstrap_p90_ci(round_degradations, control=round_control)
    log(f"{control_kind} control: pooled transport-path inflation "
        f"{pooled_infl:+.2f}% over "
        f"{len(ctrl_shared)} shared / {len(ctrl_solo)} solo "
        f"samples; raw p90 {raw_degradation:+.2f}% -> attributed "
        f"{degradation:+.2f}% (exploratory)")
    print(json.dumps({
        # The headline stays the RAW p90. The control-corrected figure
        # is published beside it as exploratory: a correction is applied
        # to the headline only once the controls' inflations have been
        # shown physical (non-negative, correlated with the stack series)
        # on the runtime being measured.
        "metric": "p90_round_ttft_degradation_4way_share_stack",
        "value": round(raw_degradation, 2),
        "unit": "percent",
        "vs_baseline": round(raw_degradation / 5.0, 3),
        # bootstrap 95% CI on the p90-of-rounds statistic itself: the SLO
        # claim is only as good as this interval's upper edge vs 5%
        "degradation_p90_ci95": [round(raw_ci[0], 2), round(raw_ci[1], 2)],
        "ci95_excludes_5pct": bool(raw_ci[1] < 5.0),
        # exploratory: control-corrected p90 + joint-bootstrap CI (see note)
        "attributed_p90_exploratory": round(degradation, 2),
        "attributed_p90_ci95_exploratory": [round(ci_lo, 2), round(ci_hi, 2)],
        "control_pooled_inflation_pct": round(pooled_infl, 2),
        "control_samples": [len(ctrl_solo), len(ctrl_shared)],
        "control_kind": control_kind,
        # Self-describing window shape: shared windows carry the 4
        # sharing tenants PLUS both always-on controls, while solo
        # baselines are single-process.
        "shared_window_sessions": TENANTS + 2,
        "solo_window_sessions": 1,
        "native_reference_pooled_inflation_pct": round(native_pooled_infl, 2),
        "native_reference_samples":
            [len(pooled_nat_solo), len(pooled_nat_shared)],
        "per_round_native_inflation": [round(x, 2) for x in round_native_infl],
        "per_round_attributed": [round(x, 2) for x in round_attributed],
        "stack_in_loop": True,
        "p50_ttft_exclusive_native_ms": round(p50_nat * 1e3, 2),
        "p50_ttft_exclusive_stack_ms": round(p50_stk * 1e3, 2),
        "p50_ttft_exclusive_in_sharing_windows_ms": round(p50_base * 1e3, 2),
        "p50_ttft_shared_ms": round(p50_shared * 1e3, 2),
        # raw A/B straddles two processes (its sign alone is not
        # meaningful); the rtt-corrected estimator subtracts each arm's
        # own probed dispatch round trip and is the wrapper-cost claim
        "libvtpu_overhead_percent": round(overhead, 2),
        "libvtpu_overhead_rtt_corrected_percent": round(overhead_corrected, 2),
        "overhead_estimator": "median_of_interleaved_micropair_deltas",
        "libvtpu_overhead_per_round": [round(o, 2) for o in round_overheads],
        "libvtpu_overhead_corrected_per_round": [
            round(o, 2) for o in round_overheads_corrected],
        "overhead_rounds_rejected": overhead_rejected or None,
        "overhead_rejection_exhausted": overhead_rejection_exhausted,
        "libvtpu_attribution": attribution,
        "shared_tenant_throttle": shared_throttle,
        # decode data-plane contract per tenant (device_gets_per_tick must
        # be 1.0 under the default device-sampled pipelined loop)
        "tenant_engine_stats": tenant_engine,
        "tenants": TENANTS,
        "tenant_contract": {"hbm": "4g", "core_limit": SHARE_CORE_LIMIT,
                            "note": "full stack, core pacing ON: libvtpu "
                                    "self-calibrates a transport floor at "
                                    "first attach (its own idle round-trip "
                                    "probe) and deducts it from duty "
                                    "charges, so the 25% cap paces chip "
                                    "busy plus only the loaded-transport "
                                    "remainder above the idle RTT; "
                                    "shared_tenant_throttle audits those "
                                    "residual admit waits (see "
                                    "SHARE_CORE_LIMIT comment)"},
        "samples_shared": len(shared_ttfts),
        "sharing_rounds": len(round_degradations),
        "per_round_degradation": [round(d, 2) for d in round_degradations],
        # the exclusive baseline per round IS the drift tracker: swings
        # here are not sharing (a spike round whose neighbors' baselines
        # also move is drift, not contention)
        "per_round_base_p50_ms": [round(m * 1e3, 2) for m in base_medians],
        # drift-rejected rounds, published for audit: the criteria read only
        # exclusive-baseline data (sub-cycle solo spread, session-median
        # drift), never the degradation, so rejection refuses drift
        # without being able to cherry-pick the sharing signal
        "sharing_rounds_rejected": [
            {"reason": r["reason"],
             "base_p50_ms": round(r["base_median"] * 1e3, 2),
             "degradation": round(r["degradation"], 2)}
            for r in rejected] or None,
        "drift_rejection_exhausted": rejection_exhausted,
        "max_round_degradation": round(max(round_degradations), 2),
        "median_round_degradation": round(statistics.median(round_degradations), 2),
    }))
    # Compact headline as the FINAL stdout line: the full artifact above
    # runs to tens of KB and drivers that keep only a prefix or parse the
    # last line would lose it — the summary is
    # a few hundred bytes and self-contained (metric, value, CI, verdict).
    # One shared implementation of the convention: vtpu/obs/summary.py.
    from vtpu.obs.summary import print_summary

    print_summary(
        "p90_round_ttft_degradation_4way_share_stack",
        round(raw_degradation, 2),
        "pass" if raw_ci[1] < 5.0 else "fail",
        unit="percent",
        ci95=[round(raw_ci[0], 2), round(raw_ci[1], 2)],
        vs_baseline=round(raw_degradation / 5.0, 3),
        rounds=len(round_degradations),
        stack_in_loop=True,
    )


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--tenant", action="store_true")
    ap.add_argument("--rank", type=int, default=0)
    args = ap.parse_args()
    if args.tenant:
        tenant_main(args)
    else:
        main()
