"""Paged-vs-dense KV A/B at an EQUAL simulated HBM budget (ISSUE 4 tentpole).

The dense ring pins slots * max_seq tokens of KV whether or not any request
ever grows that long, so a fixed HBM budget H caps concurrency at
H / max_seq slots. The paged pool spends the same H on page-granular blocks
that admissions reserve for prompt + THEIR token budget only — the same
bytes hold materially more live slots, and decode throughput for a
bandwidth-bound loop scales with live slots. Both arms run the SAME
ServingEngine, weights, and request trace; only the KV memory layout (and
the concurrency it affords under the shared budget) differs:

  dense arm:  kv_page=None, slots = H // max_seq  (worst-case pinning)
  paged arm:  kv_page=P, kv_pool_blocks = H // P, slots sized to expected
              live tokens (oversubscription; pool backpressure absorbs the
              tail instead of an allocator failure)

Headline: aggregate tokens/sec ratio over a fixed request trace.

``--tp N`` (ISSUE 5 tentpole) runs BOTH arms tensor-parallel on an
N-virtual-device ('tp',) mesh: weights column/row-sharded, the dense cache
and the paged block pool head-sharded, page tables replicated.
--hbm-tokens is then the PER-CHIP budget (what the per-container
TPU_DEVICE_MEMORY_LIMIT_<i> cap actually bounds) — the head shard divides
uniformly, so each arm's global capacity is budget * tp and the equal-HBM
discipline is enforced chip by chip. The headline is dense-TP vs paged-TP
at equal per-chip HBM; full --tp runs gate >= 2x in the exit code.

A second phase microbenches SHARED-PREFIX admission: both arms register a
system-prompt prefix and admit M suffix requests against it. The dense path
device-copies the full prefix KV into the slot per admission
(prefix_install_copies == M); the paged path maps the prefix's pool blocks
read-only into each slot's table (install copies == 0, blocks_shared > 0,
one boundary-block COW per admission when the prefix is page-unaligned) —
under --tp the blocks being shared are the head-sharded pool's.

``--attn-kernel`` (ISSUE 10 tentpole) switches to the KERNEL-vs-GATHER
long-context A/B instead: both arms run the SAME paged engine and request
trace — one long-prompt anchor keeps every tick's read window at max_seq
while short requests stream beside it (window >> live pages, the regime
where the per-tick O(window) gather materialization taxes hardest) — and
only the paged decode-attention route differs (ServingConfig.paged_attn
"gather" vs "kernel"). Deterministic gates, every run: token-equal streams
across the routes, route counters attributing every tick to its arm's
route, a compiled-HLO audit proving the pool-window gather DISAPPEARED
from the kernel arm's decode executable (count_pool_gathers == 0 at the
window-gather size; > 0 on the gather arm), auto-routing never selecting
the kernel off-TPU (pallas interprets there — the measured router keeps
it off), and both arms holding device_gets_per_tick == 1.0. The
tokens/sec ratio gates full runs ON TPU BACKENDS ONLY: off-chip the
kernel arm runs interpreted emulation, so its wall-clock is a correctness
exhibit, not a measurement (the routing table's perf basis is the
standalone study, DECODE_ATTN_r05.json — 1.1-1.9x at every serving cell).
Artifact: PAGED_ATTN_r12.json.

``--attn-kernel --spec-chunk T`` (ISSUE 19 satellite) reruns the same
kernel-vs-gather A/B with speculation enabled (spec_tokens = T-1), so
every accepting tick dispatches a T-query verify chunk instead of a
single-query decode step. The route counters then attribute MIXED-t
traffic (t=1 decode ticks interleave with t=T verify chunks under one
forced route), the HLO audit lowers spec_step at the [slots, T] draft
shape, and an extra gate pins the per-T floor-table contract: a chunk
depth with no PAGED_ATTN_T_FLOORS row never routes kernel on auto, even
on TPU. This is the on-chip sweep vehicle for re-tightening T>1 floor
rows per measured cell (every T=4 cell lost in DECODE_ATTN_r05.json, so
none ship by default).

Usage:  python benchmarks/paged_kv_bench.py [--quick] [--tp N]
            [--attn-kernel [--spec-chunk T]] [--hbm-tokens N] [--page P]
            [--requests K] [--prompt-len N] [--max-new N] [--out F]
Emits:  full artifact JSON on stdout line 1, then the compact one-line
        headline summary (metric/value/verdict — the PR-3 driver-artifact
        convention) as the FINAL stdout line; human notes on stderr.
        --out also writes the artifact to a file (default PAGED_KV_r07.json
        for full single-chip runs, PAGED_KV_TP_r08.json for full --tp
        runs; quick runs only write when --out is given).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> None:
    ap = argparse.ArgumentParser("paged-kv-bench")
    ap.add_argument("--quick", action="store_true",
                    help="CI mode: lighter trace, same A/B shape")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel width: run BOTH arms on a "
                         "('tp',) mesh of N virtual CPU devices with the "
                         "KV plane head-sharded; --hbm-tokens becomes the "
                         "PER-CHIP budget")
    ap.add_argument("--attn-kernel", action="store_true",
                    help="run the kernel-vs-gather long-context A/B "
                         "instead (same paged engine, only the paged "
                         "decode-attention route differs) -> "
                         "PAGED_ATTN_r12.json")
    ap.add_argument("--spec-chunk", type=int, default=1,
                    help="with --attn-kernel: enable speculation "
                         "(spec_tokens = T-1) so accepting ticks dispatch "
                         "T-query verify chunks — the on-chip sweep "
                         "vehicle for the per-T PAGED_ATTN_T_FLOORS rows "
                         "(default 1: plain single-query decode)")
    ap.add_argument("--hbm-tokens", type=int, default=None,
                    help="simulated KV HBM budget, in cached tokens — "
                         "PER CHIP when --tp > 1. Default 512 // tp: the "
                         "same 512-token GLOBAL budget at every tp, split "
                         "over the head shards, so the tp arms measure "
                         "'same total HBM, more chips' (per-chip pressure "
                         "at its highest — the regime paged pays off in)")
    ap.add_argument("--page", type=int, default=16,
                    help="paged arm block size (tokens)")
    ap.add_argument("--max-seq", type=int, default=512,
                    help="model context cap — what the dense ring PINS "
                         "per slot regardless of traffic")
    ap.add_argument("--requests", type=int, default=16,
                    help="requests in the throughput trace")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32,
                    help="decode tokens per request")
    ap.add_argument("--prefix-len", type=int, default=40,
                    help="shared-prefix microbench prefix length "
                         "(page-UNALIGNED by default so the COW boundary "
                         "path is exercised)")
    ap.add_argument("--prefix-requests", type=int, default=6)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None,
                    help="artifact path (default PAGED_KV_r07.json on full "
                         "runs; quick runs only write when set)")
    a = ap.parse_args()
    if a.hbm_tokens is None:
        a.hbm_tokens = 512 // a.tp
    if a.quick:
        a.requests = min(a.requests, 12)
        a.max_new = min(a.max_new, 24)
        a.prefix_requests = min(a.prefix_requests, 4)
    if a.spec_chunk < 1:
        print("--spec-chunk must be >= 1 (T = queries per verify "
              "dispatch)", file=sys.stderr)
        sys.exit(2)
    if a.spec_chunk > 1 and not a.attn_kernel:
        print("--spec-chunk only shapes the kernel-vs-gather A/B; pass "
              "--attn-kernel with it", file=sys.stderr)
        sys.exit(2)
    if a.attn_kernel:
        if a.tp > 1:
            # the A/B arms run single-chip; a silent single-chip run under
            # --tp would masquerade as a measured shard_map result. The tp=2
            # kernel contract (stream equality + collective parity) is gated
            # by tests/test_paged_attn_kernel.py instead.
            print("--attn-kernel does not take --tp: the kernel-vs-gather "
                  "A/B is single-chip (tp kernel contracts are gated in "
                  "tests/test_paged_attn_kernel.py)", file=sys.stderr)
            sys.exit(2)
        run_attn_kernel(a)
        return
    if a.tp > 1 and "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        # the mesh needs tp virtual CPU devices; must be set before jax
        # imports (argparse runs first precisely so this can work)
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={max(a.tp, 2)}"
        ).strip()

    import jax
    import jax.numpy as jnp

    from vtpu.models import ModelConfig, init_params
    from vtpu.models.transformer import kv_bytes_per_token
    from vtpu.serving import ServingConfig, ServingEngine

    mesh = None
    if a.tp > 1:
        from vtpu.parallel.mesh import make_axis_mesh

        if len(jax.devices()) < a.tp:
            print(f"need {a.tp} devices, have {len(jax.devices())}",
                  file=sys.stderr)
            sys.exit(2)
        mesh = make_axis_mesh("tp", a.tp)

    # Tiny on purpose, and smaller than decode_bench's model: a CPU tick
    # must be dominated by FIXED dispatch overhead, not by compute that
    # scales with batch — that is the regime where concurrency converts to
    # wall-clock, exactly as on a TPU whose small-batch decode tick is
    # latency-bound (the MXU runs batch 1 and batch 8 in the same time).
    # The A/B then isolates what the budget-capped concurrency costs: the
    # dense arm needs ~slots-ratio more ticks to drain the same trace.
    # n_heads scales with tp (the head axis must divide over the mesh).
    cfg = ModelConfig(
        vocab=128, d_model=32, n_heads=max(2, a.tp), n_layers=1, d_ff=64,
        max_seq=a.max_seq, head_dim=16, dtype=jnp.float32, use_pallas=False,
    )
    params = init_params(jax.random.key(0), cfg)
    bucket = max(16, a.page)
    # --hbm-tokens is per chip; the head shard divides uniformly, so the
    # GLOBAL token capacity both arms spend is budget * tp
    hbm_global = a.hbm_tokens * a.tp
    dense_slots = max(hbm_global // a.max_seq, 1)
    pool_blocks = hbm_global // a.page
    per_req_pages = -(-(a.prompt_len + a.max_new) // a.page)
    # cap the paged pool at 8 slots: on the CPU rig per-tick cost grows
    # with batch past ~8 faster than the tick count shrinks (a TPU's
    # latency-bound decode tick would keep absorbing slots for free)
    paged_slots = max(min(pool_blocks // per_req_pages, 8), dense_slots)

    def prompt(seed: int, n: int):
        return [int(t) for t in jax.random.randint(
            jax.random.key(seed), (n,), 1, cfg.vocab, jnp.int32)]

    def run_trace(name: str, serving: ServingConfig) -> dict:
        eng = ServingEngine(params, cfg, serving, mesh=mesh)
        eng.start()
        try:
            # warmup wave (compiles + steady thread state), then the trace
            for r in [eng.submit(prompt(1 + i, a.prompt_len),
                                 max_new_tokens=2)
                      for i in range(serving.slots)]:
                for _ in r.stream():
                    pass
            t0 = time.perf_counter()
            reqs = [eng.submit(prompt(100 + i, a.prompt_len),
                               max_new_tokens=a.max_new)
                    for i in range(a.requests)]
            streams = [list(r.stream()) for r in reqs]
            wall = time.perf_counter() - t0
            stats = eng.stats()
        finally:
            eng.stop()
        toks = sum(len(s) for s in streams)
        assert all(len(s) == a.max_new for s in streams), \
            f"{name}: trace lost tokens"
        out = {
            "arm": name,
            "slots": serving.slots,
            "kv_page": serving.kv_page,
            "kv_pool_blocks": serving.kv_pool_blocks,
            "wall_s": round(wall, 3),
            "tokens": toks,
            "tokens_per_sec": round(toks / wall, 1),
            "decode_ticks": stats["decode_ticks"],
            "kv_bucket_hist": {str(k): v for k, v in sorted(
                stats["kv_bucket_hist"].items())},
            "kv_hbm_bytes": stats["kv_hbm_bytes"],
            "kv_hbm_bytes_per_chip": stats["kv_hbm_bytes_per_chip"],
            "tp": stats["tp"],
            "pool_blocked_admissions": stats["pool_blocked_admissions"],
            "kv_pool_occupancy_final": stats["kv_pool_occupancy"],
            "read_pages_ratio": stats["read_pages_ratio"],
        }
        print(f"{name:>6}: {out['tokens_per_sec']:8.1f} tok/s "
              f"({serving.slots} slots, {out['decode_ticks']} ticks, "
              f"wall {out['wall_s']:.2f}s)", file=sys.stderr)
        return out

    def run_prefix(name: str, serving: ServingConfig) -> dict:
        eng = ServingEngine(params, cfg, serving, mesh=mesh)
        eng.start()
        try:
            pid = eng.register_prefix(prompt(7, a.prefix_len))
            t0 = time.perf_counter()
            reqs = [eng.submit(prompt(200 + i, 8), max_new_tokens=4,
                               prefix=pid)
                    for i in range(a.prefix_requests)]
            for r in reqs:
                for _ in r.stream():
                    pass
            wall = time.perf_counter() - t0
            stats = eng.stats()
        finally:
            eng.stop()
        out = {
            "arm": name,
            "prefix_requests": a.prefix_requests,
            "wall_s": round(wall, 3),
            "prefix_install_copies": stats["prefix_install_copies"],
            "prefix_blocks_shared": stats["prefix_blocks_shared"],
            "prefix_cow_copies": stats["prefix_cow_copies"],
        }
        print(f"{name:>6} prefix: {out['prefix_install_copies']} install "
              f"copies, {out['prefix_blocks_shared']} blocks shared, "
              f"{out['prefix_cow_copies']} COW", file=sys.stderr)
        return out

    common = dict(slots=dense_slots, prefill_buckets=(bucket,),
                  max_new_tokens=a.max_new)
    dense = run_trace("dense", ServingConfig(**common))
    paged = run_trace("paged", ServingConfig(
        **{**common, "slots": paged_slots},
        kv_page=a.page, kv_pool_blocks=pool_blocks))
    ratio = (paged["tokens_per_sec"] / dense["tokens_per_sec"]
             if dense["tokens_per_sec"] else None)

    prefix_common = dict(slots=4, prefill_buckets=(bucket,),
                         max_new_tokens=a.max_new, prefill_chunk=bucket)
    dense_px = run_prefix("dense", ServingConfig(**prefix_common))
    paged_px = run_prefix("paged", ServingConfig(
        **prefix_common, kv_page=a.page,
        kv_pool_blocks=max(pool_blocks, 4 * per_req_pages + 8)))
    zero_copy = (paged_px["prefix_install_copies"] == 0
                 and paged_px["prefix_blocks_shared"] > 0)

    # the tp arms carry a stronger bar: the tentpole's acceptance is >= 2x
    # aggregate tokens/sec over dense-TP at equal per-chip HBM
    bar = 2.0 if a.tp > 1 else 1.5
    ok = bool(ratio and ratio >= bar and zero_copy)
    artifact = {
        "metric": ("paged_kv_tp_equal_per_chip_hbm_tokens_per_sec_speedup"
                   if a.tp > 1 else
                   "paged_kv_equal_hbm_tokens_per_sec_speedup"),
        "value": ratio and round(ratio, 3),
        "unit": ("x_aggregate_tokens_per_sec_vs_dense_tp" if a.tp > 1
                 else "x_aggregate_tokens_per_sec_vs_dense"),
        "pass": ok,
        "bar": bar,
        "tp": a.tp,
        # a.hbm_tokens is already per chip; a token's bytes split over the
        # head shard, so its per-chip cost is bpt/tp — per-chip bytes =
        # (hbm_tokens * tp global tokens) * bpt / tp = hbm_tokens * bpt
        "hbm_budget_tokens_per_chip": a.hbm_tokens,
        "hbm_budget_bytes_per_chip": a.hbm_tokens * kv_bytes_per_token(cfg),
        "page": a.page,
        "dense_slots": dense_slots,
        "paged_slots": paged_slots,
        "requests": a.requests,
        "prompt_len": a.prompt_len,
        "max_new": a.max_new,
        "quick": a.quick,
        "model": {"vocab": cfg.vocab, "d_model": cfg.d_model,
                  "n_heads": cfg.n_heads, "n_layers": cfg.n_layers,
                  "max_seq": cfg.max_seq},
        "arms": [dense, paged],
        "prefix_microbench": [dense_px, paged_px],
    }
    default_out = ("PAGED_KV_TP_r08.json" if a.tp > 1 else
                   "PAGED_KV_r07.json")
    out_path = a.out or (None if a.quick else default_out)
    if out_path:
        Path(out_path).write_text(json.dumps(artifact, indent=2) + "\n")
    print(json.dumps(artifact))
    # Compact headline as the FINAL stdout line (the PR-3 convention,
    # shared implementation in vtpu/obs/summary.py).
    from vtpu.obs.summary import print_summary

    print_summary(
        artifact["metric"], artifact["value"],
        "pass" if ok else "fail", unit=artifact["unit"],
        paged_slots_vs_dense=f"{paged_slots}x{dense_slots}",
        prefix_zero_copy=zero_copy,
        prefix_install_copies_paged=paged_px["prefix_install_copies"],
        prefix_blocks_shared=paged_px["prefix_blocks_shared"],
    )
    # Exit code backs the CI step's name: the DETERMINISTIC zero-copy
    # contract always gates; the perf ratio gates full runs only (quick
    # CI boxes are too noisy to fail a 1.5x bar on).
    if not zero_copy or (not a.quick and not ok):
        sys.exit(1)


def run_attn_kernel(a) -> None:
    """Kernel-vs-gather long-context A/B (ISSUE 10): same paged engine,
    same trace, only ServingConfig.paged_attn differs. See the module
    docstring for the gate structure."""
    import jax
    import jax.numpy as jnp

    from vtpu.models import ModelConfig, init_params
    from vtpu.ops.decode_attn import (PAGED_ATTN_T_FLOORS,
                                      count_pool_gathers, paged_attn_route)
    from vtpu.serving import ServingConfig, ServingEngine
    from vtpu.serving.adapters import TransformerSlotModel

    if a.quick:
        a.max_seq = min(a.max_seq, 256)
        a.requests = min(a.requests, 6)
    backend = jax.default_backend()
    # --spec-chunk T: speculation on (spec_tokens = T-1) turns accepting
    # ticks into T-query verify chunks, so the route counters see mixed-t
    # traffic and the HLO audit runs at the [slots, T] spec_step shape —
    # the sweep vehicle for the per-T floor table
    chunk_t = a.spec_chunk
    spec = chunk_t - 1
    # one long-prompt ANCHOR pins every tick's read window at max_seq while
    # short requests stream beside it: window >> live pages for every slot
    # but the anchor's — the exact regime where the gather route's
    # per-tick O(window) materialization taxes hardest. The anchor's token
    # budget covers every short wave PLUS one tick per admission (each
    # short's prefill interlude decodes the anchor alone), so the full
    # window holds for the WHOLE trace, not just its opening ticks.
    window = a.max_seq
    slots = 4
    anchor_new = (a.max_new * max(1, -(-a.requests // (slots - 1)))
                  + a.requests)
    anchor_len = window - anchor_new - 2
    if anchor_len < 8:
        print("max_seq too small for the anchor at this trace shape "
              f"(anchor budget {anchor_new})", file=sys.stderr)
        sys.exit(2)
    short_bucket = max(64, a.page)
    cfg = ModelConfig(
        vocab=128, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq=a.max_seq, head_dim=16, dtype=jnp.float32, use_pallas=False,
    )
    params = init_params(jax.random.key(0), cfg)

    def prompt(seed: int, n: int):
        return [int(t) for t in jax.random.randint(
            jax.random.key(seed), (n,), 1, cfg.vocab, jnp.int32)]

    def serving(route):
        return ServingConfig(
            slots=slots, prefill_buckets=(short_bucket, a.max_seq),
            max_new_tokens=a.max_new, kv_page=a.page, paged_attn=route,
            spec_tokens=spec)

    def run_arm(route: str) -> dict:
        eng = ServingEngine(params, cfg, serving(route))
        eng.start()
        try:
            # warmup wave incl. one anchor-length prompt so BOTH arms'
            # window=max_seq decode executables compile before the clock
            warm = [eng.submit(prompt(1, anchor_len), max_new_tokens=2)]
            warm += [eng.submit(prompt(2 + i, a.prompt_len),
                                max_new_tokens=2) for i in range(slots - 1)]
            for r in warm:
                for _ in r.stream():
                    pass
            t0 = time.perf_counter()
            reqs = [eng.submit(prompt(100, anchor_len),
                               max_new_tokens=anchor_new)]
            reqs += [eng.submit(prompt(101 + i, a.prompt_len),
                                max_new_tokens=a.max_new)
                     for i in range(a.requests)]
            streams = [list(r.stream()) for r in reqs]
            wall = time.perf_counter() - t0
            stats = eng.stats()
        finally:
            eng.stop()
        toks = sum(len(s) for s in streams)
        assert len(streams[0]) == anchor_new, f"{route}: anchor lost tokens"
        assert all(len(s) == a.max_new for s in streams[1:]), \
            f"{route}: trace lost tokens"
        out = {
            "arm": route,
            "wall_s": round(wall, 3),
            "tokens": toks,
            "tokens_per_sec": round(toks / wall, 1),
            "streams": streams,
            "decode_ticks": stats["decode_ticks"],
            "paged_attn_kernel_ticks": stats["paged_attn_kernel_ticks"],
            "paged_attn_gather_ticks": stats["paged_attn_gather_ticks"],
            "device_gets_per_tick": stats["device_gets_per_tick"],
            "kv_bucket_hist": {str(k): v for k, v in sorted(
                stats["kv_bucket_hist"].items())},
            "read_pages_ratio": stats["read_pages_ratio"],
        }
        if spec:
            out["spec_ticks"] = stats["spec_ticks"]
            out["mean_emitted_per_spec_tick"] = \
                stats["mean_emitted_per_spec_tick"]
        print(f"{route:>6}: {out['tokens_per_sec']:8.1f} tok/s "
              f"({out['decode_ticks']} ticks, wall {out['wall_s']:.2f}s, "
              f"kernel/gather ticks {out['paged_attn_kernel_ticks']}/"
              f"{out['paged_attn_gather_ticks']})", file=sys.stderr)
        return out

    def decode_hlo(route: str) -> str:
        model = TransformerSlotModel(params, cfg, kv_page=a.page,
                                     paged_attn=route)
        state = model.init_state(slots)
        fn = jax.jit(model.decode_step,
                     static_argnames=("kv_bucket", "unroll"))
        return fn.lower(
            model.params, state, jnp.zeros((slots,), jnp.int32),
            jnp.ones((slots,), bool), window, unroll=True,
        ).compile().as_text()

    def spec_hlo(route: str) -> str:
        # the T-query analogue of decode_hlo: audit the verify-chunk
        # executable at the [slots, T] draft shape the engine dispatches
        model = TransformerSlotModel(params, cfg, kv_page=a.page,
                                     paged_attn=route)
        state = model.init_state(slots)
        fn = jax.jit(model.spec_step,
                     static_argnames=("kv_bucket", "unroll"))
        return fn.lower(
            model.params, state, jnp.zeros((slots, chunk_t), jnp.int32),
            jnp.ones((slots,), bool),
            jnp.full((slots,), chunk_t, jnp.int32), window, unroll=True,
        ).compile().as_text()

    gather = run_arm("gather")
    kernel = run_arm("kernel")
    ratio = (kernel["tokens_per_sec"] / gather["tokens_per_sec"]
             if gather["tokens_per_sec"] else None)
    # compiled-HLO audit at the pool-window gather size: the gather arm's
    # decode step materializes [B, window, H, Dh] per value plane per
    # layer; the kernel arm's executable must carry NONE of them
    min_elems = slots * window * cfg.n_heads * cfg.head_dim
    audit_hlo = spec_hlo if spec else decode_hlo
    kernel_gathers = count_pool_gathers(audit_hlo("kernel"), min_elems)
    gather_gathers = count_pool_gathers(audit_hlo("gather"), min_elems)
    gates = {
        "streams_token_equal": gather["streams"] == kernel["streams"],
        "route_counters_attributed": (
            kernel["paged_attn_kernel_ticks"] > 0
            and kernel["paged_attn_gather_ticks"] == 0
            and gather["paged_attn_gather_ticks"] > 0
            and gather["paged_attn_kernel_ticks"] == 0),
        "kernel_hlo_gather_free": kernel_gathers == 0,
        "gather_hlo_has_pool_gathers": gather_gathers > 0,
        # per-shape routing never selects the kernel where it measured
        # slower: off-TPU that is everywhere (interpreted pallas)
        "auto_route_off_tpu_is_gather": (
            backend == "tpu"
            or paged_attn_route(None, window, t=chunk_t) == "gather"),
        # a chunk depth with no floor-table row never routes kernel on
        # auto, even on TPU — the forced routes above are the only way to
        # exercise the kernel at an unmeasured T (add a
        # PAGED_ATTN_T_FLOORS row per measured winning cell to change it)
        "auto_route_unmeasured_t_is_gather": (
            chunk_t == 1
            or (chunk_t, False) in PAGED_ATTN_T_FLOORS
            or paged_attn_route(None, window, backend="tpu",
                                t=chunk_t) == "gather"),
        "device_gets_per_tick_contract": (
            gather["device_gets_per_tick"] == 1.0
            and kernel["device_gets_per_tick"] == 1.0),
        # --spec-chunk runs are vacuous unless T-query verify chunks
        # actually flowed through both routes
        "spec_chunks_dispatched": (
            not spec or (gather["spec_ticks"] > 0
                         and kernel["spec_ticks"] > 0)),
    }
    for arm in (gather, kernel):
        del arm["streams"]  # equality gated above; keep the artifact lean
    bar = 1.1
    # perf gates full runs ON CHIP only: off-TPU the kernel arm is
    # interpreted emulation, a correctness exhibit rather than a
    # measurement (the routing table's perf basis is the standalone study)
    perf_gated = (not a.quick) and backend == "tpu"
    ok = all(gates.values()) and (not perf_gated
                                  or (ratio is not None and ratio >= bar))
    artifact = {
        "metric": "paged_attn_kernel_long_context_tokens_per_sec_speedup",
        "value": ratio and round(ratio, 3),
        "unit": "x_tokens_per_sec_vs_gather_route",
        "pass": ok,
        "bar": bar,
        "perf_gated": perf_gated,
        "backend": backend,
        "quick": a.quick,
        "window_tokens": window,
        "spec_chunk": chunk_t,
        "page": a.page,
        "slots": slots,
        "anchor_prompt_len": anchor_len,
        "anchor_max_new": anchor_new,
        "requests": a.requests,
        "prompt_len": a.prompt_len,
        "max_new": a.max_new,
        "pool_window_gathers": {"kernel_arm": kernel_gathers,
                                "gather_arm": gather_gathers},
        "routing_basis": (
            "DECODE_ATTN_r05.json standalone study (real v5e, RTT-"
            "cancelled): fused kernel beats the XLA chain only at bf16 T=1 "
            "windows >= 1024 (1.10-1.64x) and int8 T=1 from 2048 "
            "(1.90x/1.01x); int8@1024 and every T=4 cell lost — auto "
            "routes the kernel on TPU at exactly the measured winning "
            "shapes (PAGED_ATTN_MIN_WINDOW{,_INT8}, T=1), gather "
            "elsewhere"),
        "deterministic_gates": gates,
        "model": {"vocab": cfg.vocab, "d_model": cfg.d_model,
                  "n_heads": cfg.n_heads, "n_layers": cfg.n_layers,
                  "max_seq": cfg.max_seq},
        "arms": [gather, kernel],
    }
    # --spec-chunk sweep cells are per-T measurements, not the T=1
    # headline artifact: they only write where --out points them
    out_path = a.out or (None if a.quick or spec else "PAGED_ATTN_r12.json")
    if out_path:
        Path(out_path).write_text(json.dumps(artifact, indent=2) + "\n")
    print(json.dumps(artifact))
    from vtpu.obs.summary import print_summary

    print_summary(
        artifact["metric"], artifact["value"],
        "pass" if ok else "fail", unit=artifact["unit"],
        window_tokens=window,
        kernel_hlo_gather_free=gates["kernel_hlo_gather_free"],
        streams_token_equal=gates["streams_token_equal"],
        perf_gated=perf_gated,
    )
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    from vtpu.util.jaxcache import place_compile_cache

    place_compile_cache()
    main()
