"""Continuous-batching serving engine over the flagship transformer.

The TPU-shaped serving loop (JetStream-style): a fixed pool of B cache slots,
one compiled prefill per bucketed prompt length, and ONE compiled decode step
for the whole pool — requests join and leave slots without recompiling
anything. All shapes are static; per-slot state is data (lengths, active
mask), never shape:

- prefill runs on a [1, bucket] prompt and scatters its KV into the slot;
- decode advances every ACTIVE slot one token per tick; inactive slots
  compute too (lockstep hardware loves uniformity) but their state is masked
  out, so a slot's garbage never leaks into a live sequence;
- admission is continuous: a request entering slot 3 never disturbs the
  sequences mid-decode in slots 0-2.

This is the data plane the vTPU middleware schedules: the TTFT benchmark's
tenants each run one of these engines against their fractional chip share.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import itertools
import logging
import queue
import threading
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from vtpu.models import transformer
from vtpu.models.transformer import ModelConfig, Params, kv_bytes_per_token
from vtpu.obs import pauses
from vtpu.obs.tickprof import TickProfiler
from vtpu.obs.warmup import WarmupClock
from vtpu.obs.trace import RequestTrace, TERMINAL_CODES, pct
from vtpu.ops.decode_attn import paged_attn_route
from vtpu.parallel.sharding import head_sharding
from vtpu.serving.adapters import (
    TransformerSlotModel,
    batched_admission_step,
    block_pass_step,
    fused_spec_decode_step,
    multi_tick_decode_step,
    sampled_decode_step,
    swap_page_gather,
    swap_page_scatter,
)
from vtpu.serving.faults import EngineDeath, FaultInjected, FaultPlan
from vtpu.serving.shed import (EngineSignals, accepts_signals,
                               load_loop_policy, load_shed_policy)

log = logging.getLogger(__name__)

# D2H/H2D staging width of the swap tier, in blocks: one compiled
# gather/scatter shape moves up to this many blocks per dispatch (entries
# larger than the stage issue multiple dispatches — still async, still
# compile-once)
SWAP_STAGE_BLOCKS = 8

# The fetch watchdog's clock, under a name of its own so that a test can
# put one it drives in its place: on a CPU an interpreted kernel's tick
# outlasts any watchdog short enough for a test
_watchdog_clock = time.perf_counter


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    slots: int = 4  # concurrent sequences (the compiled decode batch)
    prefill_buckets: tuple[int, ...] = (128, 256, 512, 1024)
    max_new_tokens: int = 64
    eos_token: int = -1  # -1: never stops early
    # Speculative decoding: draft length K (0 = off). Drafts come from
    # prompt-lookup (continue the most recent earlier occurrence of the last
    # spec_ngram tokens — no draft model, pays off on repetitive/structured
    # text); the model verifies K+1 positions in ONE bandwidth-bound tick
    # (batched_spec_step), emitting 1..K+1 tokens. Greedy sampling only: the
    # engine DROPS spec_tokens when a custom sampler, logprobs, temperature,
    # or a model without spec_step is configured — and says why, as the
    # stats()["spec_disabled_reason"] gauge plus a one-time "spec_disabled"
    # trace event (a misconfigured engine is diagnosable from a scrape, not
    # just mysteriously slow). A tick where no slot found any match falls
    # back to the plain decode step (same bytes, fewer FLOPs). Combined
    # with decode_loop_k, draft+verify FUSE into the device-resident loop
    # (see decode_loop_k below).
    spec_tokens: int = 0
    spec_ngram: int = 3
    # Adaptive speculation: a verify tick costs more than a decode tick
    # (1.06-1.35x measured in round 4 on a v5e, record removed with the
    # rig), so speculation LOSES on traffic whose drafts rarely
    # verify. The engine tracks an EMA of mean emitted tokens per spec tick
    # and stops drafting while it sits below this threshold, re-probing
    # after spec_cooloff_ticks plain ticks (workloads change). 0 = always
    # speculate.
    spec_min_mean: float = 1.25
    spec_cooloff_ticks: int = 64
    # Chunked prefill: admit prompts LONGER than the largest bucket by
    # streaming fixed-size [1, C] chunks through the decode/verify trunk
    # (chunked_prefill_into_slot). One executable per chunk size serves any
    # prompt length up to the model context, and each admission dispatch is
    # bounded at C tokens of work. None = off (bucketed prompts only).
    # Short prompts keep using buckets (one dispatch beats ceil(n/C)).
    prefill_chunk: Optional[int] = None
    # --- on-device batched sampling (the default decode path) ------------
    # Sampling runs INSIDE the jitted decode step (transformer.sample_tokens
    # composed via adapters.sampled_decode_step), so a tick fetches [B] int32
    # tokens instead of [B, vocab] f32 logits. temperature 0 = greedy;
    # temperature/top-k/top-p draw exact categorical samples via Gumbel-max
    # with one PRNG stream per slot (seeded from sampling_seed). A custom
    # ``sample=`` callable on the engine bypasses all of this (host fallback:
    # full logits fetched per tick, no pipelining); the callable receives a
    # fetched numpy [vocab] row — admission and per-tick alike — and returns
    # a token id.
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    sampling_seed: int = 0
    # Also stream log p(token) per generated token (Request.logprobs); adds
    # B*4 bytes to the one per-tick fetch. Disables speculation: a verify
    # tick returns token ids only, so spec-emitted tokens would have no
    # logprob entries and the stream/logprobs pairing would silently skew.
    logprobs: bool = False
    # One-tick-deep decode pipelining: tick t+1 is dispatched with the
    # device-resident sampled token array BEFORE tick t is delivered, so the
    # host's Python bookkeeping for tick t overlaps the device computing
    # t+1 (JAX async dispatch). A slot retired or re-admitted between the
    # two invalidates only ITS in-flight lookahead (request-identity check
    # at delivery). None = auto: on whenever device sampling is active and
    # speculation is off (a spec tick must see the newest token on the host
    # to build its draft, so speculation forces the synchronous loop).
    # False forces the synchronous loop (still one device_get per tick).
    pipeline_decode: Optional[bool] = None
    # --- batched async admission (the admission data plane) --------------
    # Same-bucket waiting prompts are coalesced into one [N, bucket] prefill
    # dispatch (N the largest warmed size that fits; sizes are capped at the
    # slot count and 1 is always included) that scatters KV into N slots at
    # once AND samples the N first tokens on device — a K-prompt burst
    # drains in ceil(K/Nmax) dispatches instead of K, with zero blocking
    # per-admission host syncs: the first tokens ride the tick loop's
    # existing batched fetch (or one batched admission fetch on an idle
    # engine). Each (N, bucket) executable is compiled in _warm_executables.
    prefill_batch_sizes: tuple[int, ...] = (1, 2, 4, 8)
    # None = auto: batched/async admission whenever device sampling is
    # active and speculation is off (the legacy path samples each first
    # token with a blocking per-admission sync — a custom sampler needs the
    # fetched logits row, and a spec tick needs the first token on the host
    # to seed its draft history). False forces the legacy serial path; an
    # explicit True that cannot be honored raises, like pipeline_decode.
    async_admission: Optional[bool] = None
    # Sarathi-style per-tick admission budget, in prompt tokens: bucketed
    # batches (N*bucket) and chunked-prefill chunks (C each) draw from one
    # budget per tick, bounding how much prefill work can be injected
    # between two decode ticks — a prompt burst then degrades live streams'
    # inter-token latency by a bounded, configurable amount instead of
    # stalling them for the whole burst. 0 = uncapped. BYPASSED while no
    # slot is decoding: an idle engine admits at full speed for the lowest
    # possible TTFT. Must cover the smallest prefill bucket (and the
    # prefill chunk, when chunking is on) or admission could starve until
    # the engine drains idle; validated at engine construction. It also
    # caps the chunked admissions in flight at budget // prefill_chunk,
    # decoding or not: the chunks one tick buys go to the oldest prompts
    # and the rest keep their place in the queue (first tokens come one
    # after another, not all at the end of a burst of long prompts).
    prefill_budget: int = 0
    # --- paged KV cache (the KV-memory data plane) -----------------------
    # kv_page (tokens per block; None = dense, bit-identical to the classic
    # per-slot ring) switches the pool state to a SHARED block pool
    # [L, n_blocks, page, H, Dh] per k/v plane plus a per-slot page table
    # [slots, max_pages] int32 — logical sequences decoupled from physical
    # KV storage (the Zorua/vLLM resource-virtualization move). Admission
    # becomes pool-aware: a request reserves pages covering prompt + its
    # token budget (not max_seq), parks on the waiting list under pool
    # exhaustion (backpressure, never OOM), and a registered prefix's
    # blocks map read-only into many slots' tables (zero-copy sharing;
    # copy-on-write only for the partial boundary block). kv_page must
    # divide max_seq and every prefill bucket.
    kv_page: Optional[int] = None
    # Pool size in blocks (excluding the reserved null block 0). None =
    # slots * max_pages — dense-equivalent capacity, no oversubscription.
    # Sizing it to EXPECTED live tokens instead (concurrency * mean
    # prompt+generation length) is the whole point: the same HBM holds
    # materially more concurrent slots, and the free-list backpressure
    # absorbs the tail instead of an allocator failure.
    kv_pool_blocks: Optional[int] = None
    # Paged decode-attention route (paged pools only). None = the measured
    # per-shape router (ops.decode_attn.paged_attn_route — the FLASH_MIN_SEQ
    # discipline: the fused Pallas table-walking kernel engages only at the
    # dispatch shapes (window, chunk width, quantization) where it beat the
    # gather path on this hardware, and never on non-TPU backends where
    # pallas is interpreted emulation).
    # "kernel" forces the fused kernel everywhere (walks the page table
    # over the pool in place — no gather_kv_pages, no dense window);
    # "gather" forces the classic gather-then-dense chain. Both routes are
    # token-equal by contract (shared kv_len masking and null-block rules);
    # stats() counts which route each tick dispatched
    # (paged_attn_kernel_ticks / paged_attn_gather_ticks). Setting a route
    # without kv_page is a config contradiction and raises.
    paged_attn: Optional[str] = None
    # --- KV overcommit (eviction + host-RAM swap + recompute-on-fault) ---
    # kv_swap (host swap tier capacity, in BLOCKS; None = overcommit off,
    # bit-identical to the plain paged pool) turns pool exhaustion into
    # backpressure-WITH-EVICTION: park(request) takes a conversation out
    # of the decode batch while its pages stay pool-resident, and when an
    # admission (or a resume) would otherwise park on the free list, the
    # engine evicts parked sessions' PRIVATE pages — lowest QoS priority
    # first, least-recently-parked within a priority — spilling them to a
    # preallocated pinned host pool via async D2H (the gather snapshot is
    # dispatched and the host copy completes off the tick path; the tick
    # loop never blocks on a swap transfer). resume(request) swaps the
    # pages back with async H2D and remaps the slot's table row before the
    # slot re-enters the decode batch. Blocks with live decode mappings or
    # shared prefix refcounts (> 1) are never evicted. kv_swap=0 is legal:
    # no host tier — every eviction drops the pages and resume rebuilds
    # the KV through the prefill path (recompute-only overcommit).
    kv_swap: Optional[int] = None
    # Recompute-vs-swap crossover, in cached tokens: a resuming session at
    # or under this length rebuilds its KV through the (chunked) prefill
    # path even when its host pages exist — re-prefilling a short sequence
    # is cheaper than a swap-in round trip. 0 = recompute only on a fault
    # (pages dropped because the host tier was full).
    kv_swap_recompute_tokens: int = 0
    # --- observability (vtpu/obs) ----------------------------------------
    # Request-lifecycle event ring capacity (submit/admit/first-token/park/
    # evict/swap/resume/retire + per-token events), read via engine.trace:
    # spans, JSONL, Chrome trace_event dumps. 0 disables the ring (the
    # latency reservoirs behind itl/ttft percentiles stay on — they ARE
    # the stats() telemetry). Recording is host-only and lock-light; the
    # overhead contract (tests/test_obs.py) is no added fetch and no
    # added host sync; the cost in tokens/sec is not measured.
    trace_events: int = 16384
    # --- disaggregated prefill/decode (vtpu/serving/disagg) --------------
    # A DisaggConfig splits the engine into role-specialized workers over
    # the shared block pool: dedicated PrefillWorker thread(s) drain the
    # admission WaitQueue, chunk-prefill directly into slot-less pool
    # blocks (the register_prefix zero-copy discipline), deliver the first
    # token WITHOUT waiting for a decode slot, and hand the decode loop a
    # filled page-table row (one fused install, handoff_copies == 0); a
    # DisaggController dynamically re-partitions prefill vs decode
    # capacity by backlog. Requires kv_page + prefill_chunk + device
    # sampling + batched admission, no speculation. None = the
    # co-scheduled loop, bit-identical streams, zero new threads.
    disagg: Optional[Any] = None
    # --- multi-tick device-resident decode loop --------------------------
    # Run k decode ticks inside ONE compiled executable: the sampled token
    # of inner tick i feeds the dispatch of tick i+1 on device, per-slot
    # early-exit masks freeze a slot that hits its budget or eos inside the
    # loop (writes masked, output padded with a sentinel), paged scatters
    # keep walking the table with device-side t//page / t%page arithmetic,
    # and the host performs ONE batched [B, k] fetch + deliver per k ticks.
    # Admission, park/evict/swap drains, disagg handoff installs and
    # repartitioning all move to flush boundaries — the lifecycle machinery
    # is untouched, it just runs 1/k as often. This targets the regime
    # where the Python tick tax (tick_phase_ms), not FLOPs, caps tokens/sec
    # at high slot counts. None (default) and 1 are bit-identical to the
    # classic one-tick loop. Requires device sampling (a custom sample=
    # callable needs host logits every tick) — an unsatisfiable k > 1
    # raises at construction, like pipeline_decode. Composes with paged
    # pools, int8 KV, tp meshes, and disagg. Combined with spec_tokens > 0
    # the loop FUSES speculation: each inner tick drafts on device (an
    # n-gram proposal from the slot's recent-token window carried in the
    # loop state) and verifies through batched_spec_step, so one flush
    # emits up to k*(spec_tokens+1) tokens against ONE host fetch; the
    # fused stream stays token-equal to both the unfused spec path and
    # plain greedy decode (greedy verification is deterministic).
    decode_loop_k: Optional[int] = None
    # HOW DEEP each fused flush runs: None = the static decode_loop_k
    # every flush (FixedLoopPolicy — bit-identical to the classic loop);
    # otherwise a LoopPolicy (vtpu/serving/shed) picked per flush from the
    # EngineSignals pressure snapshot — small k under latency SLOs or low
    # speculation acceptance, large k under saturation. Loads like
    # shed_policy: "module:attr" string, class, or instance. Requires
    # decode_loop_k (the static k is the ceiling the policy picks within).
    loop_policy: Optional[Any] = None
    # --- failure domains (deadlines, shedding, containment, faults) ------
    # Overload shedding: bound the waiting line at this depth. 0 = off
    # (unbounded queueing, the pre-PR-12 behavior). When the line
    # overflows at a tick head, the shed policy picks waiters to shed
    # with a typed SHED_OVERLOAD terminal instead of letting every
    # submit age in an unbounded queue — the first concrete actuator of
    # the ROADMAP monitor->scheduler feedback loop.
    shed_queue_depth: int = 0
    # WHICH waiters shed under overload: None = the built-in
    # priority-then-deadline policy (vtpu/serving/shed); a
    # "module:attr" string loads a user policy program (the gpu_ext
    # pluggable-policy move), a class is instantiated, an instance is
    # used as-is.
    shed_policy: Optional[Any] = None
    # Fetch watchdog: a device->host fetch stalling past this many ms
    # trips one step of the degradation ladder (drop the k-tick device
    # loop to per-token flushes, then force the paged-attention route to
    # gather) instead of letting a wedged device transfer hang the host
    # indefinitely with no diagnostic. 0 = off. Degrading is lossless —
    # both rungs are token-equal routes by contract — but the second
    # rung pays a mid-serving re-lower of the decode executables (the
    # one sanctioned breach of the warm-executables invariant: the
    # engine is already in a failure mode).
    fetch_watchdog_ms: float = 0.0
    # Watchdog RE-ESCALATION grace window: once fetch latency has stayed
    # under fetch_watchdog_ms continuously for this many ms, the ladder
    # un-degrades one rung (2->1->0: restore the paged_attn route, then
    # decode_loop_k) — a transient device stall should not leave the
    # engine gather-routed and per-token-flushed forever. Each further
    # rung needs its own full grace window, and any stalled fetch resets
    # the clock. 0 = degradation is one-way (the PR-11 behavior).
    fetch_watchdog_recover_ms: float = 0.0
    # Disagg worker-death recovery: a request whose prefill worker died
    # mid-claim is re-queued with exponential backoff up to this many
    # retries, then terminates FAULTED. (Worker restarts themselves are
    # unbounded — the supervisor always replaces a dead worker.)
    worker_retry_limit: int = 2
    worker_retry_backoff_ms: float = 10.0
    # Deterministic fault injection (vtpu/serving/faults.FaultPlan):
    # None = no seams consult anything (one attribute check per seam).
    # A plan makes the recovery paths above reproducible — the chaos
    # soak and tests/test_faults.py drive every seam through it.
    faults: Optional[Any] = None
    # Attested-duty supplier (the ROADMAP feedback-loop field): a zero-arg
    # callable returning the device's attested busy fraction in [0, 1]
    # (or None when no reading is available). Wired from the libvtpu
    # calibration region mirror when one is present — e.g.
    # ``lambda: reader.read().devices[i].core_util_percent / 100`` over a
    # vtpu.monitor.region.RegionReader — and None otherwise. The engine
    # calls it when it builds an EngineSignals snapshot, so shed policies
    # (overload victims by device-truth busyness) and fleet route
    # policies (route away from hot chips) both consume it; a raising or
    # absent supplier degrades to duty=None, never to a dead loop.
    duty_supplier: Optional[Any] = None


def choose_kv_int8(slots: int, max_window: int) -> bool:
    """Measured kv_int8 router. INT8_AB_r05.json: measured in round 5 on
    a v5e through a rig since removed, 5 interleaved repeats per cell;
    re-measure (ROADMAP Speed #3-#5):

        batch  8 x 1024: int8 1.15x faster     batch  8 x 2048: 0.96x
        batch 32 x 1024: int8 1.22x faster     batch 32 x 2048: 1.21x

    int8 halves the cache HBM everywhere; it also WINS throughput at
    batch >= 16 or windows <= 1024, and costs ~4.4% only in the
    small-batch long-window corner. Returns whether int8 is
    free-or-better for this engine shape; deployments that want density
    in that corner can still set ModelConfig.kv_int8=True and pay the
    4.4%. (The reference's memory knob never taxes the non-capped path —
    server.go:660-673 — this router keeps the same property for the
    shapes it selects.)"""
    return slots >= 16 or max_window <= 1024


class BlockAllocator:
    """Host-side free list + refcounts over the shared KV block pool.

    Block 0 is RESERVED as the null block: unmapped page-table entries
    point at it, so out-of-window gathers and overflow writes always land
    on one shared, permanently-masked block instead of memory some other
    slot owns. The allocator therefore manages ids 1..n_blocks-1.

    Refcounts carry the zero-copy prefix contract: a freshly allocated
    block starts at refcount 1 (its owner — a slot's private page or the
    prefix registry's pinned copy); mapping a prefix block read-only into
    another slot's table is share() (+1); retire/unregister is release()
    (-1, back on the free list at zero). A block with live mappings
    survives its prefix's unregistration — exactly the lifecycle the
    refcount tests pin.

    Thread-safe: admissions allocate on the serving-loop thread while
    unregister_prefix releases on a caller thread.
    """

    def __init__(self, n_blocks: int):
        if n_blocks < 2:
            raise ValueError(
                f"kv pool needs >= 2 blocks (null + 1 usable), got {n_blocks}")
        self.n_blocks = n_blocks
        # LIFO free list: recently-freed blocks are re-handed first (their
        # pool pages are the likeliest still resident in any cache level)
        self._free = list(range(n_blocks - 1, 0, -1))
        self._ref = [0] * n_blocks
        self._min_free = n_blocks - 1  # lifetime low-water of the free list
        self._lock = threading.Lock()

    @property
    def free_blocks(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def used_hwm(self) -> int:
        """Lifetime high-water mark of simultaneously-allocated blocks —
        the pool-sizing number an operator tunes kv_pool_blocks against."""
        with self._lock:
            return self.n_blocks - 1 - self._min_free

    def alloc(self, n: int) -> Optional[list[int]]:
        """n fresh blocks at refcount 1, or None (all-or-nothing) when the
        free list can't cover the request — the caller parks the admission
        instead of partially reserving."""
        with self._lock:
            if n > len(self._free):
                return None
            out = [self._free.pop() for _ in range(n)]
            for b in out:
                self._ref[b] = 1
            if len(self._free) < self._min_free:
                self._min_free = len(self._free)
            return out

    def share(self, blocks: list[int]) -> None:
        """Map already-live blocks read-only into one more table (+1)."""
        with self._lock:
            for b in blocks:
                if self._ref[b] <= 0:
                    # a hard raise, not an assert: under python -O a
                    # silently revived block would be double-mapped into
                    # two slots' tables — cross-slot KV corruption with
                    # no diagnostic
                    raise RuntimeError(f"share of dead block {b}")
                self._ref[b] += 1

    def release(self, blocks: list[int]) -> None:
        """Drop one mapping per block; a block returns to the free list
        only when its LAST mapping (slot table or prefix registry) goes."""
        with self._lock:
            for b in blocks:
                if self._ref[b] <= 0:
                    raise RuntimeError(f"double free of block {b}")
                self._ref[b] -= 1
                if self._ref[b] == 0:
                    self._free.append(b)

    def refcount(self, block: int) -> int:
        with self._lock:
            return self._ref[block]


class WaitQueue:
    """FIFO admission queue built for park/resume churn at oversubscription
    scale: a deque plus a live-membership set, so removal from anywhere in
    the line is an O(1) tombstone (set discard) instead of the old list's
    O(n) ``remove`` scan, and the repeated ``pop(0)`` head pops stay O(1)
    amortized (tombstoned heads compact lazily). Requests compare by
    IDENTITY (dataclass eq=False keeps object.__hash__), so membership is
    identity membership — the same semantics the list version's ``is``-based
    lifecycle relied on. Iteration yields live entries in FIFO order off a
    snapshot, so callers may tombstone entries mid-iteration (the batch
    coalescing path does exactly that). Thread-safe: under disaggregation
    (vtpu/serving/disagg) prefill workers claim the head while the serving
    loop appends and the lifecycle drain tombstones — every operation takes
    the internal lock, and ``take`` makes remove-if-live atomic (the
    check-then-remove a park racing a worker claim must not split)."""

    __slots__ = ("_q", "_live", "_lock")

    def __init__(self):
        self._q: "collections.deque" = collections.deque()
        self._live: set = set()
        self._lock = threading.Lock()

    def append(self, req) -> None:
        with self._lock:
            self._q.append(req)
            self._live.add(req)

    def remove(self, req) -> None:
        """Tombstone *req* wherever it sits in the line (O(1))."""
        with self._lock:
            self._live.discard(req)

    def take(self, req) -> bool:
        """Atomically tombstone *req* IF it is still live; returns whether
        this caller won it. Two racing claimants (a prefill worker and the
        park-of-waiting lifecycle path) can never both own one request."""
        with self._lock:
            if req in self._live:
                self._live.discard(req)
                return True
            return False

    def _compact(self) -> None:
        q = self._q
        while q and q[0] not in self._live:
            q.popleft()

    def head(self):
        """The oldest live entry, or None (does not pop)."""
        with self._lock:
            self._compact()
            return self._q[0] if self._q else None

    def popleft(self):
        with self._lock:
            self._compact()
            req = self._q.popleft()
            self._live.discard(req)
            return req

    def clear(self) -> None:
        with self._lock:
            self._q.clear()
            self._live.clear()

    def __contains__(self, req) -> bool:
        with self._lock:
            return req in self._live

    def __len__(self) -> int:
        with self._lock:
            return len(self._live)

    def __iter__(self):
        # dedupe: remove-then-append (the park-waiting/resume cycle)
        # leaves a stale copy in the deque alongside the re-added live
        # one; yielding it twice would let batch coalescing admit one
        # request into two slots
        with self._lock:
            snap = list(self._q)
            live = set(self._live)
        seen = set()
        for r in snap:
            if r in live and r not in seen:
                seen.add(r)
                yield r


class Status:
    """Typed terminal status on a Request (replacing the bare
    ``cancelled: bool`` a stream used to end on silently). Exactly one is
    delivered per request, as a ``Terminal`` sentinel on the stream and as
    ``Request.status``:

    - OK             the stream ran to its natural end (budget or eos)
    - CANCELLED      the client abandoned it (cancel(), or engine stop
                     ended a still-running stream)
    - SHED_DEADLINE  the request outlived its submit(deadline_ms=) —
                     shed from the waiting line before admission, or
                     aborted at the next flush boundary mid-stream
    - SHED_OVERLOAD  the shed policy dropped it from an overflowing
                     waiting line (ServingConfig.shed_queue_depth)
    - FAULTED        a failure ended it: an exception escaped this one
                     request's dispatch/deliver path (contained), its
                     prefill worker died past the retry budget, or the
                     serving loop itself died on an exception (then every
                     stream it held ends FAULTED and submit() raises)
    """

    OK = "OK"
    CANCELLED = "CANCELLED"
    SHED_DEADLINE = "SHED_DEADLINE"
    SHED_OVERLOAD = "SHED_OVERLOAD"
    FAULTED = "FAULTED"

    ALL = (OK, CANCELLED, SHED_DEADLINE, SHED_OVERLOAD, FAULTED)


class Terminal:
    """The typed end-of-stream sentinel ``Request.finish`` delivers —
    clients iterating ``stream()`` stop on it and read ``Request.status``
    for the reason; raw ``out.get()`` consumers can type-check it."""

    __slots__ = ("status",)

    def __init__(self, status: str):
        self.status = status

    def __repr__(self) -> str:
        return f"Terminal({self.status})"


@dataclasses.dataclass(eq=False)
class Request:
    # eq=False: requests compare by IDENTITY. The engine's lifecycle checks
    # are all `is`-based, and the generated __eq__ would compare the jnp
    # token arrays — which RAISES (ambiguous truth value / broadcast error)
    # the moment a list operation like `waiting.remove(req)` scans past a
    # different request, killing the serving loop.
    tokens: Any  # [S] int32 prompt (the SUFFIX when prefix is set)
    max_new_tokens: int = 0  # 0: serving config default
    prefix: Optional[int] = None  # id from ServingEngine.register_prefix
    # QoS tier for the overcommit eviction policy: when the pool runs dry,
    # parked sessions evict lowest priority first (LRU within a tier) — a
    # priority-0 batch conversation spills to host RAM before a priority-9
    # interactive one does
    priority: int = 0
    # trace identity: assigned by submit() (engine-unique, monotonic) and
    # stamped on every lifecycle event this request emits; -1 until then.
    # rid is ENGINE-LOCAL — a migrated/rebuilt session gets a fresh rid on
    # its destination, so one stream's lifecycle spans several rids.
    rid: int = -1
    # fleet journey identity: assigned by EngineFleet.submit() and STABLE
    # across engines — the key the fleet's journey stitcher joins the
    # per-engine (engine, rid) hops under. -1 for requests submitted
    # straight to an engine (no fleet, no journey).
    jid: int = -1
    # submit() timestamp (time.monotonic_ns) — the origin every derived
    # span (queue wait, TTFT) measures from
    t_submit_ns: int = 0
    # queue-departure timestamp (claimed by admission or a prefill
    # worker); with t_submit_ns it splits TTFT into queue-wait vs
    # prefill-execution (the trace's prefill_exec reservoir); 0 until then
    t_depart_ns: int = 0
    # absolute service deadline (monotonic_ns), set by submit(deadline_ms=);
    # None = no deadline. Past it the engine sheds the request — from the
    # waiting line before admission, or at the next flush boundary
    # mid-stream — with a typed SHED_DEADLINE terminal.
    deadline_ns: Optional[int] = None
    out: "queue.Queue" = dataclasses.field(default_factory=queue.Queue)
    # the typed terminal (Status.*), set EXACTLY ONCE by finish(); None
    # while the request is still in flight
    status: Optional[str] = None
    # per-token log p under the engine's sampling distribution, appended at
    # delivery when ServingConfig.logprobs is on (device-sampled path only;
    # index i pairs with the i-th DECODED token, the prefill first token has
    # no entry)
    logprobs: list = dataclasses.field(default_factory=list)
    # generated tokens actually delivered to the client's out-queue,
    # engine-agnostic (it survives migration and engine death where
    # per-engine counters don't): incremented at every delivery path,
    # read by fleet failover to tell a started-but-unrecorded session
    # (must FAULT typed — an unstarted rebuild would replay tokens the
    # client already has) from a genuinely unstarted one (safe re-queue)
    delivered: int = 0
    # for a model that generates by blocks (several tokens of a stream
    # committed in one pass, not in their order): one integer a delivered
    # token, in the stream's order, the number of the request's pass whose
    # logits committed it; None for every other model
    trail: Optional[list] = None
    # the REQUESTED terminal (cancel()/shed set it; the engine applies it
    # at the next safe boundary) — what the `cancelled` property reads
    _abort: Optional[str] = dataclasses.field(default=None, repr=False)
    _final_lock: Any = dataclasses.field(
        default_factory=threading.Lock, repr=False)

    @property
    def cancelled(self) -> bool:
        """Whether an abort (cancel or shed) has been requested: the engine
        retires the slot / tombstones the waiter at its next boundary.
        Kept as the name every lifecycle check predates — a shed request
        rides exactly the cancel machinery, only its terminal differs."""
        return self._abort is not None

    def cancel(self) -> None:
        """Abandon the request: the engine retires its slot on the next tick
        instead of decoding tokens nobody will read. Idempotent, and safe
        against a concurrent shed or disagg worker claim — whichever abort
        lands first names the terminal."""
        if self._abort is None:
            self._abort = Status.CANCELLED

    def finish(self, status: str) -> bool:
        """Deliver the typed terminal exactly once: sets ``self.status``
        and puts ONE Terminal sentinel on the stream. Idempotent and
        thread-safe — a disagg worker retiring a claim and the serving
        loop shedding the same request can both call this; exactly one
        wins (returns True), the other is a no-op. The losers' statuses
        are dropped, never double-delivered."""
        with self._final_lock:
            if self.status is not None:
                return False
            self.status = status
        self.out.put(Terminal(status))
        return True

    def stream(self):
        """Yield generated token ids until the engine delivers the typed
        terminal (read it from ``self.status`` afterwards). A bare None is
        accepted as a legacy end-of-stream for external producers."""
        while True:
            tok = self.out.get()
            if tok is None or isinstance(tok, Terminal):
                return
            yield tok


def pad_to_chunks(tokens, n: int, c: int) -> np.ndarray:
    """Right-pad an [n] prompt with zeros to a [1, ceil(n/c)*c] chunk grid
    (the one padding contract every chunked path shares; pads above the true
    length are masked by the ragged reads and overwritten before use).
    Built on the HOST: an eager device pad, and the eager slice each chunk
    then takes from it, compile once per distinct prompt length — on the
    loop thread, mid-serving."""
    out = np.zeros((1, -(-n // c) * c), np.int32)
    out[0, :n] = np.asarray(tokens)
    return out


def lookup_draft(history: list, k: int, max_ngram: int) -> Optional[list]:
    """Prompt-lookup drafting: continue the most recent earlier occurrence
    of the longest tail n-gram (<= max_ngram) found in the history. Within
    one n, a match with a FULL k-token continuation beats a more recent
    match whose continuation runs off the end of the history — on a
    periodic stream the most recent occurrence always sits flush against
    the suffix, and continuing it yields one real token plus zero padding,
    silently capping acceptance at 2/tick no matter how deep K is. Returns
    k tokens (zero-padded when only a partial match exists anywhere) or
    None when nothing matches — the caller's tick then has nothing to
    verify for this slot.

    Host-side linear scan per tick: fine at serving context lengths (the
    scan is over python ints while the device runs the previous tick); a
    production tokenizer-aware index would replace this lookup, not the
    verify machinery.
    """
    for n in range(min(max_ngram, len(history) - 1), 0, -1):
        tail = history[-n:]
        partial = None
        for i in range(len(history) - n - 1, -1, -1):
            if history[i:i + n] == tail:
                cont = history[i + n:i + n + k]
                if len(cont) == k:
                    return cont
                if cont and partial is None:
                    partial = cont + [0] * (k - len(cont))
        if partial is not None:
            return partial
    return None


def _committed(x, placement):
    """*x* as a COMMITTED array at *placement* (a no-op for one that is)."""
    if isinstance(x, jax.Array) and x.committed:
        return x
    return jax.device_put(x, placement)


class ServingEngine:
    """Continuous-batching loop: admit -> prefill -> joint decode -> stream.

    Runs a background thread; `submit()` is thread-safe and returns a Request
    whose `.stream()` yields tokens as they are produced. The loop prefers
    admission (a waiting request fills an idle slot) and otherwise advances
    every active slot one token — the standard prefill-prioritized continuous
    batching schedule.
    """

    def __init__(
        self,
        params: Params = None,
        cfg: ModelConfig = None,
        serving: ServingConfig = ServingConfig(),
        sample: Optional[Callable[[jax.Array], int]] = None,
        mesh=None,
        model=None,
    ):
        """Pass either (params, cfg) for the default dense transformer —
        with *mesh* (a ('tp',) Mesh) weights go tensor-parallel and the KV
        cache shards its head axis — or ``model=`` with any SlotModel
        adapter (vtpu/serving/adapters.py: transformer, selective SSM).
        """
        if model is None:
            if cfg is not None and getattr(cfg, "kv_int8", False) == "auto":
                # resolve the measured router HERE, before any cache/jit
                # sees the flag ("auto" is truthy and would otherwise read
                # as int8-on everywhere): int8 where it is free-or-better
                # for this engine's shape, bf16 in the one measured
                # regression corner (see choose_kv_int8)
                cfg = dataclasses.replace(
                    cfg, kv_int8=choose_kv_int8(serving.slots, cfg.max_seq))
            model = TransformerSlotModel(
                params, cfg, mesh=mesh, kv_page=serving.kv_page,
                kv_pool_blocks=serving.kv_pool_blocks,
                paged_attn=serving.paged_attn)
        # HOME DEVICE: a mesh-less engine lives where its params were put
        # (jax.device_put(params, dev) before construction), so N replicas
        # in one process each own a chip instead of all landing on device
        # 0. Everything the engine allocates eagerly — pool state, PRNG
        # keys, admission buffers, prompt uploads — is created under
        # jax.default_device(home) on the constructing thread, the loop
        # thread and submit(); jitted steps follow the committed params.
        # A mesh engine's placement is its shardings (None here).
        mesh = getattr(model, "mesh", None)
        devs = set() if mesh is not None else {
            d for leaf in jax.tree_util.tree_leaves(model.params)
            if isinstance(leaf, jax.Array) for d in leaf.devices()}
        self._device = devs.pop() if len(devs) == 1 else None
        # COMMITTED OPERANDS. jit keys its executables on each operand's
        # sharding and on whether the operand is committed: a fresh
        # ``jnp.zeros`` or host array (uncommitted) keys differently from
        # the committed array a previous step returned. Everything a step
        # both takes and returns — pool state, PRNG keys, the admission
        # buffer, the [B] token vector — is therefore committed at this
        # placement from the start (_place, _host_tokens): the home
        # device, or replicated over the serving mesh. Otherwise the
        # executable _warm_executables compiled is not the one the second
        # tick looks up, and the same program compiles again mid-stream —
        # seconds per read window on a chip (found in PR 21 when a fleet's
        # one-second heartbeat fenced replicas stalled in that compile).
        if mesh is not None:
            self._placement = NamedSharding(mesh, PartitionSpec())
        elif self._device is not None:
            self._placement = SingleDeviceSharding(self._device)
        else:
            self._placement = None
        with self._on_device():
            self._build(model, cfg, serving, sample)

    def _refused(self, what: str) -> None:
        """Raise if the slot model refuses operation ``what`` (its
        ``refuses`` says why): one that is no ServingConfig field, so
        ``check_serving`` could not refuse it at construction."""
        why = getattr(self.model, "refuses", {}).get(what)
        if why:
            raise ValueError(
                f"{type(self.model).__name__} cannot {what}: {why}")

    def _place(self, tree):
        """Commit an engine-owned pytree (pool state, PRNG keys, buffers)
        to the placement its steps will return it at."""
        if self._placement is None:
            return tree
        return jax.tree_util.tree_map(
            lambda x: _committed(x, self._placement), tree)

    def _host_tokens(self) -> jax.Array:
        """The host's [B] next-token mirror as a step operand, committed
        like the token vector a step returns (see __init__)."""
        return self._place(np.asarray(self._tokens, np.int32))

    def _on_device(self):
        """Context placing eager allocations on the engine's home device
        (thread-local, so each thread that allocates enters its own)."""
        if self._device is None:
            return contextlib.nullcontext()
        return jax.default_device(self._device)

    def _build(self, model, cfg, serving: ServingConfig, sample) -> None:
        self.model = model
        self.params = model.params
        self.cfg = getattr(model, "cfg", cfg)
        self.serving = serving
        if hasattr(model, "check_serving"):
            # a slot model that cannot serve every option refuses here,
            # by name, instead of the engine dropping one in silence
            model.check_serving(serving)
        # speculation verifies against argmax, so it is only sound under
        # greedy sampling (the device default at temperature 0); a custom
        # sampler or temperature > 0 would make the emitted stream diverge
        # from its own non-speculative distribution, a spec tick emits
        # tokens without per-token logprobs (the verify step returns ids
        # only, so logprobs streaming forces plain ticks), and a model
        # without spec_step can't speculate at all
        # a model that generates by blocks (``block_length``): a pass of so
        # many rows a slot under a mask two-sided inside the block, the
        # slots' blocks and their commits on the device (_loop_blocks)
        self._blocks = int(getattr(model, "block_length", 0) or 0)
        if self._blocks and sample is not None:
            raise ValueError(
                f"{type(model).__name__} commits a block's rows by their "
                "confidence on the device: a custom sample= callable has no "
                "row of logits to be handed")
        self._spec_tokens = (
            serving.spec_tokens
            if sample is None and serving.temperature <= 0.0
            and not serving.logprobs and hasattr(model, "spec_step")
            else 0
        )
        # requested but dropped: say WHY (stats gauge + one-time trace
        # event below) — before this gauge the drop was silent and a
        # misconfigured engine was just mysteriously slow
        self._spec_disabled_reason: Optional[str] = None
        if serving.spec_tokens and not self._spec_tokens:
            if sample is not None:
                self._spec_disabled_reason = (
                    "custom sample= callable (verification is greedy-only)")
            elif serving.temperature > 0.0:
                self._spec_disabled_reason = (
                    f"temperature={serving.temperature} "
                    "(verification is greedy-only)")
            elif serving.logprobs:
                self._spec_disabled_reason = (
                    "logprobs streaming (verify ticks return ids only)")
            else:
                self._spec_disabled_reason = (
                    f"model adapter {type(model).__name__} has no spec_step")
        self.sample = sample or (lambda logits: int(jnp.argmax(logits)))
        b = serving.slots
        # paged KV pool: page size comes from the MODEL adapter (the single
        # source of truth — the engine constructs the default adapter from
        # ServingConfig.kv_page above; an explicitly passed model must have
        # been built paged itself)
        self._page = getattr(model, "kv_page", None)
        if serving.kv_page is not None and self._page != serving.kv_page:
            raise ValueError(
                f"ServingConfig.kv_page={serving.kv_page} but the provided "
                f"model adapter was built with kv_page={self._page}; pass "
                "kv_page/kv_pool_blocks to the adapter (or just params+cfg)")
        self._paged = self._page is not None
        # paged decode-attention route (kernel vs gather), resolved per
        # dispatched window shape by ops.decode_attn.paged_attn_route; the
        # adapter is the single source of truth exactly like kv_page (the
        # trunk closes over its attribute at trace time, so the engine's
        # per-tick route counters must read the same value)
        self._paged_attn = getattr(model, "paged_attn", None)
        self._select_topk = getattr(model, "attn_select_topk", None)
        self._select_dense = getattr(model, "attn_select_dense_len", None)
        self._latent_walk = getattr(model, "walks_latent_plane", False)
        self._chunk_expands = getattr(model, "chunk_attn_expands", None)
        self._chunk_keys = getattr(model, "chunk_keys_attended", None)
        self._experts_grouped = getattr(model, "experts_grouped", None)
        # bytes of recurrent rows the state holds beside its pages (a
        # family with state-space layers says; 0 for every other)
        self._recurrent_bytes = (
            model.recurrent_state_bytes(b)
            if hasattr(model, "recurrent_state_bytes") else 0)
        self._ssm_in_kernel = getattr(model, "ssm_step_in_kernel", None)
        # rows of the ring a window layer keeps a slot (a family with
        # window layers says; None for every other)
        self._window_ring = getattr(model, "window_ring", None)
        if (serving.paged_attn is not None
                and self._paged_attn != serving.paged_attn):
            raise ValueError(
                f"ServingConfig.paged_attn={serving.paged_attn!r} but the "
                f"provided model adapter was built with "
                f"paged_attn={self._paged_attn!r}; pass paged_attn to the "
                "adapter (or just params+cfg)")
        self.state = self._place(model.init_state(b))
        # Device-side sampling is the default: the sampler is fused into the
        # jitted decode step (adapters.sampled_decode_step), so a tick's
        # device->host transfer is [B] int32 tokens (+ optional [B] f32
        # logprobs), not [B, vocab] f32 logits. A custom ``sample=``
        # callable keeps the old host path (full logits per tick) — and
        # disables pipelining, exactly as custom samplers disable
        # speculation: the host must see logits before the next dispatch.
        self._device_sampling = sample is None
        if not self._device_sampling and serving.logprobs:
            # the host fallback never computes log-probabilities (the
            # callable returns a bare token id); silently streaming empty
            # Request.logprobs would break the token/logprob pairing the
            # field promises
            raise ValueError(
                "logprobs=True requires the device sampler; it is not "
                "available with a custom sample= callable")
        # the state is donated through every step jit: the engine is its
        # only holder and reassigns self.state from the result, so XLA can
        # alias input to output instead of copying the pool state per call
        if self._blocks:
            # the pass is this family's decode step, under the same name
            # (the program ``jit_step`` of a trace; what a rehearsal of
            # the decode step compiles); the block's opening rides an
            # admission's end as the table row rides its start
            self._decode = None
            self._decode_sampled = jax.jit(
                block_pass_step(model), static_argnames=("kv_bucket",),
                donate_argnums=(1,))
            self._open_block = jax.jit(
                model.open_block, donate_argnums=(0,))
            self._rng = None
        elif self._device_sampling:
            self._decode = None
            self._decode_sampled = jax.jit(
                sampled_decode_step(
                    model, serving.temperature, serving.top_k,
                    serving.top_p, serving.logprobs),
                static_argnames=("kv_bucket", "unroll"),
                donate_argnums=(1, 4),  # state + per-slot PRNG keys
            )
            self._rng = self._place(jax.random.split(
                jax.random.key(serving.sampling_seed), b))
            # admission-time first tokens draw from their own stream (one
            # split per admission, host-side — admissions are rare next to
            # ticks); greedy never touches it
            self._admit_key = jax.random.key(serving.sampling_seed + 1)
            self._sample1 = jax.jit(
                lambda logits, key: transformer.sample_tokens(
                    logits[None], key[None],
                    temperature=serving.temperature, top_k=serving.top_k,
                    top_p=serving.top_p)[0][0])
        else:
            self._decode = jax.jit(
                model.decode_step, static_argnames=("kv_bucket", "unroll"),
                donate_argnums=(1,),
            )
            self._decode_sampled = None
            self._rng = None
        pipeline = serving.pipeline_decode
        # pipelining needs device-resident next tokens (device sampling) and
        # no speculation (a spec tick builds its draft from host history, so
        # it must observe the previous token before dispatching). auto (None)
        # downgrades silently; an EXPLICIT True that cannot be honored is a
        # config contradiction and raises, like logprobs + custom sampler
        if pipeline and (not self._device_sampling or self._spec_tokens):
            raise ValueError(
                "pipeline_decode=True requires device sampling (no custom "
                "sample= callable) and no active speculation")
        if pipeline is None:
            pipeline = True
        self._pipeline = bool(
            pipeline and self._device_sampling and not self._spec_tokens)
        # --- multi-tick device-resident decode loop (decode_loop_k) ------
        # Validated HERE, next to the paged_attn/pipeline contradiction
        # checks: every rejection names the interaction precisely. k is
        # compatible with paged pools, int8 KV, tp meshes and disagg (the
        # loop body is the unchanged shared trunk); it is rejected only
        # for the one feature that structurally needs host logits every
        # tick. Active speculation FUSES instead: the draft moves on
        # device (the slot's recent-token window rides the loop state), so
        # the old "verify needs host history every tick" objection no
        # longer holds — draft+verify run as the fori_loop body.
        loop_k = serving.decode_loop_k
        if loop_k is not None and loop_k < 1:
            raise ValueError(
                f"decode_loop_k must be >= 1 (or None), got {loop_k}")
        if loop_k is not None and loop_k > 1:
            if not self._device_sampling:
                raise ValueError(
                    f"decode_loop_k={loop_k} requires device sampling: a "
                    "custom sample= callable consumes host logits every "
                    "tick, which is exactly the per-token host round trip "
                    "the device loop removes — drop sample= or set "
                    "decode_loop_k=None")
        # k = 1 resolves to the classic loop (bit-identical to None by
        # construction, pinned in tests); stats() still reports the
        # resolved decode_loop_k so dashboards see what was asked for
        self._loop_k = loop_k if loop_k is not None and loop_k > 1 else None
        if self._loop_k:
            self._decode_loop = jax.jit(
                multi_tick_decode_step(
                    model, serving.temperature, serving.top_k,
                    serving.top_p, serving.logprobs, self._loop_k,
                    serving.eos_token),
                static_argnames=("kv_bucket", "unroll"),
                donate_argnums=(1, 4),  # state + per-slot PRNG keys
            )
        else:
            self._decode_loop = None
        # --- fused device-side speculation (loop_k x spec_tokens) --------
        # Both knobs set: each inner tick of the device loop drafts from
        # the slot's recent-token window (carried in the loop state) and
        # verifies through batched_spec_step — ONE [B, k, K+1] fetch per
        # flush, up to k*(K+1) tokens against it. The cooloff fallback
        # (acceptance EMA below spec_min_mean) runs the PLAIN _decode_loop
        # executable, so speculation disengages without leaving the fused
        # loop's flush discipline.
        self._fused_spec = bool(self._loop_k and self._spec_tokens)
        if serving.loop_policy is not None and not self._fused_spec:
            raise ValueError(
                "loop_policy requires the fused device loop "
                "(decode_loop_k > 1 AND active spec_tokens): the policy "
                "sizes the fused flush window — got "
                f"decode_loop_k={serving.decode_loop_k}, "
                f"spec_tokens={serving.spec_tokens}"
                + (f" (speculation disabled: {self._spec_disabled_reason})"
                   if self._spec_disabled_reason else ""))
        # resolved HERE like shed_policy: a bad "module:attr" string or a
        # policy without pick_k fails the constructor, never the loop
        self._loop_policy = (
            load_loop_policy(serving.loop_policy)
            if serving.loop_policy is not None else None)
        if self._fused_spec:
            # draft window: enough history for the deepest n-gram match
            # plus the continuation it proposes; a fixed small width keeps
            # the loop-state carry a few hundred bytes per slot
            self._hist_window = max(
                32, serving.spec_ngram * 2 + serving.spec_tokens + 2)
            self._decode_fused = jax.jit(
                fused_spec_decode_step(
                    model, self._loop_k, self._spec_tokens,
                    serving.eos_token, serving.spec_ngram),
                static_argnames=("kv_bucket", "unroll"),
                donate_argnums=(1,),  # state (greedy: no keys, no logprobs)
            )
        else:
            self._hist_window = 0
            self._decode_fused = None
        # monotonic_ns stamp of the last flush delivery: the floor of the
        # next flush's interpolated per-token timestamps, so a pipelined
        # flush (dispatched before the previous delivery) can never
        # synthesize token events earlier than tokens already delivered
        self._last_flush_ns = 0
        # the single-tick verify executable serves the HOST-drafted sync
        # path only; a fused engine never dispatches it (its verify trunk
        # lives inside _decode_fused), so don't build or warm it there
        self._spec = jax.jit(
            model.spec_step, static_argnames=("kv_bucket", "unroll"),
            donate_argnums=(1,),
        ) if self._spec_tokens and not self._fused_spec else None
        # a model that generates by blocks admits in chunks alone
        self._prefill = None if self._blocks else jax.jit(
            model.prefill_into_slot, donate_argnums=(1,))
        # batched async admission: device sampling supplies the fused first-
        # token sampler, and speculation needs the first token ON THE HOST
        # (draft history) — same gating shape as pipelining
        async_adm = serving.async_admission
        can_async = (
            self._device_sampling and not self._spec_tokens
            and not self._blocks and hasattr(model, "prefill_into_slots"))
        if async_adm and not can_async:
            raise ValueError(
                "async_admission=True requires device sampling (no custom "
                "sample= callable), no active speculation, and a model with "
                "prefill_into_slots")
        self._async_admission = can_async if async_adm is None else bool(async_adm)
        # warmed admission batch sizes: capped at the slot pool (an [N]
        # batch needs N free slots), 1 always present so a lone waiter
        # never waits for company
        self._admit_sizes = tuple(sorted(
            {n for n in serving.prefill_batch_sizes if 1 <= n <= b} | {1}))
        if self._async_admission:
            self._admit_step = jax.jit(
                batched_admission_step(
                    model, serving.temperature, serving.top_k, serving.top_p),
                donate_argnums=(1, 2),  # state + first-token buffer
            )
            # device-resident first token for the chunked/prefix admission
            # tails (a single [vocab] logits row, not a batch)
            self._argmax1 = jax.jit(
                lambda l: jnp.argmax(l).astype(jnp.int32))
            # [B] device buffer of pending admission first tokens plus a
            # host mask of which slots hold one: the decode dispatch merges
            # them in with ONE static-shape jitted where — never a
            # per-batch-size scatter whose first-use XLA compile would
            # stall the loop mid-serving (measured: 100-450 ms per eager
            # host-op shape on CPU — the exact stall class this admission
            # path exists to remove)
            self._admit_buf = self._place(jnp.zeros((b,), jnp.int32))
            self._set_buf1 = jax.jit(
                lambda buf, i, v: buf.at[i].set(v), donate_argnums=(0,))
        else:
            self._admit_step = None
            self._argmax1 = None
            self._admit_buf = None
        self._admit_mask = [False] * b
        # static-shape [B] token merge, shared by the admission override and
        # the pipelined loop's fed-merge (warmed — see above on compiles)
        self._merge_tokens = jax.jit(
            lambda mask, a, base: jnp.where(mask, a, base))
        chunk = serving.prefill_chunk
        if chunk and not hasattr(model, "prefill_chunk_into_slot"):
            chunk = None  # model family without a chunkable trunk (SSM)
        if chunk:
            ctx = model.max_context
            if ctx and ctx % chunk:
                # a final chunk straddling the context wall would clamp its
                # scatter start and corrupt earlier positions
                raise ValueError(
                    f"prefill_chunk {chunk} must divide max_context {ctx}")
            self._prefill_chunk = jax.jit(
                model.prefill_chunk_into_slot,
                static_argnames=("kv_bucket", "unroll"), donate_argnums=(1,))
        else:
            self._prefill_chunk = None
        self._chunk = chunk
        # decode read-buckets: one compiled executable per size, chosen per
        # tick from the longest LIVE sequence (decode bandwidth scales with
        # the read window, not the context cap)
        ctx = model.max_context
        # a slot model may state its read windows (``read_windows``): one
        # whose context is far longer than any whole-prompt admission needs
        # windows where it has no prefill bucket
        windows = getattr(model, "read_windows", None) or serving.prefill_buckets
        self._kv_buckets = tuple(
            sorted({min(bkt, ctx) for bkt in windows} | {ctx})
        ) if ctx else (0,)
        # a model with a KV cache walks its layers unrolled (the static layer
        # index lets XLA fuse the window read into attention) and reads
        # through the bounded windows above; one without (the SSM) does
        # neither
        self._unroll = model.supports_kv_buckets
        # prefill buckets past the context cap are unusable (out-of-range
        # positions); sanitize once so every consumer agrees
        self._prefill_buckets = () if self._blocks else tuple(
            bkt for bkt in serving.prefill_buckets if ctx is None or bkt <= ctx
        )
        if not self._prefill_buckets and not self._blocks:
            raise ValueError(
                f"no prefill bucket fits max_context={ctx}: "
                f"{serving.prefill_buckets}"
            )
        budget = serving.prefill_budget
        if budget:
            # every admissible unit of work must fit one tick's budget: a
            # single prompt of the LARGEST bucket (admission is per whole
            # bucket — a prompt it can never afford would head-of-line
            # block the queue until the engine drained fully idle) and a
            # prefill chunk
            floor = max(self._prefill_buckets, default=0)
            if self._chunk:
                floor = max(floor, self._chunk)
            if budget < floor:
                raise ValueError(
                    f"prefill_budget {budget} is below the largest "
                    f"admission unit {floor} (largest bucket"
                    + (f" / prefill chunk {self._chunk}" if self._chunk else "")
                    + ")")
        # chunked admissions in flight at once: as many as one tick's budget
        # advances (each takes one chunk a tick). More would hold a slot and
        # its pages and rotate for the same chunks, so every first token
        # would come late; the rest wait their turn in the queue. 0 = no cap
        self._chunk_lanes = budget // self._chunk if self._chunk else 0
        # --- paged pool bookkeeping (host side of the block pool) --------
        if self._paged:
            page = self._page
            for bkt in self._prefill_buckets:
                if bkt % page:
                    raise ValueError(
                        f"kv_page {page} must divide every prefill bucket "
                        f"(got {bkt}): admission scatters and decode read "
                        "windows are page-granular")
            # total blocks INCLUDING the reserved null block 0, resolved by
            # the adapter when it allocated the pool state
            self._n_blocks = model.n_kv_blocks
            self._max_pages = ctx // page
            self._alloc = BlockAllocator(self._n_blocks)
            # blocks currently mapped by each slot's table row (shared
            # prefix blocks included — release() decrefs, so a shared
            # block survives until its last mapping retires)
            self._slot_blocks: list[list[int]] = [[] for _ in range(b)]
            # one fused device op per admission: table row + base length
            # (prefix installs set len=base here so an empty-suffix
            # admission needs no separate device write). Compiled AT INIT
            # on this thread — never first-use inside the loop.
            self._set_table_row = jax.jit(
                lambda state, slot, row, base: {
                    **state,
                    "table": state["table"].at[slot].set(row),
                    "len": state["len"].at[slot].set(base),
                }, donate_argnums=(0,))
            # copy-on-write for a prefix's partial boundary block: one
            # [L, page, ...] block copy per plane, src -> dst
            planes = tuple(
                key for key in getattr(
                    model, "pool_planes", ("k", "v", "k_scale", "v_scale"))
                if key in self.state)

            def copy_block(state, src, dst):
                out = dict(state)
                for key in planes:
                    out[key] = state[key].at[:, dst].set(state[key][:, src])
                return out

            self._copy_block = jax.jit(copy_block, donate_argnums=(0,))
            # prefix builds run ON THE LOOP THREAD (they prefill into pool
            # blocks, mutating the shared device state a caller thread
            # must never race): register_prefix parks a work item here and
            # blocks on its event; _tick_head drains it between ticks
            self._prefix_work: "queue.Queue[dict]" = queue.Queue()
        else:
            self._alloc = None
            self._slot_blocks = [[] for _ in range(b)]
            self._prefix_work = None
        # leading blocks of each slot's table row that are SHARED prefix
        # mappings (refcounts held elsewhere too) — the split the overcommit
        # eviction policy needs: only a slot's private tail is ever swapped
        self._slot_shared = [0] * b
        # which prefix those shares came from, as (content pid, prefix
        # length) — follows the blocks through park/resume so a fleet
        # directory's refcounts and a failover rebuild's prefix-reuse can
        # name the prefix a session rides (vtpu/serving/prefixdir)
        self._slot_pid: list[Optional[tuple[str, int]]] = [None] * b
        # --- KV overcommit: eviction + host swap tier + park/resume ------
        self._swap_enabled = serving.kv_swap is not None
        if self._swap_enabled and not self._paged:
            raise ValueError(
                "kv_swap requires the paged pool (set kv_page): the dense "
                "ring has no block granularity to evict or swap")
        # park/resume commands from client threads, drained by the loop;
        # _wake lets an idle loop block on BOTH queues at once (submit and
        # park/resume set it after enqueueing) — no busy-poll while parked
        self._lifecycle_q: "queue.Queue[tuple[str, Request]]" = queue.Queue()
        self._wake = threading.Event()
        self._want_park: set = set()
        # park commands whose request was found nowhere for one pass (see
        # _process_lifecycle: may still be in _pending — grace of one tick)
        self._park_unseen: set = set()
        self._want_resume: list[Request] = []
        # parked sessions, insertion-ordered (= park order, the LRU axis);
        # each entry owns its blocks/host pages until resume or cancel
        self._parked: "collections.OrderedDict[Request, dict]" = (
            collections.OrderedDict())
        self._park_seq = 0
        self._swap_pending: list[dict] = []  # entries with in-flight D2H
        if self._swap_enabled:
            self._swap_stage = SWAP_STAGE_BLOCKS
            self._swap_planes = tuple(
                key for key in ("k", "v", "k_scale", "v_scale")
                if key in self.state)
            # the pinned host pool: one [L, kv_swap, page, ...] plane per
            # KV plane, preallocated ONCE (numpy host memory stands in for
            # pinned buffers on the CPU rig) + a host-block free list
            self._swap_host_blocks = int(serving.kv_swap)
            self._host_pool = {
                key: np.zeros(
                    (self.state[key].shape[0], self._swap_host_blocks)
                    + tuple(self.state[key].shape[2:]),
                    self.state[key].dtype)
                for key in self._swap_planes
            } if self._swap_host_blocks else {}
            self._host_free = list(range(self._swap_host_blocks))
            # bytes one pool block holds across layers/planes (global — the
            # unit swap_out_bytes/swap_in_bytes are denominated in)
            self._block_bytes = sum(
                int(np.prod((self.state[key].shape[0],)
                            + tuple(self.state[key].shape[2:])))
                * self.state[key].dtype.itemsize
                for key in self._swap_planes)
            # compile-once staging ops: gather W blocks into a contiguous
            # snapshot (the async-D2H source) / scatter W staged blocks
            # back into the pool (the async-H2D sink); ids pad with the
            # null block 0, whose reads are always masked and whose writes
            # are the established junk sink. Compiled for EVERY swap tier
            # including kv_swap=0 (which can never spill or swap in): the
            # cross-engine migration path (vtpu/serving/migrate) snapshots
            # and installs block payloads through this same staging pair,
            # host-tier or not.
            self._swap_gather = jax.jit(swap_page_gather(model))
            self._swap_scatter = jax.jit(
                swap_page_scatter(model), donate_argnums=(0,))
            mesh = getattr(model, "mesh", None)
            if mesh is not None:
                # H2D staging lands PRE-SHARDED on the head axis, so the
                # upload is the per-chip shard transfer, never a
                # replicate-then-reshard round trip
                self._stage_shardings = {
                    key: head_sharding(
                        mesh, self.state[key].ndim,
                        -2 if key in ("k", "v") else -1)
                    for key in self._swap_planes
                }
            else:
                self._stage_shardings = {}
        else:
            self._swap_stage = 0
            self._swap_planes = ()
            self._swap_host_blocks = 0
            self._host_pool = {}
            self._host_free = []
            self._block_bytes = 0
            self._swap_gather = None
            self._swap_scatter = None
            self._stage_shardings = {}
        self._pending: "queue.Queue[Request]" = queue.Queue()
        # requests pulled off the queue but not yet admitted (budget-
        # deferred or waiting for a free slot); FIFO except that same-bucket
        # prompts coalesce into one batched prefill dispatch. WaitQueue:
        # O(1) tombstone removal, so park/resume churn at oversubscription
        # scale never turns admission quadratic.
        self._waiting: WaitQueue = WaitQueue()
        self._slot_req: list[Optional[Request]] = [None] * b
        self._slot_budget = [0] * b
        self._tokens = [0] * b  # next token per slot (host-side)
        self._slot_len = [0] * b  # host mirror of cache["len"] per LIVE slot
        # per-slot token history (prompt + emitted) feeding prompt-lookup
        # drafts; only maintained while speculation is on
        self._history: list[list[int]] = [[] for _ in range(b)]
        # whether the slot's history is an EXACT cache-contents mirror: a
        # prefix unregistered in the admission window loses its tokens, so
        # that slot pads placeholders (swap still works — content-based)
        # but must never be rebuilt from history (recompute-on-fault off)
        self._slot_hist_exact = [True] * b
        # slots mid-chunked-admission: slot -> {req, padded, n, off, base};
        # the loop advances one chunk per iteration between decode ticks
        self._admitting: dict[int, dict] = {}
        # rotating start index for chunk advancement under a prefill budget,
        # so the same admitting slot never systematically loses the budget
        self._adm_rr = 0
        # async admission fetch manifest: each entry holds a device token
        # array and the (slot, req, row-index) rows the next batched fetch
        # delivers (the dispatch-side copies live in _admit_buf/_admit_mask)
        self._pending_firsts: list[dict] = []
        # slots with a dispatched-but-undelivered tick (pipelined loop
        # lookahead): a park must wait until its slot leaves this set, or
        # the in-flight token would be lost and the saved length would lag
        # the device
        self._inflight_slots: set = set()
        # adaptive-speculation state: the probe EMA starts a LITTLE above
        # breakeven — a fresh engine (or a re-probe) gets a handful of
        # ticks to prove itself, then shuts back off; resetting to the
        # optimistic maximum would spend ~30% of ticks speculating at a
        # loss forever on persistently low-acceptance traffic
        self._spec_ema = self._spec_probe_ema()
        self._spec_cooloff = 0
        # observability counters (read via stats())
        self._stats = {"generated_tokens": 0, "decode_ticks": 0,
                       "spec_ticks": 0, "spec_slot_ticks": 0,
                       "spec_emitted": 0,
                       "spec_emitted_hist": [0] * (serving.spec_tokens + 2),
                       "prefill_chunks": 0, "admissions": 0,
                       # true prompt tokens sent to the device by admission
                       # batches and chunks (pads not counted)
                       "prefill_tokens": 0,
                       # per-tick transfer accounting: every loop
                       # device->host read goes through _fetch, which counts
                       # calls and payload bytes — the proof behind the
                       # "one device_get per tick" contract. tick_fetches
                       # covers tick deliveries (admission first tokens
                       # piggyback on them for free); admission_fetches are
                       # the standalone batched first-token fetches an IDLE
                       # engine performs; admission_syncs counts the legacy
                       # path's blocking per-admission host syncs — ZERO on
                       # the batched-async path, the tentpole's contract
                       "device_gets": 0, "bytes_fetched": 0,
                       "tick_fetches": 0, "admission_fetches": 0,
                       "admission_syncs": 0,
                       # prefill_batch_hist[n]: bucketed prefill dispatches
                       # of batch size n (index 0 unused)
                       "prefill_batch_hist": [0] * (max(
                           self._admit_sizes) + 1),
                       "pipelined_ticks": 0,
                       # multi-tick device loop: loop_flushes counts k-tick
                       # dispatches (decode_ticks counts INNER ticks, k per
                       # flush, so FLOP/byte accounting stays per-tick
                       # honest); loop_early_exits counts slots that froze
                       # inside a flush (budget wall or eos) before tick k
                       "loop_flushes": 0, "loop_early_exits": 0,
                       # fused-speculation flushes (subset of loop_flushes
                       # when the draft+verify body dispatched instead of
                       # the plain loop — cooloff fallbacks are the
                       # difference) and the per-flush k the LoopPolicy
                       # actually picked, as a histogram index k
                       "fused_flushes": 0,
                       "fused_k_hist": [0] * ((self._loop_k or 0) + 1),
                       # KV-memory data plane. kv_bucket_hist: read-window
                       # bucket -> dispatched ticks — on the DENSE path
                       # this is the global longest-live-sequence read tax
                       # made visible (one long sequence drags every
                       # slot's window up). pool_blocked_admissions:
                       # admissions deferred by pool exhaustion
                       # (backpressure events, not failures).
                       # prefix_install_copies: dense full-prefix device
                       # copies at admission; prefix_blocks_shared:
                       # pool blocks mapped read-only at admission
                       # (zero-copy reuse); prefix_cow_copies: partial
                       # boundary blocks copied on write. read_pages_*:
                       # per-tick gathered LIVE pages vs window pages —
                       # the paged read's per-slot padding dedupes onto
                       # the null block, so live/window is the fraction
                       # of the window streaming distinct HBM lines.
                       "kv_bucket_hist": {},
                       # paged decode-attention routing: ticks dispatched
                       # through the fused table-walking kernel vs the
                       # gather-then-dense chain. The route is a static
                       # per-window-shape property (paged_attn_route), so
                       # these mirror exactly what the compiled executables
                       # did — the bench's kernel-vs-gather arms gate on
                       # them, and auto routing off-TPU must keep
                       # kernel_ticks at 0 (interpreted pallas never wins).
                       "paged_attn_kernel_ticks": 0,
                       "paged_attn_gather_ticks": 0,
                       "pool_blocked_admissions": 0,
                       "prefix_install_copies": 0,
                       "prefix_blocks_shared": 0,
                       "prefix_cow_copies": 0,
                       # prefix-cache outcome counters (the fleet
                       # directory's ground truth): a hit is an admission
                       # that reused registered prefix KV (share on paged,
                       # install on dense); a miss is a prefix-referencing
                       # admission whose registration vanished mid-flight.
                       # prefix_exports/prefix_tier_installs count the
                       # staged D2H/H2D movement of whole prefixes between
                       # engines and the fleet host tier;
                       # failover_prefix_reuses counts rebuilds that
                       # shared a resident prefix instead of recomputing
                       # its positions (vtpu/serving/prefixdir).
                       "prefix_hits": 0, "prefix_misses": 0,
                       "prefix_exports": 0, "prefix_tier_installs": 0,
                       "failover_prefix_reuses": 0,
                       "read_pages_live": 0, "read_pages_window": 0,
                       "read_pages_hist": {},
                       # a slot model whose attention reads a selection
                       # (``attn_select_topk``): cached tokens visible to
                       # the dispatched slots, summed over decode ticks,
                       # and how many of them attention read (the smaller
                       # of a slot's length and the selection's size); 0
                       # for every other model
                       "attn_visible_tokens": 0, "attn_selected_tokens": 0,
                       # ... and the dispatched slot-ticks of such a model
                       # whose query selected, and those that attended all
                       # they saw because that was at most the model's
                       # ``attn_select_dense_len`` tokens (a model that
                       # states none selects always)
                       "select_rows": 0, "select_rows_dense": 0,
                       # ... and the prefill chunks dispatched for such a
                       # model, with those of them whose length put their
                       # attention in the expanded form (the model's
                       # ``chunk_attn_expands``: keys and values made from
                       # the window's latents once a layer)
                       "chunk_attn_launches": 0, "chunk_attn_expanded": 0,
                       # ... those whose program holds the chunk kernel (the
                       # model's ``chunk_keys_attended``), and over all of
                       # them the window positions at or before a chunk's
                       # last, and the positions its program multiplies
                       # (the kernel: up to the chunk's end rounded up to a
                       # block; XLA's code: the whole read window)
                       "chunk_attn_kernel": 0, "chunk_keys_live": 0,
                       "chunk_keys_attended": 0,
                       # a slot model that holds experts
                       # (``experts_grouped``): the rows of every launch
                       # (a step's slots, an admission's padded batch, a
                       # chunk), and those of launches whose shape put
                       # each held expert over its own rows alone (the
                       # model's rule, read off the launch's static shape);
                       # 0 for every other model
                       "expert_rows": 0, "expert_rows_grouped": 0,
                       # a slot model whose decode step walks a latent
                       # plane (``walks_latent_plane``): cached tokens the
                       # dispatched slots could see, summed over decode
                       # ticks, and the rows the walk copied for them (a
                       # slot's live pages whole; one page for a slot that
                       # was not dispatched); 0 for every other model
                       "latent_rows_live": 0, "latent_rows_walked": 0,
                       # a slot model that keeps recurrent rows beside its
                       # pages (``recurrent_state_bytes``): slot rows a
                       # decode tick's state update touched (every slot's,
                       # the step's shape), and those of them that belonged
                       # to a dispatched slot; 0 for every other model.
                       # ssm_kernel_ticks: the decode ticks whose step was
                       # traced with the state kernel (the model's
                       # ``ssm_step_in_kernel``, the call the trace makes:
                       # every one on a TPU, none elsewhere)
                       "ssm_rows_stepped": 0, "ssm_rows_live": 0,
                       "ssm_kernel_ticks": 0,
                       # a slot model whose window layers keep a ring of
                       # the last positions a slot (``window_ring``): ring
                       # positions the dispatched slots' window attention
                       # read (a layer), summed over decode ticks (the
                       # smaller of a slot's length and the ring), beside
                       # attn_visible_tokens above, which for such a model
                       # is what its full layers' walk visits; 0 for every
                       # other model
                       "window_rows_read": 0,
                       # a slot model that generates by blocks
                       # (``block_length``), read off each pass's fetched
                       # result: passes a slot took (denoising and
                       # writing), those of them that wrote the clean
                       # block's keys and values, the rows of the slots
                       # that took a pass, those of them that were masked
                       # and could answer, and the rows committed; 0 for
                       # every other model
                       "block_slot_passes": 0, "block_write_passes": 0,
                       "block_rows_dispatched": 0, "block_rows_masked": 0,
                       "block_tokens_committed": 0,
                       # KV overcommit: parks/resumes are lifecycle events;
                       # evicted_blocks counts pool blocks reclaimed from
                       # parked sessions; swap_out/in_bytes are the D2H/H2D
                       # traffic through the host tier; swap_faults counts
                       # resumes whose pages were NOT pool-resident (the
                       # restore had to swap in or recompute);
                       # fault_recomputes is the subset rebuilt through the
                       # prefill path (pages dropped, or under the
                       # recompute crossover)
                       # pool_blocked_resumes: per-tick retries of a
                       # resume the pool could not yet cover — kept apart
                       # from pool_blocked_admissions so resume
                       # backpressure never reads as admission blocking
                       "parks": 0, "resumes": 0, "evicted_blocks": 0,
                       "swap_out_bytes": 0, "swap_in_bytes": 0,
                       "swap_faults": 0, "fault_recomputes": 0,
                       "pool_blocked_resumes": 0,
                       # failure domains: typed sheds (deadline misses /
                       # overload-policy drops), requests a contained
                       # failure terminated (FAULTED), dead prefill
                       # workers the supervisor replaced, and watchdog
                       # degradation-ladder steps. faults_injected (the
                       # FaultPlan's own count) is added by stats().
                       "shed_deadline": 0, "shed_overload": 0,
                       "faulted_requests": 0, "worker_restarts": 0,
                       "watchdog_degrades": 0,
                       # watchdog ladder re-escalation: rungs restored
                       # after the recovery grace window
                       # (fetch_watchdog_recover_ms)
                       "watchdog_recoveries": 0,
                       # live session migration (vtpu/serving/migrate):
                       # sessions extracted from / installed into this
                       # engine, the D2H/H2D payload traffic, device
                       # copies the migration path performed beyond the
                       # staging pair (contract: 0 — the handoff_copies
                       # bar applied across engines), sessions installed
                       # payload-less that will rebuild via the
                       # recompute-on-fault prefill path, and migrations
                       # that could neither transfer nor rebuild
                       "migrations_out": 0, "migrations_in": 0,
                       "migrate_out_bytes": 0, "migrate_in_bytes": 0,
                       "migration_copies": 0, "migrate_recomputes": 0,
                       "migrate_failures": 0}
        # per-slot token history (prompt + emitted) is maintained for
        # speculation drafts AND for overcommit (a parked session's cache
        # contents must be recomputable from tokens when its pages fault)
        self._track_history = bool(self._spec_tokens or self._swap_enabled)
        # per-slot inter-token latency: timestamp of the last delivery per
        # slot (a slot's FIRST token records no gap — that interval is
        # TTFT). The gap/TTFT/queue-wait reservoirs themselves live in the
        # trace substrate below: stats() percentiles are a VIEW over it.
        self._itl_last: list[Optional[float]] = [None] * b
        # observability substrate (vtpu/obs): the request-lifecycle event
        # ring + latency reservoirs/histograms, and the tick-phase
        # profiler that attributes the loop thread's time (admission head,
        # dispatch, fetch, deliver, swap drain, idle wait). Host-only by
        # construction: nothing here can add a device sync.
        self.trace = RequestTrace(capacity=serving.trace_events)
        if self._spec_disabled_reason is not None:
            # one-time event (val = the requested draft length): the trace
            # dump shows WHY the configured speculation never ran
            self.trace.record("spec_disabled", -1, -1, serving.spec_tokens)
        self._prof = TickProfiler(
            tick=self._tick_count,
            prefill=lambda: self._stats["prefill_tokens"])
        # whether this engine holds the process's pause watch
        # (vtpu/obs/pauses.py: start() acquires it, stop() releases it)
        self._watching = False
        self._warmup = WarmupClock()
        self._req_ctr = itertools.count()
        # registered prompt prefixes: id -> {tokens, buffers, len, pad,
        # last_logits}; install is a device copy, suffixes chunk from the
        # prefix offset
        self._prefixes: dict[int, dict] = {}
        self._prefix_lock = threading.Lock()
        self._next_prefix_id = 0
        # content-addressed index over the registry: prefix_id(tokens) ->
        # local id, so a fleet-tier install is idempotent and a failover
        # rebuild can find "the same prompt" without the dead engine's ids
        self._pid_index: dict[str, int] = {}
        # fleet seam (vtpu/serving/prefixdir): when set, register/
        # unregister/hit/release events report to the owning fleet's
        # PrefixDirectory; unset (the default) costs one None check
        self._prefix_listener = None
        # per padded-prefix-length COMPILED install executables, built at
        # register_prefix time on the caller's thread — a first-use compile
        # inside the serving loop would stall every live stream (the
        # _warm_executables invariant)
        self._install_jits: dict[int, Any] = {}
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # --- disaggregated prefill/decode (vtpu/serving/disagg) ----------
        # The state mutex serializes the ONLY two writers the donated
        # device state can ever have: the serving loop's tick-head +
        # dispatch section and a prefill worker's chunk dispatches. With
        # disagg off it is never taken — the loop's hot path is untouched.
        self._state_mu = threading.Lock()
        if serving.disagg is not None:
            from vtpu.serving.disagg import DisaggConfig, DisaggRuntime

            if not isinstance(serving.disagg, DisaggConfig):
                raise ValueError(
                    "ServingConfig.disagg must be a DisaggConfig, got "
                    f"{type(serving.disagg).__name__}")
            if not self._paged:
                raise ValueError(
                    "disagg requires the paged pool (set kv_page): prefill "
                    "workers build KV into slot-less pool blocks")
            if not self._chunk:
                raise ValueError(
                    "disagg requires prefill_chunk: the worker prefills "
                    "through the explicit-block_ids chunked path")
            if not self._device_sampling or self._spec_tokens:
                raise ValueError(
                    "disagg requires device sampling (no custom sample= "
                    "callable) and no active speculation")
            if not self._async_admission:
                raise ValueError(
                    "disagg requires batched/async admission (the warmed "
                    "on-device first-token samplers)")
            self._disagg = DisaggRuntime(self, serving.disagg)
        else:
            self._disagg = None
        # --- failure domains (PR 12) -------------------------------------
        # deterministic fault plan: every instrumented seam consults it
        # through _fire_fault (one attribute check when None — the seams
        # cost nothing on a clean engine)
        if serving.faults is not None and not isinstance(
                serving.faults, FaultPlan):
            raise ValueError(
                "ServingConfig.faults must be a vtpu.serving.faults."
                f"FaultPlan, got {type(serving.faults).__name__}")
        self._faults = serving.faults
        # overload shedding: the policy is resolved HERE (a bad
        # "module:attr" string fails the constructor, never the loop)
        if serving.shed_queue_depth < 0:
            raise ValueError(
                f"shed_queue_depth must be >= 0, got "
                f"{serving.shed_queue_depth}")
        self._shed_policy = load_shed_policy(serving.shed_policy)
        # signature resolved ONCE: policies with a third parameter receive
        # the EngineSignals pressure snapshot, legacy two-argument policy
        # programs keep working unchanged
        self._shed_signals = accepts_signals(self._shed_policy)
        # fetch-watchdog degradation ladder: each trip applies the next
        # APPLICABLE rung — (1) clamp the k-tick device loop to one token
        # per flush (the executable is unchanged; the per-slot cap does
        # the clamping, so the host regains per-token control with zero
        # recompiles), then (2) force the paged-attention route to gather
        # (re-lowering the decode executables — the one sanctioned
        # mid-serving compile, paid only in a failure mode). Rungs that
        # don't apply to this engine's shape are skipped at construction.
        # one-way latch: set by the first submit(deadline_ms=) so the
        # per-tick deadline sweep costs nothing on deadline-free engines
        self._deadlines_seen = False
        self._loop_cap = self._loop_k  # clamped to 1 by rung "loop_k1"
        self._degrade_rungs: list[str] = []
        if self._loop_k:
            self._degrade_rungs.append("loop_k1")
        if self._paged and self._paged_attn != "gather":
            self._degrade_rungs.append("paged_gather")
        self._degrade_level = 0
        # re-escalation state: the rungs currently APPLIED (popped back in
        # LIFO order by _recover_watchdog), the route to restore, and the
        # start of the current healthy-fetch streak (None = no streak)
        self._applied_rungs: list[str] = []
        self._paged_attn_orig = self._paged_attn
        self._healthy_since: Optional[float] = None
        # drain/migration: admission closes while the engine evacuates its
        # sessions to a peer (ServingEngine.drain) — submit() then raises
        # instead of queueing a stream the engine will never serve
        self._draining = False
        # --- fleet supervision hooks (vtpu/serving/fleet) ----------------
        if (serving.duty_supplier is not None
                and not callable(serving.duty_supplier)):
            raise ValueError(
                "ServingConfig.duty_supplier must be a zero-arg callable "
                f"returning a duty fraction (or None), got "
                f"{type(serving.duty_supplier).__name__}")
        # tick-liveness heartbeat: monotonic_ns stamped at EVERY flush
        # boundary (_tick_head — idle passes included, so a healthy idle
        # engine beats continuously). 0 until the loop's first pass: a
        # fleet monitor treats "no beat yet" as warming up (executable
        # compiles can take seconds), never as a miss.
        self._beat_ns = 0
        # session-ledger hook: when a fleet owns this engine it installs a
        # callable here; the loop invokes it at every flush boundary ON
        # THE LOOP THREAD (the single writer of slots/parked/history), so
        # the fleet's recovery-metadata ledger is a coherent snapshot.
        # None (the default) costs one attribute check per flush.
        self._ledger_hook: Optional[Callable] = None
        # the engine_death seam fired: the loop thread exited WITHOUT its
        # shutdown sweep (no terminals, no releases — a SIGKILL stand-in).
        # Read by the fleet's fencing/failover path and by _loop's finally
        # (which must skip cleanup to preserve the crash semantics).
        self._died = False
        # the exception that killed the loop thread outside the
        # engine_death seam (a compile error in _warm_executables, a bug
        # in a tick): recorded by _loop, raised by every later submit()
        # and by the first stop(), shown by stats()["loop_error"]; the
        # streams the loop held end FAULTED, never a clean CANCELLED
        self._loop_error: Optional[BaseException] = None
        self._loop_error_raised = False

    # ------------------------------------------------------------------ API

    def register_prefix(self, tokens) -> int:
        """Prefill a shared prompt prefix ONCE and return its id; submits
        passing ``prefix=id`` provide only the suffix, admitted by a device
        copy of the cached KV plus suffix chunks from the prefix offset —
        the system-prompt TTFT cost is paid at registration, not per
        request. Requires chunked prefill (ServingConfig.prefill_chunk).

        The prefix KV lives in host-of-engine device memory sliced to the
        padded prefix length ([L, 1, ceil(n/C)*C, H, Dh] per k/v plane).
        Thread-safe: builds into its OWN single-slot cache, never touching
        the serving loop's pool state.
        """
        self._refused("register_prefix")
        if not self._chunk:
            raise ValueError("register_prefix requires prefill_chunk")
        tokens = jnp.asarray(tokens, jnp.int32)
        n = int(tokens.shape[0])
        c = self._chunk
        ctx = self.model.max_context
        if n < 1 or (ctx and n > ctx - c):
            # at least one suffix chunk must fit after the prefix
            raise ValueError(f"prefix length {n} leaves no room for a suffix")
        padded = pad_to_chunks(tokens, n, c)
        pad = padded.shape[1]
        # content address (vtpu/serving/prefixdir): the cross-engine name
        # this registration reports under — identical tokens registered
        # anywhere in a fleet collapse to one directory entry
        from vtpu.serving.prefixdir import prefix_id

        cpid = prefix_id(tokens)
        if self._paged:
            # Paged: the prefix prefills into POOL BLOCKS once — the
            # registration is the only time its KV is ever computed or
            # copied; admissions then map the blocks read-only into slot
            # tables. The build mutates the shared pool state, so it runs
            # on the serving-loop thread (a work item drained by
            # _tick_head); before start() it runs inline — no loop to race.
            if self._thread is not None and self._thread.is_alive():
                item: dict = {"tokens": tokens, "padded": padded, "n": n,
                              "pad": pad, "done": threading.Event(),
                              "entry": None, "error": None}
                self._prefix_work.put(item)
                while not item["done"].wait(0.1):
                    if self._stop.is_set() or not self._thread.is_alive():
                        # flag first: if the loop still builds this item,
                        # _drain_prefix_work releases its blocks instead of
                        # leaking an entry no one will ever store; if the
                        # build finished in this instant, release it here
                        item["abandoned"] = True
                        if item["done"].is_set() and item["entry"] is not None:
                            self._alloc.release(item["entry"]["blocks"])
                            item["entry"] = None
                        raise RuntimeError(
                            "engine stopped during register_prefix")
                if item["error"] is not None:
                    raise item["error"]
                entry = item["entry"]
            else:
                entry = self._build_prefix_paged(tokens, padded, n, pad)
            entry["pid"] = cpid
            with self._prefix_lock:
                pid = self._next_prefix_id
                self._next_prefix_id += 1
                self._prefixes[pid] = entry
                self._pid_index[cpid] = pid
            if self._prefix_listener is not None:
                self._prefix_listener(
                    "register", cpid, lid=pid, tokens=entry["tokens"],
                    length=n, build_ms=entry.get("build_ms"))
            return pid
        t0 = time.perf_counter()
        scratch = self.model.init_state(1)
        for i in range(pad // c):
            off = i * c
            kv_bucket = next(
                (bkt for bkt in self._kv_buckets if bkt >= off + c), ctx)
            logits, scratch = self._prefill_chunk(
                self.params, scratch, padded[:, off:off + c],
                jnp.int32(0), jnp.int32(off), jnp.int32(min(off + c, n)),
                kv_bucket=kv_bucket, unroll=self._unroll,
            )
        kv_keys = (
            ("k", "v", "k_scale", "v_scale") if "k_scale" in scratch
            else ("k", "v"))
        buffers = {key: scratch[key][:, 0, :pad] for key in kv_keys}
        last_logits = logits[0, (n - 1) - (pad - c)]
        jax.block_until_ready(last_logits)
        build_ms = (time.perf_counter() - t0) * 1e3
        self._compile_install(pad, buffers)
        with self._prefix_lock:
            pid = self._next_prefix_id
            self._next_prefix_id += 1
            self._prefixes[pid] = {
                "tokens": [int(x) for x in tokens.tolist()],
                "buffers": buffers, "len": n, "pad": pad,
                "last_logits": last_logits, "pid": cpid,
                "build_ms": build_ms,
            }
            self._pid_index[cpid] = pid
        if self._prefix_listener is not None:
            self._prefix_listener(
                "register", cpid, lid=pid,
                tokens=[int(x) for x in tokens.tolist()], length=n,
                build_ms=build_ms)
        return pid

    def _build_prefix_paged(self, tokens, padded, n: int, pad: int) -> dict:
        """Chunk-prefill a prefix into freshly allocated pool blocks (the
        once-per-prefix compute + write; admissions map, never copy). Runs
        on whichever thread owns the pool state right now — the serving
        loop via the _prefix_work queue, or the caller before start()."""
        page, c = self._page, self._chunk
        pages = -(-pad // page)
        # runs on the pool owner's thread, so the overcommit reclaim is
        # safe here too: a prefix registration under parked pressure
        # evicts idle sessions before failing
        blocks = self._alloc_reclaim(pages)
        if blocks is None:
            # registration is an admin op: fail loudly rather than park —
            # parking a prefix build behind tenant traffic would deadlock
            # a caller holding requests that reference the new id
            raise RuntimeError(
                f"kv pool exhausted: prefix needs {pages} blocks, "
                f"{self._alloc.free_blocks} free")
        ctx = self.model.max_context
        logits = None
        t0 = time.perf_counter()
        try:
            for i in range(pad // c):
                off = i * c
                kv_bucket = next(
                    (bkt for bkt in self._kv_buckets if bkt >= off + c), ctx)
                wp = kv_bucket // page
                row = np.zeros((wp,), np.int32)
                m = min(pages, wp)
                row[:m] = blocks[:m]
                # slot = the slot count: out of range, so the helper's
                # length write DROPS — a prefix build must never touch
                # live slot state
                logits, self.state = self._prefill_chunk(
                    self.params, self.state, padded[:, off:off + c],
                    jnp.int32(self.serving.slots), jnp.int32(off),
                    jnp.int32(min(off + c, n)),
                    kv_bucket=kv_bucket, unroll=self._unroll, block_ids=row,
                )
        except Exception:
            # a failed build must not bleed the pool: no registry entry
            # will ever reference these blocks, so release them here
            self._alloc.release(blocks)
            raise
        last_logits = logits[0, (n - 1) - (pad - c)]
        jax.block_until_ready(last_logits)
        # measured build wall-time: the per-token prefill cost the fleet
        # directory's route bonus is priced from (avoided-prefill ms)
        build_ms = (time.perf_counter() - t0) * 1e3
        return {"tokens": [int(x) for x in tokens.tolist()],
                "blocks": blocks, "len": n, "pad": pad,
                "last_logits": last_logits, "build_ms": build_ms}

    def _drain_prefix_work(self) -> None:
        """Execute queued paged prefix builds on the loop thread (the pool
        state's owner). Bounded work: registrations are rare admin ops —
        one whole prefix builds per item, stalling live streams for its
        ceil(pad/C) chunks, which is the explicit price of keeping the
        pool single-writer (admission-path sharing pays zero)."""
        while True:
            try:
                item = self._prefix_work.get_nowait()
            except queue.Empty:
                return
            try:
                item["entry"] = self._build_prefix_paged(
                    item["tokens"], item["padded"], item["n"], item["pad"])
            except Exception as exc:  # surfaced on the caller's thread
                item["error"] = exc
            if item.get("abandoned") and item["entry"] is not None:
                # the registering caller gave up (engine stopping) — no
                # one will store this entry, so its blocks go straight back
                self._alloc.release(item["entry"]["blocks"])
                item["entry"] = None
            item["done"].set()

    def unregister_prefix(self, pid: int) -> None:
        """Drop a registered prefix, releasing its pinned device KV buffers
        ([L,1,pad,H,Dh] per plane). Long-lived engines serving rotating
        system prompts would otherwise leak device memory one prefix at a
        time. The per-pad install executables are deliberately kept: they
        are keyed by padded length (bounded set), not by prefix, and the
        next registration at the same pad reuses them. A request submitted
        against *pid* but not yet admitted when this runs retires with an
        end-of-stream instead of killing the serving loop."""
        with self._prefix_lock:
            entry = self._prefixes.pop(pid, None)
            if entry is None:
                raise ValueError(f"unknown prefix id {pid}")
            cpid = entry.get("pid")
            if cpid is not None and self._pid_index.get(cpid) == pid:
                del self._pid_index[cpid]
            if self._paged:
                # drop the registry's refcount hold; blocks mapped
                # read-only into live slots survive until those slots
                # retire (the allocator frees at refcount zero, never
                # before). UNDER the lock: _reserve_paged's get+share on
                # the loop thread must never interleave with this release.
                self._alloc.release(entry["blocks"])
        if cpid is not None and self._prefix_listener is not None:
            self._prefix_listener("unregister", cpid, lid=pid)

    def _compile_install(self, pad: int, buffers: dict) -> None:
        """AOT-compile the per-padded-length install executable HERE, on the
        registering caller's thread (jax.jit's own shape-keyed cache would
        compile lazily inside the serving loop instead, stalling live
        streams mid-serving). Under a tp mesh the avals carry the live
        arrays' NamedShardings — an executable lowered from bare shapes
        would compile single-device and reject the sharded state at its
        first (mid-serving) call."""
        if pad in self._install_jits:
            return

        def install(state, buffers, slot, new_len):
            out = dict(state)
            for key, buf in buffers.items():
                out[key] = state[key].at[:, slot, :buf.shape[1]].set(buf)
            out["len"] = state["len"].at[slot].set(new_len)
            return out

        def aval(x):
            sh = getattr(x, "sharding", None)
            if isinstance(sh, NamedSharding):
                return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)
            return jax.ShapeDtypeStruct(x.shape, x.dtype)

        shape_of = lambda t: jax.tree_util.tree_map(aval, t)  # noqa: E731
        self._install_jits[pad] = (
            jax.jit(install, donate_argnums=(0,))
            .lower(shape_of(self.state), shape_of(buffers),
                   jax.ShapeDtypeStruct((), jnp.int32),
                   jax.ShapeDtypeStruct((), jnp.int32))
            .compile()
        )

    def _install_prefix(self, slot: int, entry: dict) -> None:
        """Copy a registered prefix's KV into *slot* (one fused device op,
        pre-compiled at registration). Takes the caller's captured entry —
        re-looking it up by id here would reopen the unregister_prefix race
        the caller's .get() guard just closed."""
        self.state = self._install_jits[entry["pad"]](
            self.state, entry["buffers"], jnp.int32(slot),
            jnp.int32(entry["len"]))

    def submit(self, tokens, max_new_tokens: int = 0,
               prefix: Optional[int] = None, priority: int = 0,
               deadline_ms: Optional[float] = None) -> Request:
        """``deadline_ms`` bounds the request's whole service time from
        this call: past the deadline it is shed from the waiting line
        before admission, or aborted at the next flush boundary
        mid-stream, with a typed ``SHED_DEADLINE`` terminal — under
        overload a request fails fast instead of aging in an unbounded
        queue. None = no deadline; 0 is legal (sheds at the first
        boundary — the probe a load-shedding client uses)."""
        if deadline_ms is not None and deadline_ms < 0:
            raise ValueError(f"deadline_ms must be >= 0, got {deadline_ms}")
        if self._loop_error is not None:
            raise RuntimeError(
                "ServingEngine loop died") from self._loop_error
        if self._stop.is_set():
            raise RuntimeError("ServingEngine is stopped")
        if self._draining:
            # drain() closed admission: this engine is evacuating its
            # sessions to a peer and will never serve a new stream —
            # failing fast here is what lets a fleet router retarget the
            # submit instead of queueing it into a dead end
            raise RuntimeError(
                "ServingEngine is draining (admission closed); submit to "
                "the drain destination instead")
        if self._thread is None:
            # legal (requests queue until start()) but a classic trap: a
            # caller that then blocks in stream() waits forever with no
            # diagnostic
            log.warning("submit() before start(): the request will not be "
                        "served until start() is called")
        with self._on_device():
            tokens = jnp.asarray(tokens, jnp.int32)
        # validate HERE, on the caller's thread: an oversized prompt must
        # raise to its submitter, not kill the serving loop (which would
        # hang every other client forever)
        if int(tokens.shape[0]) == 0 and prefix is None:
            # with no prefix there are no logits to sample a first token
            # from: the co-scheduled path would greedy-sample off an
            # all-padding bucket (garbage) and a disagg worker has no row
            # at all — reject identically in both modes
            raise ValueError("empty prompt requires a prefix")
        if self._paged:
            # a request whose WORST-CASE private pages exceed the whole
            # pool can never admit — backpressure would park it (and, at
            # the head of the line, everything behind it) forever
            page = self._page
            base, pinned = 0, 0
            if prefix is not None:
                ent = self._prefixes.get(prefix)
                if ent is not None:
                    base = ent["len"]
                    # while this request waits, ITS prefix must stay
                    # registered (or the request retires unserved), so the
                    # registry's hold on the prefix blocks can never free —
                    # those pages are structurally unavailable to it
                    pinned = -(-ent["pad"] // page)
            total = base + int(tokens.shape[0])
            budget = max_new_tokens or self.serving.max_new_tokens
            ctx = self.model.max_context
            if ctx:
                budget = min(budget, max(ctx - total, 0))
            need = -(-max(total + budget, 1) // page) - base // page
            if need > self._n_blocks - 1 - pinned:
                raise ValueError(
                    f"request needs {need} private KV blocks at worst case "
                    f"but the pool only has {self._n_blocks - 1}"
                    + (f" ({pinned} pinned by its prefix)" if pinned else "")
                    + "; raise kv_pool_blocks or lower max_new_tokens")
        if prefix is not None:
            entry = self._prefixes.get(prefix)
            if entry is None:
                raise ValueError(f"unknown prefix id {prefix}")
            ns = int(tokens.shape[0])
            c = self._chunk
            end = entry["len"] + (-(-ns // c) * c if ns else 0)
            ctx = self.model.max_context
            if ctx and end > ctx:
                raise ValueError(
                    f"prefix {entry['len']} + padded suffix exceeds "
                    f"max_context {ctx}")
        else:
            self._bucket(int(tokens.shape[0]))
        req = Request(tokens=tokens, prefix=prefix,
                      max_new_tokens=max_new_tokens or self.serving.max_new_tokens,
                      priority=priority)
        req.rid = next(self._req_ctr)
        req.t_submit_ns = time.monotonic_ns()
        if deadline_ms is not None:
            req.deadline_ns = req.t_submit_ns + int(deadline_ms * 1e6)
            # one-way latch read by _shed_deadlines: engines that never
            # see a deadline never pay the per-tick deadline sweep
            self._deadlines_seen = True
        self.trace.record("submit", req.rid, -1, int(tokens.shape[0]))
        self._pending.put(req)
        self._wake.set()
        if self._disagg is not None:
            # wake a blocked prefill worker directly — it will find the
            # request once the next tick head drains pending into waiting
            self._disagg.notify_work()
        if self._stop.is_set():
            # raced with stop(): its drain may have missed this request; an
            # extra end-of-stream sentinel is harmless (finish is
            # idempotent), a missing one hangs the client in stream()
            self._end_stream(req, Status.CANCELLED if self._loop_error is None
                             else Status.FAULTED)
        return req

    # ------------------------------------------- failure-domain helpers

    def _end_stream(self, req: Request, status: str, slot: int = -1) -> None:
        """Deliver *req*'s typed terminal exactly once (finish is
        idempotent — racing enders collapse to one sentinel, one trace
        retire carrying the terminal code, one status)."""
        if req.finish(status):
            self.trace.record("retire", req.rid, slot,
                              TERMINAL_CODES.get(status, 0))

    def _fire_fault(self, seam: str):
        """Consult the configured FaultPlan at *seam*: the FaultSpec to
        inject (truthy) or None. One attribute check when no plan is
        configured — the seams are free on a clean engine."""
        plan = self._faults
        if plan is None:
            return None
        return plan.fire(seam)

    def _maybe_inject_dispatch(self) -> None:
        """The dispatch_exc seam: raise inside one request's deliver path
        so crash containment (the per-slot try/except in the delivery
        loops) is exercised exactly like an organic per-request bug."""
        if self._fire_fault("dispatch_exc"):
            raise FaultInjected("injected dispatch_exc")

    def _contain_fault(self, slot: int) -> None:
        """Crash containment: an exception escaped ONE request's
        dispatch/deliver path — retire only that slot with a typed
        FAULTED terminal and release everything it held; the tick loop
        and every other stream keep going. The slot's device state goes
        stale exactly like any retire's (reads masked, writes drop,
        overwritten wholesale at the next admission)."""
        req = self._slot_req[slot]
        self._stats["faulted_requests"] += 1
        if req is not None:
            self.trace.record("fault", req.rid, slot)
        log.exception("request %s faulted in slot %d; containing",
                      getattr(req, "rid", None), slot)
        self._retire(slot, status=Status.FAULTED)

    def _trip_watchdog(self, stalled_s: float) -> None:
        """A device fetch stalled past fetch_watchdog_ms: step the
        degradation ladder (see __init__) rather than hanging the host.
        Counted per APPLIED rung; an exhausted ladder logs and carries on
        — by then the engine is already in its most host-controlled,
        gather-routed shape."""
        if not self._degrade_rungs:
            log.warning("fetch watchdog: fetch stalled %.0f ms with the "
                        "degradation ladder exhausted", stalled_s * 1e3)
            return
        rung = self._degrade_rungs.pop(0)
        self._applied_rungs.append(rung)
        self._healthy_since = None  # a recovery streak ends at any stall
        self._degrade_level += 1
        self._stats["watchdog_degrades"] += 1
        self.trace.record("degrade", -1, -1, self._degrade_level)
        if rung == "loop_k1":
            # the k-tick flush executable stays; every slot's per-flush
            # cap clamps to 1, so the host observes (and can re-plan
            # around) every single token again — zero recompiles
            self._loop_cap = 1
            log.warning("fetch watchdog: fetch stalled %.0f ms — "
                        "degrading decode_loop_k=%d to per-token flushes",
                        stalled_s * 1e3, self._loop_k)
        elif rung == "paged_gather":
            # force the fused-kernel route back to the gather chain
            # (token-equal by contract) for every dispatch from here on:
            # the adapter attribute is what the trunk reads at trace
            # time, so clearing the decode jit caches re-lowers the next
            # dispatch on the gather route — a mid-serving compile, the
            # explicit price of degrading instead of hanging
            self._paged_attn = "gather"
            if hasattr(self.model, "paged_attn"):
                self.model.paged_attn = "gather"
            for fn in (self._decode_loop, self._decode_sampled,
                       self._decode, self._spec, self._decode_fused):
                if fn is not None:
                    try:
                        fn.clear_cache()
                    except AttributeError:
                        pass
            log.warning("fetch watchdog: fetch stalled %.0f ms — "
                        "degrading paged_attn to the gather route",
                        stalled_s * 1e3)

    def _recover_watchdog(self) -> None:
        """Un-degrade ONE rung after fetch latency has stayed healthy for
        the fetch_watchdog_recover_ms grace window (2->1->0, LIFO over the
        applied rungs — the last degradation undoes first). Each restored
        rung goes back onto the ladder head so a relapse re-trips it in
        the original order. Restoring the paged_attn route pays the same
        mid-serving re-lower the degrade paid — both transitions are
        token-equal routes by contract, so recovery is lossless exactly
        like degradation was."""
        if not self._applied_rungs:
            return
        rung = self._applied_rungs.pop()
        self._degrade_rungs.insert(0, rung)
        self._degrade_level -= 1
        self._stats["watchdog_recoveries"] += 1
        self.trace.record("recover", -1, -1, self._degrade_level)
        if rung == "loop_k1":
            # lift the per-slot flush cap back to the configured k: the
            # k-tick executable never left, so this is zero recompiles —
            # the exact inverse of the degrade
            self._loop_cap = self._loop_k
            log.warning("fetch watchdog: latency recovered — restoring "
                        "decode_loop_k=%d flushes", self._loop_k)
        elif rung == "paged_gather":
            self._paged_attn = self._paged_attn_orig
            if hasattr(self.model, "paged_attn"):
                self.model.paged_attn = self._paged_attn_orig
            for fn in (self._decode_loop, self._decode_sampled,
                       self._decode, self._spec, self._decode_fused):
                if fn is not None:
                    try:
                        fn.clear_cache()
                    except AttributeError:
                        pass
            log.warning("fetch watchdog: latency recovered — restoring "
                        "paged_attn=%r route", self._paged_attn_orig)

    def park(self, req: Request) -> None:
        """Take a live request out of the decode batch without ending its
        stream: token production pauses, the slot frees for other traffic,
        and the session's KV pages stay pool-resident until admission
        pressure evicts them (host-RAM swap, or drop + recompute-on-fault).
        Thread-safe and asynchronous: the serving loop performs the park at
        the next tick boundary where the slot has no in-flight token, so a
        token already dispatched is still delivered — a park never loses or
        reorders stream tokens. Parking a request still waiting for
        admission defers it (resume re-queues it); parking a finished or
        unknown request is a no-op. Requires kv_swap (the overcommit
        subsystem owns the parked lifecycle)."""
        if not self._swap_enabled:
            raise ValueError("park() requires ServingConfig.kv_swap")
        self._lifecycle_q.put(("park", req))
        self._wake.set()

    def resume(self, req: Request) -> None:
        """Bring a parked request back into the decode batch: its pages are
        swapped in from the host tier (async H2D) — or its KV rebuilt
        through the prefill path when the pages were dropped or the
        sequence sits under the recompute crossover — its page table row is
        remapped, and the stream continues from exactly the token after the
        last one delivered. Thread-safe; resuming a request that is not
        parked is a no-op."""
        if not self._swap_enabled:
            raise ValueError("resume() requires ServingConfig.kv_swap")
        self._lifecycle_q.put(("resume", req))
        self._wake.set()

    def drain(self, dst: "ServingEngine", timeout: float = 120.0) -> dict:
        """Evacuate EVERY session this engine holds — live slots, parked,
        waiting, mid-admission, worker-owned — onto *dst* via live
        migration, so the engine can be redeployed without dropping a
        stream. Admission closes first (submit() raises for the rest of
        this engine's life); each session parks at its flush boundary,
        moves as a park-shaped entry (one D2H/H2D staging pair, zero
        extra copies), and resumes on the destination at exactly its next
        token. Sessions the caller explicitly abandoned (cancel()) retire
        here with their typed terminal — drain itself never ends a
        stream. Returns the migration report
        ({"migrated", "completed", "ms"}); raises MigrationError if the
        evacuation cannot finish inside *timeout*."""
        from vtpu.serving.migrate import drain_engine

        self._refused("drain")
        return drain_engine(self, dst, timeout=timeout)

    def start(self) -> None:
        if not self._watching:
            self._watching = True
            pauses.WATCH.acquire()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        if self._disagg is not None:
            # workers block on the runtime's started event until the loop
            # finishes _warm_executables — no worker dispatch may race a
            # first-use compile or a cold pool state
            self._disagg.start()

    def stop(self) -> None:
        self._stop.set()
        self._wake.set()  # an idle loop notices the stop immediately
        if self._watching:
            self._watching = False
            pauses.WATCH.release()
        if self._thread:
            self._thread.join(timeout=10)
            # _loop's finally owns the slot/queue cleanup; touching its state
            # while it may still be mid-tick would re-create the hang. Only
            # clean up here when the loop never ran.
            if self._thread.is_alive():
                log.warning("serving loop still running 10s after stop; "
                            "its exit path will retire remaining requests")
        else:
            self._drain_all()
        if self._loop_error is not None and not self._loop_error_raised:
            # reported once: stop() stays idempotent for teardown paths
            self._loop_error_raised = True
            raise RuntimeError(
                "ServingEngine loop died") from self._loop_error

    def _drain_all(self) -> None:
        """End-of-stream for everyone still holding a Request: occupied slots
        and queued waiters alike — a client blocked in Request.stream() must
        observe the None sentinel, not hang on a dead engine."""
        if self._disagg is not None:
            self._disagg.drain()
        # a stream still running at shutdown did not complete: its
        # terminal is CANCELLED (the engine abandoned it), never OK — and
        # FAULTED when the loop died on an exception, so a dead engine
        # cannot be read as a client walking away
        ended = (Status.CANCELLED if self._loop_error is None
                 else Status.FAULTED)
        for slot in range(len(self._slot_req)):
            self._retire(slot, status=ended)
        for slot, adm in self._admitting.items():
            self._end_stream(adm["req"], adm["req"]._abort or ended)
            self._free_slot_blocks(slot)
        self._admitting.clear()
        for req in list(self._parked):
            self._release_parked(self._parked.pop(req))
            self._end_stream(req, req._abort or ended)
        self._want_park.clear()
        self._park_unseen.clear()
        self._want_resume.clear()
        if self._paged:
            # callers blocked in register_prefix must observe an error,
            # not hang on a loop that will never drain their work item
            while True:
                try:
                    item = self._prefix_work.get_nowait()
                except queue.Empty:
                    break
                item["error"] = RuntimeError("engine stopped")
                item["done"].set()
        for req in self._waiting:
            self._end_stream(req, req._abort or ended)
        self._waiting.clear()
        while True:
            try:
                req = self._pending.get_nowait()
            except queue.Empty:
                break
            self._end_stream(req, req._abort or ended)
        # unserved lifecycle commands die with the engine — but a migrate
        # TICKET has a caller blocked on its event (vtpu/serving/migrate):
        # fail it explicitly so migrate()/drain() observe the stop instead
        # of waiting out their timeout
        while True:
            try:
                kind, item = self._lifecycle_q.get_nowait()
            except queue.Empty:
                break
            if kind in ("migrate_out", "migrate_in",
                        "prefix_out", "prefix_in"):
                item.fail(RuntimeError("engine stopped mid-migration"))

    # ----------------------------------------------------------------- loop

    def _bucket(self, n: int) -> Optional[int]:
        """Smallest prefill bucket covering *n*, or None when the prompt
        goes through chunked prefill instead (longer than every bucket,
        chunking configured). Raises for prompts nothing can admit. A
        model that generates by blocks has no bucket: it has no whole-prompt
        program, and every prompt goes in chunks."""
        for b in self._prefill_buckets:
            if n <= b:
                return b
        ctx = self.model.max_context
        if self._chunk and (not ctx or n <= ctx):
            return None
        raise ValueError(
            f"prompt length {n} exceeds the largest usable bucket "
            f"{self._prefill_buckets[-1]}"
            + (f" (chunked prefill caps at max_context {ctx})"
               if self._chunk else "")
        )

    def _free_slot_blocks(self, slot: int) -> None:
        """Return a slot's mapped blocks to the allocator (refcount
        decrement — shared prefix blocks only free once every mapping and
        the registry itself have let go)."""
        if self._paged and self._slot_blocks[slot]:
            self._alloc.release(self._slot_blocks[slot])
            self._slot_blocks[slot] = []
        if self._slot_pid[slot] is not None:
            if self._slot_shared[slot] and self._prefix_listener is not None:
                # the slot's prefix shares just released: the fleet
                # directory's live refcount follows the allocator's
                self._prefix_listener("release", self._slot_pid[slot][0])
            self._slot_pid[slot] = None
        self._slot_shared[slot] = 0

    def _reserve_paged(self, slot: int, req: Request) -> bool:
        """Pool-aware admission: map every page this request can ever touch
        — prompt + ITS token budget, not max_seq — and set the slot's
        device table row (plus base length) in one fused op. A prefix-
        backed request maps the prefix's full blocks READ-ONLY (share():
        zero device copies) and pays one block copy only for a partial
        boundary block, which upcoming suffix/decode writes would otherwise
        scribble into memory other slots are reading. Returns False with
        nothing reserved when the free list can't cover the private pages:
        the caller leaves the request parked on the waiting list, and a
        later retire's release() unblocks it — backpressure, never OOM."""
        if req.prefix is not None:
            # the lookup, the share() of the prefix's full blocks, and the
            # COW-source read below must be ATOMIC against a caller-thread
            # unregister_prefix (whose release also runs under this lock):
            # a release landing between get() and share() would hand the
            # blocks back to the free list — share() would then revive a
            # dead block, or a concurrent admission's alloc could double-
            # map it into another slot's table
            with self._prefix_lock:
                entry = self._prefixes.get(req.prefix)
                if entry is None:
                    return True  # unregistered: _admit retires it, no pages
                ok = self._reserve_paged_locked(slot, req, entry)
            if ok:
                # a paged prefix hit is THE share itself (zero-copy
                # reuse); counted only on success so a backpressured
                # admission retried next tick never double-counts
                self._stats["prefix_hits"] += 1
                if entry.get("pid") is not None:
                    self._slot_pid[slot] = (entry["pid"], entry["len"])
                    # refcount events pair with the allocator's holds:
                    # a sub-page prefix shares no blocks, so it stamps
                    # no ref the release side would never drop
                    if (self._slot_shared[slot]
                            and self._prefix_listener is not None):
                        self._prefix_listener("hit", entry["pid"])
            return ok
        return self._reserve_paged_locked(slot, req, None)

    def _reserve_plan(self, req: Request,
                      entry: Optional[dict]) -> tuple[int, int, int, int]:
        """The page-reservation arithmetic every admission path shares —
        slot admission (_reserve_paged_locked) and the disagg prefill
        workers alike, so the budget clamp and page math can never
        diverge between the co-scheduled and disaggregated modes.
        Returns (base, budget, full_prefix_pages, need_priv)."""
        page = self._page
        n = int(req.tokens.shape[0])
        base = entry["len"] if entry is not None else 0
        ctx = self.model.max_context
        total = base + n
        budget = req.max_new_tokens or self.serving.max_new_tokens
        if ctx:
            budget = min(budget, ctx - total)
        reserve = -(-max(total + max(budget, 0), 1) // page)
        full = base // page  # whole prefix pages, shareable as-is
        return base, budget, full, reserve - full

    def _reserve_paged_locked(self, slot: int, req: Request,
                              entry: Optional[dict]) -> bool:
        # the share/COW sequence is mirrored by the disagg worker's
        # _reserve_locked (loop thread here: eviction-assisted alloc,
        # immediate counters, no state mutex). A semantic change to
        # boundary-block handling must land in BOTH places.
        page = self._page
        base, _, full, need_priv = self._reserve_plan(req, entry)
        shared = entry["blocks"][:full] if entry is not None else []
        # overcommit: a dry free list first evicts parked sessions' private
        # pages (QoS-then-LRU) before this admission is allowed to park —
        # pool exhaustion is backpressure-with-eviction, not a hard park
        priv = self._alloc_reclaim(need_priv) if need_priv > 0 else []
        if priv is None:
            self._stats["pool_blocked_admissions"] += 1
            return False
        if shared:
            self._alloc.share(shared)
            self._stats["prefix_blocks_shared"] += len(shared)
        row_blocks = list(shared) + priv
        if base % page:
            # copy-on-write: logical page `full` starts as a copy of the
            # prefix's partial boundary block (priv[0] sits at exactly
            # that table index)
            self.state = self._copy_block(
                self.state, jnp.int32(entry["blocks"][full]),
                jnp.int32(priv[0]))
            self._stats["prefix_cow_copies"] += 1
        self._slot_blocks[slot] = row_blocks
        self._slot_shared[slot] = len(shared)
        trow = np.zeros((self._max_pages,), np.int32)
        trow[:len(row_blocks)] = row_blocks
        self.state = self._set_table_row(
            self.state, jnp.int32(slot), trow, jnp.int32(base))
        return True

    # ------------------------------------------------ KV overcommit core

    def _alloc_reclaim(self, n: int, exclude: Optional[Request] = None):
        """BlockAllocator.alloc with the overcommit extension: when the
        free list can't cover *n*, count the RECLAIMABLE blocks (parked
        sessions' evictable private pages) before giving up — if free +
        reclaimable covers the request, evict until it fits and retry.
        ``exclude`` protects the entry being resumed from evicting itself.
        Returns the blocks or None (nothing reserved) exactly like alloc."""
        if self._fire_fault("alloc_exhaust"):
            # injected exhaustion: report a dry free list so the caller's
            # backpressure path (park the admission / retry the resume)
            # runs exactly as it would under a genuinely full pool
            return None
        got = self._alloc.alloc(n)
        if got is not None or not self._swap_enabled:
            return got
        if self._alloc.free_blocks + self._reclaimable(exclude) < n:
            return None
        self._reclaim(n, exclude)
        return self._alloc.alloc(n)

    def _reclaimable(self, exclude: Optional[Request] = None) -> int:
        return sum(
            len(e["priv"]) for r, e in self._parked.items()
            if r is not exclude and e["priv"] and self._evictable(e))

    def _evictable(self, e: dict) -> bool:
        """Can this parked entry's private pages leave the pool? Either the
        host tier has room for them, or the sequence is rebuildable through
        the prefill path (drop + recompute-on-fault). Shared prefix blocks
        are never part of the question — they are pinned by their refcounts
        and stay resident."""
        return (len(e["priv"]) <= len(self._host_free)
                or e["recompute_ok"])

    def _reclaim(self, need: int, exclude: Optional[Request] = None) -> None:
        """Evict parked sessions until the free list covers *need* blocks
        (or nothing evictable remains). Order is QoS-then-LRU within the
        tick: lowest Request.priority first, least-recently-parked within a
        tier — an interactive session outlives a batch one, and among equals
        the longest-idle spills first."""
        # O(parked log parked) per dry-list miss: fine to the ~1e3-session
        # scale the bench drives; a 1e5+-session deployment would keep a
        # (priority, seq) heap plus a running reclaimable counter instead
        # of rescanning (the WaitQueue move, applied to the parked side)
        order = sorted(
            (r for r, e in self._parked.items()
             if r is not exclude and e["priv"] and self._evictable(e)),
            key=lambda r: (self._parked[r]["priority"],
                           self._parked[r]["seq"]))
        for req in order:
            if self._alloc.free_blocks >= need:
                return
            e = self._parked[req]
            if not self._evictable(e):
                # earlier evictions in this pass consumed the host room
                # this entry's snapshot check relied on; an unrecomputable
                # entry must stay resident, never be dropped
                continue
            self._evict_entry(e)

    def _evict_entry(self, e: dict) -> None:
        """Reclaim one parked session's private pages. With host-tier room
        the pages spill: a compiled gather snapshots up to SWAP_STAGE_BLOCKS
        at a time into fresh device buffers (pure async dispatch), the host copy
        is STARTED (copy_to_host_async) and completes off the tick path
        (_drain_swap_outs), and the pool blocks release immediately — the
        snapshot, not the pool, feeds the host copy, so a new admission can
        overwrite the blocks the same tick. Without room the pages drop and
        resume recomputes (the _evictable gate guaranteed it can)."""
        priv = e["priv"]
        m = len(priv)
        # injected D2H loss: the spill "fails in transit" — recomputable
        # entries drop their pages (resume rides recompute-on-fault); an
        # unrecomputable entry ignores the injection and spills normally
        # (dropping it would wedge the resume: correctness over chaos)
        d2h_lost = (e["recompute_ok"]
                    and self._fire_fault("swap_d2h_loss") is not None)
        if (not d2h_lost and m <= len(self._host_free)
                and self._swap_host_blocks):
            e["host"] = [self._host_free.pop() for _ in range(m)]
            snaps = []
            w = self._swap_stage
            for i in range(0, m, w):
                grp = priv[i:i + w]
                ids = np.zeros((w,), np.int32)
                ids[:len(grp)] = grp
                snap = self._swap_gather(self.state, ids)
                for leaf in jax.tree_util.tree_leaves(snap):
                    start = getattr(leaf, "copy_to_host_async", None)
                    if start is not None:
                        start()
                snaps.append((snap, len(grp)))
            e["pend"] = snaps
            self._swap_pending.append(e)
            self._stats["swap_out_bytes"] += m * self._block_bytes
            spilled = True
        elif e["recompute_ok"]:
            e["dropped"] = True
            spilled = False
        else:
            # neither spillable nor rebuildable: the pages MUST stay
            # resident (dropping them would wedge the resume) — correct
            # backpressure, enforced here as the last line even if a
            # caller's evictability snapshot went stale
            return
        self._stats["evicted_blocks"] += m
        self.trace.record("evict", e["req"].rid, -1, m)
        if spilled:
            self.trace.record("swap_out", e["req"].rid, -1,
                              m * self._block_bytes)
        self._alloc.release(priv)
        e["priv"] = []

    def _drain_swap_outs(self) -> None:
        """Land completed D2H snapshots in the pinned host pool —
        opportunistic: only snapshots whose transfers report ready, so the
        tick path never blocks on a swap. A resume that needs its pages
        before they report ready finalizes its own entry directly
        (_swap_in -> _finalize_swap_out); shutdown releases pending
        entries without landing them (_release_parked)."""
        for e in list(self._swap_pending):
            if not all(
                    getattr(leaf, "is_ready", lambda: True)()
                    for snap, _ in e["pend"]
                    for leaf in jax.tree_util.tree_leaves(snap)):
                continue
            self._finalize_swap_out(e)

    def _finalize_swap_out(self, e: dict) -> None:
        off = 0
        for snap, cnt in e["pend"]:
            hbs = e["host"][off:off + cnt]
            for key in self._swap_planes:
                # one fancy-indexed copy per plane (this runs on the tick
                # path — no per-block Python slice loop)
                self._host_pool[key][:, hbs] = np.asarray(snap[key])[:, :cnt]
            off += cnt
        e["pend"] = None
        self._swap_pending.remove(e)

    def _release_parked(self, e: dict) -> None:
        """Return EVERYTHING a parked entry owns: held prefix shares,
        still-resident private blocks, host-tier pages, in-flight
        snapshots. The cancel-while-parked / cancel-mid-swap / shutdown
        sweep — nothing a dead session held may leak."""
        if e in self._swap_pending:
            e["pend"] = None
            self._swap_pending.remove(e)
        if e["shared"]:
            self._alloc.release(e["shared"])
            e["shared"] = []
            if (e.get("pid") is not None
                    and self._prefix_listener is not None):
                self._prefix_listener("release", e["pid"])
        if e["priv"]:
            self._alloc.release(e["priv"])
            e["priv"] = []
        if e["host"] is not None:
            self._host_free.extend(e["host"])
            e["host"] = None

    def _can_recompute(self, seq_len: int) -> bool:
        """A sequence is rebuildable when a prefill bucket covers it or
        chunked prefill is configured (any length up to the context)."""
        return (any(b >= seq_len for b in self._prefill_buckets)
                or self._prefill_chunk is not None)

    def _seed_history(self, slot: int, req: Request, n: int) -> None:
        """Seed a slot's token history as a cache-contents mirror of the
        *n* installed positions: prefix tokens + prompt. If the prefix was
        unregistered in the admission window its tokens are gone — under
        overcommit the gap pads with placeholders so the length invariant
        (_parkable) holds and the slot stays parkable, but it is flagged
        inexact: such a session may swap (content-based) yet must never be
        rebuilt from history."""
        entry = (self._prefixes.get(req.prefix)
                 if req.prefix is not None else None)
        pre = entry["tokens"] if entry else []
        toks = [int(x) for x in req.tokens.tolist()]
        miss = n - len(pre) - len(toks)
        self._slot_hist_exact[slot] = miss <= 0
        if miss > 0 and self._swap_enabled:
            pre = list(pre) + [0] * miss
        self._history[slot] = list(pre) + toks

    def _parkable(self, slot: int) -> bool:
        """A slot can park once at least one token has been DELIVERED for
        it (the pending-token invariant: history holds cache contents plus
        exactly the one delivered-but-unwritten token) and no token is in
        flight for it (the pipelined loop's lookahead must settle first —
        dispatch exclusion makes that happen within one tick)."""
        return (slot not in self._inflight_slots
                and len(self._history[slot]) == self._slot_len[slot] + 1)

    def _do_park(self, slot: int) -> None:
        req = self._slot_req[slot]
        nshared = self._slot_shared[slot]
        blocks = self._slot_blocks[slot]
        spid = self._slot_pid[slot]
        self._parked[req] = {
            "req": req,
            # cache contents by construction: history minus the pending
            # token (whose KV lands only when a decode tick consumes it)
            "tokens": list(self._history[slot][:-1]),
            "pending": self._tokens[slot],
            "budget": self._slot_budget[slot],
            "seq_len": self._slot_len[slot],
            "n_pages": len(blocks),
            "shared": blocks[:nshared],  # refcount holds kept while parked
            "priv": blocks[nshared:],    # evictable: this session's own KV
            "host": None, "pend": None, "dropped": False,
            # an inexact history (placeholder prefix tokens after an
            # unregister race) can never rebuild this cache: swap-only
            "recompute_ok": (self._can_recompute(self._slot_len[slot])
                             and self._slot_hist_exact[slot]),
            "hist_exact": self._slot_hist_exact[slot],
            "priority": req.priority,
            "seq": self._park_seq,
            # the prefix identity rides the park: its shares transfer to
            # the entry (holds MOVE — no release event), and a payload-
            # less rebuild on another engine can re-share the same
            # content pid instead of recomputing the prefix positions
            "pid": spid[0] if spid is not None else None,
            "prefix_len": spid[1] if spid is not None else 0,
        }
        self._park_seq += 1
        # free the slot WITHOUT releasing blocks (the entry owns them now);
        # the device table row goes stale exactly like a retire's (reads
        # masked, writes drop, overwritten wholesale at the next mapping)
        self._slot_req[slot] = None
        self._slot_budget[slot] = 0
        self._slot_len[slot] = 0
        self._slot_blocks[slot] = []
        self._slot_shared[slot] = 0
        self._slot_pid[slot] = None
        self._history[slot] = []
        self._slot_hist_exact[slot] = True
        self._itl_last[slot] = None
        self._admit_mask[slot] = False
        self._stats["parks"] += 1
        self.trace.record("park", req.rid, slot, len(blocks))

    def _process_lifecycle(self) -> None:
        """Drain park/resume commands from client threads and apply the
        parks whose slots have settled; also sweep cancelled parked
        sessions (their client walked away — everything they hold goes
        back, exactly like a live slot's cancel)."""
        while True:
            try:
                kind, req = self._lifecycle_q.get_nowait()
            except queue.Empty:
                break
            if kind in ("migrate_out", "migrate_in"):
                # cross-engine migration tickets (vtpu/serving/migrate):
                # served HERE, on the loop thread — the owner of the
                # parked set, the allocator-assisted reclaim, and the
                # donated device state the staging ops consume. ``req``
                # is the ticket; the handler answers it (never raises —
                # a failed migration must not take the loop down).
                from vtpu.serving.migrate import handle_migrate_command

                handle_migrate_command(self, kind, req)
                continue
            if kind in ("prefix_out", "prefix_in"):
                # whole-prefix export/install tickets (vtpu/serving/
                # prefixdir): same loop-thread ownership rules as a
                # migration — the staging pair and the registry lock
                # both live here
                from vtpu.serving.prefixdir import handle_prefix_command

                handle_prefix_command(self, kind, req)
                continue
            if kind == "park":
                if req in self._parked and req in self._want_resume:
                    # park overtook a still-queued (possibly
                    # backpressured) resume: drop the resume and leave
                    # the session parked — symmetric with the
                    # resume-cancels-pending-park case below
                    self._want_resume.remove(req)
                else:
                    self._want_park.add(req)
            elif req in self._want_park:
                # resume overtook a park that never settled: they cancel
                # out — the session just keeps decoding (dropping the
                # resume instead would strand a parked client forever)
                self._want_park.discard(req)
            elif req in self._parked and req not in self._want_resume:
                # the resume-latency span starts HERE (command accepted),
                # one lifecycle drain after the client's resume() call
                self.trace.record("resume", req.rid)
                self._want_resume.append(req)
        for req in list(self._want_park):
            if req.cancelled or req in self._parked:
                self._want_park.discard(req)
                self._park_unseen.discard(req)
                continue
            if self._waiting.take(req):
                # not yet admitted (and atomically won from any racing
                # prefill-worker claim): park it unstarted — resume
                # re-queues through normal admission, no pages to save
                self._park_unseen.discard(req)
                self._parked[req] = {
                    "req": req, "unstarted": True, "tokens": [],
                    "pending": None, "budget": 0, "seq_len": 0,
                    "n_pages": 0, "shared": [], "priv": [], "host": None,
                    "pend": None, "dropped": False, "recompute_ok": True,
                    "hist_exact": True, "priority": req.priority,
                    "seq": self._park_seq,
                }
                self._park_seq += 1
                self._want_park.discard(req)
                self._stats["parks"] += 1
                self.trace.record("park", req.rid)
                continue
            try:
                slot = self._slot_req.index(req)
            except ValueError:
                # mid-chunked-admission (parks once admitted) — or nowhere
                # to be found. "Nowhere" is ambiguous for ONE pass: the
                # submit may still sit in _pending (put there after this
                # tick's pending drain but before its command drain), so
                # the command survives one miss and is only discarded on
                # the second consecutive one — by then the next pending
                # drain has certainly run and a vanished request is
                # genuinely finished
                owned = (self._disagg is not None
                         and self._disagg.owns(req))
                if owned:
                    # mid-prefill on a worker, or a completed handoff
                    # awaiting a slot: like a mid-chunked admission, the
                    # park settles once the session reaches a slot
                    self._park_unseen.discard(req)
                elif not any(adm["req"] is req
                             for adm in self._admitting.values()):
                    if req in self._park_unseen:
                        self._want_park.discard(req)
                        self._park_unseen.discard(req)
                    else:
                        self._park_unseen.add(req)
                continue
            self._park_unseen.discard(req)
            if self._parkable(slot):
                self._want_park.discard(req)
                self._do_park(slot)
        for req in [r for r, e in self._parked.items() if r.cancelled]:
            self._release_parked(self._parked.pop(req))
            self._end_stream(req, req._abort or Status.CANCELLED)

    def _advance_resumes(self, budget: float = float("inf")) -> float:
        """Bring resumed sessions back into slots, FIFO over resume order,
        ahead of new admissions (they are older traffic). Three paths per
        entry: still-resident pages remap in one fused table write;
        swapped pages allocate (evicting if needed), async-H2D through the
        staging shape, and remap; dropped pages — or sequences under the
        recompute crossover — rebuild through the prefill path (bucketed
        in one dispatch, chunked across ticks for long sequences). A
        bucketed rebuild spends its bucket from the per-tick prompt-token
        ``budget`` exactly like an admission would — a resume wave
        degrades live streams by the configured bound, never a stall. A
        full pool, full slot set, or spent budget leaves the entry queued
        for the next tick: resume backpressure, never a loss. Returns the
        remaining budget."""
        while self._want_resume:
            req = self._want_resume[0]
            e = self._parked.get(req)
            if e is None or req.cancelled:
                # cancel raced the resume: the parked sweep (or a prior
                # pass) already cleaned up / will clean up
                self._want_resume.pop(0)
                continue
            if e.get("unstarted"):
                self._want_resume.pop(0)
                del self._parked[req]
                self._waiting.append(req)
                self._stats["resumes"] += 1
                continue
            slot = next(
                (i for i in range(self.serving.slots)
                 if self._slot_req[i] is None and i not in self._admitting),
                None)
            if slot is None:
                break  # no slot to resume into: wait for a retire
            if e["priv"]:
                # resident fast path FIRST: pages never left the pool, so
                # one fused table-row remap beats both restore paths — the
                # recompute crossover only arbitrates swap-in vs rebuild,
                # never a free remap (and recomputing here would leak the
                # resident blocks)
                self._finish_resume_slot(slot, e)
            elif e["dropped"] or (
                    e["seq_len"] <= self.serving.kv_swap_recompute_tokens
                    and e["recompute_ok"]):
                bkt = next((b for b in self._prefill_buckets
                            if b >= e["seq_len"]), None)
                if bkt is not None and bkt > budget:
                    break  # budget spent: the rebuild waits one tick
                if not self._begin_recompute(slot, e):
                    break  # pool can't cover it yet: stays parked
                if bkt is not None:
                    budget -= bkt
            else:
                if not self._swap_in(slot, e):
                    break
            self._want_resume.pop(0)
        return budget

    def _swap_in(self, slot: int, e: dict) -> bool:
        """Restore a swapped session: allocate private blocks (reclaiming
        if the free list is dry — the entry itself is excluded), upload the
        host pages through the compiled staging scatter (device_put is an
        async H2D; under a mesh the staging lands pre-sharded on the head
        axis so each chip uploads only its shard), remap the table row, and
        restore the slot. No blocking host sync anywhere on this path."""
        if e["recompute_ok"] and self._fire_fault("swap_h2d_loss"):
            # injected H2D loss: the host restore "fails in transit" —
            # the entry drops its host pages and rebuilds through the
            # prefill path (the same recompute-on-fault route a dropped
            # eviction takes); unrecomputable entries ignore the
            # injection and restore normally
            e["dropped"] = True
            return self._begin_recompute(slot, e)
        need = e["n_pages"] - len(e["shared"])
        priv = self._alloc_reclaim(need, exclude=e["req"])
        if priv is None:
            self._stats["pool_blocked_resumes"] += 1
            return False
        if e["pend"] is not None:
            self._finalize_swap_out(e)  # rare: resume raced its own D2H
        w = self._swap_stage
        for i in range(0, need, w):
            grp = priv[i:i + w]
            hgrp = e["host"][i:i + w]
            ids = np.zeros((w,), np.int32)
            ids[:len(grp)] = grp
            pages = {}
            for key in self._swap_planes:
                buf = np.zeros(
                    (self._host_pool[key].shape[0], w)
                    + self._host_pool[key].shape[2:],
                    self._host_pool[key].dtype)
                # one fancy-indexed gather per plane — the resume-latency
                # critical path pays no per-block Python loop
                buf[:, :len(hgrp)] = self._host_pool[key][:, hgrp]
                sh = self._stage_shardings.get(key)
                pages[key] = (jax.device_put(buf, sh) if sh is not None
                              else buf)
            self.state = self._swap_scatter(self.state, ids, pages)
        self._host_free.extend(e["host"])
        e["host"] = None
        e["priv"] = priv
        self._stats["swap_in_bytes"] += need * self._block_bytes
        self._stats["swap_faults"] += 1
        self.trace.record("swap_in", e["req"].rid, slot,
                          need * self._block_bytes)
        self._finish_resume_slot(slot, e)
        return True

    def _finish_resume_slot(self, slot: int, e: dict) -> None:
        """Remap a restored entry's table row and put the session back in
        its slot: the next decode tick feeds its pending token exactly as
        if the park never happened."""
        row_blocks = e["shared"] + e["priv"]
        self._slot_blocks[slot] = row_blocks
        self._slot_shared[slot] = len(e["shared"])
        if e.get("pid") is not None and e["shared"]:
            # the entry's prefix holds move back onto the slot
            self._slot_pid[slot] = (e["pid"], e["prefix_len"])
        e["shared"] = []
        e["priv"] = []
        trow = np.zeros((self._max_pages,), np.int32)
        trow[:len(row_blocks)] = row_blocks
        self.state = self._set_table_row(
            self.state, jnp.int32(slot), trow, jnp.int32(e["seq_len"]))
        self._restore_slot(slot, e)

    def _restore_slot(self, slot: int, e: dict) -> None:
        req = e["req"]
        self._slot_req[slot] = req
        self._slot_budget[slot] = e["budget"]
        self._tokens[slot] = e["pending"]
        self._slot_len[slot] = e["seq_len"]
        if self._track_history:
            self._history[slot] = list(e["tokens"]) + [e["pending"]]
        self._slot_hist_exact[slot] = e.get("hist_exact", True)
        self._itl_last[slot] = None  # the resume gap is not an ITL sample
        if req in self._parked:
            del self._parked[req]
            self._stats["resumes"] += 1

    def _try_prefix_reuse(self, slot: int, e: dict) -> Optional[bool]:
        """Rebuild a payload-less entry AROUND a locally registered
        prefix: share the registry's blocks for the session's content
        pid (COW the boundary like any admission) and chunk-prefill only
        the private tail — the failover path that makes a survivor serve
        a hot system prompt with ZERO recomputed prefix tokens. Returns
        None to fall through to the whole-sequence recompute (pid not
        resident, tokens diverged, inexact history), False when the pool
        cannot cover the tail yet (entry stays parked, retried next
        tick), True on success."""
        pid = e.get("pid")
        plen = int(e.get("prefix_len") or 0)
        if (pid is None or plen <= 0 or not self._chunk
                or not e.get("hist_exact", True)):
            return None
        req, n, need = e["req"], e["seq_len"], e["n_pages"]
        page = self._page
        full = plen // page
        if plen > n or full == 0 or need <= full:
            return None
        toks = e["tokens"]
        with self._prefix_lock:
            lid = self._pid_index.get(pid)
            entry = self._prefixes.get(lid) if lid is not None else None
            if (entry is None or entry["len"] != plen
                    or entry["tokens"] != list(toks[:plen])):
                return None
            priv = self._alloc_reclaim(need - full, exclude=req)
            if priv is None:
                self._stats["pool_blocked_resumes"] += 1
                return False
            shared = list(entry["blocks"][:full])
            self._alloc.share(shared)
            self._stats["prefix_blocks_shared"] += len(shared)
            if plen % page:
                # the partial boundary block COWs exactly as at admission
                # (priv[0] sits at table index `full`)
                self.state = self._copy_block(
                    self.state, jnp.int32(entry["blocks"][full]),
                    jnp.int32(priv[0]))
                self._stats["prefix_cow_copies"] += 1
        row_blocks = shared + priv
        self._slot_blocks[slot] = row_blocks
        self._slot_shared[slot] = len(shared)
        self._slot_pid[slot] = (pid, plen)
        trow = np.zeros((self._max_pages,), np.int32)
        trow[:len(row_blocks)] = row_blocks
        self.state = self._set_table_row(
            self.state, jnp.int32(slot), trow, jnp.int32(plen))
        if e["host"] is not None:
            if e["pend"] is not None:
                e["pend"] = None
                self._swap_pending.remove(e)
            self._host_free.extend(e["host"])
            e["host"] = None
        ns = n - plen
        self._stats["swap_faults"] += 1
        self._stats["fault_recomputes"] += 1
        self._stats["failover_prefix_reuses"] += 1
        self._stats["prefix_hits"] += 1
        if self._prefix_listener is not None:
            self._prefix_listener("hit", pid)
        # val = the TAIL length: the white-box contract that the prefix
        # positions were shared, never re-prefilled
        self.trace.record("fault_recompute", req.rid, slot, ns)
        if ns == 0:
            # the whole cache WAS the prefix (empty-suffix session parked
            # right after its first token): nothing to rebuild
            self._restore_slot(slot, e)
            return True
        self._admitting[slot] = {
            "req": req,
            "padded": pad_to_chunks(
                jnp.asarray(toks[plen:], jnp.int32), ns, self._chunk),
            "n": n, "off": 0, "base": plen,
            "resume": {"req": req, "pending": e["pending"],
                       "budget": e["budget"], "seq_len": n,
                       "tokens": toks},
        }
        del self._parked[req]
        self._stats["resumes"] += 1
        return True

    def _begin_recompute(self, slot: int, e: dict) -> bool:
        """Rebuild a faulted (or crossover-short) session's KV through the
        prefill path. The whole sequence goes PRIVATE — held prefix shares
        release and the prefix positions recompute like any others (the
        trunk is deterministic, so the rebuilt pool content matches what
        decode wrote). Short sequences take one bucketed dispatch (via the
        warmed batched-admission step — its sampled token is discarded, the
        pending token is already on the host); longer ones ride the
        chunked-admission machinery, budget-bounded across ticks."""
        req = e["req"]
        n = e["seq_len"]
        need = e["n_pages"]
        if e["priv"]:
            # defensive: callers route resident entries to the remap fast
            # path, but a rebuild must never strand still-held blocks —
            # and once the content is released the entry IS dropped, so a
            # failed alloc below leaves it in a consistent
            # retry-as-recompute state instead of routing to _swap_in
            self._alloc.release(e["priv"])
            e["priv"] = []
            e["dropped"] = True
        if not e["shared"]:
            # failover-rebuild fast path: a payload-less entry whose
            # content pid is registered HERE shares the prefix blocks and
            # recomputes only its private tail (an entry still HOLDING
            # shares — a local eviction park — keeps the established
            # release-and-recompute route below)
            got = self._try_prefix_reuse(slot, e)
            if got is not None:
                return got
        priv = self._alloc_reclaim(need, exclude=req)
        if priv is None:
            self._stats["pool_blocked_resumes"] += 1
            return False
        if e["shared"]:
            self._alloc.release(e["shared"])
            e["shared"] = []
            if (e.get("pid") is not None
                    and self._prefix_listener is not None):
                self._prefix_listener("release", e["pid"])
        if e["host"] is not None:
            if e["pend"] is not None:
                e["pend"] = None
                self._swap_pending.remove(e)
            self._host_free.extend(e["host"])
            e["host"] = None
        self._slot_blocks[slot] = priv
        self._slot_shared[slot] = 0
        trow = np.zeros((self._max_pages,), np.int32)
        trow[:need] = priv
        self.state = self._set_table_row(
            self.state, jnp.int32(slot), trow, jnp.int32(0))
        self._stats["swap_faults"] += 1
        self._stats["fault_recomputes"] += 1
        self.trace.record("fault_recompute", req.rid, slot, n)
        toks = e["tokens"]
        bucket = next((b for b in self._prefill_buckets if b >= n), None)
        if bucket is not None:
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :n] = toks
            if self._admit_step is not None:
                # the warmed (1, bucket) admission executable doubles as
                # the recompute prefill; its sampled first token lands in
                # _admit_buf but the mask stays False, so it is never
                # merged — the pending token is the real next input
                keys = jax.random.split(self._admit_key, 2)
                self._admit_key = keys[0]
                _, self._admit_buf, self.state = self._admit_step(
                    self.params, self.state, self._admit_buf, padded,
                    np.asarray([slot], np.int32),
                    np.asarray([n], np.int32), keys[1:])
            else:
                _, self.state = self._prefill(
                    self.params, self.state, padded, jnp.int32(slot),
                    jnp.int32(n))
            self._restore_slot(slot, e)
            return True
        # chunked rebuild: rides _advance_admissions one [1, C] chunk per
        # tick; the final chunk restores the slot instead of sampling
        self._admitting[slot] = {
            "req": req,
            "padded": pad_to_chunks(jnp.asarray(toks, jnp.int32), n,
                                    self._chunk),
            "n": n, "off": 0, "base": 0,
            "resume": {"req": req, "pending": e["pending"],
                       "budget": e["budget"], "seq_len": n,
                       "tokens": toks},
        }
        del self._parked[req]
        self._stats["resumes"] += 1
        return True

    def _admit(self, slot: int, req: Request) -> None:
        """Admit ONE request into *slot*. Prefix-cached and chunked prompts
        route the same way in both admission modes (install/park); a
        bucketed prompt here is the LEGACY serial path — one [1, bucket]
        dispatch plus a blocking first-token sync. Batched-async bucketed
        admission goes through _admit_batch instead."""
        prompt = req.tokens
        n = int(prompt.shape[0])
        if req.prefix is not None:
            entry = self._prefixes.get(req.prefix)
            if entry is None:
                # unregister_prefix raced with this submit: fail just this
                # request (end-of-stream), never the loop serving everyone.
                # Pages reserved for it (the unregister may have landed
                # between reservation and here) go straight back.
                log.warning("request references unregistered prefix %s; "
                            "retiring it unserved", req.prefix)
                self._free_slot_blocks(slot)
                self._stats["prefix_misses"] += 1
                self._stats["faulted_requests"] += 1
                self.trace.record("fault", req.rid, slot)
                self._end_stream(req, Status.FAULTED, slot)
                return
            if self._paged:
                # zero-copy: _reserve_paged already mapped the prefix's
                # blocks into this slot's table (and COW'd the boundary);
                # there is no install copy to perform
                pass
            else:
                self._install_prefix(slot, entry)
                self._stats["prefix_install_copies"] += 1
                # dense hits count at the install (the paged ones counted
                # at _reserve_paged's share — each mode's reuse moment);
                # no listener event: dense installs hold no block refs
                # for a release to ever pair with
                self._stats["prefix_hits"] += 1
            base = entry["len"]
            if n == 0:
                # no suffix: the first token comes straight from the
                # prefix's stored final logits
                if self._async_admission:
                    self._begin_slot_async(
                        slot, req, entry["last_logits"], base)
                else:
                    self._finish_admit(
                        slot, req, self._sample_first(entry["last_logits"]),
                        base)
                return
            self._admitting[slot] = {
                "req": req, "padded": pad_to_chunks(prompt, n, self._chunk),
                "n": base + n, "off": 0, "base": base}
            return
        bucket = self._bucket(n)
        if self._blocks:
            # the prompt's whole blocks go in chunks; its other tokens open
            # the first generated block as rows already committed
            # (_open_first_block), at once where no whole block precedes
            prompt = np.asarray(prompt)  # one fetch an admission
            n -= n % self._blocks
            prompt, tail = prompt[:n], prompt[n:]
            if n == 0:
                self._open_first_block(slot, req, tail)
                return
        if bucket is None:
            # Chunked prefill is INCREMENTAL: park the request and let the
            # serving loop advance one [1, C] chunk per iteration, so live
            # streams decode between chunks — that interleaving is what
            # makes "head-of-line work bounded at C tokens" true (a
            # back-to-back chunk loop here would stall exactly like one
            # monolithic dispatch).
            self._admitting[slot] = {
                "req": req, "padded": pad_to_chunks(prompt, n, self._chunk),
                "n": n, "off": 0, "base": 0}
            if self._blocks:
                self._admitting[slot]["tail"] = tail
            return
        padded = np.zeros((1, bucket), np.int32)  # host-built, as above
        padded[0, :n] = np.asarray(prompt)
        logits, self.state = self._prefill(
            self.params, self.state, padded, jnp.int32(slot), jnp.int32(n)
        )
        self._stats["prefill_tokens"] += n
        self._note_expert_rows(bucket)
        self._stats["prefill_batch_hist"][1] += 1
        self._finish_admit(slot, req, self._sample_first(logits), n)

    def _admit_batch(self, slots: list[int], reqs: list[Request],
                     bucket: int) -> None:
        """Batched async admission: one [N, bucket] prefill dispatch that
        scatters N prompts' KV into N slots and samples their first tokens
        on device. NOTHING here blocks on the device: the sampled [N] token
        array stays device-resident — fed into the next decode dispatch as
        a per-slot override, and delivered to the clients through the tick
        loop's batched fetch (_deliver's firsts manifest)."""
        n = len(reqs)
        lens = [int(r.tokens.shape[0]) for r in reqs]
        # the padded batch is built in NUMPY: a jnp .at[].set here would
        # XLA-compile one scatter per (row, length) shape at first use —
        # measured 100-450 ms stalls inside the serving loop. Host memory
        # writes cost nothing and the jitted step transfers the array once.
        padded = np.zeros((n, bucket), np.int32)
        for i, req in enumerate(reqs):
            padded[i, :lens[i]] = np.asarray(req.tokens)
        # one key split per admission BATCH (host-side; admissions are rare
        # next to ticks; the split/slice shapes are warmed per batch size).
        # Greedy never consumes the keys but the executable still takes
        # them, so the signature is sampling-config-agnostic.
        keys = jax.random.split(self._admit_key, n + 1)
        self._admit_key, batch_keys = keys[0], keys[1:]
        with jax.profiler.TraceAnnotation(
                "vtpu.admit.batch", n=n, bucket=bucket, tokens=sum(lens)):
            tok, self._admit_buf, self.state = self._admit_step(
                self.params, self.state, self._admit_buf, padded,
                np.asarray(slots, np.int32), np.asarray(lens, np.int32),
                batch_keys,
            )
        self._stats["prefill_tokens"] += sum(lens)
        self._note_expert_rows(n * bucket)
        rows = []
        for i, (slot, req) in enumerate(zip(slots, reqs)):
            self._begin_slot(slot, req, lens[i])
            self._admit_mask[slot] = True
            rows.append((slot, req, i))
        self._pending_firsts.append({"tokens": tok, "rows": rows})
        self._stats["prefill_batch_hist"][n] += 1

    def _begin_slot(self, slot: int, req: Request, n: int,
                    firsts: int = 1) -> None:
        """Async-admission slot bookkeeping: everything _finish_admit does
        EXCEPT consuming the first token's value, which is still device-
        resident (delivered later by _emit_first through a batched fetch).
        The first token's budget slice is reserved here so the dispatch
        predicates see the same numbers as the legacy path (``firsts`` 0:
        an admission that yields no token, _open_first_block)."""
        self._slot_req[slot] = req
        ctx = self.model.max_context
        budget = min(req.max_new_tokens, ctx - n) if ctx else req.max_new_tokens
        self._slot_budget[slot] = budget - firsts
        self._slot_len[slot] = n
        self._itl_last[slot] = None
        if self._track_history:
            # cache-contents mirror (prefix + prompt; the first token joins
            # at delivery via _emit_first) — what a park must save and a
            # recompute-on-fault rebuilds
            self._seed_history(slot, req, n)
        self._stats["admissions"] += 1
        self._note_admit(req, slot, n)

    def _begin_slot_async(self, slot: int, req: Request, logits_row,
                          n: int) -> None:
        """Async admission for the single-row tails (prefix-only and final-
        chunk): sample the first token on device from one [vocab] logits
        row and queue it for the next batched fetch."""
        if self.serving.temperature <= 0.0:
            tok = self._argmax1(logits_row)
        else:
            self._admit_key, sub = jax.random.split(self._admit_key)
            tok = self._sample1(logits_row, sub)
        self._begin_slot(slot, req, n)
        self._admit_buf = self._set_buf1(
            self._admit_buf, jnp.int32(slot), tok)
        self._admit_mask[slot] = True
        self._pending_firsts.append({"tokens": tok, "rows": [(slot, req, None)]})

    def _admit_waiting(self, budget: float) -> tuple[bool, float]:
        """Admission scheduler: fill free slots from the waiting list under
        the per-tick prompt-token budget. FIFO at the head; same-bucket
        prompts COALESCE from anywhere in the list into one [N, bucket]
        batched dispatch (async mode), so a burst drains in ceil(K/Nmax)
        dispatches. Head-of-line blocking on budget is deliberate: when the
        head's bucket doesn't fit the remaining budget, nothing younger
        jumps it — the deferral lasts one tick, not a scheduling epoch.
        Returns (any admission happened, remaining budget)."""
        admitted = False
        free = [i for i in range(self.serving.slots)
                if self._slot_req[i] is None and i not in self._admitting]
        while self._waiting and free:
            head = self._waiting.head()
            if head.cancelled:
                self._waiting.popleft()
                self._end_stream(head, head._abort or Status.CANCELLED)
                continue
            n_head = int(head.tokens.shape[0])
            if head.prefix is not None or self._bucket(n_head) is None:
                # chunked routes park and pay their prompt tokens from the
                # budget as their chunks advance (see _advance_admissions)
                if self._chunk_lanes and len(self._admitting) >= self._chunk_lanes:
                    break  # the budget's chunks are all spoken for: head waits
                if self._paged and not self._reserve_paged(free[0], head):
                    break  # pool exhausted: head parks (backpressure)
                self._waiting.popleft()
                head.t_depart_ns = time.monotonic_ns()
                self.trace.record("queue_depart", head.rid, free[0])
                self._admit(free.pop(0), head)
                admitted = True
                continue
            bucket = self._bucket(n_head)
            if not self._async_admission:
                if bucket > budget:
                    break
                if self._paged and not self._reserve_paged(free[0], head):
                    break  # pool exhausted: head parks (backpressure)
                self._waiting.popleft()
                head.t_depart_ns = time.monotonic_ns()
                self.trace.record("queue_depart", head.rid, free[0])
                self._admit(free.pop(0), head)
                budget -= bucket
                admitted = True
                continue
            # gather the head's same-bucket companions (FIFO within the
            # bucket) into the largest warmed batch that fits the free
            # slots and the remaining budget
            cap = min(len(free), max(self._admit_sizes))
            group = [head]
            for req in self._waiting:
                if req is head:
                    continue
                if len(group) >= cap:
                    break
                if (not req.cancelled and req.prefix is None
                        and self._bucket(int(req.tokens.shape[0])) == bucket):
                    group.append(req)
            fit = [s for s in self._admit_sizes
                   if s <= len(group) and s * bucket <= budget]
            if not fit:
                break  # budget exhausted for the head-of-line bucket
            n = max(fit)
            batch = group[:n]
            if self._paged:
                # pool-aware batch: reserve per member in FIFO order; the
                # first member the free list can't cover truncates the
                # batch (nothing younger jumps it — same head-of-line
                # discipline as the budget), shrunk to a WARMED size with
                # the overshoot's reservations rolled back
                ok = 0
                for j, req in enumerate(batch):
                    if not self._reserve_paged(free[j], req):
                        break
                    ok += 1
                if ok == 0:
                    break  # head blocked on pool: stays parked in waiting
                m = max(s for s in self._admit_sizes if s <= ok)
                for j in range(m, ok):
                    self._free_slot_blocks(free[j])
                batch = batch[:m]
            for req in batch:
                self._waiting.remove(req)
                req.t_depart_ns = time.monotonic_ns()
                self.trace.record("queue_depart", req.rid)
            slots = [free.pop(0) for _ in batch]
            self._admit_batch(slots, batch, bucket)
            budget -= len(batch) * bucket
            admitted = True
        return admitted, budget

    def _install_handoffs(self) -> bool:
        """Disaggregated decode-side pickup: map each completed handoff's
        already-filled blocks into a freed slot — ONE fused table-row +
        length write (the same op a resume remap uses) and pure host
        bookkeeping. The prefill worker already computed and delivered the
        first token, so the slot resumes with its pending token exactly
        like a parked session: the next decode tick feeds it and the
        existing one-fetch tick contract carries the stream. ZERO KV bytes
        move here — handoff_copies stays 0 by construction."""
        rt = self._disagg
        installed = False
        for slot in range(self.serving.slots):
            if self._slot_req[slot] is not None or slot in self._admitting:
                continue
            while True:
                e = rt.pop_ready()
                if e is None:
                    return installed
                req = e["req"]
                if not req.cancelled:
                    break
                # discard the dead entry and retry the SAME free slot: a
                # live handoff behind it must not wait out a tick. The
                # worker delivered its first token, so the request BEGAN
                # service — count the admission (the installed and
                # worker-retired paths both do; dropping it here would
                # undercount vs co-scheduled under cancellation load)
                blocks = e["shared"] + e["priv"]
                if blocks:
                    self._alloc.release(blocks)
                self._stats["admissions"] += 1
                self._end_stream(req, req._abort or Status.CANCELLED)
            n_pages, seq_len = e["n_pages"], e["seq_len"]
            # the handoff entry is park-shaped by construction, so the
            # resume remap IS the install: one fused table-row + length
            # write plus the shared slot-restore bookkeeping (a field
            # added to the restore path cannot miss handed-off sessions)
            self._finish_resume_slot(slot, e)
            # the next decode token's gap counts from the worker's first-
            # token delivery, the same clock origin the co-scheduled
            # path's _emit_first stamps (the restore cleared it)
            self._itl_last[slot] = e["t_first"]
            self._stats["admissions"] += 1
            self.trace.record("pool_install", req.rid, slot, n_pages)
            self.trace.record("admit", req.rid, slot, seq_len)
            installed = True
        return installed

    def _advance_admissions(self, budget: float = float("inf")) -> float:
        """One prefill chunk per mid-admission slot (then back to the decode
        tick), sharing the per-tick prompt-token budget with bucketed
        admission. The rotation makes budget pressure fair: a different
        admitting slot leads each tick, so no admission systematically
        starves. The final chunk completes admission."""
        order = sorted(self._admitting)
        if len(order) > 1:
            lead = self._adm_rr % len(order)
            order = order[lead:] + order[:lead]
        self._adm_rr += 1
        for slot in order:
            adm = self._admitting[slot]
            req, n, off, base = adm["req"], adm["n"], adm["off"], adm["base"]
            if req.cancelled:
                del self._admitting[slot]
                self._free_slot_blocks(slot)
                self._end_stream(req, req._abort or Status.CANCELLED, slot)
                continue
            c = self._chunk
            if c > budget:
                break  # remaining admitting slots advance next tick
            try:
                # off indexes the (suffix-)padded array; base is the
                # installed prefix length, so the device offset is base+off
                need = base + off + c
                kv_bucket = next(
                    (bkt for bkt in self._kv_buckets if bkt >= need),
                    self.model.max_context,
                )
                extra = {}
                if self._paged:
                    # the slot's mapped blocks, window-sized and
                    # null-padded: chunk gathers/scatters are
                    # page-granular over the pool
                    wp = kv_bucket // self._page
                    row = np.zeros((wp,), np.int32)
                    blocks = self._slot_blocks[slot]
                    m = min(len(blocks), wp)
                    row[:m] = blocks[:m]
                    extra["block_ids"] = row
                real = min(base + off + c, n) - (base + off)  # less the pads
                with jax.profiler.TraceAnnotation(
                        "vtpu.admit.chunk", tokens=real):
                    logits, self.state = self._prefill_chunk(
                        self.params, self.state,
                        adm["padded"][:, off:off + c],
                        jnp.int32(slot), jnp.int32(base + off),
                        jnp.int32(base + off + real),
                        kv_bucket=kv_bucket, unroll=self._unroll, **extra,
                    )
                adm["off"] = off + c
                budget -= c
                self._stats["prefill_chunks"] += 1
                self._stats["prefill_tokens"] += real
                if self._chunk_expands is not None:
                    self._stats["chunk_attn_launches"] += 1
                    self._stats["chunk_attn_expanded"] += int(
                        self._chunk_expands(c))
                    kernel, attended = self._chunk_keys(c, need, kv_bucket)
                    self._stats["chunk_attn_kernel"] += int(kernel)
                    self._stats["chunk_keys_live"] += need
                    self._stats["chunk_keys_attended"] += attended
                self._note_expert_rows(c)
                self.trace.record("prefill_chunk", req.rid, slot, c)
                if adm["off"] >= adm["padded"].shape[1]:  # final chunk
                    del self._admitting[slot]
                    if adm.get("resume") is not None:
                        # chunked recompute-on-fault: the cache is rebuilt
                        # and the pending token was delivered BEFORE the
                        # park — restore the slot, sample and emit nothing
                        self._restore_slot(slot, adm["resume"])
                        continue
                    if self._blocks:  # no first token: the passes' own
                        self._open_first_block(slot, req, adm["tail"])
                        continue
                    pad = adm["padded"].shape[1]
                    last_row = logits[0, (n - base - 1) - (pad - c)]
                    if self._async_admission:
                        self._begin_slot_async(slot, req, last_row, n)
                    else:
                        self._finish_admit(
                            slot, req, self._sample_first(last_row), n)
            except Exception:
                # crash containment on the per-request admission path: the
                # one admitting request faults (typed terminal, reserved
                # blocks released); live streams and the other admissions
                # keep going
                self._admitting.pop(slot, None)
                self._stats["faulted_requests"] += 1
                self.trace.record("fault", req.rid, slot)
                log.exception("request %s faulted mid-admission in slot "
                              "%d; containing", req.rid, slot)
                self._free_slot_blocks(slot)
                self._slot_req[slot] = None
                self._end_stream(req, Status.FAULTED, slot)
        return budget

    def _sample_first(self, logits) -> int:
        """Sample a request's FIRST token from its prefill logits. Host
        fallback uses the configured callable; device sampling draws greedy
        (key-free argmax) or one categorical sample from the admission key
        stream. Either way this is a per-ADMISSION device sync of a handful
        of bytes, not a per-tick one — the tick loop's transfer contract
        (see _fetch) is unaffected. The callable's contract is a fetched
        numpy [vocab] row at BOTH call sites (here and the per-tick
        fallback loop), never a device array. Counted as an admission_sync:
        the batched-async path exists to make this counter stay at zero."""
        self._stats["admission_syncs"] += 1
        if not self._device_sampling:
            return self.sample(jax.device_get(logits))
        if self.serving.temperature <= 0.0:
            return int(jnp.argmax(logits))
        self._admit_key, sub = jax.random.split(self._admit_key)
        return int(self._sample1(logits, sub))

    def _fetch(self, arrays, kind: str = "tick", ticks: int = 1):
        """The loop's ONLY device->host read: one batched device_get per
        call, counted with its payload bytes so stats() can prove the
        per-tick transfer contract (device_gets_per_tick == 1.0, and
        bytes_fetched_per_tick == B*4 on the device-sampled path vs
        B*vocab*4 on the host-sampler fallback; with the k-tick device
        loop ONE fetch covers k inner ticks — device_gets_per_token ==
        1/k). kind="tick" is a tick delivery (admission first tokens
        piggyback on it for free); kind="admission" is the standalone
        batched first-token fetch an idle engine performs so TTFT never
        waits for a decode tick. ``ticks`` attributes the fetch phase over
        the inner ticks the fetched flush carried."""
        self._stats["device_gets"] += 1
        self._stats["tick_fetches" if kind == "tick"
                     else "admission_fetches"] += 1
        self._stats["bytes_fetched"] += sum(
            a.size * a.dtype.itemsize
            for a in jax.tree_util.tree_leaves(arrays))
        t0 = _watchdog_clock()
        # fetch phase = device wait + transfer: on the pipelined loop this
        # is the time the host blocks for the in-flight tick to finish —
        # the device-bound share of the tick, attributed separately from
        # the Python bookkeeping phases
        with self._prof.phase("fetch", ticks=ticks):
            spec = self._fire_fault("delayed_fetch")
            if spec is not None:
                # injected device stall: the fetch blocks like a wedged
                # transfer would — what the watchdog below exists to catch
                time.sleep(spec.arg or 0.05)
            out = jax.device_get(arrays)
        dt = _watchdog_clock() - t0
        wd = self.serving.fetch_watchdog_ms
        if wd:
            if dt * 1e3 > wd:
                self._trip_watchdog(dt)
            elif (self._applied_rungs
                    and self.serving.fetch_watchdog_recover_ms):
                # healthy fetch on a degraded engine: extend (or start)
                # the recovery streak; a full grace window of them
                # un-degrades one rung, and the clock restarts so every
                # further rung needs its own window
                now = _watchdog_clock()
                if self._healthy_since is None:
                    self._healthy_since = now
                elif ((now - self._healthy_since) * 1e3
                        >= self.serving.fetch_watchdog_recover_ms):
                    self._recover_watchdog()
                    self._healthy_since = now
        return out

    def _tick_count(self) -> int:
        """The ``tick`` id of the loop's profiler spans."""
        return self._stats["decode_ticks"] + self._stats["spec_ticks"]

    def _note_expert_rows(self, rows: int, launches: int = 1) -> None:
        """``launches`` launches of ``rows`` rows each through a model that
        holds experts: host integers, no fetch."""
        if self._experts_grouped is None:
            return
        self._stats["expert_rows"] += rows * launches
        if self._experts_grouped(rows):
            self._stats["expert_rows_grouped"] += rows * launches

    def _note_kv_window(self, kv_bucket: int, lens: list[int],
                        t: int = 1, ticks: int = 1, wrote: int = 1) -> None:
        """Per-dispatch read-window telemetry. kv_bucket_hist surfaces the
        global read tax: every dispatched tick's window, set by the LONGEST
        live sequence — on the dense path that window is streamed verbatim
        for every slot. ``lens`` carries each dispatched slot's device-side
        length THIS tick will read up to (exclusive of the ``wrote`` = 1
        position a step writes to the cache before it reads; 0 for a pass
        of a model that generates by blocks, whose rows read the block's
        own keys beside the cache and not from it); under paging the
        live-page counters quantify how much of the window each slot
        actually maps (the rest dedupes onto the null block instead of
        streaming distinct lines). ``ticks`` (> 1 for a
        k-tick device-loop flush) scales every per-tick counter so the
        window/route accounting stays denominated in INNER ticks; the
        live-page figures use the dispatch-time lengths for all k (a
        bounded undercount of at most one page per slot per flush — the
        loop advances lengths on device, invisible between flushes)."""
        hist = self._stats["kv_bucket_hist"]
        key = int(kv_bucket) or int(self.model.max_context or 0)
        hist[key] = hist.get(key, 0) + ticks
        self._note_expert_rows(self.serving.slots * t, ticks)
        if self._select_topk or self._window_ring or self._blocks:
            # + wrote: a step sees the token it writes
            self._stats["attn_visible_tokens"] += (
                sum(lens) + wrote * len(lens)) * ticks
        if self._select_topk:
            whole = [ln + 1 <= (self._select_dense or 0) for ln in lens]
            self._stats["attn_selected_tokens"] += sum(
                ln + 1 if w else min(ln + 1, self._select_topk)
                for ln, w in zip(lens, whole)) * ticks
            self._stats["select_rows_dense"] += sum(whole) * ticks
            self._stats["select_rows"] += (len(lens) - sum(whole)) * ticks
        if self._window_ring:
            self._stats["window_rows_read"] += sum(
                min(ln + 1, self._window_ring) for ln in lens) * ticks
        elif self._recurrent_bytes:
            self._stats["ssm_rows_stepped"] += self.serving.slots * ticks
            self._stats["ssm_rows_live"] += len(lens) * ticks
            if self._ssm_in_kernel is not None and self._ssm_in_kernel():
                self._stats["ssm_kernel_ticks"] += ticks
        if self._paged and lens:
            page = self._page
            live = sum(-(-(ln + wrote) // page) for ln in lens)
            self._stats["read_pages_live"] += live * ticks
            if self._latent_walk:
                self._stats["latent_rows_live"] += (
                    sum(lens) + len(lens)) * ticks
                self._stats["latent_rows_walked"] += page * ticks * (
                    live + self.serving.slots - len(lens))
            self._stats["read_pages_window"] += (key // page) * len(lens) * ticks
            rh = self._stats["read_pages_hist"]
            rh[live] = rh.get(live, 0) + ticks
            # kernel-vs-gather route accounting: the trunk resolves the
            # route statically from the same (override, window, chunk
            # width, quantization) inputs, so this host-side count IS what
            # the dispatched executable did
            # (a model that generates by blocks states its own rule)
            route = self.model.block_attn_route() if self._blocks else \
                paged_attn_route(self._paged_attn, key, t=t,
                                 quant="k_scale" in self.state)
            self._stats["paged_attn_kernel_ticks" if route == "kernel"
                        else "paged_attn_gather_ticks"] += ticks

    def _note_itl(self, slot: int, now: float) -> None:
        """Record one inter-token gap for *slot* into the trace substrate
        (first token after admission only stamps the clock — that interval
        is TTFT). The stats() percentiles and the exporter's ITL histogram
        are views over what lands here."""
        last = self._itl_last[slot]
        if last is not None:
            self.trace.note_itl(now - last)
        self._itl_last[slot] = now

    def _note_admit(self, req: Request, slot: int, n: int) -> None:
        """Trace an admission: the 'admit' lifecycle event plus the
        queue-wait reservoir sample (submit -> slot bookkeeping)."""
        now_ns = time.monotonic_ns()
        self.trace.record("admit", req.rid, slot, n)
        if req.t_submit_ns:
            self.trace.note_queue_wait((now_ns - req.t_submit_ns) / 1e9)

    def _note_first_token(self, req: Request, slot: int) -> None:
        """Trace a request's first delivered token + its TTFT sample, and
        the prefill-execution component (queue departure -> first token):
        with the queue-wait reservoir it splits TTFT into where the time
        actually went — the attribution the disagg A/B is judged on."""
        now_ns = time.monotonic_ns()
        self.trace.record("first_token", req.rid, slot)
        if req.t_submit_ns:
            self.trace.note_ttft((now_ns - req.t_submit_ns) / 1e9)
        dep = req.t_depart_ns or req.t_submit_ns
        if dep:
            self.trace.note_prefill_exec((now_ns - dep) / 1e9)

    def _deliver_firsts(self, firsts: list[dict],
                        fetched: Optional[list] = None) -> None:
        """Deliver admission first tokens from their device arrays. When
        ``fetched`` is None this is the IDLE-engine path: one standalone
        batched fetch for the whole admission wave (kind="admission" —
        never counted against the tick contract). Otherwise the caller
        already fetched the arrays jointly with a tick's tokens and passes
        the host copies. Delivery order guarantees a slot's first token
        precedes any decode token the same pass delivers for it."""
        if fetched is None:
            fetched = self._fetch(tuple(f["tokens"] for f in firsts),
                                  kind="admission")
        if self._died:
            return  # fleet fencing, post-fetch (see _deliver)
        for f, arr in zip(firsts, fetched):
            for slot, req, idx in f["rows"]:
                if req is not self._slot_req[slot]:
                    continue  # retired between dispatch and delivery
                if req.cancelled:
                    self._retire(slot)
                    continue
                try:
                    self._emit_first(
                        slot, int(arr if idx is None else arr[idx]))
                except Exception:
                    # containment: a first-token delivery failure kills
                    # only its own admission
                    self._contain_fault(slot)

    def _emit_first(self, slot: int, tok: int) -> None:
        """Deliver an async-admitted request's FIRST token (its budget
        slice was already reserved by _begin_slot; the cache length does
        not move — the token's KV lands when the next decode tick consumes
        it, exactly like the legacy path)."""
        req = self._slot_req[slot]
        self._tokens[slot] = tok
        if self._track_history:
            self._history[slot].append(tok)
        self._itl_last[slot] = time.perf_counter()
        self._note_first_token(req, slot)
        req.delivered += 1
        req.out.put(tok)
        self._stats["generated_tokens"] += 1
        if self._slot_budget[slot] <= 0 or tok == self.serving.eos_token:
            self._retire(slot)

    def _deliver(self, tick: dict, firsts: Optional[list] = None) -> None:
        """Deliver one decode tick's device-sampled tokens: ONE batched
        fetch, then pure-Python bookkeeping (stream, budget, eos, retire),
        the "deliver" phase. ``firsts`` is this pass's async-admission
        manifest: the first-token arrays ride
        the SAME batched fetch (a few extra bytes, zero extra syncs) and
        are delivered before the tick's tokens, so a freshly admitted
        slot's stream always starts with its prefill-derived token.

        ``tick["reqs"]`` snapshots each slot's Request AT DISPATCH; a slot
        whose occupant changed since (retired on the previous delivery,
        cancelled, or recycled to a new request) fails the identity check
        and its in-flight token is dropped — that token belongs to a
        sequence that no longer exists, and the device state it advanced is
        overwritten by the slot's next admission. This check is what makes
        the one-tick lookahead safe: retire/admit invalidate a single
        slot's lookahead, never the tick."""
        extra = tuple(f["tokens"] for f in firsts) if firsts else ()
        if tick["logprobs"] is not None:
            toks, lps, *first_arrs = self._fetch(
                (tick["tokens"], tick["logprobs"]) + extra)
        else:
            toks, *first_arrs = self._fetch((tick["tokens"],) + extra)
            lps = None
        if self._died:
            # the fleet fencing flag, checked AFTER the fetch (the block
            # site a wedged loop thread resumes from): a DEAD-declared
            # engine's sessions may already be rebuilt on survivors —
            # emitting here would deliver the same tokens from two
            # engines. Drop the whole delivery; the loop exits at its
            # next while-check without cleanup (crash semantics).
            return
        with self._prof.phase("deliver"):
            if firsts:
                self._deliver_firsts(firsts, fetched=first_arrs)
            now = time.perf_counter()
            for slot, req in enumerate(tick["reqs"]):
                if req is None or req is not self._slot_req[slot]:
                    continue
                try:
                    self._emit(slot, int(toks[slot]),
                               float(lps[slot]) if lps is not None else None,
                               now=now)
                except Exception:
                    # crash containment: an exception in ONE request's deliver
                    # path retires only that slot (typed FAULTED, blocks
                    # released) — the tick and every other stream keep going
                    self._contain_fault(slot)

    def _emit(self, slot: int, tok: int, lp: Optional[float] = None,
              now: Optional[float] = None,
              when: Optional[int] = None) -> None:
        """Per-slot bookkeeping for ONE delivered decode token — the single
        implementation behind the device-sampled delivery (_deliver), the
        host-sampler fallback and a clean block's tokens (_deliver_blocks),
        so budget/eos/retire semantics cannot fork between the paths.
        Mirrors the device first: its cache length advanced for this slot
        at dispatch, unconditionally of what eos does below. ``when`` marks
        a token of a model that generates by blocks, the request's pass
        that committed it (``Request.trail``): the writing pass, not the
        token, advances such a slot's length, and its admission delivered
        no first token, so the first one is stamped here."""
        self._maybe_inject_dispatch()
        req = self._slot_req[slot]
        self._tokens[slot] = tok
        if when is None:
            self._slot_len[slot] += 1
        elif req.delivered == 0:
            self._note_first_token(req, slot)
        self._note_itl(slot, now if now is not None else time.perf_counter())
        self.trace.record("token", req.rid, slot)
        # logprob (and the trail's entry) BEFORE the queue put: the put
        # unblocks the client thread, which may immediately read
        # logprobs[-1] expecting this token's entry to exist
        if lp is not None:
            req.logprobs.append(lp)
        if when is not None:
            req.trail.append(when)
        req.delivered += 1
        req.out.put(tok)
        self._stats["generated_tokens"] += 1
        self._slot_budget[slot] -= 1
        if self._track_history:
            self._history[slot].append(tok)
        if self._slot_budget[slot] <= 0 or tok == self.serving.eos_token:
            self._retire(slot)

    def _finish_admit(self, slot: int, req: Request, first: int, n: int) -> None:
        self._slot_req[slot] = req
        # the KV cache is a hard wall: never decode past max_seq
        ctx = self.model.max_context
        budget = min(req.max_new_tokens, ctx - n) if ctx else req.max_new_tokens
        self._slot_budget[slot] = budget - 1
        self._tokens[slot] = first
        self._slot_len[slot] = n
        if self._track_history:
            # _seed_history's .get tolerates the prefix having been
            # unregistered after this request's KV was installed — the
            # copied cache stays valid; the history pads placeholders
            # (flagged inexact) under overcommit, or simply loses the
            # optional prefix tokens for speculation drafts
            self._seed_history(slot, req, n)
            self._history[slot].append(first)
        self._stats["admissions"] += 1
        self._stats["generated_tokens"] += 1
        self._itl_last[slot] = time.perf_counter()
        self._note_admit(req, slot, n)
        self._note_first_token(req, slot)
        req.delivered += 1
        req.out.put(first)
        if self._slot_budget[slot] <= 0 or first == self.serving.eos_token:
            self._retire(slot)

    def _open_first_block(self, slot: int, req: Request,
                          tail: np.ndarray) -> None:
        """The end of an admission into a model that generates by blocks:
        the prompt's whole blocks are cached (the slot's length), its other
        tokens (``tail``, on the host) open the first generated block on
        the device as rows already committed, and the slot joins the
        passes. No token is delivered: the first come from the block's own
        passes."""
        bl = self._blocks
        n = int(req.tokens.shape[0])
        self._begin_slot(slot, req, n, firsts=0)
        self._slot_len[slot] = n - len(tail)
        req.trail = []
        ids = np.zeros((bl,), np.int32)
        ids[:len(tail)] = tail
        self.state = self._open_block(
            self.state, jnp.int32(slot), ids, np.arange(bl) >= len(tail),
            jnp.int32(n + self._slot_budget[slot]))
        if self._slot_budget[slot] <= 0:
            self._retire(slot)

    def _deliver_blocks(self, tick: dict) -> None:
        """Deliver one pass of a model that generates by blocks: ONE fetch
        of the pass's result, read by the model (``read_pass``: a row a
        slot), the counters of what the device did, and, for every slot
        whose block the pass left clean, its tokens in position order with
        the request's pass that committed each (``Request.trail``), cut at
        the request's budget. The request-identity check of _deliver guards
        the lookahead here too: a slot retired or given to another request
        since the dispatch drops its row."""
        (rows,) = self._fetch((tick["result"],))
        if self._died:
            return  # fleet fencing, post-fetch (see _deliver)
        bl = self._blocks
        got = self.model.read_pass(rows)
        took = int(got.took.sum())
        self._stats["block_slot_passes"] += took
        self._stats["block_write_passes"] += int(got.wrote.sum())
        self._stats["block_rows_dispatched"] += took * bl
        self._stats["block_rows_masked"] += int(got.eligible[got.took].sum())
        self._stats["block_tokens_committed"] += int(
            got.committed[got.took].sum())
        with self._prof.phase("deliver"):
            now = time.perf_counter()
            for slot, req in enumerate(tick["reqs"]):
                if req is None or req is not self._slot_req[slot]:
                    continue
                if got.wrote[slot]:
                    self._slot_len[slot] = int(got.first[slot]) + bl
                if not got.clean[slot]:
                    continue
                try:
                    for tok, when in zip(got.ids[slot], got.when[slot]):
                        if when < 0:  # a row of the prompt, or past the end
                            continue
                        self._emit(slot, int(tok), now=now, when=int(when))
                        if self._slot_req[slot] is not req:
                            break  # retired: budget spent, or eos
                except Exception:
                    self._contain_fault(slot)

    def _spec_probe_ema(self) -> float:
        """EMA value for a fresh probe: slightly above breakeven, so a
        losing probe decays below the gate within a few ticks (~6% spec
        duty cycle at the default cooloff, vs ~30% if reset to the
        optimistic maximum)."""
        return (self.serving.spec_min_mean or 1.0) + 0.25

    def _spec_allowed(self) -> bool:
        """Adaptive gate: drafting pauses while the per-slot emitted EMA
        sits below breakeven, and re-probes after the cooloff elapses."""
        if not self.serving.spec_min_mean:
            return True
        if self._spec_cooloff > 0:
            self._spec_cooloff -= 1
            if self._spec_cooloff == 0:
                self._spec_ema = self._spec_probe_ema()
            return False
        return True

    def signals(self) -> EngineSignals:
        """The engine's pressure snapshot as an ``EngineSignals`` — the
        SAME shape the shed policy receives at the overload seam, exposed
        so a fleet router (vtpu/serving/fleet.RoutePolicy) scores engines
        on it. Thread-safe for cross-thread readers: every field is a
        single read of a counter, gauge or locked property. ``duty`` is
        the attested device busy fraction from
        ``ServingConfig.duty_supplier`` (None without one — a raising
        supplier degrades to None, never to a dead caller)."""
        duty = None
        sup = self.serving.duty_supplier
        if sup is not None:
            try:
                duty = sup()
            except Exception:
                log.exception("duty_supplier raised; reporting duty=None")
        return EngineSignals(
            queue_depth=self._pending.qsize() + len(self._waiting),
            active_slots=sum(r is not None for r in self._slot_req),
            pool_free=self._alloc.free_blocks if self._paged else None,
            pool_used_hwm=self._alloc.used_hwm if self._paged else None,
            parked_sessions=len(self._parked),
            prefill_backlog=(self._disagg.backlog()
                             if self._disagg is not None
                             else len(self._admitting)),
            now_ns=time.monotonic_ns(),
            pool_blocks=(self._n_blocks - 1) if self._paged else None,
            draining=self._draining,
            duty=duty,
            # the cooloff EMA, policy-visible: LoopPolicy sizes the fused
            # flush window on it, Route/ShedPolicy can score with it
            spec_mean_accepted=(round(self._spec_ema, 3)
                                if self._spec_tokens else None),
        )

    def stats(self) -> dict:
        """Serving counters snapshot (thread-safe reads of monotonic
        counters): token/tick totals, speculation acceptance, occupancy.
        Acceptance numbers are PER SLOT-TICK (delivered tokens / slot
        participations) — directly comparable to spec_min_mean."""
        s = dict(self._stats)
        s["spec_emitted_hist"] = list(s["spec_emitted_hist"])
        s["prefill_batch_hist"] = list(s["prefill_batch_hist"])
        s["kv_bucket_hist"] = dict(s["kv_bucket_hist"])
        s["read_pages_hist"] = dict(s["read_pages_hist"])
        s["mean_emitted_per_spec_tick"] = round(
            s["spec_emitted"] / s["spec_slot_ticks"], 3
        ) if s["spec_slot_ticks"] else None
        s["spec_ema"] = round(self._spec_ema, 3)
        s["spec_cooling_off"] = self._spec_cooloff > 0
        # WHY configured speculation isn't running (None = not requested,
        # or running fine) — the silent-drop diagnosable from a scrape
        s["spec_disabled_reason"] = self._spec_disabled_reason
        s["fused_spec"] = self._fused_spec
        s["fused_k_hist"] = list(s["fused_k_hist"])
        s["loop_policy"] = (type(self._loop_policy).__name__
                            if self._loop_policy is not None else None)
        s["active_slots"] = sum(r is not None for r in self._slot_req)
        s["admitting_slots"] = len(self._admitting)
        s["queued"] = self._pending.qsize() + len(self._waiting)
        s["registered_prefixes"] = len(self._prefixes)
        # pool blocks currently mapped as SHARED prefix leads (live slots
        # + parked entries' held shares): a gauge computed from the
        # bookkeeping itself, so it can never drift from the allocator.
        # Snapshot-tolerant of a racing park/resume on the loop thread —
        # the two lists conserve the holds between them.
        try:
            s["prefix_shared_blocks"] = (
                sum(self._slot_shared)
                + sum(len(e["shared"]) for e in list(self._parked.values())))
        except RuntimeError:  # dict mutated mid-iteration: retry once
            s["prefix_shared_blocks"] = (
                sum(self._slot_shared)
                + sum(len(e["shared"]) for e in list(self._parked.values())))
        # per-tick transfer + host-overhead telemetry (the decode data-plane
        # contract: ONE batched device_get per tick delivery — admission
        # first tokens piggyback on it; an idle engine's admission wave
        # performs its own single batched fetch, counted separately so the
        # tick ratio stays an exact contract; B*4 bytes when sampling is
        # on-device, B*vocab*4 on the host-sampler fallback)
        ticks = s["decode_ticks"] + s["spec_ticks"]
        s["device_gets_per_tick"] = (
            round(s["tick_fetches"] / ticks, 4) if ticks else None)
        s["bytes_fetched_per_tick"] = (
            round(s["bytes_fetched"] / ticks, 1) if ticks else None)
        # multi-tick device loop: decode_ticks counts INNER ticks (k per
        # flush), so the transfer ratio above generalizes on its own —
        # device_gets_per_token is the explicit per-token reading of the
        # same contract (1.0 with the loop off, 1/k with a k-tick loop);
        # the host's share per inner tick is tick_phase_ms'
        # mean_ms_per_tick. tests/test_device_loop.py holds both to 1/k
        # exactly.
        s["decode_loop_k"] = self._loop_k or 1
        s["device_gets_per_token"] = (
            round(s["tick_fetches"] / ticks, 4) if ticks else None)
        # span telemetry is a VIEW over the trace substrate (vtpu/obs):
        # the ITL/TTFT/queue-wait reservoirs the engine feeds as it
        # delivers tokens — the same numbers the vtpu_serving_* exporter
        # publishes as histograms and bench.py audits per tenant
        gaps = sorted(self.trace.itl_gaps())
        for q, key in ((0.5, "itl_p50_ms"), (0.99, "itl_p99_ms")):
            v = pct(gaps, q)
            s[key] = round(v * 1e3, 3) if v is not None else None
        ttfts = sorted(self.trace.ttft_samples())
        for q, key in ((0.5, "ttft_p50_ms"), (0.95, "ttft_p95_ms"),
                       (0.99, "ttft_p99_ms")):
            v = pct(ttfts, q)
            s[key] = round(v * 1e3, 3) if v is not None else None
        waits = sorted(self.trace.queue_wait_samples())
        for q, key in ((0.5, "queue_wait_p50_ms"), (0.99, "queue_wait_p99_ms")):
            v = pct(waits, q)
            s[key] = round(v * 1e3, 3) if v is not None else None
        # prefill-execution component of TTFT (queue departure -> first
        # token): with the queue-wait reservoir above it attributes a TTFT
        # regression to waiting vs prefilling — the split a span carries
        # (tests/test_disagg.py) and the ttft_benchmark /stats endpoint reports
        pexec = sorted(self.trace.prefill_exec_samples())
        for q, key in ((0.5, "prefill_exec_p50_ms"),
                       (0.99, "prefill_exec_p99_ms")):
            v = pct(pexec, q)
            s[key] = round(v * 1e3, 3) if v is not None else None
        s["trace_enabled"] = self.trace.enabled
        s["trace_events_recorded"] = self.trace.events_recorded
        s["trace_events_dropped"] = self.trace.events_dropped
        # ring-health gauges: a wrapping ring silently truncates derived
        # spans AND the fleet's stitched journeys (token conservation
        # reads the ring) — utilization at 1.0 means events are falling
        # off and the scrape should say so before a post-mortem finds out
        s["trace_ring_capacity"] = self.trace.capacity if self.trace.enabled else 0
        s["trace_ring_utilization"] = (
            round(min(self.trace.events_recorded, self.trace.capacity)
                  / self.trace.capacity, 4)
            if self.trace.enabled else None)
        # tick-phase attribution: where the loop thread's time goes
        # (admission head / dispatch / fetch / deliver / swap drain / idle
        # wait), and the warm-up's seconds by kind
        s["tick_phase_ms"] = self._prof.snapshot()
        # the time lost whole: the loop's samples judged long (the last 64;
        # their sums are tick_phase_ms' long_ms) and the process's pauses
        # and collections (one watch a process: engines of one process
        # report the same)
        s["tick_long"] = self._prof.long_snapshot()
        s["pauses"] = pauses.WATCH.snapshot()
        s["warmup_s"] = self._warmup.snapshot()
        s["device_sampling"] = self._device_sampling
        s["pipelined"] = self._pipeline
        s["batched_admission"] = self._async_admission
        # KV-memory data plane: what sequence memory actually costs. The
        # dense estimate is the worst-case pin (slots * max_seq — what the
        # classic ring allocates no matter the traffic); the paged figure
        # is the pool's real footprint. Their ratio at equal slot count is
        # the oversubscription headroom the driver artifacts audit.
        s["paged"] = self._paged
        s["kv_page"] = self._page
        cfg = self.cfg
        # SSM configs have no attention geometry (no KV cache to estimate)
        bpt = getattr(self.model, "kv_bytes_per_token", None) or (
            kv_bytes_per_token(cfg)
            if cfg is not None and hasattr(cfg, "head_dim") else None)
        ctx = self.model.max_context
        # Under a tp mesh the cache/pool shards its head axis, so each chip
        # holds 1/tp of the global bytes — and the per-container
        # TPU_DEVICE_MEMORY_LIMIT_<i> cap the operator sizes against is a
        # PER-CHIP number. kv_hbm_bytes therefore reports per-chip bytes
        # under a mesh (global == per-chip on one chip, so the single-chip
        # figures are unchanged); kv_hbm_bytes_per_chip carries the same
        # numbers explicitly for audits that must not care about the mesh.
        mesh = getattr(self.model, "mesh", None)
        tp = int(mesh.shape.get("tp", 1)) if mesh is not None else 1
        s["tp"] = tp
        s["kv_hbm_bytes"] = {
            "dense": (self.serving.slots * ctx * bpt // tp
                      if bpt and ctx else None),
            "paged": (self._n_blocks * self._page * bpt // tp
                      if self._paged and bpt else None),
        }
        s["kv_hbm_bytes_per_chip"] = dict(s["kv_hbm_bytes"])
        # a session's memory that does not grow with its length: the
        # recurrent rows of every slot, beside the pool's bytes above
        s["recurrent_state_bytes"] = self._recurrent_bytes
        # ... of which a family with window layers holds rings: the rows a
        # ring has, and what a cached token would cost those layers were
        # they paged as the full layers are (None: no window layers)
        s["window_ring"] = self._window_ring
        # rows of a block, for a model that generates by blocks (0: none)
        s["block_length"] = self._blocks
        s["ring_bytes_per_position"] = getattr(
            self.model, "ring_bytes_per_position", None)
        if self._paged:
            usable = self._n_blocks - 1  # minus the reserved null block
            free = self._alloc.free_blocks
            s["kv_pool_blocks"] = usable
            s["kv_pool_free"] = free
            s["kv_pool_used"] = usable - free
            s["kv_pool_occupancy"] = round(
                (usable - free) / usable, 4) if usable else None
            s["read_pages_ratio"] = (
                round(s["read_pages_live"] / s["read_pages_window"], 4)
                if s["read_pages_window"] else None)
            s["kv_pool_used_hwm"] = self._alloc.used_hwm
        else:
            s["kv_pool_blocks"] = None
            s["kv_pool_free"] = None
            s["kv_pool_used"] = None
            s["kv_pool_occupancy"] = None
            s["read_pages_ratio"] = None
            s["kv_pool_used_hwm"] = None
        # KV overcommit: parked population and the host swap tier's state
        # (capacity/free in blocks); the flow counters — parks/resumes,
        # evicted_blocks, swap_out/in_bytes, swap_faults, fault_recomputes
        # — ride the _stats copy above
        # failure domains: the FaultPlan's own injection count (0 with no
        # plan — the seams are inert), next to the shed/fault/restart/
        # degrade counters riding the _stats copy above
        s["faults_injected"] = (
            self._faults.injected_total if self._faults is not None else 0)
        # live migration / drain: whether admission is closed for an
        # evacuation — the gauge a fleet router reads to stop targeting
        # this engine (the flow counters ride the _stats copy above)
        s["draining"] = self._draining
        s["kv_swap"] = self.serving.kv_swap if self._swap_enabled else None
        s["parked_sessions"] = len(self._parked)
        s["swap_host_blocks"] = (
            self._swap_host_blocks if self._swap_enabled else None)
        s["swap_host_free"] = (
            len(self._host_free) if self._swap_enabled else None)
        # disaggregated prefill/decode: handoff counters (handoff_copies
        # is the zero-copy contract — device copies performed by the
        # handoff path, 0 by construction), the live prefill backlog the
        # controller partitions on, and the worker-side flow counters
        # merged into the engine totals so the two modes stay comparable.
        # Worker fetches land in admission_fetches/device_gets (their own
        # thread's reads, like idle-engine admission fetches) and NEVER in
        # tick_fetches — device_gets_per_tick stays a decode-side contract.
        if self._disagg is not None:
            rtc = self._disagg.counters_snapshot()
            s["disagg"] = True
            s["handoffs"] = rtc["handoffs"]
            s["handoff_copies"] = rtc["handoff_copies"]
            s["repartitions"] = self._disagg.controller.repartitions
            s["prefill_backlog"] = self._disagg.backlog()
            s["prefill_share_tokens"] = self._disagg.controller.prefill_share
            s["generated_tokens"] += rtc["first_tokens"]
            s["admissions"] += rtc["worker_retired"]
            # a claimed or ready request has left _waiting but is not
            # streaming yet — without this the queued gauge under-reads
            # the moment disagg turns on (cross-mode dashboards compare it)
            s["queued"] += self._disagg.owned()
            s["prefill_chunks"] += rtc["prefill_chunks"]
            s["prefill_tokens"] += rtc["prefill_tokens"]
            s["device_gets"] += rtc["fetches"]
            s["admission_fetches"] += rtc["fetches"]
            s["bytes_fetched"] += rtc["bytes_fetched"]
            s["prefix_blocks_shared"] += rtc["prefix_blocks_shared"]
            s["prefix_cow_copies"] += rtc["prefix_cow_copies"]
            s["pool_blocked_admissions"] += rtc["pool_blocked_prefills"]
            # worker-side failure-domain counters: deadline sheds at the
            # claim path and faults a worker terminated, merged so the
            # totals stay mode-equal with the co-scheduled loop
            s["shed_deadline"] += rtc["shed_deadline"]
            s["faulted_requests"] += rtc["faulted_requests"]
        else:
            s["disagg"] = False
            s["handoffs"] = 0
            s["handoff_copies"] = 0
            s["repartitions"] = 0
            s["prefill_backlog"] = 0
            s["prefill_share_tokens"] = None
        s["loop_error"] = (None if self._loop_error is None
                           else repr(self._loop_error))
        return s

    @property
    def tick_profile(self) -> TickProfiler:
        """The tick-phase profiler (vtpu/obs/tickprof): per-phase bounded
        histograms behind stats()['tick_phase_ms'] and the exporter's
        vtpu_serving_tick_phase_seconds family."""
        return self._prof

    def _retire(self, slot: int, status: Optional[str] = None) -> None:
        req = self._slot_req[slot]
        if req is not None:
            # terminal resolution: an explicit status (FAULTED, shutdown
            # CANCELLED) wins; otherwise the request's own requested abort
            # (cancel/shed) names the reason; a clean budget/eos end is OK
            self._end_stream(req, status or req._abort or Status.OK, slot)
        self._slot_req[slot] = None
        self._slot_budget[slot] = 0
        self._slot_len[slot] = 0
        self._history[slot] = []
        self._slot_hist_exact[slot] = True
        self._itl_last[slot] = None
        self._admit_mask[slot] = False
        # paged: the slot's pages go back to the pool — this release is
        # what un-parks a pool-blocked admission on the next tick. The
        # device table row stays stale (inactive reads are masked, writes
        # drop) and is overwritten wholesale at the next reservation.
        self._free_slot_blocks(slot)

    def _warm_executables(self) -> None:
        """Compile every decode and prefill bucket before serving: a
        first-use compile mid-serving would stall every live stream for
        seconds at each bucket boundary. Runs on the loop thread (start()
        stays fast). The decode warm tick is all-inactive (advances nothing);
        the prefill warm writes junk into slot 0's row, which is harmless —
        no request occupies it and admission overwrites slot state. Each
        program warms under a profiler span ``vtpu.warm`` (ids: program,
        bucket), for a trace of a slow start read by hand."""
        b = self.serving.slots
        span = functools.partial(jax.profiler.TraceAnnotation, "vtpu.warm")
        tokens = self._place(np.zeros((b,), np.int32))
        inactive = jnp.zeros((b,), bool)
        for bucket in (self._kv_buckets if self._unroll else (0,)):
            with span(program="decode", bucket=bucket):
                # Each device-sampled step is dispatched TWICE: with tokens
                # built on the host (a tick after idle) and with the tokens the
                # step itself returned (every steady-state tick). Where the two
                # carry the same sharding the second dispatch is a cache hit
                # and costs one masked tick; where they do not (a mesh whose
                # compiler picked another output sharding) the second
                # executable is compiled HERE instead of mid-stream.
                if self._blocks:
                    # a pass over no active slot: nothing commits, nothing
                    # is written
                    _, self.state = self._decode_sampled(
                        self.params, self.state, inactive, bucket)
                elif self._loop_k:
                    # the k-tick flush executable replaces the single-tick
                    # sampled step as the loop's only decode dispatch; warm it
                    # per read bucket (all-inactive, zero caps: k masked ticks
                    # advance nothing)
                    fed = tokens
                    for _ in range(2):
                        _, _, fed, _, self.state, self._rng = self._decode_loop(
                            self.params, self.state, fed, inactive, self._rng,
                            jnp.zeros((b,), jnp.int32), bucket,
                            unroll=self._unroll,
                        )
                elif self._device_sampling:
                    fed = tokens
                    for _ in range(2):
                        fed, _, self.state, self._rng = self._decode_sampled(
                            self.params, self.state, fed, inactive, self._rng,
                            bucket, unroll=self._unroll,
                        )
                else:
                    _, self.state = self._decode(
                        self.params, self.state, tokens, inactive, bucket,
                        unroll=self._unroll,
                    )
                if self._spec is not None:
                    _, _, self.state = self._spec(
                        self.params, self.state,
                        jnp.zeros((b, self._spec_tokens + 1), jnp.int32),
                        inactive, jnp.zeros((b,), jnp.int32), bucket,
                        unroll=self._unroll,
                    )
                if self._fused_spec:
                    # the fused draft+verify flush; the traced k_dyn bound
                    # means this ONE executable serves every policy-picked
                    # k <= loop_k (the plain _decode_loop above stays warm
                    # too — it is the cooloff fallback dispatch)
                    _, _, _, self.state = self._decode_fused(
                        self.params, self.state, tokens, inactive,
                        jnp.zeros((b,), jnp.int32),
                        jnp.zeros((b, self._hist_window), jnp.int32),
                        jnp.zeros((b,), jnp.int32),
                        jnp.int32(self._loop_k), bucket, unroll=self._unroll,
                    )
        if self._async_admission:
            # one executable per (batch size, bucket): the batched admission
            # step (prefill N rows + KV scatter + on-device first-token
            # sample + first-token buffer scatter)
            for bucket in self._prefill_buckets:
                for n in self._admit_sizes:
                    with span(program=f"admit_step[{n}]", bucket=bucket):
                        _, self._admit_buf, self.state = self._admit_step(
                            self.params, self.state, self._admit_buf,
                            jnp.zeros((n, bucket), jnp.int32),
                            jnp.arange(n, dtype=jnp.int32),
                            jnp.ones((n,), jnp.int32),
                            jax.random.split(jax.random.key(0), n),
                        )
            # the admission path's HOST-side op shapes: key split + slices
            # per batch size, the static-shape token merge, the single-slot
            # buffer write. Each is trivial work but its first-use XLA
            # compile costs 100-450 ms — unacceptable inside the loop.
            for n in self._admit_sizes:
                keys = jax.random.split(jax.random.key(0), n + 1)
                _, _ = keys[0], keys[1:]
        elif self._blocks:
            # no whole-prompt program; the block's opening, as the table
            # row's install below (slot 0 holds no request: its block ends
            # at 0 and does nothing)
            self.state = self._open_block(
                self.state, jnp.int32(0), np.zeros((self._blocks,), np.int32),
                np.ones((self._blocks,), bool), jnp.int32(0))
        else:
            for bucket in self._prefill_buckets:
                with span(program="prefill_into_slot", bucket=bucket):
                    logits, self.state = self._prefill(
                        self.params, self.state,
                        jnp.zeros((1, bucket), jnp.int32),
                        jnp.int32(0), jnp.int32(1),
                    )
        if self._device_sampling and not self._blocks:
            # the [B] token merge serves both the pipelined fed-merge and
            # the admission override — warm its one executable
            # (both token operands committed, as every serving call's are)
            self._merge_tokens(jnp.zeros((b,), bool), tokens, tokens)
        vocab = getattr(self.cfg, "vocab", None)
        # a logits row as the admission tails get it: a step's output
        row = (self._place(jnp.zeros((vocab,), jnp.float32)) if vocab
               else None)
        if not self._async_admission and self._device_sampling \
                and self.serving.temperature > 0.0:
            # the admission-time sampler draws the first token of every
            # request; its first-use compile must not happen in-loop either
            self._sample1(logits, jax.random.key(0))
        if self._async_admission and row is not None:
            # single-row admission tails (prefix-only, final chunk) sample
            # through these; warm them so a first prefix-cached admission
            # can't compile inside the loop
            if self.serving.temperature > 0.0:
                first = self._sample1(row, jax.random.key(0))
            else:
                first = self._argmax1(row)
            # the single-slot buffer write takes that device-resident token
            self._admit_buf = self._set_buf1(
                self._admit_buf, jnp.int32(0), first)
        if self._prefill_chunk is not None:
            # one executable per (chunk, read-bucket) pair. EVERY bucket
            # >= chunk is reachable: prefix-cached admissions chunk from
            # unaligned offsets (need = base + off + C), so needs are not
            # just multiples of C
            for bkt in [x for x in self._kv_buckets if x >= self._chunk]:
                extra = (
                    {"block_ids": np.zeros((bkt // self._page,), np.int32)}
                    if self._paged else {})
                with span(program="prefill_chunk_into_slot", bucket=bkt):
                    _, self.state = self._prefill_chunk(
                        self.params, self.state,
                        jnp.zeros((1, self._chunk), jnp.int32),
                        jnp.int32(0), jnp.int32(0), jnp.int32(1),
                        kv_bucket=bkt, unroll=self._unroll, **extra,
                    )
        if self._paged:
            # the per-admission table-row install and the boundary-block
            # COW copy: trivial ops, but their first-use compile must not
            # land inside the loop (the _warm_executables invariant). The
            # table-row warm doubles as cleanup: slot 0's warm-time junk
            # length resets to 0.
            self.state = self._set_table_row(
                self.state, jnp.int32(0),
                np.zeros((self._max_pages,), np.int32), jnp.int32(0))
            self.state = self._copy_block(
                self.state, jnp.int32(0), jnp.int32(0))
        if self._swap_enabled and self._swap_host_blocks:
            # the swap staging pair: one gather and one scatter executable
            # at the staging width (all-null ids — reads and writes land on
            # the always-masked null block). First-use compiles of the swap
            # path must never land inside the loop, same invariant as every
            # other executable here. (kv_swap=0 has no staging to warm.)
            ids = np.zeros((self._swap_stage,), np.int32)
            snap = self._swap_gather(self.state, ids)
            pages = {
                key: (jax.device_put(np.zeros(snap[key].shape,
                                              snap[key].dtype),
                                     self._stage_shardings[key])
                      if key in self._stage_shardings
                      else np.zeros(snap[key].shape, snap[key].dtype))
                for key in self._swap_planes
            }
            self.state = self._swap_scatter(self.state, ids, pages)

    def _loop(self) -> None:
        try:
            with self._on_device():
                with self._warmup.timing():
                    self._warm_executables()
                if self._disagg is not None:
                    self._disagg.started.set()
                if self._blocks:
                    self._loop_blocks()
                elif self._fused_spec:
                    self._loop_fused()
                elif self._loop_k:
                    self._loop_device()
                elif self._pipeline:
                    self._loop_pipelined()
                else:
                    self._loop_sync()
        except EngineDeath:
            # the engine_death seam: the loop thread vanishes WITHOUT its
            # shutdown sweep — no terminals, no releases, clients left
            # hanging (the SIGKILL stand-in). The finally below observes
            # _died and skips cleanup; recovering the sessions is the
            # fleet supervisor's job (ledger + failover), reclaiming the
            # host bookkeeping is its reap's.
            return
        except Exception as exc:
            # anything else that escapes — a compile error while warming,
            # a bug in a tick — is recorded before the sweep below runs,
            # so the streams it ends read FAULTED and submit()/stats()/
            # stop() name the cause instead of a silently idle engine
            self._loop_error = exc
            self._stop.set()
            log.exception("serving loop died")
        finally:
            if self._died:
                return
            if self._disagg is not None:
                # workers first: the drain below owns everything they
                # might still be releasing (their stop paths return blocks
                # and end streams; join bounds the wait). _stop may not be
                # set yet when the loop died on an exception — set it so
                # the workers observe the shutdown.
                self._stop.set()
                self._disagg.started.set()
                self._disagg.join()
            # the loop owns slot/queue state, so it also owns the shutdown
            # sweep: every live Request gets its end-of-stream sentinel the
            # moment the loop exits (stop() only waits, never mutates)
            self._drain_all()

    def _tick_head(self) -> bool:
        """Between-tick host work shared by both loop flavors: drain the
        pending queue into the waiting list, advance in-flight chunked
        admissions, fill free slots from the waiting list (same-bucket
        prompts coalescing into batched prefill dispatches), and retire
        slots whose client walked away. All prefill work — chunk advances
        and bucketed batches — draws from ONE per-tick prompt-token budget
        (ServingConfig.prefill_budget), bypassed while nothing is decoding
        so an idle engine admits at full speed. In-flight chunks spend
        first: finishing an admission frees its head-of-line latency and
        its budget claim. Returns whether any admission happened.

        The whole head is the "admission" phase, less the swap drain
        (a phase of its own, opened inside it): where a TTFT outlier's
        host share of the tick went. Under the k-tick device loop the
        head runs once per FLUSH, so its cost amortizes over k inner
        ticks (tick_phase_ms mean_ms_per_tick)."""
        with self._prof.phase("admission", ticks=self._loop_k or 1):
            return self._tick_head_work()

    def _tick_head_work(self) -> bool:
        # fleet supervision, in ledger-then-heartbeat-then-death order:
        # (1) the session ledger records recovery metadata as of the LAST
        # delivery (everything delivered so far is reflected; the
        # in-flight dispatch is not — it dies with a crash and is
        # regenerated by the rebuild, never duplicated); (2) the
        # tick-liveness heartbeat stamps; (3) the engine_death seam fires
        # AFTER both, so at the deterministic death point the ledger is
        # exactly as fresh as the stream the client saw.
        hook = self._ledger_hook
        if hook is not None:
            try:
                hook(self)
            except Exception:  # a fleet bug must not take the loop down
                log.exception("session-ledger hook raised; continuing")
        self._beat_ns = time.monotonic_ns()
        if self._fire_fault("engine_death"):
            self._died = True
            raise EngineDeath("injected engine_death at the flush boundary")
        if self._paged:
            self._drain_prefix_work()
        while True:
            try:
                self._waiting.append(self._pending.get_nowait())
            except queue.Empty:
                break
        self._shed_deadlines()
        if self._swap_enabled:
            # overcommit housekeeping, all non-blocking: apply settled
            # parks, land READY swap-out transfers in the host pool (a
            # still-in-flight one waits — the tick never blocks on D2H)
            self._process_lifecycle()
            with self._prof.phase("swap_drain", ticks=self._loop_k or 1):
                self._drain_swap_outs()
        if self._disagg is not None and self._swap_enabled:
            # reclaim assist: a prefill worker's allocator miss posts the
            # needed block count — eviction of parked pages runs HERE, on
            # the parked-state owner's thread, never on a worker
            need = self._disagg.take_needed_blocks()
            if need:
                self._reclaim(need)
        decoding = any(r is not None for r in self._slot_req)
        budget = (
            float(self.serving.prefill_budget)
            if self.serving.prefill_budget and decoding else float("inf"))
        budget = self._advance_admissions(budget)
        if self._swap_enabled:
            # resumes slot in ahead of NEW admissions (older traffic) but
            # draw from the SAME per-tick prompt-token budget: a bucketed
            # recompute is a full prefill dispatch, and a resume wave must
            # degrade live streams' ITL by the configured bound, not stall
            # them (chunked rebuilds ride the budgeted
            # _advance_admissions path above on subsequent ticks)
            budget = self._advance_resumes(budget)
        if self._disagg is not None:
            # crash containment, worker domain: detect dead prefill
            # workers, recover what they held (release + bounded-backoff
            # re-queue or typed FAULTED), restart them, and re-admit
            # retry entries whose backoff elapsed — all on THIS thread,
            # the owner of every structure the recovery touches
            self._disagg.watch()
            # role split: the loop never admits from the waiting line —
            # prefill workers own it; the loop only INSTALLS completed
            # handoffs (one fused table-row write per session, zero
            # copies) into freed slots, resumes first (older traffic)
            admitted = self._install_handoffs()
            if len(self._waiting):
                # wake workers only when there is something to claim: the
                # drain above just surfaced new heads, or a retire/reclaim
                # this tick freed pool blocks a dry-pool claim was waiting
                # on. Steady decode with an empty line skips the broadcast
                # (submit() notifies directly, so no wakeup is lost).
                self._disagg.notify_work()
        else:
            admitted, _ = self._admit_waiting(budget)
        self._shed_overload()
        for slot in range(self.serving.slots):
            req = self._slot_req[slot]
            if req is not None and req.cancelled:
                self._retire(slot)
        return admitted

    def _shed_deadlines(self) -> None:
        """Deadline enforcement at the tick head (the flush boundary).
        A waiting request past its deadline is shed BEFORE admission —
        atomically (WaitQueue.take), so a racing disagg worker claim and
        this shed can never both own it. A live or mid-chunked-admission
        request past its deadline is marked for abort; the cancel sweep
        at the end of this same tick head retires it, delivering the
        typed SHED_DEADLINE terminal through the exact machinery a
        client cancel rides (shed and cancel stay idempotent against
        each other by construction: whichever abort lands first names
        the terminal)."""
        if not self._deadlines_seen:
            # no submit has ever carried a deadline: the sweep below
            # would be pure per-tick overhead (a waiting-line snapshot +
            # a slot scan) — keep the clean-engine cost at one attribute
            # check, the same bar as the fault seams
            return
        now = time.monotonic_ns()
        for req in self._waiting:
            if (req.deadline_ns is not None and now > req.deadline_ns
                    and not req.cancelled):
                if self._waiting.take(req):
                    self._stats["shed_deadline"] += 1
                    self.trace.record(
                        "shed", req.rid, -1,
                        TERMINAL_CODES[Status.SHED_DEADLINE])
                    self._end_stream(req, Status.SHED_DEADLINE)
        live = [r for r in self._slot_req if r is not None]
        live += [adm["req"] for adm in self._admitting.values()]
        for req in live:
            if (req.deadline_ns is not None and now > req.deadline_ns
                    and req._abort is None):
                req._abort = Status.SHED_DEADLINE
                self._stats["shed_deadline"] += 1
                self.trace.record("shed", req.rid, -1,
                                  TERMINAL_CODES[Status.SHED_DEADLINE])

    def _shed_overload(self) -> None:
        """Overload shedding, AFTER this tick's admissions: whatever
        still overflows shed_queue_depth is genuine excess (a burst that
        free slots could absorb is never shed), and the pluggable
        ShedPolicy picks the victims — lowest QoS first by default —
        instead of the line growing without bound. Stale picks (claimed
        or cancelled in the window) lose the atomic take and are skipped."""
        depth = self.serving.shed_queue_depth
        if not depth:
            return
        excess = len(self._waiting) - depth
        if excess <= 0:
            return
        try:
            waiters = list(self._waiting)
            if self._shed_signals:
                # the pressure snapshot the policy decides against — pool
                # state (and attested duty, when a supplier is wired)
                # included, so overload victims can be chosen by MEMORY or
                # DEVICE pressure, not queue depth alone (the
                # monitor->scheduler feedback loop's engine-side
                # actuator). queue_depth pins to THIS shed decision's
                # waiter snapshot, not the racing pending-queue size.
                signals = dataclasses.replace(
                    self.signals(), queue_depth=len(waiters))
                victims = list(self._shed_policy.select(
                    waiters, excess, signals))[:excess]
            else:
                victims = list(self._shed_policy.select(
                    waiters, excess))[:excess]
        except Exception:
            # a user-loaded policy program raising must not take the
            # serving loop down with it (the same containment bar as a
            # custom sample= callable): log, shed nothing this tick, and
            # let the next tick head retry — the line stays bounded by
            # retries, the engine stays alive
            log.exception("shed policy %r raised; skipping this tick's "
                          "overload shed", type(self._shed_policy).__name__)
            return
        for req in victims:
            if self._waiting.take(req):
                self._stats["shed_overload"] += 1
                self.trace.record("shed", req.rid, -1,
                                  TERMINAL_CODES[Status.SHED_OVERLOAD])
                self._end_stream(req, Status.SHED_OVERLOAD)

    def _idle_wait(self, admitted: bool) -> None:
        """Nothing to decode and nothing in flight: block briefly on the
        queue so an idle engine doesn't spin — unless admissions are mid-
        chunk (keep advancing them) or one just landed this pass. The
        request joins the waiting list and the next _tick_head admits it
        into the FIRST FREE slot — this helper never picks a slot itself
        (an earlier version hardcoded slot 0, correct only because its
        guard implied every slot was free; see the regression test)."""
        if self._admitting or admitted:
            return
        # block on the shared wake event, not the pending queue alone: a
        # resume command arrives on the lifecycle queue, and an idle
        # engine full of parked sessions must neither busy-poll nor floor
        # resume latency at this sleep (submit/park/resume all set _wake
        # AFTER enqueueing, so a consumed wake always finds its item on
        # the next _tick_head drain)
        with self._prof.phase("idle_wait"):
            if self._wake.wait(timeout=0.05):
                self._wake.clear()
            try:
                self._waiting.append(self._pending.get_nowait())
            except queue.Empty:
                return

    def _loop_pipelined(self) -> None:
        """One-tick-deep decode pipeline (device sampling on, speculation
        off):

            dispatch tick t   -> device starts computing t immediately
            deliver tick t-1  -> ONE batched device_get (t-1 is already
                                 done), then Python bookkeeping runs WHILE
                                 the device works on t

        Tick t's token inputs are tick t-1's sampled tokens, still
        device-resident — no host round-trip sits between consecutive
        ticks. The host runs one tick behind, so slot lifecycle needs care:

        - budget exhaustion is PREDICTED at dispatch: a slot whose
          in-flight token spends its last budget is excluded from the new
          tick (it will retire at delivery), so the device length never
          runs past the budget wall;
        - eos is not predictable: an eos at t-1 wastes exactly one
          slot-tick of device work at t, and _deliver's request-identity
          check drops the orphaned token (the slot's next admission
          overwrites the over-advanced cache row wholesale);
        - a slot admitted after t's dispatch joins at t+1, its prefill
          first token supplied as a host override into the lookahead
          array.
        """
        b = self.serving.slots
        inflight: Optional[dict] = None
        # the [B] active mask only changes on admit/retire; cache the device
        # array keyed on the dispatch set so steady-state ticks skip the
        # rebuild + upload (the tokens input already skips its own)
        active = None
        active_key: Optional[tuple] = None
        # under disaggregation the tick-head + dispatch section (every
        # loop-side mutation of the donated device state) runs inside the
        # state mutex; it is released before the blocking delivery fetch
        # and the idle wait so prefill workers dispatch in those windows
        locking = self._disagg is not None
        while not self._stop.is_set():
            if locking:
                self._state_mu.acquire()
            locked = locking
            try:
                admitted = self._tick_head()
                # this pass's async-admission manifest: their device token
                # arrays ride the delivery fetch below (or a standalone
                # batched admission fetch when no tick is in flight to
                # piggyback on)
                firsts = self._pending_firsts
                self._pending_firsts = []
                # fed[i]: slot i's next token is the in-flight tick's
                # device sample (same request then and now; identity
                # survives neither retire nor recycle)
                fed = [
                    inflight is not None
                    and inflight["reqs"][i] is not None
                    and inflight["reqs"][i] is self._slot_req[i]
                    for i in range(b)
                ]
                dispatch = [
                    i for i in range(b)
                    if self._slot_req[i] is not None
                    and self._slot_req[i] not in self._want_park
                    and self._slot_budget[i] - (1 if fed[i] else 0) > 0
                ]
                new_inflight = None
                if dispatch:
                    with self._prof.phase("dispatch"):
                        live = set(dispatch)
                        if inflight is not None and all(fed[i] for i in dispatch):
                            # steady state (no admit/retire since last tick):
                            # feed the in-flight device tokens straight back —
                            # no host upload, no where; non-dispatched rows
                            # carry stale device values the active mask ignores
                            tokens = inflight["tokens"]
                        elif inflight is None:
                            tokens = self._host_tokens()
                        else:
                            tokens = self._merge_tokens(
                                jnp.asarray(fed, bool), inflight["tokens"],
                                self._host_tokens())
                        over = [i for i in dispatch if self._admit_mask[i]]
                        if over:
                            # freshly admitted slots: their first tokens are
                            # still device-resident in _admit_buf (scattered
                            # there inside the prefill dispatch) — one
                            # static-shape jitted merge, no host visit and no
                            # per-pattern compile
                            tokens = self._merge_tokens(
                                jnp.asarray([i in over for i in range(b)], bool),
                                self._admit_buf, tokens)
                            for i in over:
                                self._admit_mask[i] = False
                        if active_key != tuple(dispatch):
                            active = jnp.asarray(
                                [i in live for i in range(b)], bool)
                            active_key = tuple(dispatch)
                        if self._unroll:
                            # the host length mirror lags one tick for
                            # in-flight slots; the read window must cover the
                            # DEVICE length
                            need = 1 + max(
                                self._slot_len[i] + (1 if fed[i] else 0)
                                for i in dispatch)
                            kv_bucket = next(
                                (bkt for bkt in self._kv_buckets if bkt >= need),
                                self.model.max_context,
                            )
                        else:
                            kv_bucket = 0
                        self._note_kv_window(
                            kv_bucket,
                            [self._slot_len[i] + (1 if fed[i] else 0)
                             for i in dispatch])
                        tok_d, lp_d, self.state, self._rng = self._decode_sampled(
                            self.params, self.state, tokens, active, self._rng,
                            kv_bucket, unroll=self._unroll,
                        )
                        self._stats["decode_ticks"] += 1
                        if self._disagg is not None:
                            # one decode tick elapsed: refill the controller's
                            # prefill allowance at the current partition
                            self._disagg.on_tick()
                        if inflight is not None:
                            self._stats["pipelined_ticks"] += 1
                        new_inflight = {
                            "tokens": tok_d, "logprobs": lp_d,
                            "reqs": [self._slot_req[i] if i in live else None
                                     for i in range(b)],
                        }
            finally:
                if locked:
                    self._state_mu.release()
            if not dispatch and inflight is None:
                if firsts:
                    # admissions whose every request spends its whole budget
                    # on the first token: deliver (and retire) them now
                    self._deliver_firsts(firsts)
                else:
                    self._idle_wait(admitted)
                continue
            if inflight is not None:
                self._deliver(inflight, firsts=firsts)
            elif firsts:
                # no tick in flight to piggyback on (the engine was idle):
                # one standalone batched fetch for the whole admission wave
                self._deliver_firsts(firsts)
            inflight = new_inflight
            # what the NEXT _tick_head must treat as in flight: a park for
            # one of these slots defers until its lookahead token lands
            # (dispatch exclusion above guarantees that within one tick)
            self._inflight_slots = (
                {i for i in range(b) if inflight["reqs"][i] is not None}
                if inflight is not None else set())
        if inflight is not None and not self._died:
            # stop() landed between dispatch and delivery: the tick's
            # tokens are already computed — deliver them so a mid-stream
            # client loses nothing the sync loop would have given it (and
            # the device_gets == decode_ticks contract survives shutdown).
            # A _died engine must NOT deliver (the fleet fencing flag: by
            # now the sessions may be rebuilt on survivors, and a late
            # delivery here would duplicate their tokens).
            self._deliver(inflight)

    def _loop_blocks(self) -> None:
        """_loop_pipelined for a model that generates by blocks
        (vtpu/models/blockdiff.py): one pass a tick for every slot that
        holds a request, pass t + 1 dispatched before pass t's result is
        fetched. What each slot does next (a denoising pass, the writing
        pass, nothing more because its generation has reached its end) is
        decided on the device from the slot's block, which lives in the
        state, so nothing is fed back from the host and nothing is
        predicted: a slot whose last block is already clean on the device
        takes a pass that does nothing until the host has delivered it and
        retired the slot. A slot admitted after t's dispatch joins at
        t + 1 with its first block opened by the admission."""
        b = self.serving.slots
        inflight: Optional[dict] = None
        active = None
        active_key: Optional[tuple] = None
        while not self._stop.is_set():
            admitted = self._tick_head()
            dispatch = [i for i in range(b) if self._slot_req[i] is not None]
            new_inflight = None
            if dispatch:
                with self._prof.phase("dispatch"):
                    live = set(dispatch)
                    if active_key != tuple(dispatch):
                        active = jnp.asarray(
                            [i in live for i in range(b)], bool)
                        active_key = tuple(dispatch)
                    # the host's mirror of a slot's length lags the passes
                    # in flight and not yet fetched: two, of which at most
                    # one advances it by a block; the window must hold the
                    # block after that one too
                    need = max(self._slot_len[i] for i in dispatch) \
                        + 2 * self._blocks
                    kv_bucket = next(
                        (bkt for bkt in self._kv_buckets if bkt >= need),
                        self.model.max_context)
                    self._note_kv_window(
                        kv_bucket, [self._slot_len[i] for i in dispatch],
                        t=self._blocks, wrote=0)
                    result, self.state = self._decode_sampled(
                        self.params, self.state, active, kv_bucket)
                    self._stats["decode_ticks"] += 1
                    if inflight is not None:
                        self._stats["pipelined_ticks"] += 1
                    new_inflight = {
                        "result": result,
                        "reqs": [self._slot_req[i] if i in live else None
                                 for i in range(b)]}
            if not dispatch and inflight is None:
                self._idle_wait(admitted)
                continue
            if inflight is not None:
                self._deliver_blocks(inflight)
            inflight = new_inflight
            self._inflight_slots = (
                {i for i in range(b) if inflight["reqs"][i] is not None}
                if inflight is not None else set())
        if inflight is not None and not self._died:
            self._deliver_blocks(inflight)  # as _loop_pipelined's last

    def _loop_device(self) -> None:
        """Multi-tick device-resident decode loop (decode_loop_k = k > 1):
        every dispatch is a k-tick FLUSH — one compiled executable runs k
        decode ticks with on-device token feedback (inner tick i's sampled
        token feeds tick i+1 without visiting the host), per-slot
        early-exit masks (budget wall / eos freeze a slot in place, its
        writes masked like any inactive lane), and paged scatters walking
        the table with device-side t//page arithmetic. The host performs
        ONE batched [B, k] fetch + deliver per flush, and ALL lifecycle
        machinery — admission, park/evict/swap drains, disagg handoff
        installs, repartitioning — runs at flush boundaries only (the same
        _tick_head, 1/k as often).

        Pipelining is flush-deep, the PR-1 discipline generalized:

            dispatch flush t   -> device starts k ticks immediately
            deliver flush t-1  -> ONE batched device_get, then Python
                                  bookkeeping for k tokens per slot runs
                                  WHILE the device works on t

        Flush t's token inputs are flush t-1's final sampled tokens
        (``carry``), still device-resident. The host runs one FLUSH
        behind, so the lookahead rules generalize k-deep:

        - budget exhaustion is PREDICTED at dispatch: each slot's cap is
          its remaining budget minus the in-flight flush's predicted
          emissions, and a slot whose cap hits zero is excluded (it will
          retire at delivery) — the device length never runs past the
          budget wall, so paged reservations are never exceeded;
        - eos is not predictable: an eos inside flush t freezes the slot
          ON DEVICE for the rest of t (early exit — no wasted inner
          ticks), wastes at most one slot-flush of device work at t+1,
          and _deliver_flush's request-identity check drops the orphaned
          column (retire/admit invalidate ONE slot's k-deep lookahead,
          never the flush);
        - a park request defers to the next flush boundary: the slot is
          excluded from the new dispatch, its in-flight tokens land at
          delivery, and the settled slot parks with host/device lengths
          reconciled.

        pipeline_decode=False degenerates to a synchronous flush loop
        (dispatch, deliver, repeat — still one fetch per k ticks)."""
        b = self.serving.slots
        k = self._loop_k
        inflight: Optional[dict] = None
        active = None
        active_key: Optional[tuple] = None
        locking = self._disagg is not None
        while not self._stop.is_set():
            if locking:
                self._state_mu.acquire()
            locked = locking
            try:
                admitted = self._tick_head()
                firsts = self._pending_firsts
                self._pending_firsts = []
                fed = [
                    inflight is not None
                    and inflight["reqs"][i] is not None
                    and inflight["reqs"][i] is self._slot_req[i]
                    for i in range(b)
                ]
                # budget remaining after the in-flight flush's PREDICTED
                # emissions (exact unless the slot eos'd mid-flight — and
                # an eos'd slot retires at delivery, so over-subtraction
                # only ever excludes a slot that is leaving anyway)
                rem = [
                    self._slot_budget[i]
                    - (inflight["pred"][i] if fed[i] else 0)
                    for i in range(b)
                ]
                dispatch = [
                    i for i in range(b)
                    if self._slot_req[i] is not None
                    and self._slot_req[i] not in self._want_park
                    and rem[i] > 0
                ]
                new_inflight = None
                if dispatch:
                    with self._prof.phase("dispatch", ticks=k):
                        live = set(dispatch)
                        if inflight is not None and all(fed[i] for i in dispatch):
                            # steady state: feed the in-flight flush's final
                            # tokens straight back — no host upload, no merge
                            tokens = inflight["carry"]
                        elif inflight is None:
                            tokens = self._host_tokens()
                        else:
                            tokens = self._merge_tokens(
                                jnp.asarray(fed, bool), inflight["carry"],
                                self._host_tokens())
                        over = [i for i in dispatch if self._admit_mask[i]]
                        if over:
                            # freshly admitted slots: first tokens still
                            # device-resident in _admit_buf (see _loop_pipelined)
                            tokens = self._merge_tokens(
                                jnp.asarray([i in over for i in range(b)], bool),
                                self._admit_buf, tokens)
                            for i in over:
                                self._admit_mask[i] = False
                        if active_key != tuple(dispatch):
                            active = jnp.asarray(
                                [i in live for i in range(b)], bool)
                            active_key = tuple(dispatch)
                        # per-slot early-exit caps: remaining budget clamped to
                        # k — the device freezes the slot after its cap'th
                        # emission, so a flush can never overdraw a budget (or
                        # the paged reservation denominated in it). _loop_cap
                        # is k unless the fetch watchdog degraded the engine
                        # to per-token flushes (then 1: same executable, the
                        # cap does the clamping).
                        pred = [min(rem[i], self._loop_cap) if i in live else 0
                                for i in range(b)]
                        cap = jnp.asarray(pred, jnp.int32)
                        if self._unroll:
                            # the read window must cover the DEVICE length at
                            # the END of this flush: host mirror + in-flight
                            # predicted emissions + k more
                            need = k + max(
                                self._slot_len[i]
                                + (inflight["pred"][i] if fed[i] else 0)
                                for i in dispatch)
                            kv_bucket = next(
                                (bkt for bkt in self._kv_buckets if bkt >= need),
                                self.model.max_context,
                            )
                        else:
                            kv_bucket = 0
                        self._note_kv_window(
                            kv_bucket,
                            [self._slot_len[i]
                             + (inflight["pred"][i] if fed[i] else 0)
                             for i in dispatch],
                            ticks=k)
                        out_d, cnt_d, carry_d, lp_d, self.state, self._rng = \
                            self._decode_loop(
                                self.params, self.state, tokens, active,
                                self._rng, cap, kv_bucket, unroll=self._unroll)
                        self._stats["decode_ticks"] += k
                        self._stats["loop_flushes"] += 1
                        if self._disagg is not None:
                            # k decode ticks elapsed in one dispatch: the
                            # controller's token bucket refills per inner tick
                            # so the prefill partition is flush-rate-invariant
                            for _ in range(k):
                                self._disagg.on_tick()
                        if inflight is not None:
                            self._stats["pipelined_ticks"] += k
                        new_inflight = {
                            "tokens": out_d, "counts": cnt_d, "carry": carry_d,
                            "logprobs": lp_d, "pred": pred,
                            "t_disp_ns": time.monotonic_ns(),
                            "reqs": [self._slot_req[i] if i in live else None
                                     for i in range(b)],
                        }
            finally:
                if locked:
                    self._state_mu.release()
            if not dispatch and inflight is None:
                if firsts:
                    self._deliver_firsts(firsts)
                else:
                    self._idle_wait(admitted)
                continue
            if not self._pipeline:
                # synchronous flush loop (pipeline_decode=False): deliver
                # the flush just dispatched before the next one — the host
                # tax still amortizes over k, only the overlap is missing
                if new_inflight is not None:
                    self._deliver_flush(
                        new_inflight, firsts=firsts)
                elif firsts:
                    self._deliver_firsts(firsts)
                self._inflight_slots = set()
                continue
            if inflight is not None:
                self._deliver_flush(inflight, firsts=firsts)
            elif firsts:
                # no flush in flight to piggyback on (the engine was idle):
                # one standalone batched fetch for the admission wave
                self._deliver_firsts(firsts)
            inflight = new_inflight
            # what the NEXT _tick_head must treat as in flight: a park for
            # one of these slots defers to the flush boundary
            self._inflight_slots = (
                {i for i in range(b) if inflight["reqs"][i] is not None}
                if inflight is not None else set())
        if inflight is not None and not self._died:
            # stop() landed between dispatch and delivery: the flush's
            # tokens are already computed — deliver them (same contract as
            # the one-tick pipelined loop's shutdown delivery; _died gates
            # it exactly as there — a fenced engine never delivers late)
            self._deliver_flush(inflight)

    def _deliver_flush(self, flush: dict,
                       firsts: Optional[list] = None) -> None:
        """Deliver one k-tick flush: ONE batched fetch for the [B, k]
        token matrix + per-slot emitted counts (+ optional logprobs), then
        the same budget/eos/retire bookkeeping as _deliver — amortized
        over up to k tokens per slot. ``flush["reqs"]`` snapshots each
        slot's Request at dispatch; the identity check drops a retired or
        recycled slot's whole in-flight COLUMN (the PR-1 single-token
        lookahead invalidation, k-deep). Host-replicated state reconciles
        here: the length mirror advances by exactly the device's per-slot
        count, so the page-table rows the host holds stay truthful at
        every flush boundary.

        Trace fidelity: the k per-token events share one host observation,
        so they are recorded with timestamps INTERPOLATED across the flush
        window (dispatch -> delivery, floored at the previous flush's
        delivery) and flagged via val=1; a ``loop_flush`` event carrying k
        marks each delivery. Derived ITL spans stay well-defined — the
        user-visible reservoir records one inter-flush gap per slot, the
        spec-tick convention for burst deliveries."""
        k = self._loop_k
        extra = tuple(f["tokens"] for f in firsts) if firsts else ()
        if flush["logprobs"] is not None:
            toks, counts, lps, *first_arrs = self._fetch(
                (flush["tokens"], flush["counts"], flush["logprobs"])
                + extra, ticks=k)
        else:
            toks, counts, *first_arrs = self._fetch(
                (flush["tokens"], flush["counts"]) + extra, ticks=k)
            lps = None
        if self._died:
            # fleet fencing, post-fetch (see _deliver): a DEAD-declared
            # engine must not emit — its sessions may live on survivors
            return
        with self._prof.phase("deliver", ticks=k):
            if firsts:
                self._deliver_firsts(firsts, fetched=first_arrs)
            now = time.perf_counter()
            now_ns = time.monotonic_ns()
            # interpolation window: this flush's tokens were computed between
            # its dispatch and this delivery, but a PIPELINED flush dispatches
            # before the previous delivery — flooring at the previous
            # delivery keeps synthesized stamps monotonic per slot
            start_ns = max(flush["t_disp_ns"], self._last_flush_ns)
            self.trace.record("loop_flush", -1, -1, k)
            eos = self.serving.eos_token
            for slot, req in enumerate(flush["reqs"]):
                if req is None or req is not self._slot_req[slot]:
                    continue
                try:
                    self._maybe_inject_dispatch()
                    cnt = int(counts[slot])
                    if cnt < k:
                        # froze inside the loop: budget wall (cap < k) or eos
                        # (or the watchdog's per-token degrade clamped the cap)
                        self._stats["loop_early_exits"] += 1
                    if cnt == 0:
                        continue
                    emitted = [int(t) for t in toks[slot, :cnt]]
                    # host/device reconciliation: mirror the device's length
                    # advance BEFORE any retire below, exactly like the spec
                    # path
                    self._slot_len[slot] += cnt
                    self._slot_budget[slot] -= cnt
                    span = max(now_ns - start_ns, 0)
                    for j, tok in enumerate(emitted):
                        ts = start_ns + ((j + 1) * span) // cnt
                        self.trace.record_at(ts, "token", req.rid, slot, 1)
                        # logprob BEFORE the queue put (see _emit)
                        if lps is not None:
                            req.logprobs.append(float(lps[slot, j]))
                        req.delivered += 1
                        req.out.put(tok)
                    self._stats["generated_tokens"] += cnt
                    if self._track_history:
                        self._history[slot].extend(emitted)
                    self._tokens[slot] = emitted[-1]
                    # one ITL gap per (slot, flush): the burst reaches the
                    # client in one delivery, so the user-visible ITL is the
                    # inter-flush gap — the spec-tick convention
                    self._note_itl(slot, now)
                    if self._slot_budget[slot] <= 0 or emitted[-1] == eos:
                        self._retire(slot)
                except Exception:
                    # crash containment, k-deep: one request's whole flush
                    # column dies with its slot — the flush and every other
                    # stream keep going (the PR-1 identity-check discipline
                    # applied to failures instead of recycles)
                    self._contain_fault(slot)
            self._last_flush_ns = now_ns

    def _loop_fused(self) -> None:
        """Fused speculation flush loop: draft + verify run INSIDE the
        device loop, so each flush is up to k spec ticks of up to K+1
        tokens each against ONE [B, k, K+1] fetch. Synchronous by
        construction — the device drafts from the recent-token window the
        HOST re-uploads at each flush head (built from _history, which
        needs the previous flush delivered), so dispatch and delivery
        alternate like _loop_sync while the host tax still amortizes over
        k*(K+1) tokens.

        Per flush head: (1) lifecycle at the boundary (_tick_head,
        unchanged); (2) the LoopPolicy picks this flush's window k from
        the EngineSignals snapshot, clamped to [1, watchdog-capped
        loop_k] — the traced fori_loop bound means every k shares one
        executable, zero recompiles; (3) the cooloff hysteresis gates
        HERE: while the acceptance EMA sits below spec_min_mean the flush
        dispatches the PLAIN _decode_loop executable instead (speculation
        disengages without leaving the flush discipline), re-probing
        exactly like the sync spec path."""
        b = self.serving.slots
        kmax = self._loop_k
        chunk = self._spec_tokens + 1
        w = self._hist_window
        while not self._stop.is_set():
            admitted = self._tick_head()
            firsts = self._pending_firsts
            self._pending_firsts = []
            active_slots = [
                i for i in range(b) if self._slot_req[i] is not None]
            if not active_slots:
                if firsts:
                    self._deliver_firsts(firsts)
                else:
                    self._idle_wait(admitted)
                continue
            # watchdog-capped ceiling, then the policy's pick within it
            k_cap = min(self._loop_cap or 1, kmax)
            k = k_cap
            if self._loop_policy is not None:
                try:
                    k = int(self._loop_policy.pick_k(k_cap, self.signals()))
                except Exception:
                    log.exception(
                        "loop_policy.pick_k raised; using k=%d", k_cap)
                    k = k_cap
                k = max(1, min(k, k_cap))
            # cooloff: while speculation is underwater this flush runs
            # through the plain k-tick executable (token-equal by
            # contract, same flush boundary), and keeps re-probing
            fused = self._spec_allowed()
            with self._prof.phase("dispatch", ticks=k if fused else kmax):
                tokens = self._host_tokens()
                active = jnp.asarray(
                    [self._slot_req[i] is not None for i in range(b)], bool)
                flush = {
                    "reqs": [self._slot_req[i] if i in active_slots else None
                             for i in range(b)]}
                if not fused:
                    pred = [min(self._slot_budget[i], k_cap)
                            if i in active_slots else 0 for i in range(b)]
                    cap = jnp.asarray(pred, jnp.int32)
                    if self._unroll:
                        need = kmax + max(
                            self._slot_len[i] for i in active_slots)
                        kv_bucket = next(
                            (bkt for bkt in self._kv_buckets if bkt >= need),
                            self.model.max_context,
                        )
                    else:
                        kv_bucket = 0
                    self._note_kv_window(
                        kv_bucket,
                        [self._slot_len[i] for i in active_slots],
                        ticks=kmax)
                    out_d, cnt_d, carry_d, lp_d, self.state, self._rng = \
                        self._decode_loop(
                            self.params, self.state, tokens, active,
                            self._rng, cap, kv_bucket, unroll=self._unroll)
                    self._stats["decode_ticks"] += kmax
                    self._stats["loop_flushes"] += 1
                    flush.update(
                        tokens=out_d, counts=cnt_d, carry=carry_d,
                        logprobs=lp_d, pred=pred)
                else:
                    # the draft window: each live slot's recent tokens,
                    # right-aligned into [B, W] (the device shifts accepted
                    # runs in as the flush progresses — the host only
                    # seeds it)
                    hist = np.zeros((b, w), np.int32)
                    hlen = np.zeros((b,), np.int32)
                    for i in active_slots:
                        h = self._history[i][-w:]
                        if h:
                            hist[i, w - len(h):] = h
                            hlen[i] = len(h)
                    cap = jnp.asarray(
                        [max(self._slot_budget[i], 0)
                         if i in active_slots else 0 for i in range(b)],
                        jnp.int32)
                    if self._unroll:
                        # the read window must cover the deepest possible
                        # advance: k inner ticks of a full K+1-token chunk
                        need = k * chunk + max(
                            self._slot_len[i] for i in active_slots)
                        kv_bucket = next(
                            (bkt for bkt in self._kv_buckets if bkt >= need),
                            self.model.max_context,
                        )
                    else:
                        kv_bucket = 0
                    self._note_kv_window(
                        kv_bucket,
                        [self._slot_len[i] + k * chunk - 1
                         for i in active_slots],
                        t=chunk, ticks=k)
                    out_d, cnt_d, _carry_d, self.state = self._decode_fused(
                        self.params, self.state, tokens, active, cap,
                        jnp.asarray(hist), jnp.asarray(hlen), jnp.int32(k),
                        kv_bucket, unroll=self._unroll)
                    self._stats["spec_ticks"] += k
                    self._stats["loop_flushes"] += 1
                    self._stats["fused_flushes"] += 1
                    self._stats["fused_k_hist"][k] += 1
                    flush.update(tokens=out_d, counts=cnt_d, k=k)
                flush["t_disp_ns"] = time.monotonic_ns()
            if fused:
                self._deliver_fused_flush(flush, firsts=firsts)
            else:
                self._deliver_flush(flush, firsts=firsts)

    def _deliver_fused_flush(self, flush: dict,
                             firsts: Optional[list] = None) -> None:
        """Deliver one fused-speculation flush: ONE batched fetch for the
        [B, k, K+1] token cube + [B, k] per-tick counts, then the spec
        path's budget/eos/retire bookkeeping with VARIABLE per-slot
        advance — slot b emitted sum(counts[b, :]) tokens this flush, not
        a fixed k. The host length mirror advances by exactly the
        device's summed count BEFORE eos truncation (the sync spec
        convention, applied k-deep), the request-identity check drops a
        retired/recycled slot's whole k*(K+1) in-flight column, and
        acceptance accounting (spec_emitted_hist, the cooloff EMA) counts
        DELIVERED tokens per (slot, inner tick) exactly as the sync spec
        path does per tick."""
        k = flush["k"]
        extra = tuple(f["tokens"] for f in firsts) if firsts else ()
        toks, counts, *first_arrs = self._fetch(
            (flush["tokens"], flush["counts"]) + extra, ticks=k)
        if self._died:
            return  # fleet fencing, post-fetch (see _deliver)
        with self._prof.phase("deliver", ticks=k):
            if firsts:
                self._deliver_firsts(firsts, fetched=first_arrs)
            now = time.perf_counter()
            now_ns = time.monotonic_ns()
            start_ns = max(flush["t_disp_ns"], self._last_flush_ns)
            self.trace.record("loop_flush", -1, -1, k)
            eos = self.serving.eos_token
            hist_stats = self._stats["spec_emitted_hist"]
            emitted_total = 0
            participations = 0
            for slot, req in enumerate(flush["reqs"]):
                if req is None or req is not self._slot_req[slot]:
                    continue
                try:
                    self._maybe_inject_dispatch()
                    per_tick = [
                        [int(x) for x in toks[slot, i, :int(c)]]
                        for i, c in enumerate(counts[slot]) if int(c) > 0
                    ]
                    if len(per_tick) < k:
                        # froze inside the loop: budget wall or eos (or the
                        # lane never ran — cap was already 0)
                        self._stats["loop_early_exits"] += 1
                    if not per_tick:
                        continue
                    emitted = [t for run in per_tick for t in run]
                    # mirror the device's length advance BEFORE eos
                    # truncation so host and device lengths never diverge
                    self._slot_len[slot] += len(emitted)
                    if eos in emitted:
                        emitted = emitted[: emitted.index(eos) + 1]
                    # acceptance accounting per (slot, inner tick), DELIVERED
                    # tokens only — the device's raw counts include the
                    # post-eos tail nobody receives
                    left = len(emitted)
                    for run in per_tick:
                        d = min(len(run), max(left, 0))
                        hist_stats[min(d, len(hist_stats) - 1)] += 1
                        left -= d
                    participations += len(per_tick)
                    emitted_total += len(emitted)
                    span = max(now_ns - start_ns, 0)
                    cnt = len(emitted)
                    for j, tok in enumerate(emitted):
                        ts = start_ns + ((j + 1) * span) // cnt
                        self.trace.record_at(ts, "token", req.rid, slot, 1)
                        req.delivered += 1
                        req.out.put(tok)
                    self._stats["generated_tokens"] += cnt
                    self._slot_budget[slot] -= cnt
                    self._history[slot].extend(emitted)
                    self._tokens[slot] = emitted[-1]
                    # one ITL gap per (slot, flush): the spec-tick burst
                    # convention, k-deep
                    self._note_itl(slot, now)
                    if self._slot_budget[slot] <= 0 or emitted[-1] == eos:
                        self._retire(slot)
                except Exception:
                    # crash containment, k*(K+1)-deep: one request's whole
                    # flush column dies with its slot, the rest keep going
                    self._contain_fault(slot)
            self._stats["spec_slot_ticks"] += participations
            self._stats["spec_emitted"] += emitted_total
            if participations:
                # the cooloff EMA moves once per flush toward this flush's
                # mean delivered-per-slot-tick — the same gate, same
                # threshold, evaluated at the flush cadence
                self._spec_ema = (
                    0.9 * self._spec_ema + 0.1 * emitted_total / participations)
                if (self.serving.spec_min_mean
                        and self._spec_ema < self.serving.spec_min_mean):
                    self._spec_cooloff = self.serving.spec_cooloff_ticks
            self._last_flush_ns = now_ns

    def _loop_sync(self) -> None:
        """Synchronous tick loop: dispatch, deliver, repeat. Used when a
        custom host sampler needs the full logits each tick, or when
        speculation is on (drafts are built from host-side history, so the
        newest token must be observed before the next dispatch). Still one
        batched device_get per tick — only the overlap is missing."""
        b = self.serving.slots
        # disaggregation serializes the loop's state mutations against the
        # prefill workers' (see _loop_pipelined): the tick head and the
        # decode dispatch each run under the state mutex, and the only
        # disagg-reachable branch here is the device-sampled one (disagg
        # forbids custom samplers and speculation). Everything between the
        # two locked sections reads host-side slot structures the workers
        # never touch.
        locking = self._disagg is not None
        while not self._stop.is_set():
            if locking:
                with self._state_mu:
                    admitted = self._tick_head()
            else:
                admitted = self._tick_head()
            # async-admission first tokens (device sampling with pipelining
            # off): delivered through this tick's batched fetch, same
            # contract as the pipelined loop
            firsts = self._pending_firsts
            self._pending_firsts = []
            active_slots = [i for i in range(b) if self._slot_req[i] is not None]
            if not active_slots:
                if firsts:
                    self._deliver_firsts(firsts)
                else:
                    self._idle_wait(admitted)
                continue
            # 2. one decode tick for the whole pool; the read window is the
            # smallest bucket past the longest LIVE sequence (this tick
            # writes chunk tokens starting at len, so the view must cover
            # len + chunk). The dispatch phase holds the host work on its
            # side too (array builds, bucket pick, draft scans), as in the
            # pipelined loop.
            with self._prof.phase("dispatch"):
                tokens = self._host_tokens()
                over = [i for i in active_slots if self._admit_mask[i]]
                if over:
                    # freshly admitted slots' first tokens, still device-resident
                    # in _admit_buf: one static-shape jitted merge
                    tokens = self._merge_tokens(
                        jnp.asarray([i in over for i in range(b)], bool),
                        self._admit_buf, tokens)
                    for i in over:
                        self._admit_mask[i] = False
                active = jnp.asarray(
                    [self._slot_req[i] is not None for i in range(b)], bool
                )
                # speculative tick when any slot found a draft; else the plain
                # step (same KV bytes, fewer FLOPs)
                drafts = None
                if self._spec_tokens and self._spec_allowed():
                    k = self._spec_tokens
                    drafts = [
                        lookup_draft(self._history[i], k, self.serving.spec_ngram)
                        if i in active_slots else None
                        for i in range(b)
                    ]
                    if not any(d is not None for d in drafts):
                        drafts = None
                chunk = (self._spec_tokens + 1) if drafts is not None else 1
                if self._unroll:
                    need = chunk + max(self._slot_len[i] for i in active_slots)
                    kv_bucket = next(
                        (bkt for bkt in self._kv_buckets if bkt >= need),
                        self.model.max_context,
                    )
                else:
                    kv_bucket = 0
                self._note_kv_window(
                    kv_bucket,
                    [self._slot_len[i] + chunk - 1 for i in active_slots],
                    t=chunk)
                if drafts is not None:
                    draft = jnp.asarray(
                        [
                            [self._tokens[i]] + (drafts[i] or [0] * k)
                            for i in range(b)
                        ],
                        jnp.int32,
                    )
                    cap = jnp.asarray(
                        [max(self._slot_budget[i], 0) for i in range(b)], jnp.int32
                    )
                    pred, count, self.state = self._spec(
                        self.params, self.state, draft, active, cap, kv_bucket,
                        unroll=self._unroll,
                    )
                elif self._device_sampling:
                    # fused device sampling: the tick returns [B] tokens, not
                    # logits, and _deliver does the one batched fetch
                    if locking:
                        with self._state_mu:
                            tok_d, lp_d, self.state, self._rng = \
                                self._decode_sampled(
                                    self.params, self.state, tokens, active,
                                    self._rng, kv_bucket, unroll=self._unroll)
                        self._disagg.on_tick()
                    else:
                        tok_d, lp_d, self.state, self._rng = self._decode_sampled(
                            self.params, self.state, tokens, active, self._rng,
                            kv_bucket, unroll=self._unroll,
                        )
                    self._stats["decode_ticks"] += 1
                    # active_slots IS the set of non-None _slot_req entries
                    # this iteration, so the snapshot is simply the list (the
                    # pipelined loop's dispatch can be a strict subset; here it
                    # cannot)
                    tick = {"tokens": tok_d, "logprobs": lp_d,
                            "reqs": list(self._slot_req)}
                else:
                    # host-sampler fallback: fetch the FULL logits once (still a
                    # single batched device_get — never B per-slot syncs) and run
                    # the callable per live row
                    logits, self.state = self._decode(
                        self.params, self.state, tokens, active, kv_bucket,
                        unroll=self._unroll,
                    )
                    self._stats["decode_ticks"] += 1
            if drafts is not None:
                pred, count = self._fetch((pred, count))
                if self._died:
                    return  # fleet fencing, post-fetch (see _deliver)
                with self._prof.phase("deliver"):
                    t0 = time.perf_counter()
                    emitted_total = 0
                    for slot in active_slots:
                        try:
                            self._maybe_inject_dispatch()
                            emitted = [int(x)
                                       for x in pred[slot, : int(count[slot])]]
                            # the device advanced this slot's cache length by
                            # count[slot]; mirror it BEFORE any eos truncation
                            # so host and device lengths can never diverge
                            self._slot_len[slot] += int(count[slot])
                            eos = self.serving.eos_token
                            if eos in emitted:
                                emitted = emitted[: emitted.index(eos) + 1]
                            req = self._slot_req[slot]
                            for tok in emitted:
                                self.trace.record("token", req.rid, slot)
                                req.delivered += 1
                                req.out.put(tok)
                            # acceptance accounting uses DELIVERED tokens
                            # (post-eos truncation): the device's raw count
                            # includes tokens past eos nobody receives
                            emitted_total += len(emitted)
                            # acceptance histogram: delivered tokens per
                            # (slot, spec tick) — the measured distribution
                            # behind any speedup claim (index 0 = slot
                            # emitted nothing usable)
                            hist = self._stats["spec_emitted_hist"]
                            bucket_i = min(len(emitted), len(hist) - 1)
                            hist[bucket_i] += 1
                            self._stats["generated_tokens"] += len(emitted)
                            self._slot_budget[slot] -= len(emitted)
                            self._history[slot].extend(emitted)
                            if emitted:
                                self._tokens[slot] = emitted[-1]
                                # one gap per (slot, spec tick): the burst
                                # reaches the client in one flush, so the
                                # user-visible ITL is the inter-flush gap,
                                # not intra-burst zeros
                                self._note_itl(slot, t0)
                            if (
                                self._slot_budget[slot] <= 0
                                or (emitted and emitted[-1] == eos)
                            ):
                                self._retire(slot)
                        except Exception:
                            # crash containment on the spec deliver path too:
                            # one request's burst dies with its slot, the
                            # verify tick and every other stream keep going
                            self._contain_fault(slot)
                    self._stats["spec_ticks"] += 1
                    self._stats["spec_slot_ticks"] += len(active_slots)
                    self._stats["spec_emitted"] += emitted_total
                    # per-slot EMA drives the adaptive gate: below breakeven,
                    # stop paying for verification
                    self._spec_ema = (
                        0.9 * self._spec_ema
                        + 0.1 * emitted_total / max(len(active_slots), 1)
                    )
                    if (self.serving.spec_min_mean
                            and self._spec_ema < self.serving.spec_min_mean):
                        self._spec_cooloff = self.serving.spec_cooloff_ticks
                continue
            if self._device_sampling:
                self._deliver(tick, firsts=firsts)
                continue
            logits = self._fetch(logits)
            if self._died:
                return  # fleet fencing, post-fetch (see _deliver)
            with self._prof.phase("deliver"):
                for slot in active_slots:
                    try:
                        # the custom sampler runs INSIDE the containment: a
                        # callable raising on one row faults one request,
                        # never the loop serving everyone
                        self._emit(slot, self.sample(logits[slot]))
                    except Exception:
                        self._contain_fault(slot)
