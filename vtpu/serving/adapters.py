"""Slot-model adapters: the contract between the continuous-batching engine
and a model family.

The reference middleware ships no data plane at all (SURVEY §2.6); vTPU's
serving engine is model-agnostic so every family it schedules can also be
served: the dense transformer (KV-cache decode, bounded read windows), the
selective SSM (O(1) recurrent state — no cache growth with context, the
profile attention can't offer), and models that keep both kinds of state a
session (``HybridSlotModel``: recurrent rows beside a paged pool;
``WindowSlotModel``: a ring of the last positions beside a paged pool). An
adapter owns the per-slot device state; the engine owns slots, admission,
and streaming.

Contract (all shapes static; the engine jits these with the state donated):
  params                        pytree passed back into every call
  max_context                   int cap on prompt+generation, or None
  supports_kv_buckets           True if decode accepts a bounded read window
  init_state(slots) -> state
  prefill_into_slot(params, state, padded[1,bucket], slot, true_len)
      -> (last_logits[V], state)
  decode_step(params, state, tokens[B], active[B], kv_bucket) -> (logits, state)

decode_step's [B, vocab] logits are a DEVICE-INTERNAL value on the default
serving path: the engine composes decode_step with the on-device sampler
(sampled_decode_step below) inside one jit, so a decode tick returns [B]
int32 tokens — the array the pipelined loop feeds straight into the next
dispatch. Logits only cross to the host when a custom ``sample=`` callable
is configured (the fallback path, which also disables pipelining).

``prefill_chunk_into_slot`` with an explicit ``block_ids`` row (and the
out-of-range slot sentinel that drops the length write) doubles as the
SLOT-LESS prefill contract: ``register_prefix`` builds shared prefixes
through it, and the disaggregated prefill workers (vtpu/serving/disagg)
reuse exactly the same path to fill pool blocks with no slot and no page
table — which is why a handoff can install with zero copies.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from vtpu.models import (
    blockdiff, hybrid, moe, slots as slot_steps, sparselinear, swa,
    transformer,
)
from vtpu.models.hybrid import (
    hybrid_decode_step,
    hybrid_prefill_chunk,
    hybrid_prefill_rows,
    init_hybrid_state,
)
from vtpu.models.latent import (
    LayerOfStack,
    init_latent_cache,
    latent_decode_step,
    latent_prefill_chunk,
    latent_prefill_rows,
)
from vtpu.models.moe import moe_decode_ffn, moe_prefill
from vtpu.models.ssm import init_ssm_state, ssm_decode_step, ssm_prefill
from vtpu.models.transformer import (
    hold_projections,
    init_kv_cache,
    init_paged_kv_cache,
    kv_bytes_per_token,
    kv_plane_shape,
    kv_quantized,
    multi_tick_decode,
    multi_tick_spec_decode,
    prefill,
)
from vtpu.ops import chunk_attn
from vtpu.ops.decode_attn import PAGED_ATTN_ROUTES
from vtpu.ops.latent import chunk_keys_attended, expands_window
from vtpu.parallel.sharding import (
    constrain_paged_kv,
    head_sharding,
    kv_cache_shardings,
    paged_kv_shardings,
    shard_moe_params,
    shard_params,
)


def sampled_decode_step(model: Any, temperature: float, top_k: int,
                        top_p: float, logprobs: bool):
    """Compose a slot model's decode_step with the on-device batched sampler
    (models.transformer.sample_tokens) into ONE jit-able step:

        (params, state, tokens[B], active[B], keys[B], kv_bucket, unroll)
            -> (next_tokens[B] int32, logprobs[B] f32 | None, state, keys)

    Works for every adapter family — the sampler only sees the [B, vocab]
    logits the decode contract already guarantees. Sampling config is bound
    statically here so XLA fuses filter + Gumbel + argmax into the decode
    executable; the per-tick transfer is then B*4 bytes of tokens instead
    of B*vocab*4 of logits."""
    # the sampler is looked up on its module when a step is traced: the
    # benchmark's tests plant a faulty one there and must see it served
    def step(params, state, tokens, active, keys, kv_bucket, unroll=False):
        logits, state = model.decode_step(
            params, state, tokens, active, kv_bucket, unroll=unroll)
        tok, lp, keys = transformer.sample_tokens(
            logits, keys, temperature=temperature, top_k=top_k, top_p=top_p,
            return_logprobs=logprobs)
        return tok, lp, state, keys

    return step


def multi_tick_decode_step(model: Any, temperature: float, top_k: int,
                           top_p: float, logprobs: bool, k: int,
                           eos_token: int):
    """Compose a slot model's decode_step with the on-device sampler into a
    k-tick device-resident loop (models.transformer.multi_tick_decode) —
    ONE jit-able flush:

        (params, state, tokens[B], active[B], keys[B], cap[B], kv_bucket,
         unroll) -> (out[B, k] int32, counts[B] int32, carry[B] int32,
                     logprobs[B, k] f32 | None, state, keys)

    The loop body is the UNCHANGED per-family decode step (the same trunk
    every layout — dense, paged, int8, MoE — already routes through), so a
    k-tick flush is token-equal to k single ticks by construction; the
    engine jits this with the state and keys donated, and the returned
    ``carry`` feeds the next flush's dispatch device-resident. ``cap`` is
    each slot's remaining token budget clamped to k (the per-slot
    early-exit wall); ``eos_token`` freezes a slot the tick after it
    samples it. One flush replaces k dispatch/fetch/deliver round trips —
    the host tick tax amortizes over k tokens."""
    def step(params, state, tokens, active, keys, cap, kv_bucket,
             unroll=False):
        def decode(st, tok, act):
            return model.decode_step(params, st, tok, act, kv_bucket,
                                     unroll=unroll)

        def sample(logits, keys):
            return transformer.sample_tokens(
                logits, keys, temperature=temperature, top_k=top_k,
                top_p=top_p, return_logprobs=logprobs)

        return multi_tick_decode(
            decode, sample, k, eos_token, logprobs, state, tokens, active,
            keys, cap)

    return step


def fused_spec_decode_step(model: Any, k: int, spec_tokens: int,
                           eos_token: int, ngram: int):
    """Compose a slot model's spec_step (the batched_spec_step verify
    trunk) with the device-side n-gram draft into a k-tick fused
    speculation loop (models.transformer.multi_tick_spec_decode) — ONE
    jit-able flush:

        (params, state, tokens[B], active[B], cap[B], hist[B, W],
         hist_len[B], k_dyn, kv_bucket, unroll)
            -> (out[B, k, spec_tokens+1] int32, counts[B, k] int32,
                carry[B] int32, state)

    The inner body is the UNCHANGED per-family spec_step (draft through
    the spec_verify_loop trunk — dense, paged, int8, MoE all route through
    it), so a fused flush is token-equal to k host-driven verify ticks by
    construction, and greedy verification makes both token-equal to plain
    greedy decode. ``hist``/``hist_len`` carry each slot's recent token
    window (right-aligned) for the on-device draft; ``cap`` is the
    per-slot remaining budget (variable per-slot advance truncates against
    it exactly); ``k_dyn`` is the LoopPolicy-chosen flush window for THIS
    dispatch — traced, so every k <= the static maximum shares one
    executable. Speculation requires greedy sampling, so there are no keys
    and no logprobs on this path."""
    def step(params, state, tokens, active, cap, hist, hist_len, k_dyn,
             kv_bucket, unroll=False):
        def spec(st, draft, act, bud):
            return model.spec_step(params, st, draft, act, bud, kv_bucket,
                                   unroll=unroll)

        return multi_tick_spec_decode(
            spec, k, spec_tokens, ngram, eos_token, state, tokens, active,
            cap, hist, hist_len, k_dyn)

    return step


def block_pass_step(model: Any):
    """A block-generating slot model's pass as ONE jit-able step:

        (params, state, active[B], kv_bucket) -> (result[B, 5 + 2 * bl]
                                                  int32, state)

    The slots' blocks, their phases and their commits live in the state
    (vtpu/models/blockdiff.py), so the step takes no tokens and returns
    none to feed back: the pipelined loop dispatches pass t + 1 with the
    state pass t returned and fetches pass t's result meanwhile. The
    closure's name is the program's in a profiler trace (``jit_step``), as
    the decode closures' above: a pass is this family's decode step."""
    def step(params, state, active, kv_bucket):
        return model.block_pass(params, state, active, kv_bucket)

    return step


def batched_admission_step(model: Any, temperature: float, top_k: int,
                           top_p: float):
    """Compose a slot model's batched prefill (prefill_into_slots) with the
    on-device sampler into ONE jit-able admission step:

        (params, state, buf[B], tokens[N, bucket], slots[N], true_lens[N],
         keys[N]) -> (first_tokens[N] int32, buf[B], state)

    The engine compiles one executable per (N, bucket) pair (N from
    ServingConfig.prefill_batch_sizes) in _warm_executables. Everything an
    admission needs — N prompts' trunk forward, the per-slot KV scatter,
    the N first tokens, AND their scatter into the engine's per-slot
    first-token buffer ``buf`` — happens inside this single dispatch, so
    the host never blocks on the device to admit and the next decode
    dispatch picks the tokens up from ``buf`` with one static-shape merge
    (no per-batch-size host-op compiles in the serving loop). Greedy
    ignores ``keys``; the signature keeps them so the executable shape is
    sampling-agnostic. The closure's name is the program's in a profiler
    trace (``jit_admit_step``); the decode closures above are ``jit_step``,
    which is how the benchmark's readers tell the two apart."""
    def admit_step(params, state, buf, tokens, slots, true_lens, keys):
        last, state = model.prefill_into_slots(
            params, state, tokens, slots, true_lens)
        tok, _, _ = transformer.sample_tokens(
            last, keys, temperature=temperature, top_k=top_k, top_p=top_p)
        with jax.named_scope("sample"):
            buf = buf.at[slots].set(tok)
        return tok, buf, state

    return admit_step


def swap_page_gather(model: Any):
    """KV-overcommit D2H staging source: gather up to W pool blocks (ids
    [W] int32, padded with the null block 0) into a contiguous snapshot —
    one plane dict of [L, W, page, ...] arrays, a fresh buffer independent
    of the pool, so the engine can release (and even re-use) the blocks the
    same tick while copy_to_host_async drains the snapshot. Under a tp mesh
    the snapshot is constrained to the pool's head shard: the gather is
    chip-local and the host copy that follows is the per-chip shard
    transfer. Family-agnostic — the planes come from the state itself."""

    def gather(state, ids):
        out = {}
        for key in ("k", "v", "k_scale", "v_scale"):
            if key not in state:
                continue
            g = state[key][:, ids]  # [L, W, page, ...]
            if model.mesh is not None:
                g = jax.lax.with_sharding_constraint(
                    g, head_sharding(
                        model.mesh, g.ndim,
                        -2 if key in ("k", "v") else -1))
            out[key] = g
        return out

    return gather


def swap_page_scatter(model: Any):
    """KV-overcommit H2D staging sink: scatter W staged blocks (the same
    [L, W, page, ...] plane dict the gather produced, uploaded from the
    pinned host pool) back into pool blocks *ids* (padded ids write the
    always-masked null block). The pool state is donated by the engine's
    jit and pinned back to its head shards on exit, so a swap-in can never
    drift the pool through an unsharded layout."""

    def scatter(state, ids, pages):
        out = dict(state)
        for key, val in pages.items():
            out[key] = state[key].at[:, ids].set(val)
        return _constrain_paged(model, out)

    return scatter


class _CountsHeadChunks:
    """What the engine asks of a family that caches heads to count its
    prefill chunks by the form of their attention (``stats()``'s five
    ``chunk_*`` counters): its window holds keys and values as they are
    read, so no chunk expands one, and whether a chunk's program holds the
    chunk kernel is the rule the traced program applies to the same shapes
    (``vtpu.ops.chunk_attn.takes``). A family whose chunk hands the
    attention other shapes than ``cfg``'s stacked heads says so
    (``_chunk_attn_shapes``)."""

    def chunk_attn_expands(self, queries: int) -> bool:
        return False

    def chunk_keys_attended(self, queries: int, end: int,
                            window: int) -> tuple[bool, int]:
        """(whether the program of a chunk of ``queries`` tokens over a
        read window of ``window`` holds the chunk kernel, the window
        positions it multiplies for a chunk whose last position is
        ``end - 1``)."""
        return chunk_attn.chunk_keys_attended(
            *self._chunk_attn_shapes(queries, window), end, self.mesh)

    def _chunk_attn_shapes(self, queries: int, window: int):
        return _stacked_chunk_shapes(self.cfg, queries, window)


def _stacked_chunk_shapes(cfg, queries: int, window: int):
    """(q, keys, values) of a chunk of ``queries`` tokens over a window of
    ``window`` as ``transformer.cached_attention`` hands them to the
    attention, for a cache of ``cfg``'s heads (``kv_plane_shape``)."""
    dtype = jnp.int8 if kv_quantized(cfg) else cfg.dtype
    plane = jax.ShapeDtypeStruct(
        (1, window) + kv_plane_shape(cfg), dtype)
    return (jax.ShapeDtypeStruct(
        (1, queries, cfg.n_heads, cfg.head_dim), cfg.dtype), plane, plane)


class _CachedAttentionSlotModel(_CountsHeadChunks):
    """The slot model of a family that attends over a per-slot KV cache
    (vtpu/models/slots): the state's allocation (dense rows or a paged
    block pool, on one chip or head-sharded over a ('tp',) mesh) and the
    five step methods, once. A family states what differs: how its
    parameters shard (``_shard``), its whole-prompt forward (``_prefill_fn``;
    None is the dense forward vtpu/models/slots defaults to) and the block
    after attention (``_ffn``; None is the dense MLP).

    kv_pool_blocks counts USABLE blocks; n_kv_blocks (resolved at
    init_state once the slot count is known) includes the reserved null
    block 0. ``paged_attn`` (None/"kernel"/"gather") is the paged
    decode-attention route override the decode/spec steps thread into the
    trunk — None resolves the measured per-shape router; forcing a route
    without a paged pool is a config contradiction and raises.
    """

    supports_kv_buckets = True

    def __init__(self, params: Any, cfg: Any, mesh: Optional[Any] = None,
                 kv_page: Optional[int] = None,
                 kv_pool_blocks: Optional[int] = None,
                 paged_attn: Optional[str] = None):
        if paged_attn is not None:
            if paged_attn not in PAGED_ATTN_ROUTES:
                raise ValueError(
                    f"paged_attn must be one of {PAGED_ATTN_ROUTES} or None "
                    f"(auto), got {paged_attn!r}")
            if kv_page is None:
                raise ValueError(
                    "paged_attn forces a paged decode-attention route, but "
                    "the cache is dense (kv_page=None) — there is no paged "
                    "read path to route")
        self.cfg = cfg
        self.mesh = mesh
        self.max_context = cfg.max_seq
        self.kv_page = kv_page
        self.kv_pool_blocks = kv_pool_blocks
        self.n_kv_blocks = None
        self.paged_attn = paged_attn
        # wq, wk, wv held as the serving programs' products read them, in
        # a dict of the adapter's own: the caller's keeps what it was given
        params = {**params, "layers": hold_projections(params["layers"], cfg)}
        if mesh is None:
            self.params = params
        else:
            _validate_serving_mesh(mesh, cfg)
            self.params = self._shard(params)

    def _shard(self, params):
        raise NotImplementedError

    def _prefill_fn(self, true_lens):
        """The ``prefill_fn(params, cfg, tokens)`` of an admission whose
        true lengths are *true_lens*: a scalar for one row, [N] for a
        batch."""
        raise NotImplementedError

    def _ffn(self):
        return None

    def init_state(self, slots: int):
        if self.kv_page is None:
            make = lambda: init_kv_cache(self.cfg, slots)
            shardings = kv_cache_shardings
        else:
            self.n_kv_blocks = _pool_blocks(self, slots)
            make = lambda: init_paged_kv_cache(
                self.cfg, slots, self.kv_page, self.n_kv_blocks)
            shardings = paged_kv_shardings
        if self.mesh is None:
            return make()
        # allocate the cache or pool directly head-sharded: one sized past
        # a chip's HBM must never exist unsharded, not even for a device_put
        return jax.jit(make, out_shardings=shardings(
            self.mesh, quantized=kv_quantized(self.cfg)))()

    def prefill_into_slot(self, params, state, padded, slot, true_len):
        logits, new = slot_steps.prefill_into_slot(
            params, self.cfg, _constrain_paged(self, state), padded, slot,
            true_len,
            prefill_fn=self._prefill_fn(true_len),
            mesh=self.mesh,
        )
        return logits, _constrain_paged(self, new)

    def prefill_into_slots(self, params, state, padded, slots, true_lens):
        logits, new = slot_steps.prefill_into_slots(
            params, self.cfg, _constrain_paged(self, state), padded, slots,
            true_lens,
            prefill_fn=self._prefill_fn(true_lens),
            mesh=self.mesh,
        )
        return logits, _constrain_paged(self, new)

    def decode_step(self, params, state, tokens, active, kv_bucket,
                    unroll=False):
        logits, new = slot_steps.batched_decode_step(
            cfg=self.cfg, params=params, cache=_constrain_paged(self, state),
            tokens=tokens, active=active, kv_bucket=kv_bucket,
            ffn_fn=self._ffn(), unroll=unroll, mesh=self.mesh,
            paged_attn=self.paged_attn,
        )
        return logits, _constrain_paged(self, new)

    def spec_step(self, params, state, draft, active, cap, kv_bucket,
                  unroll=False):
        pred, count, new = slot_steps.batched_spec_step(
            cfg=self.cfg, params=params, cache=_constrain_paged(self, state),
            draft=draft, active=active, cap=cap, kv_bucket=kv_bucket,
            ffn_fn=self._ffn(), unroll=unroll, mesh=self.mesh,
            paged_attn=self.paged_attn,
        )
        return pred, count, _constrain_paged(self, new)

    def prefill_chunk_into_slot(self, params, state, chunk, slot, offset,
                                new_len, kv_bucket=0, unroll=False,
                                block_ids=None):
        logits, new = slot_steps.chunked_prefill_into_slot(
            params, self.cfg, _constrain_paged(self, state), chunk, slot,
            offset, new_len, kv_bucket=kv_bucket, unroll=unroll,
            ffn_fn=self._ffn(), block_ids=block_ids, mesh=self.mesh,
        )
        return logits, _constrain_paged(self, new)


class TransformerSlotModel(_CachedAttentionSlotModel):
    """Dense transformer with a slot-pooled KV cache (vtpu/models/transformer).

    With ``mesh`` (a ('tp',) Mesh), weights are tensor-parallel and the KV
    cache shards its head axis — multi-chip serving with the same slot
    machinery; XLA places the per-layer all-reduces on ICI. The paged block
    pool (``kv_page``) composes: pools allocate head-sharded over 'tp'
    (paged_kv_shardings), page tables and the allocator stay host-side and
    replicated, and every page gather/scatter is chip-local on the head
    shard — no collectives beyond the dense TP path's.
    """

    def _shard(self, params):
        return shard_params(params, self.mesh)

    def _prefill_fn(self, true_lens):
        if jnp.ndim(true_lens) == 0:
            # one row: the plain whole-prompt forward, every position's
            # logits back, which is what slots.prefill_into_slot runs
            # when it is given none
            return None
        # logits_at: gather each row's final position before the vocab
        # projection — the [N, bucket, vocab] intermediate never exists
        return lambda p, c, t: prefill(
            p, c, t, logits_at=true_lens - 1, mesh=self.mesh)


class MoeSlotModel(_CachedAttentionSlotModel):
    """Expert-parallel MoE (vtpu/models/moe): the transformer attention
    trunk with routed experts as the post-attention block, so it shares the
    slot-KV-cache machinery (including bounded decode read windows) and only
    swaps the FFN into the shared decode loop.

    With ``mesh`` (a ('tp',) Mesh) the attention trunk goes tensor-parallel
    exactly like the dense family (heads column-sharded, KV cache/pool
    head-sharded) and the expert stacks shard their E axis over the same
    'tp' devices when it divides (vtpu/parallel/sharding.py
    moe_tp_param_shardings — not expert.py's ep-axis moe_param_shardings)
    — the serving mesh carries both parallelisms.
    """

    def _shard(self, params):
        return shard_moe_params(params, self.mesh, self.cfg.n_experts)

    def _prefill_fn(self, true_lens):
        # true_len (a scalar, or [N] for a batch: per-row routing masks)
        # keeps pads out of routing, so capacity follows the cf formula
        # instead of the full bucket; the full [N, bucket, vocab] logits
        # come back and the caller gathers the final positions
        return lambda p, c, t: moe_prefill(p, c, t, true_len=true_lens)

    def _ffn(self):
        # moe_decode_ffn's capacity >= tokens guarantee covers chunk pads
        # the same way it covers retired slots' garbage: nothing can drop
        return moe_decode_ffn(self.cfg)


def _validate_serving_mesh(mesh: Any, cfg: Any) -> None:
    """Construction-time checks for a tensor-parallel serving mesh — every
    rejection names the offending dimension, so a bad pairing fails loudly
    here instead of as a wrong-sharding surprise (or an XLA shape error)
    mid-serving."""
    extra = {a: n for a, n in mesh.shape.items() if a != "tp" and n != 1}
    if extra:
        # decode ticks would replicate across every non-tp axis
        # (dp, slice, ...) with zero throughput gain; slots are the
        # batch axis and stay local
        raise ValueError(
            f"serving mesh must be tp-only, got extra axes {extra}"
        )
    tp = int(mesh.shape.get("tp", 1))
    if cfg.n_heads % tp:
        # per-token-per-head int8 scales share the head axis, so one check
        # covers both planes — the message names each offending dimension
        raise ValueError(
            f"tp={tp} must divide the attention head count "
            f"(n_heads={cfg.n_heads}): q/k/v and the KV cache/pool shard "
            "their head axis over 'tp'"
            + (f", as do the int8 k_scale/v_scale pool head groups "
               f"(= n_heads = {cfg.n_heads})" if kv_quantized(cfg) else ""))


def _pool_blocks(model: Any, slots: int) -> int:
    """Blocks a paged adapter's pool allocates: ``kv_pool_blocks`` usable
    ones (unset: every slot's whole context) and the reserved null block
    0. An explicit 0 must never silently become the dense-equivalent
    default: the operator asked for a pool that cannot exist."""
    if model.kv_pool_blocks is not None and model.kv_pool_blocks < 1:
        raise ValueError(
            f"kv_pool_blocks must be >= 1, got {model.kv_pool_blocks}")
    usable = (model.kv_pool_blocks if model.kv_pool_blocks is not None
              else slots * (model.max_context // model.kv_page))
    return usable + 1


def _check_read_windows(read_windows, kv_page: int, max_seq: int) -> None:
    for w in read_windows or ():
        if w % kv_page or w > max_seq:
            raise ValueError(
                f"read window {w} must be a multiple of kv_page "
                f"{kv_page} and at most max_seq {max_seq}")


def _constrain_paged(model: Any, state: Any) -> Any:
    """Pin a paged pool pytree to its head shards at the step boundary
    (no-op for dense caches or single-chip pools). Applied on entry AND
    exit of every adapter step so the donated pool can never round-trip
    through an unsharded layout the compiler picked for itself."""
    if model.mesh is None or getattr(model, "kv_page", None) is None:
        return state
    return constrain_paged_kv(state, model.mesh)


class SsmSlotModel:
    """Selective SSM (vtpu/models/ssm): O(1) per-slot recurrent state, so
    there is no context cap and nothing for a read window to bound — decode
    cost is independent of how long each sequence has run."""

    supports_kv_buckets = False
    max_context = None

    def __init__(self, params: Any, cfg: Any):
        self.params = params
        self.cfg = cfg

    def init_state(self, slots: int):
        return init_ssm_state(self.cfg, slots)

    def prefill_into_slot(self, params, state, padded, slot, true_len):
        logits, row = ssm_prefill(params, self.cfg, padded, true_len)
        new_state = {
            "conv": state["conv"].at[:, slot].set(row["conv"][:, 0]),
            "h": state["h"].at[:, slot].set(row["h"][:, 0]),
        }
        return logits[0, true_len - 1], new_state

    def prefill_into_slots(self, params, state, padded, slots, true_lens):
        # ssm_prefill gathers its recurrent state at ONE scalar position
        # (dynamic_slice start), so per-row true lengths go through vmap —
        # one fused batched executable, same layer math as the single-slot
        # path (the state-extraction slice becomes a batched gather)
        def one(tokens_row, n):
            logits, row = ssm_prefill(params, self.cfg, tokens_row[None], n)
            return logits[0, n - 1], {"conv": row["conv"][:, 0],
                                      "h": row["h"][:, 0]}

        last, rows = jax.vmap(one)(padded, true_lens)
        # vmap stacked the row axis first: [N, L, ...] -> scatter at axis 1
        new_state = {
            "conv": state["conv"].at[:, slots].set(
                jnp.moveaxis(rows["conv"], 0, 1)),
            "h": state["h"].at[:, slots].set(jnp.moveaxis(rows["h"], 0, 1)),
        }
        return last, new_state

    def decode_step(self, params, state, tokens, active, kv_bucket,
                    unroll=False):
        del kv_bucket, unroll  # O(1) state: nothing to window or unroll
        logits, new = ssm_decode_step(params, self.cfg, state, tokens)
        keep = active[None, :, None, None]
        return logits, {
            "conv": jnp.where(keep, new["conv"], state["conv"]),
            "h": jnp.where(keep, new["h"], state["h"]),
        }


def _experts_grouped(model, rows: int) -> bool:
    """Whether a launch of ``rows`` rows has each expert the model holds
    multiply the rows routed to it alone: the rule the traced program
    applies to its shapes (``vtpu.models.moe.experts_grouped``), for the
    engine's counters. ``LatentSlotModel``'s and ``WindowSlotModel``'s
    ``experts_grouped``."""
    cfg = model.cfg
    return moe.experts_grouped(rows, cfg.d_model, cfg.d_ff_expert)


class LatentSlotModel:
    """Latent attention, under a learned sparse selection or over all that
    is cached, over two stacks of layers (vtpu/models/latent): a paged
    pool of latents, with the indexer's key pool beside it where the model
    has an indexer, walked by the one page table, so the engine's
    allocator, chunked admission and sampler serve it as they serve the
    other families.

    It states what the engine cannot know of it: ``read_windows`` (a
    32 k context needs read windows where no whole-prompt bucket exists),
    ``kv_bytes_per_token`` (a latent row, and an indexer key where there
    is one, a layer, not heads), ``attn_select_topk`` (what a decode tick
    reads of what it sees; None: all of it, walked page by page:
    ``walks_latent_plane``), ``chunk_attn_expands`` (the form
    a chunk's attention takes at a given length), ``chunk_keys_attended``
    (whether that form runs in the kernel, and the window positions it
    then multiplies), ``experts_grouped`` (whether a launch of so many rows
    has each held expert multiply its own rows alone) and ``pool_planes``.
    All read off the model's configuration and the backend. Paged only. Not supported, and
    refused by name: a mesh, an int8 cache, speculation and the swap tier
    (a forced ``ServingConfig.paged_attn`` the engine refuses itself: there
    is one route, so ``paged_attn`` is None)."""

    supports_kv_buckets = True
    mesh = None
    paged_attn = None

    def __init__(self, params: Any, cfg: Any, kv_page: Optional[int] = None,
                 kv_pool_blocks: Optional[int] = None,
                 read_windows: Optional[tuple] = None,
                 mesh: Optional[Any] = None):
        if mesh is not None:
            raise ValueError(
                "LatentSlotModel serves one chip's share of each layer and "
                "has no sharding rule: pass no mesh")
        if kv_page is None:
            raise ValueError(
                "LatentSlotModel has a paged cache only: set kv_page")
        if getattr(cfg, "kv_int8", False):
            raise ValueError("LatentSlotModel has no int8 cache")
        _check_read_windows(read_windows, kv_page, cfg.max_seq)
        self.params = params
        self.cfg = cfg
        self.max_context = cfg.max_seq
        self.kv_page = kv_page
        self.kv_pool_blocks = kv_pool_blocks
        self.n_kv_blocks = None
        self.read_windows = tuple(sorted(read_windows)) if read_windows else None
        self.kv_bytes_per_token = cfg.kv_bytes_per_token
        selects = cfg.has_indexer
        self.pool_planes = ("ckv", "ik") if selects else ("ckv",)
        self.attn_select_topk = cfg.index_topk if selects else None
        self.walks_latent_plane = not selects

    def check_serving(self, serving) -> None:
        """Refuse the ServingConfig options this family cannot serve."""
        if serving.spec_tokens:
            raise ValueError(
                "LatentSlotModel has no spec_step (a verify chunk would "
                "need a selection a draft position, and the latent walk "
                "one query a slot): set spec_tokens=0")
        if serving.kv_swap is not None:
            raise ValueError(
                "LatentSlotModel's pool planes have no swap staging: "
                "set kv_swap=None")

    def init_state(self, slots: int):
        self.n_kv_blocks = _pool_blocks(self, slots)
        return init_latent_cache(
            self.cfg, slots, self.kv_page, self.n_kv_blocks)

    def prefill_into_slot(self, params, state, padded, slot, true_len):
        logits, new = self.prefill_into_slots(
            params, state, padded, jnp.asarray(slot)[None],
            jnp.asarray(true_len)[None])
        return logits[0], new

    def prefill_into_slots(self, params, state, padded, slots, true_lens):
        return latent_prefill_rows(
            params, self.cfg, state, padded, slots, true_lens)

    def chunk_attn_expands(self, queries: int) -> bool:
        """Whether a chunk of ``queries`` tokens attends its window in the
        expanded form: the rule the traced program applies to its shapes
        (``vtpu.ops.latent.expands_window``), for the engine's counter."""
        cfg = self.cfg
        return expands_window(queries, cfg.kv_rank, cfg.nope_dim, cfg.v_dim)

    def chunk_keys_attended(self, queries: int, end: int,
                            window: int) -> tuple[bool, int]:
        """(whether the program of a chunk of ``queries`` tokens holds the
        chunk kernel, the window positions it multiplies for a chunk whose
        last position is ``end - 1``): the rule the traced program applies
        (``vtpu.ops.latent.chunk_keys_attended``), for the engine's
        counters."""
        cfg = self.cfg
        return chunk_keys_attended(
            queries, cfg.kv_rank, cfg.nope_dim, cfg.v_dim, end, window)

    experts_grouped = _experts_grouped

    def decode_step(self, params, state, tokens, active, kv_bucket,
                    unroll=False):
        del unroll  # five layers in two stacks: always walked unrolled
        return latent_decode_step(
            params, self.cfg, state, tokens, active,
            kv_bucket or self.max_context)

    def prefill_chunk_into_slot(self, params, state, chunk, slot, offset,
                                new_len, kv_bucket=0, unroll=False,
                                block_ids=None):
        del unroll
        window = kv_bucket or self.max_context
        if block_ids is None:  # the slot's own table row
            block_ids = state["table"][slot, :window // self.kv_page]
        return latent_prefill_chunk(
            params, self.cfg, state, chunk, slot, offset, new_len, window,
            block_ids)


class HybridSlotModel(_CountsHeadChunks):
    """Mamba-2 layers among grouped-query attention layers
    (vtpu/models/hybrid): a session's state is of two kinds, pages of the
    paged pool for the attention layers and a slot-indexed row of
    convolution window and recurrent state for the Mamba layers, in one
    engine state, so the allocator, batched and chunked admission, the
    read windows and the sampler serve it as they serve the other families.

    Shared with ``_CachedAttentionSlotModel``, not copied: the pool and
    page table (``init_paged_kv_cache`` over the attention stack's
    ``ModelConfig``), the held projections, the decode tick's page scatter
    (``slots.decode_kv_writer``), a chunk's window and write-back, a
    whole-prompt admission's page install, and both read routes
    (``transformer.cached_attention``; ``paged_attn`` forces one as the
    dense family's does).

    It states what the engine cannot know of it: ``read_windows``,
    ``kv_bytes_per_token`` (the attention layers alone) and
    ``recurrent_state_bytes`` (the rows, whatever a session's length).
    Paged only. What a session of two kinds of state cannot do yet is
    refused by name, each with the mechanism that is missing
    (``check_serving``, ``refuses``)."""

    supports_kv_buckets = True
    mesh = None
    # what the engine asks before an operation that is no ServingConfig
    # field: a session's pages can be shared, parked or shipped, its
    # recurrent rows cannot yet
    refuses = {
        "register_prefix": (
            "a shared prefix is pages; a session that starts from one also "
            "needs the recurrent rows as they stood at the prefix's last "
            "token, and no snapshot of them is kept at a boundary"),
        "drain": (
            "migration ships a session as pool pages; the recurrent rows "
            "have no staging"),
    }

    def __init__(self, params: Any, cfg: Any, kv_page: Optional[int] = None,
                 kv_pool_blocks: Optional[int] = None,
                 read_windows: Optional[tuple] = None,
                 paged_attn: Optional[str] = None,
                 mesh: Optional[Any] = None):
        if mesh is not None:
            raise ValueError(
                "HybridSlotModel has no sharding rule for the recurrent "
                "rows (a head-sharded state beside a head-sharded pool): "
                "pass no mesh")
        if kv_page is None:
            raise ValueError(
                "HybridSlotModel has a paged cache only: set kv_page")
        if cfg.kv_int8:
            raise ValueError(
                "HybridSlotModel has no int8 cache: the pool stores several "
                "key/value heads a row, which the per-head scales' planes "
                "do not follow (kv_int8=False)")
        if paged_attn is not None and paged_attn not in PAGED_ATTN_ROUTES:
            raise ValueError(
                f"paged_attn must be one of {PAGED_ATTN_ROUTES} or None "
                f"(auto), got {paged_attn!r}")
        _check_read_windows(read_windows, kv_page, cfg.max_seq)
        self.cfg = cfg
        self.max_context = cfg.max_seq
        self.kv_page = kv_page
        self.kv_pool_blocks = kv_pool_blocks
        self.n_kv_blocks = None
        self.paged_attn = paged_attn
        self.read_windows = tuple(sorted(read_windows)) if read_windows else None
        self.kv_bytes_per_token = kv_bytes_per_token(cfg.attention)
        self.params = {**params, "attention": hold_projections(
            params["attention"], cfg.attention)}

    def check_serving(self, serving) -> None:
        """Refuse the ServingConfig options this family cannot serve."""
        if serving.spec_tokens:
            raise ValueError(
                "HybridSlotModel has no spec_step: a rejected draft would "
                "need the recurrent rows rolled back to the last accepted "
                "token, and a step keeps no earlier copy (spec_tokens=0)")
        if serving.kv_swap is not None:
            raise ValueError(
                "HybridSlotModel cannot park or swap a session: its pages "
                "could be staged, its recurrent rows have no snapshot at "
                "the boundary (kv_swap=None; park, resume and migrate "
                "need it)")
        if serving.disagg is not None:
            raise ValueError(
                "HybridSlotModel has no slot-less prefill: a prefill worker "
                "fills pool blocks, and the recurrent rows have no home "
                "outside a slot (disagg=None)")

    def recurrent_state_bytes(self, slots: int) -> int:
        return slots * self.cfg.recurrent_bytes_per_slot

    def _chunk_attn_shapes(self, queries: int, window: int):
        return _stacked_chunk_shapes(self.cfg.attention, queries, window)

    def ssm_step_in_kernel(self) -> bool:
        """Whether a decode step traced now updates the recurrent state in
        the Pallas kernel: the question the trace itself asks."""
        return hybrid.step_in_kernel(1)

    def init_state(self, slots: int):
        self.n_kv_blocks = _pool_blocks(self, slots)
        return init_hybrid_state(
            self.cfg, slots, self.kv_page, self.n_kv_blocks)

    def prefill_into_slot(self, params, state, padded, slot, true_len):
        logits, new = self.prefill_into_slots(
            params, state, padded, jnp.asarray(slot)[None],
            jnp.asarray(true_len)[None])
        return logits[0], new

    def prefill_into_slots(self, params, state, padded, slots, true_lens):
        return hybrid_prefill_rows(
            params, self.cfg, state, padded, slots, true_lens)

    def decode_step(self, params, state, tokens, active, kv_bucket,
                    unroll=False):
        del unroll  # attention layers unrolled, Mamba runs looped: _walk
        return hybrid_decode_step(
            params, self.cfg, state, tokens, active,
            kv_bucket or self.max_context, paged_attn=self.paged_attn)

    def prefill_chunk_into_slot(self, params, state, chunk, slot, offset,
                                new_len, kv_bucket=0, unroll=False,
                                block_ids=None):
        del unroll
        window = kv_bucket or self.max_context
        if block_ids is None:  # the slot's own table row
            block_ids = state["table"][slot, :window // self.kv_page]
        return hybrid_prefill_chunk(
            params, self.cfg, state, chunk, slot, offset, new_len, window,
            block_ids)


class SparseLinearSlotModel(_CountsHeadChunks):
    """Block-sparse attention layers among linear-attention layers
    (vtpu/models/sparselinear): a session's state is of three kinds, pages
    of the paged pool (a plane a layer and key/value head), the compressed
    keys that the selection scores (a plane walked by the same page table)
    and a slot-indexed row of matrices a head for the linear layers, in one
    engine state, so the allocator, batched and chunked admission, the read
    windows and the sampler serve it as they serve the other families.

    It states what the engine cannot know of it: ``read_windows``,
    ``kv_bytes_per_token`` (the sparse layers' keys, values and compressed
    keys), ``recurrent_state_bytes`` (the rows, whatever a session's
    length), ``attn_select_topk`` (the tokens of the blocks a query keeps)
    with ``attn_select_dense_len`` (up to which a query attends all it
    sees) and ``pool_planes``. Paged only, a page a selection block;
    ``paged_attn`` forces the route of the selected pages' walk as the
    dense family's does. What a session of three kinds of state cannot do
    yet is refused by name, each with the mechanism that is missing
    (``check_serving``, ``refuses``): ``HybridSlotModel``'s list, for the
    same reasons and one more plane."""

    supports_kv_buckets = True
    mesh = None
    pool_planes = ("k", "v", "ck")
    refuses = {
        "register_prefix": (
            "a shared prefix is pages and their compressed keys; a session "
            "that starts from one also needs the linear layers' rows as "
            "they stood at the prefix's last token, and no snapshot of "
            "them is kept at a boundary"),
        "drain": (
            "migration ships a session as pool pages; the compressed keys' "
            "plane and the linear layers' rows have no staging"),
    }

    def __init__(self, params: Any, cfg: Any, kv_page: Optional[int] = None,
                 kv_pool_blocks: Optional[int] = None,
                 read_windows: Optional[tuple] = None,
                 paged_attn: Optional[str] = None,
                 mesh: Optional[Any] = None):
        if mesh is not None:
            raise ValueError(
                "SparseLinearSlotModel has no sharding rule for the linear "
                "layers' rows nor for a selection a key/value head (a "
                "head-sharded state beside a head-sharded pool): pass no "
                "mesh")
        if kv_page is None:
            raise ValueError(
                "SparseLinearSlotModel has a paged cache only: set kv_page")
        if kv_page != cfg.block_size:
            raise ValueError(
                f"SparseLinearSlotModel selects whole pages: kv_page "
                f"{kv_page} must be the selection's block_size "
                f"{cfg.block_size}")
        if cfg.kv_int8:
            raise ValueError(
                "SparseLinearSlotModel has no int8 cache: the compressed "
                "keys are means of the stored keys, and the per-head "
                "scales' planes do not follow a plane a head (kv_int8="
                "False)")
        if paged_attn is not None and paged_attn not in PAGED_ATTN_ROUTES:
            raise ValueError(
                f"paged_attn must be one of {PAGED_ATTN_ROUTES} or None "
                f"(auto), got {paged_attn!r}")
        _check_read_windows(read_windows, kv_page, cfg.max_seq)
        self.params = sparselinear.hold_projections(params, cfg)
        self.cfg = cfg
        self.max_context = cfg.max_seq
        self.kv_page = kv_page
        self.kv_pool_blocks = kv_pool_blocks
        self.n_kv_blocks = None
        self.paged_attn = paged_attn
        self.read_windows = tuple(sorted(read_windows)) if read_windows else None
        self.kv_bytes_per_token = cfg.kv_bytes_per_token
        self.attn_select_topk = cfg.topk * cfg.block_size
        self.attn_select_dense_len = cfg.dense_len

    def check_serving(self, serving) -> None:
        """Refuse the ServingConfig options this family cannot serve."""
        if serving.spec_tokens:
            raise ValueError(
                "SparseLinearSlotModel has no spec_step: a rejected draft "
                "would need the linear layers' rows rolled back to the "
                "last accepted token, and a step keeps no earlier copy "
                "(spec_tokens=0)")
        if serving.kv_swap is not None:
            raise ValueError(
                "SparseLinearSlotModel cannot park or swap a session: its "
                "pages and compressed keys could be staged, its linear "
                "layers' rows have no snapshot at the boundary "
                "(kv_swap=None; park, resume and migrate need it)")
        if serving.disagg is not None:
            raise ValueError(
                "SparseLinearSlotModel has no slot-less prefill: a prefill "
                "worker fills pool blocks, and the linear layers' rows "
                "have no home outside a slot (disagg=None)")

    def recurrent_state_bytes(self, slots: int) -> int:
        return slots * self.cfg.recurrent_bytes_per_slot

    def _chunk_attn_shapes(self, queries: int, window: int):
        """A sparse layer's chunk: a key/value head's G query heads over
        its own plane of the window, a token a row."""
        cfg = self.cfg
        plane = jax.ShapeDtypeStruct((1, window, cfg.head_dim), cfg.dtype)
        return (jax.ShapeDtypeStruct(
            (1, queries, cfg.group, cfg.head_dim), cfg.dtype), plane, plane)

    def chunk_keys_attended(self, queries: int, end: int,
                            window: int) -> tuple[bool, int]:
        """A window past ``dense_len`` is attended under the selection's
        mask: in the chunk kernel where it also takes the mask's blocks
        (``chunk_attn.takes_mask``), the rule the traced program applies."""
        if window > self.cfg.dense_len and not chunk_attn.takes_mask(
                (window // self.kv_page,), window):
            return False, window
        return super().chunk_keys_attended(queries, end, window)

    def ssm_step_in_kernel(self) -> bool:
        """Whether a decode step traced now updates the linear layers' rows
        in the Pallas kernel: the question the trace itself asks."""
        return sparselinear.step_in_kernel(1)

    def init_state(self, slots: int):
        self.n_kv_blocks = _pool_blocks(self, slots)
        return sparselinear.init_sparselinear_state(
            self.cfg, slots, self.kv_page, self.n_kv_blocks)

    def prefill_into_slot(self, params, state, padded, slot, true_len):
        logits, new = self.prefill_into_slots(
            params, state, padded, jnp.asarray(slot)[None],
            jnp.asarray(true_len)[None])
        return logits[0], new

    def prefill_into_slots(self, params, state, padded, slots, true_lens):
        return sparselinear.sparselinear_prefill_rows(
            params, self.cfg, state, padded, slots, true_lens)

    def decode_step(self, params, state, tokens, active, kv_bucket,
                    unroll=False):
        del unroll  # sparse layers unrolled, linear runs looped: _walk
        return sparselinear.sparselinear_decode_step(
            params, self.cfg, state, tokens, active,
            kv_bucket or self.max_context, paged_attn=self.paged_attn)

    def prefill_chunk_into_slot(self, params, state, chunk, slot, offset,
                                new_len, kv_bucket=0, unroll=False,
                                block_ids=None):
        del unroll
        window = kv_bucket or self.max_context
        if block_ids is None:  # the slot's own table row
            block_ids = state["table"][slot, :window // self.kv_page]
        return sparselinear.sparselinear_prefill_chunk(
            params, self.cfg, state, chunk, slot, offset, new_len, window,
            block_ids)


class WindowSlotModel(_CountsHeadChunks):
    """Window layers among full layers (vtpu/models/swa): a session's cache
    is of two kinds, pages of the paged pool for the full layers and a
    slot-indexed ring of ``window`` rows for the window layers, in one
    engine state, so the allocator, batched and chunked admission, the read
    windows and the sampler serve it as they serve the other families.

    It states what the engine cannot know of it: ``read_windows``,
    ``kv_bytes_per_token`` (the full layers alone: pages are charged for
    them and for nothing else), ``recurrent_state_bytes`` (the rings,
    whatever a session's length), ``window_ring`` (the rows a ring holds),
    ``ring_bytes_per_position`` (what a cached token would cost the
    window layers were they paged) and ``experts_grouped`` (whether a
    launch of so many rows has each held expert multiply its own rows
    alone). Paged only; ``paged_attn`` forces the
    full layers' decode route as the dense family's does. What a session
    with rings cannot do yet is refused by name, each with the mechanism
    that is missing (``check_serving``, ``refuses``)."""

    supports_kv_buckets = True
    mesh = None
    refuses = {
        "register_prefix": (
            "a shared prefix is pages; a session that starts from one also "
            "needs the window layers' rings as they stood at the prefix's "
            "last token, and no snapshot of them is kept at a boundary"),
        "drain": (
            "migration ships a session as pool pages; the window layers' "
            "rings have no staging"),
    }

    def __init__(self, params: Any, cfg: Any, kv_page: Optional[int] = None,
                 kv_pool_blocks: Optional[int] = None,
                 read_windows: Optional[tuple] = None,
                 paged_attn: Optional[str] = None,
                 mesh: Optional[Any] = None):
        if mesh is not None:
            raise ValueError(
                "WindowSlotModel has no sharding rule for the rings (rows "
                "of key/value heads side by side beside a pool of the "
                "same): pass no mesh")
        if kv_page is None:
            raise ValueError(
                "WindowSlotModel has a paged cache only: set kv_page")
        if getattr(cfg, "kv_int8", False):
            raise ValueError(
                "WindowSlotModel has no int8 cache: a row holds a token's "
                "heads side by side, which the per-head scales' planes do "
                "not follow")
        if paged_attn is not None and paged_attn not in PAGED_ATTN_ROUTES:
            raise ValueError(
                f"paged_attn must be one of {PAGED_ATTN_ROUTES} or None "
                f"(auto), got {paged_attn!r}")
        _check_read_windows(read_windows, kv_page, cfg.max_seq)
        self.cfg = cfg
        self.max_context = cfg.max_seq
        self.kv_page = kv_page
        self.kv_pool_blocks = kv_pool_blocks
        self.n_kv_blocks = None
        self.paged_attn = paged_attn
        self.read_windows = tuple(sorted(read_windows)) if read_windows else None
        self.kv_bytes_per_token = cfg.kv_bytes_per_token
        self.window_ring = cfg.window
        self.ring_bytes_per_position = cfg.ring_bytes_per_position
        self.params = {**params, "layers": swa.hold_projections(
            params["layers"], cfg)}

    def check_serving(self, serving) -> None:
        """Refuse the ServingConfig options this family cannot serve."""
        if serving.spec_tokens:
            raise ValueError(
                "WindowSlotModel has no spec_step: a rejected draft would "
                "need the rings' overwritten rows back, and a step keeps no "
                "earlier copy (spec_tokens=0)")
        if serving.kv_swap is not None:
            raise ValueError(
                "WindowSlotModel cannot park or swap a session: its pages "
                "could be staged, its rings have no snapshot at the "
                "boundary (kv_swap=None; park, resume and migrate need it)")
        if serving.disagg is not None:
            raise ValueError(
                "WindowSlotModel has no slot-less prefill: a prefill worker "
                "fills pool blocks, and the rings have no home outside a "
                "slot (disagg=None)")

    def recurrent_state_bytes(self, slots: int) -> int:
        return slots * self.cfg.ring_bytes_per_slot

    experts_grouped = _experts_grouped

    def _chunk_attn_shapes(self, queries: int, window: int):
        """A full layer's chunk: rows of key/value heads side by side."""
        cfg = self.cfg

        def of(*shape):
            return jax.ShapeDtypeStruct(shape, cfg.dtype)

        return (of(1, queries, cfg.n_heads, cfg.head_dim),
                of(1, window, cfg.n_kv_heads * cfg.head_dim),
                of(1, window, cfg.n_kv_heads * cfg.v_head_dim))

    def init_state(self, slots: int):
        self.n_kv_blocks = _pool_blocks(self, slots)
        return swa.init_swa_state(
            self.cfg, slots, self.kv_page, self.n_kv_blocks)

    def prefill_into_slot(self, params, state, padded, slot, true_len):
        logits, new = self.prefill_into_slots(
            params, state, padded, jnp.asarray(slot)[None],
            jnp.asarray(true_len)[None])
        return logits[0], new

    def prefill_into_slots(self, params, state, padded, slots, true_lens):
        return swa.swa_prefill_rows(
            params, self.cfg, state, padded, slots, true_lens)

    def decode_step(self, params, state, tokens, active, kv_bucket,
                    unroll=False):
        del unroll  # seven layers of three kinds: always walked unrolled
        return swa.swa_decode_step(
            params, self.cfg, state, tokens, active,
            kv_bucket or self.max_context, paged_attn=self.paged_attn)

    def prefill_chunk_into_slot(self, params, state, chunk, slot, offset,
                                new_len, kv_bucket=0, unroll=False,
                                block_ids=None):
        del unroll
        window = kv_bucket or self.max_context
        if block_ids is None:  # the slot's own table row
            block_ids = state["table"][slot, :window // self.kv_page]
        return swa.swa_prefill_chunk(
            params, self.cfg, state, chunk, slot, offset, new_len, window,
            block_ids)


class BlockDiffSlotModel(_CountsHeadChunks):
    """Generation by diffusion over blocks (vtpu/models/blockdiff.py): the
    expert decoder on the shared trunk, its cache the paged pool walked by
    the one page table, so the engine's allocator, chunked admission and
    read windows serve it as they serve the other families. What it yields
    is not a token a step: ``block_length`` says so, and the engine then
    runs its loop of passes (``ServingEngine._loop_blocks``), admits
    without a first token and hands a block's tokens over when the device
    reports it clean.

    It states what the engine cannot know of it: ``block_length``,
    ``read_windows``, ``kv_bytes_per_token``, ``experts_grouped`` and
    ``block_attn_route`` (the route a pass traced now takes, for the
    engine's counters), and keeps the layout of its state and of a pass's
    result to itself: the engine opens a block through ``open_block`` and
    reads a pass through ``read_pass``. Paged only. What cannot serve this family yet is
    refused by name, each with the mechanism that is missing
    (``check_serving``, ``refuses``)."""

    supports_kv_buckets = True
    mesh = None
    refuses = {
        "register_prefix": (
            "a shared prefix's pages could be mapped, but an admission from "
            "one opens its first block at the prefix's end, and the prefix "
            "build has no pass that ends on a block boundary with nothing "
            "sampled"),
        "drain": (
            "migration ships a session as pool pages and a next token; a "
            "session here is also a block on the device (ids, flags, the "
            "passes that committed them), which has no staging"),
    }

    def __init__(self, params: Any, cfg: Any, kv_page: Optional[int] = None,
                 kv_pool_blocks: Optional[int] = None,
                 read_windows: Optional[tuple] = None,
                 paged_attn: Optional[str] = None,
                 mesh: Optional[Any] = None):
        if mesh is not None:
            raise ValueError(
                "BlockDiffSlotModel serves one chip's share of each layer "
                "(its held experts) and has no sharding rule: pass no mesh")
        if kv_page is None:
            raise ValueError(
                "BlockDiffSlotModel has a paged cache only: set kv_page")
        if cfg.kv_int8:
            raise ValueError(
                "BlockDiffSlotModel has no int8 cache: the walk that joins "
                "the pool with a pass's own keys reads bfloat16 pages")
        blockdiff.block_attn_route(paged_attn)  # a bad name raises here
        _check_read_windows(read_windows, kv_page, cfg.max_seq)
        self.cfg = cfg
        self.max_context = cfg.max_seq
        self.kv_page = kv_page
        self.kv_pool_blocks = kv_pool_blocks
        self.n_kv_blocks = None
        self.paged_attn = paged_attn
        self.block_length = cfg.block_length
        self.read_windows = tuple(sorted(read_windows)) if read_windows else None
        self.kv_bytes_per_token = kv_bytes_per_token(cfg)
        self.params = {**params, "layers": hold_projections(
            params["layers"], cfg)}

    def check_serving(self, serving) -> None:
        """Refuse the ServingConfig options this family cannot serve."""
        if not serving.prefill_chunk:
            raise ValueError(
                "BlockDiffSlotModel admits a prompt in chunks under the "
                "block mask and has no whole-prompt program: set "
                "prefill_chunk (a multiple of block_length)")
        if serving.prefill_chunk % self.block_length:
            raise ValueError(
                f"prefill_chunk {serving.prefill_chunk} must be a multiple "
                f"of block_length {self.block_length}: a chunk's edge may "
                "not cut a block, whose rows see each other")
        if serving.spec_tokens:
            raise ValueError(
                "BlockDiffSlotModel has no spec_step: a pass already "
                "commits several rows of a block, and a draft beyond the "
                "block has no rows to verify it (spec_tokens=0)")
        if serving.decode_loop_k and serving.decode_loop_k > 1:
            raise ValueError(
                "BlockDiffSlotModel has no device loop or fused loop: a "
                "flush of k passes would hand blocks over inside the loop, "
                "and the loop's carry is a token a slot (decode_loop_k=None)")
        if serving.kv_swap is not None:
            raise ValueError(
                "BlockDiffSlotModel cannot park or swap a session: its "
                "pages could be staged, its block on the device has no "
                "snapshot (kv_swap=None; park, resume and migrate need it)")
        if serving.disagg is not None:
            raise ValueError(
                "BlockDiffSlotModel has no slot-less prefill: a prefill "
                "worker hands over a first token, and an admission here "
                "yields none (disagg=None)")
        if serving.temperature > 0.0 or serving.logprobs:
            raise ValueError(
                "BlockDiffSlotModel commits a row's best token by its "
                "confidence on the device: no temperature, no logprobs")
        if serving.pipeline_decode is False:
            raise ValueError(
                "BlockDiffSlotModel has the pipelined loop alone "
                "(pipeline_decode=None)")

    experts_grouped = _experts_grouped

    def block_attn_route(self) -> str:
        """The route a pass traced now takes: the question the trace asks."""
        return blockdiff.block_attn_route(self.paged_attn)

    def init_state(self, slots: int):
        self.n_kv_blocks = _pool_blocks(self, slots)
        return blockdiff.init_block_state(
            self.cfg, slots, self.kv_page, self.n_kv_blocks)

    def block_pass(self, params, state, active, kv_bucket):
        return blockdiff.block_pass(
            params, self.cfg, state, active, kv_bucket or self.max_context,
            paged_attn=self.paged_attn)

    def open_block(self, state, slot, ids, masked, end):
        """An admission's last act: ``slot``'s first generated block opened
        at its cached length, ``ids [block_length]`` committed where
        ``masked`` is False, its generation ending at position ``end``."""
        return blockdiff.open_block(state, slot, ids, masked, end)

    def read_pass(self, result):
        """``block_pass``'s fetched result by name, an entry a slot
        (``blockdiff.PassResult``): what the engine delivers and counts."""
        return blockdiff.read_pass(result)

    def prefill_chunk_into_slot(self, params, state, chunk, slot, offset,
                                new_len, kv_bucket=0, unroll=False,
                                block_ids=None):
        del unroll  # the held experts' kernels read a static layer's stack
        window = kv_bucket or self.max_context
        if block_ids is None:  # the slot's own table row
            block_ids = state["table"][slot, :window // self.kv_page]
        return slot_steps.chunked_prefill_into_slot(
            params, self.cfg, state, chunk, slot, offset, new_len,
            kv_bucket=kv_bucket, unroll=True,
            ffn_fn=moe.held_moe_ffn(self.cfg), block_ids=block_ids,
            layer_of=LayerOfStack)
