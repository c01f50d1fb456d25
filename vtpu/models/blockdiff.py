"""Generation by diffusion over blocks (SDAR's language model): the expert
decoder of ``moe.py`` on the shared trunk of ``transformer.py``, under a mask
that is causal between blocks of ``block_length`` positions and two-sided
inside one, generating a block at a time.

What differs from a model that yields a token a step, by mechanism:

- **A pass** is ``block_length`` rows a slot, at the next positions after
  what the slot has cached. The rows of one slot see each other both ways
  and every cached token before the block; logits are read at a masked
  row's own position (no shift). One launch serves the whole slot pool,
  slots in either phase together (``block_pass``).
- **A slot's block lives on the device** between passes (the ``blk_*``
  leaves of the engine state): its ids, a flag a row (masked or committed: never a
  comparison with the mask id, which a prompt or a commit may hold as an
  ordinary token), the pass that committed each row, the request's pass
  count and the position its generation ends at. The block's first position
  is the slot's cached length (``len``). The host dispatches pass t + 1
  before it has fetched pass t: the device itself knows what each slot
  does next.
- **Two phases.** A slot with a masked row it may still commit takes a
  *denoising* pass: the mask id embedded at masked rows, attention over
  cache and own block, the head, and the commit (``commit_rows``); the
  pool is left bit for bit as it was (the scatter's block id is out of
  range for such a slot and drops). A slot with none takes the *writing*
  pass: the clean block's keys and values scattered into its pages
  (``kv_write``), the length advanced by a block, and the next block opened
  all masked. Rows at or past the request's end are never committed and
  stay masked: a stream cut inside its last block reads the mask id there
  in every pass, which is what its replay can rebuild.
- **Commits are chosen on the device**: confidence is the best token's
  probability under the float32 softmax of the row's logits; the masked
  rows over ``confidence_threshold`` commit, and never fewer than
  ``block_length / denoising_steps`` of the most confident (ties to the
  lower position). A threshold of None is the static rule.
- **Attention over the pool and the pass's own keys** (scope
  ``block_attn``): on the kernel's route the paged walk for grouped queries
  reads the slot's live pages in place for all ``block_length x G`` query
  rows a key/value head and returns each row's log-sum-exp, the own block's
  ``block_length`` keys are scored beside it, and the two softmaxes are
  joined exactly; the gather route lays the own rows into the gathered
  window at their positions and masks by length. A chunk of a prompt runs
  ``slots.chunked_prefill_into_slot`` under the same block mask
  (``cfg.attn_block``; ``transformer.cached_attention``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from vtpu.models.latent import LayerOfStack
from vtpu.models.moe import MoEConfig, held_moe_ffn
from vtpu.models.transformer import (
    _qkv,
    init_paged_kv_cache,
    kv_heads,
    spec_verify_loop,
)
from vtpu.ops import causal_attention, gather_kv_pages, rope_angles
from vtpu.ops.decode_attn import PAGED_ATTN_ROUTES, paged_decode_attention

Params = dict[str, Any]
# the columns of a pass's result, a row a slot (``block_pass``; ``read_pass``
# names them for the host)
PHASE, CLEAN, FIRST, ELIGIBLE, COMMITTED, IDS = 0, 1, 2, 3, 4, 5
NONE, DENOISE, WRITE = 0, 1, 2


class PassResult(NamedTuple):
    """A pass's fetched result by name, an entry a slot (``read_pass``)."""

    took: Any       # [B] bool: the slot took a pass, of either phase
    wrote: Any      # [B] bool: it was the writing pass
    clean: Any      # [B] bool: this pass committed the block's last open row
    first: Any      # [B] int: the block's first position
    eligible: Any   # [B] int: rows that could answer
    committed: Any  # [B] int: rows that did
    ids: Any        # [B, bl] int: the block's ids after the pass
    when: Any       # [B, bl] int: the request's pass that committed each
                    # (-1: a row of the prompt, or still masked)


def read_pass(result) -> PassResult:
    """``block_pass``'s result [B, 5 + 2 * bl], fetched, by name."""
    bl = (result.shape[1] - IDS) // 2
    phase = result[:, PHASE]
    return PassResult(
        took=phase != NONE, wrote=phase == WRITE,
        clean=result[:, CLEAN] != 0, first=result[:, FIRST],
        eligible=result[:, ELIGIBLE], committed=result[:, COMMITTED],
        ids=result[:, IDS:IDS + bl], when=result[:, IDS + bl:])


@dataclasses.dataclass(frozen=True)
class BlockDiffConfig(MoEConfig):
    """``MoEConfig`` (toy sizes by default; vbench/sut/blockdiff.py gives
    the published) and what generation by blocks adds; ``moe.init_moe_params``
    makes its seeded weights (the QK-norm gains, the untied head, the held
    share of the expert stacks)."""

    qk_norm: bool = True
    tied_head: bool = False
    rope_theta: float = 1e6
    block_length: int = 4
    mask_token_id: int = 0
    # a deployment's two: passes a block at the least rate of commits, and
    # the confidence over which a row commits whatever its rank (None: the
    # static rule, block_length / denoising_steps rows a pass exactly)
    denoising_steps: int = 4
    confidence_threshold: Optional[float] = 0.9

    def __post_init__(self):
        bl, steps = self.block_length, self.denoising_steps
        if bl < 1 or steps < 1 or bl % steps:
            raise ValueError(
                f"denoising_steps {steps} must divide block_length {bl}")
        if self.max_seq % bl:
            raise ValueError(
                f"block_length {bl} must divide max_seq {self.max_seq}")
        if not 0 <= self.mask_token_id < self.vocab:
            raise ValueError(
                f"mask_token_id {self.mask_token_id} lies outside the "
                f"vocabulary of {self.vocab}")

    @property
    def attn_block(self) -> int:
        """What ``transformer.cached_attention`` masks a chunk by."""
        return self.block_length

    @property
    def commits_a_pass(self) -> int:
        return self.block_length // self.denoising_steps


def init_block_state(cfg: BlockDiffConfig, slots: int, page: int,
                     n_blocks: int) -> dict[str, jax.Array]:
    """The engine state: the paged pool, page table and lengths as
    ``init_paged_kv_cache`` lays them, and every slot's block beside them
    (an idle slot's block is all masked and ends at 0: it does nothing)."""
    if page % cfg.block_length:
        raise ValueError(
            f"block_length {cfg.block_length} must divide kv page {page}")
    bl = cfg.block_length
    return {
        **init_paged_kv_cache(cfg, slots, page, n_blocks),
        "blk_ids": jnp.zeros((slots, bl), jnp.int32),
        "blk_masked": jnp.ones((slots, bl), bool),
        "blk_when": jnp.full((slots, bl), -1, jnp.int32),
        "blk_pass": jnp.zeros((slots,), jnp.int32),
        "blk_end": jnp.zeros((slots,), jnp.int32),
    }


def open_block(state, slot, ids, masked, end):
    """The state with ``slot``'s first generated block opened at its cached
    length (an admission's last act): the prompt's tail ``ids [bl]`` as
    rows already committed (``masked`` False there), the rest masked, the
    request's pass count at 0 and its generation ending at ``end``."""
    return {**state,
            "blk_ids": state["blk_ids"].at[slot].set(ids),
            "blk_masked": state["blk_masked"].at[slot].set(masked),
            "blk_when": state["blk_when"].at[slot].set(-1),
            "blk_pass": state["blk_pass"].at[slot].set(0),
            "blk_end": state["blk_end"].at[slot].set(end)}


def block_attn_route(override: Optional[str],
                     backend: Optional[str] = None) -> str:
    """The route of a pass's attention: forced, or the paged walk on a TPU
    and the gathered window elsewhere (the Pallas interpreter is a
    correctness rig). A static property of the program, so the engine's
    route counters read the same call."""
    if override is not None:
        if override not in PAGED_ATTN_ROUTES:
            raise ValueError(
                f"paged_attn must be one of {PAGED_ATTN_ROUTES} or None "
                f"(auto), got {override!r}")
        return override
    return "kernel" if (backend or jax.default_backend()) == "tpu" \
        else "gather"


def commit_rows(cfg: BlockDiffConfig, logits: jax.Array,
                eligible: jax.Array):
    """Which of a block's rows a pass commits, and to what: logits [B, bl,
    V] float32, eligible [B, bl] (masked rows the request may still
    commit). A row's token is its best and its confidence that token's
    probability under the softmax; the eligible rows over
    ``confidence_threshold`` commit, and never fewer than
    ``commits_a_pass`` of the most confident (ties to the lower position);
    a block with fewer eligible rows commits them all. Returns (tokens
    [B, bl] int32, commit [B, bl] bool)."""
    bl = cfg.block_length
    top = jnp.max(logits, axis=-1)
    tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    conf = 1.0 / jnp.sum(jnp.exp(logits - top[..., None]), axis=-1)
    score = jnp.where(eligible, conf, -1.0)
    row = jnp.arange(bl)
    ahead = (score[:, None, :] > score[:, :, None]) | (
        (score[:, None, :] == score[:, :, None])
        & (row[None, None, :] < row[None, :, None]))
    rank = jnp.sum(ahead, axis=-1)  # rows that go before this one
    commit = rank < cfg.commits_a_pass
    if cfg.confidence_threshold is not None:
        commit = commit | (conf > cfg.confidence_threshold)
    return tokens, commit & eligible


def _block_attention(cfg: BlockDiffConfig, state, kv_bucket: int, write,
                     route: str):
    """``attend(l, lp, x, kv) -> (attn [B, bl, H, Dh], kv)`` of one pass:
    the block's keys and values scattered into the pool for the slots in
    ``write`` alone, then every row's attention over the cached tokens
    before the block and the block's own rows."""
    bl = cfg.block_length
    lens = state["len"]
    page, n_blocks = state["k"].shape[2], state["k"].shape[1]
    table_w = state["table"][:, :(kv_bucket or cfg.max_seq) // page]
    pos = lens[:, None] + jnp.arange(bl)[None, :]
    positions = jnp.minimum(pos, cfg.max_seq - 1)
    cos, sin = rope_angles(cfg.max_seq, cfg.head_dim, cfg.rope_theta)
    blocks = jnp.take_along_axis(
        state["table"], jnp.minimum(pos // page, state["table"].shape[1] - 1),
        axis=1)
    # a denoising slot's block id is out of range and its rows drop: the
    # pool stays as it was
    wblk = jnp.where(write[:, None] & (pos < cfg.max_seq), blocks, n_blocks)
    woff = pos % page
    plane = state["k"].shape[-2:]
    hk, g = kv_heads(cfg), cfg.n_heads // kv_heads(cfg)
    scale = cfg.head_dim ** -0.5
    rows = jnp.arange(lens.shape[0])[:, None]
    cached = jnp.broadcast_to(lens[:, None], pos.shape)

    def attend(l, lp, x, kv):
        q, k, v = _qkv(cfg, lp, x, cos, sin, positions)
        # the block's rows as the pool stores a token's
        k_rows, v_rows = (a.reshape(a.shape[:2] + plane) for a in (k, v))
        with jax.named_scope("kv_write"):
            kv = {**kv,
                  "k": kv["k"].at[l, wblk, woff].set(k_rows, mode="drop"),
                  "v": kv["v"].at[l, wblk, woff].set(v_rows, mode="drop")}
        with jax.named_scope("attn"), jax.named_scope("block_attn"):
            if route == "kernel":
                return _joined(q, k, v, kv, l), kv
            # the gathered window with the block's rows laid in at their
            # positions, read up to the block's end by every row
            win_k = gather_kv_pages(kv["k"][l], table_w).at[rows, pos].set(
                k_rows, mode="drop")
            win_v = gather_kv_pages(kv["v"][l], table_w).at[rows, pos].set(
                v_rows, mode="drop")
            return causal_attention(
                q, win_k, win_v, kv_len=cached + bl, scale=scale), kv

    def _joined(q, k, v, kv, l):
        """The paged walk over the cache and the own block's scores, each
        a softmax of its own, joined by their log-sum-exps."""
        far, far_lse = paged_decode_attention(
            q, kv["k"], kv["v"], table_w, cached, layer=l, scale=scale,
            lse=True)
        b = q.shape[0]
        qg = q.reshape(b, bl, hk, g, cfg.head_dim)
        s = jnp.einsum("bqkgd,bskd->bqkgs", qg, k,
                       preferred_element_type=jnp.float32) * scale
        top = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - top)
        total = jnp.sum(p, axis=-1, keepdims=True)
        near = jnp.einsum("bqkgs,bskd->bqkgd", (p / total).astype(v.dtype),
                          v, preferred_element_type=jnp.float32)
        near_lse = (top + jnp.log(total)).reshape(b, bl, cfg.n_heads, 1)
        near = near.reshape(b, bl, cfg.n_heads, cfg.head_dim)
        far_lse = far_lse[..., None]
        both = jnp.maximum(far_lse, near_lse)
        w_far, w_near = jnp.exp(far_lse - both), jnp.exp(near_lse - both)
        out = (w_far * far.astype(jnp.float32) + w_near * near) / (
            w_far + w_near)
        return out.astype(q.dtype)

    return attend


def block_pass(params: Params, cfg: BlockDiffConfig, state, active,
               kv_bucket: int = 0, paged_attn: Optional[str] = None):
    """One pass for the whole slot pool: active [B] -> (result [B, 5 + 2 *
    bl] int32, state). A row of the result is a slot's ``PHASE`` (``NONE``:
    idle, or its generation has reached its end; ``DENOISE``; ``WRITE``),
    ``CLEAN`` (1 where this pass committed the block's last open row: its
    tokens may be handed over), ``FIRST`` (the block's first position),
    ``ELIGIBLE`` and ``COMMITTED`` (rows that could answer, rows that did),
    then the block's ids and the request's pass that committed each (-1: a
    row of the prompt, or still masked) as they stand after the pass. The
    attention reads the first ``kv_bucket`` positions of a slot's table row
    (0: all)."""
    bl = cfg.block_length
    lens, end = state["len"], state["blk_end"]
    ids, masked, when = (state["blk_ids"], state["blk_masked"],
                         state["blk_when"])
    passes = state["blk_pass"]
    pos = lens[:, None] + jnp.arange(bl)[None, :]
    eligible = masked & (pos < end[:, None]) & active[:, None]
    denoise = jnp.any(eligible, axis=1)
    write = active & ~denoise & (lens < end)
    tokens = jnp.where(masked, cfg.mask_token_id, ids)
    route = block_attn_route(paged_attn)
    logits, new_kv = spec_verify_loop(
        params, cfg, state, tokens, kv_bucket, None,
        ffn_fn=held_moe_ffn(cfg), unroll=True,
        attend=_block_attention(cfg, state, kv_bucket, write, route),
        layer_of=LayerOfStack)
    with jax.named_scope("sample"):
        best, commit = commit_rows(cfg, logits, eligible)
        ids = jnp.where(commit, best, ids)
        masked = masked & ~commit
        when = jnp.where(commit, passes[:, None], when)
        clean = denoise & ~jnp.any(masked & (pos < end[:, None]), axis=1)
        phase = jnp.where(denoise, DENOISE, jnp.where(write, WRITE, NONE))
        result = jnp.concatenate([
            jnp.stack([phase, clean.astype(jnp.int32), lens,
                       jnp.sum(eligible, axis=1),
                       jnp.sum(commit, axis=1)], axis=1).astype(jnp.int32),
            ids, when], axis=1)
        # the writing pass hands the block over: the next one opens masked
        # at the next positions
        opened = write[:, None]
        new = {**state, **new_kv,
               "len": jnp.where(write, lens + bl, lens),
               "blk_ids": jnp.where(opened, 0, ids),
               "blk_masked": masked | opened,
               "blk_when": jnp.where(opened, -1, when),
               "blk_pass": passes + (phase != NONE)}
    return result, new
