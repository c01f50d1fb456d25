"""Toy reference of a model that generates by diffusion over blocks, a
reference alone (the program has no model of it yet): the dense block
under a mask that is causal between blocks of ``block_length`` positions
and two-sided inside one. A block starts as ``mask_token_id`` wherever the
prompt does not reach; a pass over the block, against the clean blocks
before it, gives logits at the masked positions' own rows, some of them
are committed, and passes repeat until none is masked. A served token is
therefore answered by its own row, of the pass that committed it, whose
input held mask ids where the block was not yet committed.

``passes`` rebuilds those inputs from the commit trail and lays them side
by side: the clean sequence first (segment 0), then one copy of a block
for every pass that committed a token of it (segments 1, 2, ...), holding
what that pass saw. ``beside`` carries each row's position, block and
segment, from which ``layer`` makes the rotary angles and the mask: a row
attends its own segment up to its own block, and a block's copy the clean
blocks before it. ``replay_blocks`` of the configuration bounds how many
blocks' copies one pass of the replay holds (absent: all of them)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from vbench.reference import common, dense

weight_specs = dense.weight_specs
decode_step_cost = dense.decode_step_cost


def passes(cfg: dict, prompt, served, trail) -> list[dict]:
    bl, mask = cfg["block_length"], cfg["mask_token_id"]
    p, end = len(prompt), len(prompt) + len(served)
    clean = np.concatenate([prompt, served]).astype(np.int32)
    trail = np.asarray(trail)
    blocks = list(range(p // bl, -(-end // bl)))
    per = cfg.get("replay_blocks") or len(blocks)
    out = []
    for at in range(0, len(blocks), per):
        group = blocks[at:at + per]
        # the clean blocks that the group's copies attend: all before its last
        tokens = [clean[:min(group[-1] * bl, end)]]
        pos = [np.arange(len(tokens[0]))]
        seg = [np.zeros(len(tokens[0]), np.int32)]
        rows, chosen = [], []
        for b in group:
            here = np.arange(b * bl, (b + 1) * bl)
            new = (here >= p) & (here < end)
            when = np.where(new, trail[np.clip(here - p, 0, len(served) - 1)],
                            -1)
            for t in sorted(set(when[new].tolist())):
                seen = (here < p) | (new & (when < t))
                state = np.where(seen, clean[np.minimum(here, end - 1)], mask)
                offset = sum(len(x) for x in tokens)
                tokens.append(state)
                pos.append(here)
                seg.append(np.full(bl, len(seg), np.int32))
                rows.append(offset + np.flatnonzero(new & (when == t)))
                chosen.append(clean[here[new & (when == t)]])
        pos = np.concatenate(pos)
        out.append({"tokens": np.concatenate(tokens),
                    "rows": np.concatenate(rows),
                    "chosen": np.concatenate(chosen),
                    "beside": {"pos": pos, "block": pos // bl,
                               "seg": np.concatenate(seg)}})
    return out


def _rope_at(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """``common.rope`` at the positions given (a block's copy repeats the
    positions of the clean block it stands for)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer(cfg: dict, w: dict, x: jax.Array, precision: str,
          beside: dict) -> jax.Array:
    """One block over the rows of one pass x [S, D] (float32); a padding
    row (position -1) attends itself alone and nothing attends it."""
    pos, block, seg = beside["pos"], beside["block"], beside["seg"]
    s = x.shape[0]
    h, dh = cfg["num_attention_heads"], cfg["head_dim"]
    hi = jax.lax.Precision.HIGHEST
    n = common.rms_norm(x, w["attn_norm"], cfg["rms_norm_eps"])
    q = _rope_at(common.mm(n, w["wq"], precision).reshape(s, h, dh), pos,
                 cfg["rope_theta"])
    k = _rope_at(common.mm(n, w["wk"], precision).reshape(s, h, dh), pos,
                 cfg["rope_theta"])
    v = common.mm(n, w["wv"], precision).reshape(s, h, dh)
    own = (seg[None, :] == seg[:, None]) & (block[None, :] <= block[:, None])
    before = (seg[None, :] == 0) & (block[None, :] < block[:, None])
    sees = ((own | before) & (pos[None, :] >= 0)) | jnp.eye(s, dtype=bool)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=hi) / math.sqrt(dh)
    probs = jax.nn.softmax(jnp.where(sees[None], scores, -jnp.inf), axis=-1)
    attn = jnp.einsum("hqk,khd->qhd", probs, v, precision=hi)
    x = x + common.mm(attn.reshape(s, h * dh), w["wo"], precision)
    n = common.rms_norm(x, w["mlp_norm"], cfg["rms_norm_eps"])
    return x + common.swiglu(n, w["w_gate"], w["w_up"], w["w_down"],
                             precision)
