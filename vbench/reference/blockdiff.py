"""Plain reference of a sparse-expert decoder that generates by diffusion over
blocks (SDAR's language model, ``model_type`` ``sdar_moe``), as the
configuration's file cuts it: ``num_hidden_layers`` layers, every one sparse
(``decoder_sparse_step`` 1, ``mlp_only_layers`` []), holding a share of the
routed experts. Float32, ``highest``, no cache.

A layer on hidden x (pre-norm residual blocks, epsilon ``rms_norm_eps``, a
final norm, an output head of its own: ``tie_word_embeddings`` false):
  n = rms_norm(x); q = W_q n as H heads of Dh = ``head_dim``; k = W_k n and
  v = W_v n as Hk = ``num_key_value_heads`` heads; no bias. An RMS norm a
  head (gain [Dh]) on q and on k, then rotary positions over the whole head,
  halves rotated against each other, base ``rope_theta``. Query head h reads
  key/value head h // (H / Hk).
  score_h(i, j) = Dh^-0.5 q_h,i . k_g,j; p = softmax over the j that i sees.
  **The mask**: position i sees position j iff j // B <= i // B, B =
  ``block_length``: causal between blocks, two-sided inside one, in the
  prompt as well.
  x = x + W_o concat_h(sum_j p_h(i, j) v_g,j).
  n = rms_norm(x); g = softmax(n W_r) over all ``num_experts_published``
  experts in float32; the ``num_experts_per_tok`` largest are chosen and
  weigh g_e / sum_chosen g (``norm_topk_prob``); x = x + sum_e w_e
  SwiGLU_e(n), experts ``moe_intermediate_size`` wide, no shared expert,
  nothing dropped; of the routed experts only the held ones add
  (``held_experts_first`` .., ``num_experts`` of them: the rest left out,
  here and in the program alike).

**Generation**: a block starts as B mask ids (``mask_token_id``) at the next
B positions; a pass embeds the block's present state, attends every clean
block before it and the block's own rows, and gives logits at each masked
position's own row (no shift); confidence is the softmax probability of the
row's best token; a pass commits the masked rows over a threshold, and never
fewer than B / denoising_steps of the most confident; when no row is masked
a last pass over the clean block gives the keys and values that later
blocks read. A prompt of L tokens is L // B whole blocks; its other L % B
tokens open the first generated block as rows already committed. Rows at or
past a request's end (prompt + ``max_new_tokens``) are never committed: a
stream cut inside its last block holds the mask id there in every pass.

``passes`` rebuilds each pass's input from the commit trail and lays the
passes side by side: the clean sequence first (segment 0), then one copy of
a block for every pass that committed a token of it (segments 1, 2, ...),
holding what that pass saw. ``beside`` carries each row's position, block
and segment, from which ``layer`` makes the rotary angles and the mask: a
row attends its own segment up to its own block, and a block's copy the
clean blocks before it. One pass of the replay holds every block's copies.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from vbench.reference import common

_HI = jax.lax.Precision.HIGHEST
_QUERY_BLOCK = 128


def _dims(cfg: dict) -> dict:
    return dict(
        d=cfg["hidden_size"], h=cfg["num_attention_heads"],
        hk=cfg["num_key_value_heads"], dh=cfg["head_dim"],
        f=cfg["moe_intermediate_size"], e=cfg["num_experts_published"],
        held=cfg["num_experts"],
        k=cfg["num_experts_per_tok"], layers=cfg["num_hidden_layers"],
        bl=cfg["block_length"])


def weight_specs(cfg: dict) -> list[dict]:
    m, t, v = _dims(cfg), cfg["dtype"], cfg["vocab_size"]
    d, h, hk, dh, f = m["d"], m["h"], m["hk"], m["dh"], m["f"]

    def leaf(name, shape, fan_in, layered=True, dtype=t):
        return {"name": name, "shape": list(shape), "fan_in": fan_in,
                "dtype": dtype, "layered": layered}

    return [
        leaf("embed", [v, d], d, layered=False),
        leaf("final_norm", [d], None, layered=False),
        leaf("lm_head", [v, d], d, layered=False),
        leaf("attn_norm", [d], None),
        leaf("wq", [d, h * dh], d),
        leaf("wk", [d, hk * dh], d),
        leaf("wv", [d, hk * dh], d),
        leaf("q_norm", [dh], None),
        leaf("k_norm", [dh], None),
        leaf("wo", [h * dh, d], h * dh),
        leaf("mlp_norm", [d], None),
        # the router as wide as published, the held experts' stacks
        leaf("router", [d, m["e"]], d, dtype="float32"),
        leaf("e_gate", [m["held"], d, f], d),
        leaf("e_up", [m["held"], d, f], d),
        leaf("e_down", [m["held"], f, d], f),
    ]


# --------------------------------------------------------------- the replay


def passes(cfg: dict, prompt, served, trail) -> list[dict]:
    bl, mask = cfg["block_length"], cfg["mask_token_id"]
    p, end = len(prompt), len(prompt) + len(served)
    clean = np.concatenate([prompt, served]).astype(np.int32)
    trail = np.asarray(trail)
    blocks = range(p // bl, -(-end // bl))
    # the clean blocks that the copies attend: all before the last
    tokens = [clean[:min(blocks[-1] * bl, end)]]
    pos = [np.arange(len(tokens[0]))]
    seg = [np.zeros(len(tokens[0]), np.int32)]
    rows, chosen = [], []
    for b in blocks:
        here = np.arange(b * bl, (b + 1) * bl)
        new = (here >= p) & (here < end)
        when = np.where(new, trail[np.clip(here - p, 0, len(served) - 1)], -1)
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
    return [{"tokens": np.concatenate(tokens),
             "rows": np.concatenate(rows),
             "chosen": np.concatenate(chosen),
             "beside": {"pos": pos, "block": pos // bl,
                        "seg": np.concatenate(seg)}}]


# ---------------------------------------------------------------- the layer


def rope_at(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """``common.rope`` at the positions given (a block's copy repeats the
    positions of the clean block it stands for): x [S, H, Dh]."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def sees(beside: dict, rows: jax.Array) -> jax.Array:
    """[len(rows), S] bool: what each of ``rows`` attends. A row of the
    clean sequence (segment 0) sees the clean rows up to the end of its own
    block; a row of a block's copy sees its own copy and the clean blocks
    before it; a padding row (position -1) sees itself alone and nothing
    sees it."""
    pos, block, seg = beside["pos"], beside["block"], beside["seg"]
    own = (seg[None, :] == seg[rows, None]) & (
        block[None, :] <= block[rows, None])
    before = (seg[None, :] == 0) & (block[None, :] < block[rows, None])
    itself = jnp.arange(pos.shape[0])[None, :] == rows[:, None]
    return ((own | before) & (pos[None, :] >= 0)) | itself


def attention_block(cfg: dict, w: dict, x: jax.Array, precision: str,
                    beside: dict) -> jax.Array:
    """x + W_o . attention over the rows of one pass x [S, D], queries in
    blocks of 128 so that a long pass's scores fit."""
    m = _dims(cfg)
    s = x.shape[0]
    h, hk, dh = m["h"], m["hk"], m["dh"]
    g = h // hk
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    n = common.rms_norm(x, w["attn_norm"], eps)
    q = common.mm(n, w["wq"], precision).reshape(s, h, dh)
    k = common.mm(n, w["wk"], precision).reshape(s, hk, dh)
    v = common.mm(n, w["wv"], precision).reshape(s, hk, dh)
    q = rope_at(common.rms_norm(q, w["q_norm"], eps), beside["pos"], theta)
    k = rope_at(common.rms_norm(k, w["k_norm"], eps), beside["pos"], theta)
    block = min(_QUERY_BLOCK, s)
    if s % block:
        raise ValueError(f"a pass of {s} rows is no multiple of {block}")
    scale = dh ** -0.5

    def one_block(start):
        rows = start + jnp.arange(block)
        qb = q[rows].reshape(block, hk, g, dh)
        logits = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=_HI) * scale
        logits = jnp.where(sees(beside, rows)[None, None], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("kgqs,skd->qkgd", probs, v, precision=_HI)
        return out.reshape(block, h * dh)

    out = jax.lax.map(one_block, jnp.arange(0, s, block))
    return x + common.mm(out.reshape(s, h * dh), w["wo"], precision)


def route_gates(cfg: dict, w: dict, n: jax.Array, precision: str) -> jax.Array:
    """[S, E] float32 over all published experts: a chosen expert's softmax
    probability over the sum of the chosen ones', 0 elsewhere."""
    if not cfg["norm_topk_prob"]:
        raise ValueError("this reference renormalises the chosen weights")
    probs = jax.nn.softmax(common.mm(n, w["router"], precision), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return jnp.zeros_like(probs).at[
        jnp.arange(n.shape[0])[:, None], top_i].set(top_p)


def expert_block(cfg: dict, w: dict, n: jax.Array, precision: str,
                 gates: jax.Array = None) -> jax.Array:
    """The held experts' part of the layer's result over n [S, D]."""
    first, held = cfg["held_experts_first"], cfg["num_experts"]
    if gates is None:
        gates = route_gates(cfg, w, n, precision)
    gates = gates[:, first:first + held]

    def one_expert(acc, xs):
        wg, wu, wd, g = xs
        return acc + g[:, None] * common.swiglu(n, wg, wu, wd, precision), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(n),
        (w["e_gate"], w["e_up"], w["e_down"], gates.T))
    return routed


def layer(cfg: dict, w: dict, x: jax.Array, precision: str,
          beside: dict) -> jax.Array:
    """One block over the rows of one pass x [S, D] (float32)."""
    x = attention_block(cfg, w, x, precision, beside)
    n = common.rms_norm(x, w["mlp_norm"], cfg["rms_norm_eps"])
    return x + expert_block(cfg, w, n, precision)


# -------------------------------------------------- operations and bytes


def block_attn_pass_cost(cfg: dict, batch: int, live_tokens: int) -> tuple:
    """(FLOPs, bytes) of one pass's attention (the scope ``block_attn``)
    for ``batch`` streams holding ``live_tokens`` cached tokens in all,
    every layer: each of a stream's B rows' H query heads against every
    live cached token's key and against the block's own B keys, and the
    probabilities against the values; a cached token's key and value rows
    (Hk heads) read once a layer whatever the rows that read them, the
    block's own keys and values and its queries and results besides."""
    m, el = _dims(cfg), 2
    bl = m["bl"]
    seen = bl * live_tokens + batch * bl * bl   # (query row, key) pairs
    flops = 2 * 2 * m["h"] * m["dh"] * seen
    byts = 2 * m["hk"] * m["dh"] * el * (live_tokens + batch * bl) \
        + 2 * m["h"] * m["dh"] * el * batch * bl
    return m["layers"] * flops, m["layers"] * byts


def decode_step_cost(cfg: dict, batch: int, live_tokens: int) -> tuple:
    """(FLOPs, bytes) the algorithm needs for one pass of B rows a live
    stream: every weight outside the routed experts read once; of the held
    experts those a row of the pass chose, taken as min(held, expected
    choices) a layer, and k * held / E of them computed a row; every live
    cached token's key and value read once a layer; the head over every
    row. A writing pass's rows written are the block's, counted with the
    attention's bytes."""
    m, el = _dims(cfg), 2
    d, rows = m["d"], batch * m["bl"]
    af, ab = block_attn_pass_cost(cfg, batch, live_tokens)
    hf, hb = common.head_step_cost(cfg, rows)
    proj = d * m["dh"] * (2 * m["h"] + 2 * m["hk"])   # W_q, W_o, W_k, W_v
    here = m["k"] * m["held"] / m["e"]           # chosen and held, a row
    expert = 3 * d * m["f"]
    flops = af + hf + m["layers"] * rows * (
        2 * proj + 2 * d * m["e"] + 2 * expert * here)
    byts = ab + hb + m["layers"] * (
        proj * el + d * m["e"] * 4 + expert * el * min(m["held"], rows * here))
    return flops, byts
