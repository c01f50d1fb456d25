"""Plain reference of a decoder of two mixers (MiniCPM-SALA): block-sparse
attention that selects whole blocks of the cache by a score over compressed
keys (InfLLM-v2, MiniCPM4 report arXiv:2506.07900 section 2.2) beside
Lightning linear-attention layers whose state is a matrix a head
(arXiv:2401.04658, MiniMax-01 arXiv:2501.08313), every layer followed by a
SwiGLU, under the muP scalars. Float32, ``highest``, no cache, no chunked
algebra: the linear recurrence is a ``lax.scan`` over time, one token a
step, and the sparse layer selects for every query row on its own.

With ``r = scale_depth / sqrt(depth)``, ``depth`` the PUBLISHED number of
layers (32, not the cut's), n = rms_norm(x):

  trunk        h0 = scale_emb * E[token];  x = x + r * mixer(n);
               x = x + r * W_down(silu(W_gate n') * W_up n'), n' = rms_norm(x);
               logits = W_head rms_norm(x) / (hidden_size / dim_model_base)
  linear       q, k, v = W n as H heads of d;  q, k = rms_norm_head(q),
               rms_norm_head(k) (a gain of d each);  rotary on the whole
               head of q and k (halves rotated against each other, base
               rope_theta);  S_t = lambda_h S_{t-1} + k_t v_t^T  ([d, d],
               float32),  o_t = q_t^T S_t / sqrt(d);  o = rms_norm_head(o)
               (a gain of d);  o = o * sigmoid(W_g n);  W_o o.
               lambda_h = exp(-2^(-8 (h + 1) / H) (1 - l / (L - 1) +
               1e-5)), h = 0 .. H - 1, ``l`` the layer's index in the
               PUBLISHED model of L = ``depth`` layers (``layer_indices``
               places a cut's layers in it): a later layer forgets more
               slowly. The slopes and the factor by layer are those of
               MiniMax-01's released modeling code (``_build_slope_tensor``
               and ``slope_rate * (1 - layer_idx / (num_hidden_layers - 1)
               + 1e-5)``), of which TransNormerLLM's (arXiv:2307.14995)
               ``lambda = exp(-8 h / H (1 - l / L))`` is the older form;
               arXiv:2401.04658 and arXiv:2501.08313 write the recurrence
               with a decay and print no slope.
  sparse       q Hq heads, k, v Hk heads of d (G = Hq / Hk query heads a
               key/value head), q, k = rms_norm_head(.), NO rotary, scale
               1 / sqrt(d), causal. A query at position t that sees more
               than ``dense_len`` tokens (t + 1 > dense_len):
               (a) compressed keys c_j = mean(k[stride j : stride j +
                   kernel]) a key/value head, every j whose ``kernel``
                   tokens lie at or before t;
               (b) r_h = softmax_j(q_h . c_j / sqrt(d)) for each of the
                   group's G heads, summed over them;
               (c) the score of block b (tokens block b .. block b + block
                   - 1) is the maximum of those sums over the windows that
                   overlap it, j = 4 b - 1 .. 4 b + 3 (for kernel 32, stride
                   16, block 64: every j with stride j < block (b + 1) and
                   stride j + kernel > block b);
               (d) the first ``init_blocks`` blocks and the ``window_size /
                   block`` blocks that end with the block holding t are
                   always kept;
               (e) the ``topk`` highest blocks are kept, the forced ones
                   among them, ties to the lower index;
               (f) softmax attention of the group's G heads over the tokens
                   of the kept blocks at or before t.
               With at most ``dense_len`` tokens visible: plain causal
               attention. Then o = o * sigmoid(W_g n);  W_o o.

What the harness cannot express of the published model is the
configuration's ``departures``: ``vbench/check.py`` embeds the tokens itself,
so layer 0 is of a kind of its own (``sparse_in``) that multiplies its input
by ``scale_emb`` and then is a sparse layer (exact); and it closes with
``common.head``, whose logits lack the division by ``hidden_size /
dim_model_base`` (``logits`` below has it, for the tests).

``assumed`` (the configuration's file repeats each): the sizes of (a)-(e)
are MiniCPM4's published ``sparse_config``, which the SALA row of the
catalog does not repeat; the sum over a group's heads before the maximum
over windows; forced blocks counted inside the ``topk``; the local window
counted in whole blocks, ``window_size / block`` of them ending with the
query's own; compressed keys made of normed keys; the decay's slopes and
their factor by layer as MiniMax-01's code has them, ``l`` counted over
all the published layers of both kinds (PR 47's first form had no factor,
as its issue wrote it; the review had the publication followed); the
output norm a head and before the gate; a gate of full width on both
kinds; ``mup_denominator`` takes no part; rotary
pairing by halves; bfloat16; recurrent rows float32.

Seeded leaves are uniform around zero, or ones. Under the QK-norm a score's
spread does not depend on ``wq`` / ``wk`` at all (a normed head has unit
mean square whatever projected it), so the seeded selection is spread by
the gain of ``q_norm`` instead: drawn ones, a query scores a mean of 32
independent keys with a deviation of 0.18, (b) is flat, a sparse layer
averages 1500 tokens into an output a fifteenth of a linear layer's, and a
wrong selection would hide. ``map_leaves`` multiplies ``q_norm`` by
``Q_GAIN`` = 2, in the sparse layers alone (``assumed``): measured at the
published selection sizes on a narrow model (PR 47, PERF.md section 6),
gains of 1.5 to 4 tell a selection of the most recent blocks from
bfloat16's own error equally well (7.5 times), a gain of 1 less (4.9), and
bfloat16's own error grows with the gain (a softmax over token scores that
spread by 4 is a handful of tokens, and a block the two sides rank
differently then moves a whole row). ``vbench/sut/sparselinear.py`` hands
the program the same.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from vbench.reference import common

_HI = jax.lax.Precision.HIGHEST
Q_GAIN = 2.0   # the sparse layers' q_norm gain on seeded weights
_ROWS = 128    # query rows a block of the sparse layer's score matrices

KINDS = {"minicpm4": "sparse", "lightning-attn": "linear"}


def layer_kinds(cfg: dict) -> list[str]:
    """A kind a layer: ``sparse_in`` (layer 0, with the embedding's scale),
    ``sparse``, and ``linear.<l>`` for a linear layer at index ``l`` of the
    published model (``layer_indices``): its decay depends on ``l``, and a
    kind is all that ``vbench/check.py`` tells ``layer``."""
    kinds = [KINDS[m] for m in cfg["mixer_types"]]
    at = cfg["layer_indices"]
    if kinds[0] != "sparse":
        raise ValueError("the first layer carries the embedding's scale "
                         "and has to be a sparse layer")
    if len(at) != len(kinds) or list(at) != sorted(set(at)) or not (
            0 <= at[0] and at[-1] < cfg["residual_depth"]):
        raise ValueError(f"layer_indices {at} does not place {len(kinds)} "
                         f"layers in a model of {cfg['residual_depth']}")
    return ["sparse_in"] + [
        kind if kind == "sparse" else f"linear.{l}"
        for kind, l in zip(kinds[1:], at[1:])]


def linear_kinds(cfg: dict) -> list[str]:
    """The linear layers' kinds, in the model's order."""
    return [k for k in layer_kinds(cfg) if k.startswith("linear.")]


def sparse_config(cfg: dict) -> dict:
    """The selection's sizes: the file's ``sparse_config`` (``assumed``:
    MiniCPM4's published one)."""
    s = cfg["sparse_config"]
    if (s["block_size"] % s["kernel_stride"] or s["kernel_size"]
            != 2 * s["kernel_stride"] or s["window_size"] % s["block_size"]):
        raise ValueError("compressed windows of two strides, whole strides "
                         "a block, whole blocks a local window")
    return s


def _dims(cfg: dict) -> dict:
    if cfg["lightning_nkv"] != cfg["lightning_nh"]:
        raise ValueError("a key a head: lightning_nkv == lightning_nh")
    return dict(d=cfg["hidden_size"], f=cfg["intermediate_size"],
                hq=cfg["num_attention_heads"], hk=cfg["num_key_value_heads"],
                dh=cfg["head_dim"], lh=cfg["lightning_nh"],
                ld=cfg["lightning_head_dim"])


def residual_scale(cfg: dict) -> float:
    """scale_depth / sqrt(the published depth)."""
    return cfg["scale_depth"] / math.sqrt(cfg["residual_depth"])


def weight_specs(cfg: dict) -> list[dict]:
    m, t, v = _dims(cfg), cfg["dtype"], cfg["vocab_size"]
    d, f = m["d"], m["f"]

    def leaf(name, shape, fan_in, kind=None, dtype=t, layered=True):
        spec = {"name": name, "shape": list(shape), "fan_in": fan_in,
                "dtype": dtype, "layered": layered}
        if kind is not None:
            spec["kind"] = kind
        return spec

    qd, kvd, ld = m["hq"] * m["dh"], m["hk"] * m["dh"], m["lh"] * m["ld"]

    def sparse(kind):
        return [
            leaf("attn_norm", [d], None, kind),
            leaf("wq", [d, qd], d, kind),
            leaf("wk", [d, kvd], d, kind),
            leaf("wv", [d, kvd], d, kind),
            leaf("q_norm", [m["dh"]], None, kind),
            leaf("k_norm", [m["dh"]], None, kind),
            leaf("wg", [d, qd], d, kind),
            leaf("wo", [qd, d], qd, kind),
        ]

    def linear(kind):
        return [
            leaf("attn_norm", [d], None, kind),
            leaf("wq", [d, ld], d, kind),
            leaf("wk", [d, ld], d, kind),
            leaf("wv", [d, ld], d, kind),
            leaf("q_norm", [m["ld"]], None, kind),
            leaf("k_norm", [m["ld"]], None, kind),
            leaf("o_norm", [m["ld"]], None, kind),
            leaf("wg", [d, ld], d, kind),
            leaf("wo", [ld, d], ld, kind),
        ]

    # the embedding as granite's file draws it: at variance 1 / d the
    # stream would be scale_emb E[token] against layers that add about one
    # an element, and the head would read the input token back over every
    # other; at 1.5 / scale_emb of that range the layers move the stream
    embed_fan = d * (cfg["scale_emb"] / 1.5) ** 2
    return [
        leaf("embed", [v, d], embed_fan, layered=False),
        leaf("head", [v, d], d, layered=False),
        leaf("final_norm", [d], None, layered=False),
        # every layer: the SwiGLU after the mixer
        leaf("mlp_norm", [d], None),
        leaf("w_gate", [d, f], d),
        leaf("w_up", [d, f], d),
        leaf("w_down", [f, d], f),
        *sparse("sparse_in"),
        *sparse("sparse"),
        *(spec for kind in linear_kinds(cfg) for spec in linear(kind)),
    ]


def map_leaves(leaves: dict) -> dict:
    """A sparse layer's drawn ``q_norm`` (ones) times ``Q_GAIN``. Works on
    one layer's leaves and on a stack of them."""
    out = dict(leaves)
    out["q_norm"] = (leaves["q_norm"].astype(jnp.float32) * Q_GAIN).astype(
        leaves["q_norm"].dtype)
    return out


def decay(n_heads: int, layer: int, depth: int) -> jax.Array:
    """lambda_h = exp(-2^(-8 (h + 1) / H) (1 - l / (L - 1) + 1e-5)) of the
    layer at index ``layer`` of ``depth``, [H] float32."""
    h = np.arange(1, n_heads + 1)
    rate = 1.0 - layer / max(depth - 1, 1) + 1e-5
    return jnp.asarray(np.exp(-rate * np.exp2(-8.0 * h / n_heads)),
                       jnp.float32)


# ------------------------------------------------------------- the mixers


def linear_scan(q, k, v, lam, s0=None):
    """The recurrence over time. q, k, v [S, H, d], lam [H], float32 ->
    (o [S, H, d] without the scale, the last state [H, d, d]): S_t = lam
    S_{t-1} + k_t v_t^T, o_t = q_t^T S_t."""
    if s0 is None:
        s0 = jnp.zeros((q.shape[1], k.shape[2], v.shape[2]), jnp.float32)

    def step(s, inp):
        q_t, k_t, v_t = inp
        s = lam[:, None, None] * s + k_t[:, :, None] * v_t[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", q_t, s, precision=_HI)

    s, o = jax.lax.scan(step, s0, (q, k, v), unroll=4)
    return o, s


def linear(cfg: dict, w: dict, n: jax.Array, precision: str,
           at: int) -> jax.Array:
    """The linear mixer of the layer at index ``at`` of the published
    model."""
    m, s = _dims(cfg), n.shape[0]
    h, dh, eps = m["lh"], m["ld"], cfg["rms_norm_eps"]
    q, k, v = (common.mm(n, w[name], precision).reshape(s, h, dh)
               for name in ("wq", "wk", "wv"))
    q = common.rope(common.rms_norm(q, w["q_norm"], eps), cfg["rope_theta"])
    k = common.rope(common.rms_norm(k, w["k_norm"], eps), cfg["rope_theta"])
    o, _ = linear_scan(q, k, v, decay(h, at, cfg["residual_depth"]))
    o = common.rms_norm(o / math.sqrt(dh), w["o_norm"], eps)
    o = o.reshape(s, h * dh) * jax.nn.sigmoid(common.mm(n, w["wg"], precision))
    return common.mm(o, w["wo"], precision)


def compressed_keys(k: jax.Array, sp: dict) -> jax.Array:
    """(a): k [S, Hk, d] -> c [J, Hk, d], c_j the mean of the ``kernel``
    keys from ``stride j``; J counts the windows that lie whole inside S."""
    stride, kernel = sp["kernel_stride"], sp["kernel_size"]
    j = max((k.shape[0] - kernel) // stride + 1, 0)
    halves = k[:(j + 1) * stride].reshape(j + 1, stride, *k.shape[1:]).sum(1)
    return (halves[:-1] + halves[1:]) / kernel


def kept_blocks(q, c, pos, sp: dict, n_blocks: int) -> jax.Array:
    """(b)-(e) for the query rows q [R, Hq, d] at positions pos [R] over
    the compressed keys c [J, Hk, d]: keep [R, Hk, n_blocks] bool."""
    r, hq, dh = q.shape
    j, hk = c.shape[:2]
    stride, kernel, block = (sp["kernel_stride"], sp["kernel_size"],
                             sp["block_size"])
    per = block // stride
    s = jnp.einsum("rhgd,jhd->rhgj", q.reshape(r, hk, hq // hk, dh), c,
                   precision=_HI) / math.sqrt(dh)
    whole = (jnp.arange(j) * stride + kernel - 1)[None, :] <= pos[:, None]
    seen = whole[:, None, None, :]
    # -1e30, not -inf: a row that sees no whole window yet (it is dense, and
    # what it selects here is not read) must stay finite
    p = jnp.where(seen, jax.nn.softmax(jnp.where(seen, s, -1e30), -1), 0.0)
    summed = jnp.where(whole[:, None, :], p.sum(2), -jnp.inf)  # [R, Hk, J]
    # (c): block b takes windows per b - 1 .. per b + per - 1
    pad = jnp.full((r, hk, 1), -jnp.inf)
    wide = jnp.concatenate(
        [pad, summed, jnp.broadcast_to(
            pad, (r, hk, max(n_blocks * per - j, 0)))], axis=-1)
    score = jnp.max(jnp.stack(
        [wide[..., i:i + n_blocks * per:per] for i in range(per + 1)]), 0)
    mine = pos // block  # the block holding the query
    b = jnp.arange(n_blocks)[None, :]
    forced = (b < sp["init_blocks"]) | (
        (b <= mine[:, None]) & (b > mine[:, None]
                                - sp["window_size"] // block))
    score = jnp.where(forced[:, None, :], jnp.inf, score)
    score = jnp.where((b <= mine[:, None])[:, None, :], score, -jnp.inf)
    k_top = min(sp["topk"], n_blocks)
    _, idx = jax.lax.top_k(score, k_top)  # ties: the lower index first
    keep = jnp.zeros((r, hk, n_blocks), bool).at[
        jnp.arange(r)[:, None, None], jnp.arange(hk)[None, :, None],
        idx].set(True)
    return keep & (b <= mine[:, None])[:, None, :]


def sparse(cfg: dict, w: dict, n: jax.Array, precision: str,
           select=kept_blocks) -> jax.Array:
    m, s = _dims(cfg), n.shape[0]
    sp, eps = sparse_config(cfg), cfg["rms_norm_eps"]
    hq, hk, dh = m["hq"], m["hk"], m["dh"]
    w = map_leaves(w)
    q = common.mm(n, w["wq"], precision).reshape(s, hq, dh)
    k = common.mm(n, w["wk"], precision).reshape(s, hk, dh)
    v = common.mm(n, w["wv"], precision).reshape(s, hk, dh)
    q = common.rms_norm(q, w["q_norm"], eps)
    k = common.rms_norm(k, w["k_norm"], eps)
    block = sp["block_size"]
    nb = -(-s // block)
    c = compressed_keys(k, sp)
    kpos = jnp.arange(s)
    selects = s > sp["dense_len"] and c.shape[0] > 0

    def rows(inp):
        """A block of query rows (the score matrices of all S rows at once
        would not fit): qb [R, Hq, d] at positions pos [R]."""
        qb, pos = inp
        allow = jnp.broadcast_to((kpos[None, :] <= pos[:, None])[:, None, :],
                                 (qb.shape[0], hk, s))
        if selects:
            keep = jnp.repeat(select(qb, c, pos, sp, nb), block,
                              axis=-1)[..., :s]
            keep = keep | (pos + 1 <= sp["dense_len"])[:, None, None]
            allow = allow & keep                            # [R, Hk, S]
        sc = jnp.einsum("rhgd,shd->hgrs", qb.reshape(-1, hk, hq // hk, dh), k,
                        precision=_HI) / math.sqrt(dh)
        sc = jnp.where(jnp.swapaxes(allow, 0, 1)[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hgrs,shd->rhgd", p, v, precision=_HI).reshape(
            -1, hq * dh)

    pad = -s % _ROWS  # a padding row sees what the last row sees
    qp = jnp.concatenate([q, jnp.zeros((pad, hq, dh), q.dtype)])
    pp = jnp.minimum(jnp.arange(s + pad), s - 1)
    out = jax.lax.map(rows, (qp.reshape(-1, _ROWS, hq, dh),
                             pp.reshape(-1, _ROWS)))
    out = out.reshape(s + pad, hq * dh)[:s]
    o = out * jax.nn.sigmoid(common.mm(n, w["wg"], precision))
    return common.mm(o, w["wo"], precision)


def layer(cfg: dict, w: dict, x: jax.Array, precision: str,
          kind: str) -> jax.Array:
    """One layer of ``kind`` over a whole sequence x [S, D] (float32)."""
    r, eps = residual_scale(cfg), cfg["rms_norm_eps"]
    if kind == "sparse_in":
        x = x * cfg["scale_emb"]
    n = common.rms_norm(x, w["attn_norm"], eps)
    if kind.startswith("linear."):
        mixed = linear(cfg, w, n, precision, int(kind.split(".")[1]))
    else:
        mixed = sparse(cfg, w, n, precision)
    x = x + r * mixed
    n = common.rms_norm(x, w["mlp_norm"], eps)
    return x + r * common.swiglu(n, w["w_gate"], w["w_up"], w["w_down"],
                                 precision)


def logits(cfg: dict, g: dict, x: jax.Array, precision: str) -> jax.Array:
    """The model's own logits over the rows given: ``common.head`` over
    ``hidden_size / dim_model_base`` (the harness compares without it)."""
    return common.head(cfg, g, x, precision) / (
        cfg["hidden_size"] / cfg["dim_model_base"])


# ---------------------------------------------------------------- the costs

_EL = 2  # bfloat16 weights, activations, cache and compressed keys


def _counts(cfg: dict) -> tuple:
    n_linear = len(linear_kinds(cfg))
    return cfg["num_hidden_layers"] - n_linear, n_linear


def _weights(cfg: dict) -> tuple:
    """(a sparse layer's mixer weights, a linear layer's, a SwiGLU's), in
    parameters."""
    m = _dims(cfg)
    d, qd, kvd, ld = (m["d"], m["hq"] * m["dh"], m["hk"] * m["dh"],
                      m["lh"] * m["ld"])
    return d * (3 * qd + 2 * kvd), 5 * d * ld, 3 * d * m["f"]


def ssm_step_cost(cfg: dict, batch: float) -> tuple:
    """(FLOPs, bytes) of the state update, the readout, the output norm
    and the gate of one decode step over ``batch`` live streams, all linear
    layers: each stream's state [H, d, d] float32 read and written once
    (two multiplies and an add an element for the update, a multiply and an
    add for the readout), its q, k, v, gate rows in and its output out."""
    m = _dims(cfg)
    _, layers = _counts(cfg)
    state = m["lh"] * m["ld"] * m["ld"]
    width = m["lh"] * m["ld"]
    flops = batch * (5 * state + 8 * width)
    byts = batch * (2 * state * 4 + 5 * width * _EL) + m["ld"] * _EL
    return layers * flops, layers * byts


def ssm_chunk_cost(cfg: dict, tokens: float) -> tuple:
    """(FLOPs, bytes) of the same parts of one prefill chunk of ``tokens``
    tokens of one prompt, all linear layers, in the chunked form at the
    chunk Q (``lightning_chunk``): the q k^T product a head (2 T Q d), the
    masked mix against v (2 T Q d), the state a chunk adds and the state's
    part of the output (2 T d d each), a head; the decay's exponentials
    (T Q); bytes: q, k, v, gate in and the output out once, the carried
    state in and out once."""
    m = _dims(cfg)
    _, layers = _counts(cfg)
    q, h, d = cfg["lightning_chunk"], m["lh"], m["ld"]
    flops = tokens * h * (4 * q * d + 4 * d * d + q + 8 * d)
    byts = tokens * 5 * h * d * _EL + 2 * h * d * d * 4
    return layers * flops, layers * byts


def _selected(cfg: dict, batch: float, visible_tokens: float) -> float:
    """Cached tokens the sparse layers' attention reads of
    ``visible_tokens`` over ``batch`` streams, each taken at the mean
    length: all of them up to ``dense_len``, ``topk`` blocks past it."""
    sp = sparse_config(cfg)
    mean = visible_tokens / max(batch, 1e-9)
    if mean <= sp["dense_len"]:
        return visible_tokens
    return batch * min(mean, sp["topk"] * sp["block_size"])


def blocksparse_attn_step_cost(cfg: dict, batch: float,
                               visible_tokens: float) -> tuple:
    """(FLOPs, bytes) of the selection and the attention of one decode
    step over ``batch`` streams that see ``visible_tokens`` cached tokens
    in all, all sparse layers: the visible compressed keys (one a
    ``kernel_stride`` tokens a key/value head) read once and scored by all
    Hq heads, the selected tokens' keys and values read once, both products
    for all Hq heads. The same work whatever implements it."""
    m = _dims(cfg)
    layers, _ = _counts(cfg)
    sp = sparse_config(cfg)
    kvd, qd = m["hk"] * m["dh"], m["hq"] * m["dh"]
    comp = visible_tokens / sp["kernel_stride"]
    sel = _selected(cfg, batch, visible_tokens)
    flops = 2 * comp * qd + 2 * 2 * sel * qd
    byts = (comp * kvd + 2 * sel * kvd) * _EL
    return layers * flops, layers * byts


def decode_step_cost(cfg: dict, batch: float, live_tokens: float) -> tuple:
    """(FLOPs, bytes) the algorithm needs for one decode step: every weight
    read once, each live stream's recurrent rows read and written once, the
    visible compressed keys and the selected keys and values of the sparse
    layers read once, the new token's written."""
    m = _dims(cfg)
    n_sparse, n_linear = _counts(cfg)
    w_sparse, w_linear, w_mlp = _weights(cfg)
    sf, sb = ssm_step_cost(cfg, batch)
    af, ab = blocksparse_attn_step_cost(cfg, batch, live_tokens)
    hf, hb = common.head_step_cost(cfg, batch)
    params = n_sparse * w_sparse + n_linear * w_linear + (
        n_sparse + n_linear) * w_mlp
    kvd = m["hk"] * m["dh"]
    flops = batch * 2 * params + sf + af + hf
    byts = (params * _EL + sb + ab + hb
            + n_sparse * batch * 2 * kvd * _EL)
    return flops, byts
