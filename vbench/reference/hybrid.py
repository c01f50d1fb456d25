"""Plain reference of the hybrid decoder (granite-4.0-h / Bamba): Mamba-2
layers among grouped-query attention layers, every layer followed by a
SwiGLU, under the Granite family's multipliers. Float32, ``highest``, no
cache and no chunked algebra: the state-space recurrence is a ``lax.scan``
over time, one token a step.

With ``r = residual_multiplier``, n = rms_norm(x):
  every layer  x = x + r * mixer(n);  x = x + r * W_down(silu(W_gate n') *
               W_up n'), n' = rms_norm(x)
  attention    q = W_q n (Hq heads), k = W_k n, v = W_v n (Hk heads, each
               read by Hq / Hk query heads); no rotary positions;
               softmax(q . k * attention_multiplier) under the causal mask;
               W_o
  mamba        [z | xBC | dt] = W_in n;  xBC = silu(conv_K(xBC) + bias), a
               causal depthwise convolution over the last K tokens;
               [x | B | C] = xBC (x as H heads of P, B and C of N, one group);
               dt = softplus(dt + dt_bias), A = -exp(A_log), a head;
               h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t  ([H, P, N]);
               y_t = h_t C_t + D x_t;  y = rms_norm(y * silu(z)) over all
               H * P channels;  W_out

What the harness cannot express of the published model is the
configuration's ``departures``: ``vbench/check.py`` embeds the tokens itself,
so the first layer is of a kind of its own, ``mamba_in``, which multiplies
its input by ``embedding_multiplier`` and then is a Mamba layer (exact); and
it closes with ``common.head``, whose logits lack the division by
``logits_scaling`` (the compared gaps are in units that many times the
model's own; ``logits`` below has the division, for the tests).

Seeded leaves are uniform around zero, or ones. Drawn so, the steps would be
``softplus(0) = 0.7`` and the state would forget in a handful of tokens, so a
lost carry would hide under any tolerance. ``map_leaves`` puts ``dt_bias``
and ``A_log`` where the published initialiser does (the configuration's
``assumed``); ``vbench/sut/hybrid.py`` hands the program the same.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from vbench.reference import common

_HI = jax.lax.Precision.HIGHEST


def layer_kinds(cfg: dict) -> list[str]:
    kinds = list(cfg["layer_types"])
    if kinds[0] != "mamba":
        raise ValueError("the first layer carries the embedding's "
                         "multiplier and has to be a mamba layer")
    return ["mamba_in"] + kinds[1:]


def _dims(cfg: dict) -> dict:
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    if h * p != cfg["mamba_expand"] * cfg["hidden_size"]:
        raise ValueError("mamba_n_heads * mamba_d_head is not the inner width")
    if cfg["mamba_n_groups"] != 1:
        raise ValueError("one group of B and C")
    n = cfg["mamba_d_state"]
    return dict(d=cfg["hidden_size"], f=cfg["shared_intermediate_size"],
                h=h, p=p, n=n, di=h * p, dc=h * p + 2 * n,
                k=cfg["mamba_d_conv"], hq=cfg["num_attention_heads"],
                hk=cfg["num_key_value_heads"], dh=cfg["head_dim"])


def weight_specs(cfg: dict) -> list[dict]:
    m, t, v = _dims(cfg), cfg["dtype"], cfg["vocab_size"]
    d, f, di, dc = m["d"], m["f"], m["di"], m["dc"]

    def leaf(name, shape, fan_in, kind=None, dtype=t, layered=True):
        spec = {"name": name, "shape": list(shape), "fan_in": fan_in,
                "dtype": dtype, "layered": layered}
        if kind is not None:
            spec["kind"] = kind
        return spec

    def mamba(kind):
        return [
            leaf("norm", [d], None, kind),
            leaf("in_proj", [d, di + dc + m["h"]], d, kind),
            leaf("conv_w", [m["k"], dc], m["k"], kind),
            leaf("conv_b", [dc], 300, kind),             # +-0.1
            leaf("dt_bias", [m["h"]], 3, kind, "float32"),  # +-1: map_leaves
            leaf("a_log", [m["h"]], 3, kind, "float32"),
            leaf("d_skip", [m["h"]], None, kind, "float32"),
            leaf("gate_norm", [di], None, kind),
            leaf("out_proj", [di, d], di, kind),
        ]

    qd, kvd = m["hq"] * m["dh"], m["hk"] * m["dh"]
    # Two ranges that the multipliers force on seeded leaves (the
    # configuration's ``assumed``). The tied embedding, drawn at variance
    # 1 / d like the rest, would put 12 E[token] into a stream whose layers
    # add about 1.5 an element, and the head would read the input token
    # back 7 deviations over every other: a constant stream that no
    # precision moves. At 1.5 / embedding_multiplier of that range the
    # token's own logit leads by about one. And ``attention_multiplier`` is
    # 1 / head_dim, for trained queries and keys that agree: on seeded ones
    # the scores would spread by 0.125 and every softmax would be flat, so
    # a lost page could not show. wq is drawn wider, to scores that spread
    # by 2.
    embed_fan = d * (cfg["embedding_multiplier"] / 1.5) ** 2
    wq_fan = d * m["dh"] * cfg["attention_multiplier"] ** 2 / 4
    return [
        leaf("embed", [v, d], embed_fan, layered=False),
        leaf("final_norm", [d], None, layered=False),
        # every layer: the SwiGLU after the mixer
        leaf("mlp_norm", [d], None),
        leaf("w_gate", [d, f], d),
        leaf("w_up", [d, f], d),
        leaf("w_down", [f, d], f),
        *mamba("mamba_in"),
        *mamba("mamba"),
        leaf("attn_norm", [d], None, "attention"),
        leaf("wq", [d, qd], wq_fan, "attention"),
        leaf("wk", [d, kvd], d, "attention"),
        leaf("wv", [d, kvd], d, "attention"),
        leaf("wo", [qd, d], qd, "attention"),
    ]


def map_leaves(leaves: dict) -> dict:
    """The drawn ``dt_bias`` and ``a_log`` (uniform on +-1) put where the
    published initialiser puts them: steps ``softplus(dt_bias)`` of 0.004 to
    0.03 (its 0.001 to 0.1) and ``A = -exp(a_log)`` from -1 to -16. Works
    on one layer's leaves and on a stack of them."""
    out = dict(leaves)
    out["dt_bias"] = leaves["dt_bias"] - 4.6
    out["a_log"] = jnp.log(1.0 + 7.5 * (leaves["a_log"] + 1.0))
    return out


# ------------------------------------------------------------- the mixers


def attention(cfg: dict, w: dict, n: jax.Array, precision: str) -> jax.Array:
    m, s = _dims(cfg), n.shape[0]
    group = m["hq"] // m["hk"]
    q = common.mm(n, w["wq"], precision).reshape(s, m["hq"], m["dh"])
    k = common.mm(n, w["wk"], precision).reshape(s, m["hk"], m["dh"])
    v = common.mm(n, w["wv"], precision).reshape(s, m["hk"], m["dh"])
    # common.causal_attention scales by dh ** -0.5: hand it q so that the
    # product is scaled by attention_multiplier
    q = q * (cfg["attention_multiplier"] * m["dh"] ** 0.5)
    out = common.causal_attention(
        q, jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1))
    return common.mm(out.reshape(s, m["hq"] * m["dh"]), w["wo"], precision)


def ssm_scan(x, dt, a, b, c, h0=None):
    """The recurrence over time. x [S, H, P], dt [S, H], a [H], b and c
    [S, N], all float32 -> (y [S, H, P], the last state [H, P, N])."""
    if h0 is None:
        h0 = jnp.zeros(x.shape[1:] + (b.shape[-1],), jnp.float32)

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = (jnp.exp(dt_t * a)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return h, jnp.einsum("hpn,n->hp", h, c_t, precision=_HI)

    h, y = jax.lax.scan(step, h0, (x, dt, b, c))
    return y, h


def conv_silu(cfg: dict, w: dict, xbc: jax.Array) -> jax.Array:
    """silu(causal depthwise convolution + bias): tap j of the K multiplies
    the input K - 1 - j tokens back, zeros before the sequence."""
    k, s = cfg["mamba_d_conv"], xbc.shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((k - 1, xbc.shape[1]), jnp.float32), xbc])
    taps = w["conv_w"].astype(jnp.float32)
    out = w["conv_b"].astype(jnp.float32) + sum(
        padded[j:j + s] * taps[j] for j in range(k))
    return jax.nn.silu(out)


def mamba(cfg: dict, w: dict, n: jax.Array, precision: str) -> jax.Array:
    m, s = _dims(cfg), n.shape[0]
    w = map_leaves(w)
    di, dc, ns = m["di"], m["dc"], m["n"]
    proj = common.mm(n, w["in_proj"], precision)
    z, xbc, dt = proj[:, :di], proj[:, di:di + dc], proj[:, di + dc:]
    xbc = conv_silu(cfg, w, xbc)
    x = xbc[:, :di].reshape(s, m["h"], m["p"])
    dt = jax.nn.softplus(dt + w["dt_bias"])
    y, _ = ssm_scan(x, dt, -jnp.exp(w["a_log"]), xbc[:, di:di + ns],
                    xbc[:, di + ns:])
    y = y + w["d_skip"][:, None] * x
    y = common.rms_norm(y.reshape(s, di) * jax.nn.silu(z), w["gate_norm"],
                        cfg["rms_norm_eps"])
    return common.mm(y, w["out_proj"], precision)


def layer(cfg: dict, w: dict, x: jax.Array, precision: str,
          kind: str) -> jax.Array:
    """One layer of ``kind`` over a whole sequence x [S, D] (float32)."""
    r, eps = cfg["residual_multiplier"], cfg["rms_norm_eps"]
    if kind == "mamba_in":
        x = x * cfg["embedding_multiplier"]
    if kind == "attention":
        mixed = attention(cfg, w, common.rms_norm(x, w["attn_norm"], eps),
                          precision)
    else:
        mixed = mamba(cfg, w, common.rms_norm(x, w["norm"], eps), precision)
    x = x + r * mixed
    n = common.rms_norm(x, w["mlp_norm"], eps)
    return x + r * common.swiglu(n, w["w_gate"], w["w_up"], w["w_down"],
                                 precision)


def logits(cfg: dict, g: dict, x: jax.Array, precision: str) -> jax.Array:
    """The model's own logits over the rows given: ``common.head`` over
    ``logits_scaling`` (the harness compares without the division)."""
    return common.head(cfg, g, x, precision) / cfg["logits_scaling"]


# ---------------------------------------------------------------- the costs

_EL = 2  # bfloat16 weights, activations, cache and convolution window


def _mlp_cost(m: dict, rows: float) -> tuple:
    return rows * 2 * 3 * m["d"] * m["f"], 3 * m["d"] * m["f"] * _EL


def ssm_step_cost(cfg: dict, batch: float) -> tuple:
    """(FLOPs, bytes) of the convolution, the state update and the gate of
    one decode step over ``batch`` live streams, all Mamba layers: each
    stream's state [H, P, N] float32 read and written once, its window
    [K - 1, Dc] read and written once, the taps and the per-head leaves
    read once; the update is two multiplies and an add an element, the
    readout a multiply and an add."""
    m = _dims(cfg)
    layers = layer_kinds(cfg).count("mamba") + 1
    state = m["h"] * m["p"] * m["n"]
    window = (m["k"] - 1) * m["dc"]
    flops = batch * (5 * state + 2 * m["k"] * m["dc"] + 8 * m["di"])
    byts = (batch * (2 * state * 4 + 2 * window * _EL
                     + (2 * m["di"] + m["dc"]) * _EL)
            + (m["k"] + 1) * m["dc"] * _EL + 3 * m["h"] * 4 + m["di"] * _EL)
    return layers * flops, layers * byts


def ssm_chunk_cost(cfg: dict, tokens: float) -> tuple:
    """(FLOPs, bytes) of the same three parts of one prefill chunk of
    ``tokens`` tokens of one prompt, all Mamba layers, in the chunked form
    at the published chunk Q: the C B^T product (2 T Q N), the masked mix
    against x (2 T Q H P), the state a chunk adds and the state's part of
    the output (2 T H P N each), and the decay's exponentials (T Q H);
    bytes: the projected channels in and the gated output out once, the
    carried state in and out once."""
    m = _dims(cfg)
    layers = layer_kinds(cfg).count("mamba") + 1
    q = cfg["mamba_chunk_size"]
    hp = m["h"] * m["p"]
    flops = tokens * (2 * q * m["n"] + 2 * q * hp + 4 * hp * m["n"]
                      + q * m["h"] + 2 * m["k"] * m["dc"] + 8 * m["di"])
    byts = (tokens * (m["dc"] + 2 * m["di"] + m["h"]) * _EL
            + 2 * hp * m["n"] * 4 + 2 * (m["k"] - 1) * m["dc"] * _EL)
    return layers * flops, layers * byts


def decode_step_cost(cfg: dict, batch: float, live_tokens: float) -> tuple:
    """(FLOPs, bytes) the algorithm needs for one decode step: every weight
    read once, each live stream's recurrent rows read and written once, the
    live keys and values of the attention layers read once."""
    m = _dims(cfg)
    kinds = layer_kinds(cfg)
    n_attn = kinds.count("attention")
    n_mamba = len(kinds) - n_attn
    d, di, dc = m["d"], m["di"], m["dc"]
    qd, kvd = m["hq"] * m["dh"], m["hk"] * m["dh"]
    mf, mb = _mlp_cost(m, batch)
    sf, sb = ssm_step_cost(cfg, batch)
    w_mamba = d * (di + dc + m["h"]) + di * d
    w_attn = d * (qd + 2 * kvd) + qd * d
    hf, hb = common.head_step_cost(cfg, batch)
    flops = (len(kinds) * mf + sf + n_mamba * batch * 2 * w_mamba
             + n_attn * (batch * 2 * w_attn + 2 * 2 * live_tokens * qd) + hf)
    byts = (len(kinds) * mb + sb + n_mamba * w_mamba * _EL
            + n_attn * (w_attn * _EL + 2 * (live_tokens + batch) * kvd * _EL)
            + hb)
    return flops, byts
