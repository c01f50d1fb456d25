"""What the plain references share: norms, rotary positions, causal
attention, and the matrix product in the precision asked for.

``precision`` is ``"f32"`` (the reference: float32 operands, ``highest``)
or ``"fp8"`` (the control, the nearest precision below the bfloat16 the
configurations state: both operands of every projection rounded to
float8_e4m3 with a per-tensor absmax scale, products accumulated in
float32).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F8_MAX = 448.0  # largest finite float8_e4m3fn


def _to_fp8(x: jax.Array) -> jax.Array:
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(x: jax.Array, w: jax.Array, precision: str) -> jax.Array:
    """x @ w in float32 over the stated operand precision."""
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if precision == "fp8":
        x, w = _to_fp8(x), _to_fp8(w)
    elif precision != "f32":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def rms_norm(x: jax.Array, gain: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain.astype(jnp.float32)


def rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotary positions 0..S-1 on x [S, H, Dh], halves rotated against each
    other (the ``rotate_half`` convention of the published models)."""
    s, _, dh = x.shape
    half = dh // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     block: int = 512) -> jax.Array:
    """softmax(q k^T / sqrt(Dh)) v under the causal mask; [S, H, Dh] each.
    Query rows go in blocks so the score matrix of a long prompt fits."""
    s, h, dh = q.shape
    scale = 1.0 / math.sqrt(dh)
    hi = jax.lax.Precision.HIGHEST
    kpos = jnp.arange(s)[None, None, :]
    out = []
    for start in range(0, s, block):
        qb = q[start:start + block]
        scores = jnp.einsum("qhd,khd->hqk", qb, k, precision=hi) * scale
        qpos = (start + jnp.arange(qb.shape[0]))[None, :, None]
        scores = jnp.where(kpos <= qpos, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", probs, v, precision=hi))
    return jnp.concatenate(out, axis=0)


def attention_block(cfg: dict, w: dict, x: jax.Array,
                    precision: str) -> jax.Array:
    """x + Wo . attention(rope(Wq n), rope(Wk n), Wv n), n = rms_norm(x)."""
    s = x.shape[0]
    h, dh = cfg["num_attention_heads"], cfg["head_dim"]
    n = rms_norm(x, w["attn_norm"], cfg["rms_norm_eps"])
    q = rope(mm(n, w["wq"], precision).reshape(s, h, dh), cfg["rope_theta"])
    k = rope(mm(n, w["wk"], precision).reshape(s, h, dh), cfg["rope_theta"])
    v = mm(n, w["wv"], precision).reshape(s, h, dh)
    attn = causal_attention(q, k, v).reshape(s, h * dh)
    return x + mm(attn, w["wo"], precision)


def swiglu(x: jax.Array, w_gate, w_up, w_down, precision: str) -> jax.Array:
    gate = jax.nn.silu(mm(x, w_gate, precision))
    return mm(gate * mm(x, w_up, precision), w_down, precision)


def head(cfg: dict, g: dict, x: jax.Array, precision: str) -> jax.Array:
    """Final norm and the output head over the rows given."""
    n = rms_norm(x, g["final_norm"], cfg["rms_norm_eps"])
    return mm(n, g[cfg["output_head"]].T, precision)


def attn_specs(cfg: dict) -> list[dict]:
    """Leaves every family here shares: embedding, attention, norms."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    qd = cfg["num_attention_heads"] * cfg["head_dim"]
    t = cfg["dtype"]
    return [
        {"name": "embed", "shape": [v, d], "fan_in": d, "dtype": t,
         "layered": False},
        {"name": "final_norm", "shape": [d], "fan_in": None, "dtype": t,
         "layered": False},
        {"name": "wq", "shape": [d, qd], "fan_in": d, "dtype": t,
         "layered": True},
        {"name": "wk", "shape": [d, qd], "fan_in": d, "dtype": t,
         "layered": True},
        {"name": "wv", "shape": [d, qd], "fan_in": d, "dtype": t,
         "layered": True},
        {"name": "wo", "shape": [qd, d], "fan_in": qd, "dtype": t,
         "layered": True},
        {"name": "attn_norm", "shape": [d], "fan_in": None, "dtype": t,
         "layered": True},
        {"name": "mlp_norm", "shape": [d], "fan_in": None, "dtype": t,
         "layered": True},
    ]


def attn_step_cost(cfg: dict, batch: int, live_tokens: int) -> tuple:
    """(FLOPs, bytes) of the attention part of one decode step over
    ``batch`` streams holding ``live_tokens`` cached tokens in all: the
    four projections (weights read once), scores and values over the live
    tokens only (each cached K and V element read once, the new token's
    written once), per layer."""
    d = cfg["hidden_size"]
    qd = cfg["num_attention_heads"] * cfg["head_dim"]
    el = 2  # bfloat16
    flops = batch * 2 * 4 * d * qd + 2 * 2 * live_tokens * qd
    byts = 4 * d * qd * el + 2 * (live_tokens + batch) * qd * el
    return flops, byts


def head_step_cost(cfg: dict, batch: int) -> tuple:
    """(FLOPs, bytes) of embedding rows in and the output head out."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return batch * 2 * d * v, (v * d + batch * d) * 2
