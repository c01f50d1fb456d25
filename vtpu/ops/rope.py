"""Rotary position embeddings, precomputed-table style (static shapes for jit)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def rope_angles(max_seq: int, head_dim: int, base: float = 10000.0) -> tuple[jax.Array, jax.Array]:
    """Precompute (cos, sin) tables of shape [max_seq, head_dim//2] in f32."""
    half = head_dim // 2
    freqs = 1.0 / (base ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    pos = jnp.arange(max_seq, dtype=jnp.float32)
    angles = jnp.outer(pos, freqs)  # [S, half]
    return jnp.cos(angles), jnp.sin(angles)


def yarn_inv_freq(dim: int, base: float, factor: float, original_max: int,
                  beta_fast: float = 32.0, beta_slow: float = 1.0) -> jax.Array:
    """YaRN's [dim//2] inverse frequencies: pair i turns at ``base**(-2i/dim)``
    where it completes more than ``beta_fast`` rotations over the original
    context (left as trained), at that over ``factor`` where it completes
    fewer than ``beta_slow`` (interpolated), and at a linear blend of the two
    over the pair indices in between."""
    half = dim // 2
    extrapolated = 1.0 / (base ** (jnp.arange(half, dtype=jnp.float32) / half))

    def pair_of(rotations: float) -> float:  # the pair that turns so often
        return (dim * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return extrapolated / factor * ramp + extrapolated * (1.0 - ramp)


def yarn_rope_angles(max_seq: int, dim: int, base: float, factor: float,
                     original_max: int, beta_fast: float = 32.0,
                     beta_slow: float = 1.0, attn_factor: float = 1.0,
                     ) -> tuple[jax.Array, jax.Array]:
    """(cos, sin) tables [max_seq, dim//2] over YaRN's frequencies, each
    scaled by ``attn_factor`` (the published mscale / mscale_all_dim)."""
    inv = yarn_inv_freq(dim, base, factor, original_max, beta_fast, beta_slow)
    angles = jnp.outer(jnp.arange(max_seq, dtype=jnp.float32), inv)
    return jnp.cos(angles) * attn_factor, jnp.sin(angles) * attn_factor


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array, positions: jax.Array) -> jax.Array:
    """Rotate pairs (x_even, x_odd) by the per-position angle.

    x: [B, S, H, Dh]; positions: [B, S] int32 absolute positions (supports both
    prefill, where positions = arange, and decode, where it is the cache index).
    """
    half = x.shape[-1] // 2
    c = cos[positions][:, :, None, :]  # [B, S, 1, half]
    s = sin[positions][:, :, None, :]
    x1 = x[..., :half]
    x2 = x[..., half:]
    rot = jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
    return rot.astype(x.dtype)
