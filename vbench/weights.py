"""Weights from ``--seed``, made by the benchmark and by nothing else.

Every leaf is a pure function of (seed, leaf index, layer index): the whole
model is made on the device in one jitted call for the system under test,
and the plain reference makes the same leaves again, one layer at a time,
after the program's state is freed. Neither side takes what the other made.

A leaf is uniform on [-a, a] with a = sqrt(3 / fan_in) (variance 1/fan_in,
the scale of the program's own init), drawn in float32 and rounded once to
the served type; norm gains are ones.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative whole number (the driver's seeds pass
    2**31, which a signed 32-bit word does not hold)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0x3FFFFFFF)
    return jax.random.fold_in(key, (seed >> 30) & 0x3FFFFFFF)


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def make_leaf(key: jax.Array, spec: dict, index: int, layer) -> jax.Array:
    """One leaf of one layer (``layer`` may be traced; 0 for unlayered)."""
    shape, dtype = tuple(spec["shape"]), _dtype(spec["dtype"])
    if spec["fan_in"] is None:
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(jax.random.fold_in(key, index), layer)
    a = math.sqrt(3.0 / spec["fan_in"])
    return jax.random.uniform(k, shape, jnp.float32, -a, a).astype(dtype)


def make_layer(key: jax.Array, specs: list[dict], layer) -> dict:
    """The layered leaves of one layer, by name."""
    return {s["name"]: make_leaf(key, s, i, layer)
            for i, s in enumerate(specs) if s["layered"]}


def make_globals(key: jax.Array, specs: list[dict]) -> dict:
    """The leaves that no layer owns (embedding, final norm)."""
    return {s["name"]: make_leaf(key, s, i, 0)
            for i, s in enumerate(specs) if not s["layered"]}


def build(key: jax.Array, specs: list[dict], n_layers: int) -> dict:
    """The whole model: layered leaves stacked [L, ...], made layer by
    layer, so the float32 draw of one layer is the largest temporary."""
    out = make_globals(key, specs)
    out["layers"] = jax.lax.map(
        lambda l: make_layer(key, specs, l),
        jnp.arange(n_layers, dtype=jnp.int32))
    return out


def make_all(seed: int, specs: list[dict], n_layers: int) -> dict:
    """``build`` in one jitted call on the device."""
    return jax.jit(lambda key: build(key, specs, n_layers))(seed_key(seed))
