"""Small shard_map helpers shared by the ring/pipeline/expert kernels."""

from __future__ import annotations

import jax


def pvary(x, axis: str):
    """Mark x as varying over `axis` (zero-init scan carries under shard_map)."""
    return jax.lax.pcast(x, axis, to="varying")
