"""Pure-jnp oracle for the popcount-checksum kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import as_bits


def popcnt_blocked_ref(x: jax.Array) -> jax.Array:
    """(nblocks, rows, 128) → (nblocks,) uint32 per-block popcounts."""
    return jnp.sum(jax.lax.population_count(as_bits(x)).astype(jnp.uint32),
                   axis=(1, 2), dtype=jnp.uint32)
