"""Pure-jnp oracle for the dirty_diff kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import as_bits


def dirty_diff_blocked_ref(cur: jax.Array, snap: jax.Array) -> jax.Array:
    """(nblocks, rows, 128) ×2 → (nblocks,) int32 dirty flags (any byte
    differs)."""
    return jnp.any(as_bits(cur) != as_bits(snap), axis=(1, 2)).astype(jnp.int32)
