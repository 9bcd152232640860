"""Pure-jnp oracle for the fused flush scan."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import as_bits


def flush_scan_blocked_ref(cur: jax.Array, snap: jax.Array):
    """(nblocks, rows, 128) ×2 → per-block (dirty flags, popcounts)."""
    bits = as_bits(cur)
    dirty = jnp.any(bits != as_bits(snap), axis=(1, 2)).astype(jnp.int32)
    cnt = jnp.sum(jax.lax.population_count(bits).astype(jnp.uint32),
                  axis=(1, 2), dtype=jnp.uint32)
    return dirty, cnt
