"""Pure-jnp oracle for the fused restore pipeline.

The restore story is one scatter: packed block *i* lands at base block
``idx[i]``, and its popcount is compared against the checksum the
manifest recorded at save time. The oracle realizes it as
``base.at[idx].set(packed)`` plus a vectorized popcount — bit-identical
to the Pallas kernel's per-step aliased scatter (blocks outside ``idx``
keep the base bytes in both), and shared by the staged fallback so
staged and fused restores agree bit-for-bit on the assembled image.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import as_bits


def block_popcounts(packed: jax.Array) -> jax.Array:
    """(k, rows, 128) → (k,) uint32 per-block popcounts."""
    bits = jax.lax.population_count(as_bits(packed))
    return jnp.sum(bits.astype(jnp.uint32), axis=(1, 2), dtype=jnp.uint32)


def apply_unpack_blocked_ref(base: jax.Array, packed: jax.Array,
                             idx: jax.Array, expected: jax.Array):
    """(nblocks, rows, 128) base + (k, rows, 128) packed → (out, ok, counts).

    One logical pass: ``out`` is ``base`` with ``out[idx[i]] =
    packed[i]`` (``idx`` duplicate-free), ``ok[i]`` is 1 iff packed
    block i's popcount equals ``expected[i]``, ``counts`` are the actual
    popcounts. Verification is *reported*, not enforced — the caller
    discards the image when any verdict fails, exactly like the staged
    restore rejects a manifest entry on its first bad page.
    """
    counts = block_popcounts(packed)
    ok = (counts == expected.astype(jnp.uint32)).astype(jnp.int32)
    out = base.at[idx.astype(jnp.int32)].set(packed)
    return out, ok, counts
