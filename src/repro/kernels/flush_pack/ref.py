"""Pure-jnp oracle for the fused flush pipeline.

The compaction story is a single exclusive prefix sum over the dirty
flags: dirty block *b* lands at packed position ``prefix[b]``. The ref
oracle realizes it as an index scatter (``.at[dst].set``, clean blocks
routed to a discard row) followed by a masked gather — bit-identical to
the Pallas kernel's sequential prefix-sum writes, and reused by
``delta_pack.pack_dirty`` so the staged fallback shares one compaction
implementation (no host-side ``np.flatnonzero`` anywhere on the save
path).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.common import as_bits


def exclusive_prefix_sum(flags: jax.Array) -> jax.Array:
    """(nblocks,) int dirty flags → (nblocks,) int32 exclusive prefix sum
    (the packed-delta offset of each dirty block)."""
    f = flags.astype(jnp.int32)
    return jnp.cumsum(f) - f


def compact_index(flags: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """On-device prefix-sum compaction of a dirty bitmap.

    Returns ``(index, total)``: ``index`` is (nblocks,) int32 whose first
    ``total`` entries are the dirty block ids in ascending order (the
    rest are don't-care zeros), ``total`` is the scalar dirty count.
    Equivalent to ``np.flatnonzero`` but computed on device — only the
    scalar ``total`` ever needs a host sync.
    """
    n = flags.shape[0]
    off = exclusive_prefix_sum(flags)
    dst = jnp.where(flags > 0, off, n)        # clean blocks → discard row
    index = jnp.zeros((n + 1,), jnp.int32).at[dst].set(
        jnp.arange(n, dtype=jnp.int32))[:n]
    return index, jnp.sum(flags.astype(jnp.int32))


def flush_pack_blocked_ref(cur: jax.Array, snap: jax.Array):
    """(nblocks, rows, 128) ×2 → (flags, counts, offsets, packed, index).

    One logical pass: ``flags`` (int32 dirty bitmap), ``counts`` (uint32
    per-block popcounts of ``cur``), ``offsets`` (exclusive prefix sum of
    ``flags``), ``packed`` (same shape as ``cur``; the first
    ``sum(flags)`` blocks are the dirty blocks in ascending block order),
    ``index`` (int32; first ``sum(flags)`` entries are the dirty block
    ids). Entries of ``packed``/``index`` beyond the dirty count are
    zero-filled don't-cares.

    Only the small int32 ``index`` is built by scatter; ``packed`` is a
    gather through it plus a live mask — one read of ``cur``, one write
    of the output, no full-size scatter (the scatter variant copies its
    zero operand before updating, a third pass over the data).
    """
    nblocks = cur.shape[0]
    bits = as_bits(cur)
    flags = jnp.any(bits != as_bits(snap), axis=(1, 2)).astype(jnp.int32)
    counts = jnp.sum(jax.lax.population_count(bits).astype(jnp.uint32),
                     axis=(1, 2), dtype=jnp.uint32)
    offsets = exclusive_prefix_sum(flags)
    index, total = compact_index(flags)
    live = jnp.arange(nblocks, dtype=jnp.int32) < total
    packed = jnp.where(live[:, None, None], jnp.take(cur, index, axis=0),
                       jnp.zeros((), cur.dtype))
    return flags, counts, offsets, packed, index
