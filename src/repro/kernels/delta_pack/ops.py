"""Public ops: pack dirty blocks to a compact delta / apply a delta.

The flusher decides CoW-vs-µLog per page on the host (HybridPolicy), after
which the dirty-block index vector is host-known; these ops therefore take a
concrete index array. Index vectors are bucketed to power-of-two lengths by
the persistence layer to bound the number of compiled shapes.
"""

from __future__ import annotations

from typing import Literal, Tuple

import jax
import jax.numpy as jnp

from repro.core.blocks import TPU_TILE
from repro.kernels.common import as_blocks, from_blocks, resolve_impl
from repro.kernels.delta_pack.kernel import delta_apply_blocked, delta_pack_blocked
from repro.kernels.delta_pack.ref import (
    delta_apply_blocked_ref,
    delta_pack_blocked_ref,
)

Impl = Literal["auto", "pallas", "interpret", "ref"]


def pack_delta(
    buf: jax.Array,
    idx: jax.Array,
    *,
    block_bytes: int = TPU_TILE,
    impl: Impl = "auto",
) -> jax.Array:
    """Gather blocks ``idx`` of a flat buffer → (k, rows, 128) compact delta."""
    blocked, _ = as_blocks(buf, block_bytes)
    ran = resolve_impl(impl)
    if ran == "ref":
        return delta_pack_blocked_ref(blocked, idx)
    return delta_pack_blocked(blocked, idx, interpret=ran == "interpret")


def pack_dirty(
    buf: jax.Array,
    flags: jax.Array,
    *,
    block_bytes: int = TPU_TILE,
    impl: Impl = "auto",
) -> Tuple[jax.Array, jax.Array, int]:
    """Pack the dirty blocks of a flat buffer given its dirty bitmap.

    The index build is the shared on-device prefix-sum compaction from
    ``flush_pack`` (no host ``np.flatnonzero``): only the scalar dirty
    count crosses to the host, to size the gather. Returns
    ``(delta (k, rows, 128), idx (k,) int32, k)`` — the same compaction
    story the fused kernel uses, so staged and fused paths agree
    bit-for-bit on packing order (ascending block id).
    """
    from repro.kernels.flush_pack.ref import compact_index

    index, total = compact_index(flags)
    k = int(total)
    idx = index[:k]
    return pack_delta(buf, idx, block_bytes=block_bytes, impl=impl), idx, k


def apply_delta(
    buf: jax.Array,
    delta: jax.Array,
    idx: jax.Array,
    *,
    block_bytes: int = TPU_TILE,
    impl: Impl = "auto",
) -> jax.Array:
    """Scatter a packed delta back into a flat buffer; returns the new buffer
    (same shape/dtype as ``buf``)."""
    blocked, n = as_blocks(buf, block_bytes)
    ran = resolve_impl(impl)
    if ran == "ref":
        out = delta_apply_blocked_ref(blocked, delta, idx)
    else:
        out = delta_apply_blocked(blocked, delta, idx,
                                  interpret=ran == "interpret")
    return from_blocks(out, n).reshape(buf.shape)
