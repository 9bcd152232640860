"""Public op: one-pass (dirty bitmap, per-block popcount) of a flat buffer.

Used by CheckpointManager.save: replaces the separate dirty_diff pass and
the host-side per-page popcount with a single device scan.
"""

from __future__ import annotations

from typing import Literal, Tuple

import jax
import jax.numpy as jnp

from repro.core.blocks import TPU_TILE
from repro.kernels.common import (TILE_BLOCKS, as_blocks, pad_blocks_to_tile,
                                  resolve_impl)
from repro.kernels.flush_scan.kernel import flush_scan_blocked
from repro.kernels.flush_scan.ref import flush_scan_blocked_ref

Impl = Literal["auto", "pallas", "interpret", "ref"]


def flush_scan(cur: jax.Array, snap: jax.Array, *,
               block_bytes: int = TPU_TILE,
               impl: Impl = "auto") -> Tuple[jax.Array, jax.Array]:
    """((nblocks,) int32 dirty flags, (nblocks,) uint32 popcounts)."""
    if cur.shape != snap.shape or cur.dtype != snap.dtype:
        raise ValueError("cur and snap must match in shape and dtype")
    cur_b, _ = as_blocks(cur, block_bytes)
    snap_b, _ = as_blocks(snap, block_bytes)
    nblocks = cur_b.shape[0]
    ran = resolve_impl(impl)
    if ran == "ref":
        return flush_scan_blocked_ref(cur_b, snap_b)
    padded = pad_blocks_to_tile(nblocks, TILE_BLOCKS)
    if padded != nblocks:
        pad = ((0, padded - nblocks), (0, 0), (0, 0))
        cur_b = jnp.pad(cur_b, pad)
        snap_b = jnp.pad(snap_b, pad)
    dirty, cnt = flush_scan_blocked(cur_b, snap_b,
                                    interpret=ran == "interpret")
    return dirty[:nblocks], cnt[:nblocks]
