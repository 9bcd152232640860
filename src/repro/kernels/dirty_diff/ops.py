"""Public op: dirty-block bitmap of a flat parameter buffer.

Dispatch per :func:`repro.kernels.common.resolve_impl`: the compiled Pallas
kernel on TPU, the Pallas interpreter on request (``impl="pallas"`` or
``"interpret"`` off-TPU — used by the test suite), the pure-jnp reference
otherwise. Both paths share padding/reshape via :mod:`repro.kernels.common`.
"""

from __future__ import annotations

from typing import Literal

import jax
import jax.numpy as jnp

from repro.core.blocks import TPU_TILE
from repro.kernels.common import (TILE_BLOCKS, as_blocks, pad_blocks_to_tile,
                                  resolve_impl)
from repro.kernels.dirty_diff.kernel import dirty_diff_blocked
from repro.kernels.dirty_diff.ref import dirty_diff_blocked_ref

Impl = Literal["auto", "pallas", "interpret", "ref"]


def dirty_blocks(
    cur: jax.Array,
    snap: jax.Array,
    *,
    block_bytes: int = TPU_TILE,
    impl: Impl = "auto",
) -> jax.Array:
    """int32 (nblocks,) dirty flags for a flat buffer vs its snapshot.

    nblocks = ceil(cur.size * itemsize / block_bytes); the tail block is
    zero-padded identically on both sides (never spuriously dirty).
    """
    if cur.shape != snap.shape or cur.dtype != snap.dtype:
        raise ValueError("cur and snap must match in shape and dtype")
    cur_b, _ = as_blocks(cur, block_bytes)
    snap_b, _ = as_blocks(snap, block_bytes)
    nblocks = cur_b.shape[0]
    ran = resolve_impl(impl)
    if ran == "ref":
        return dirty_diff_blocked_ref(cur_b, snap_b)
    padded = pad_blocks_to_tile(nblocks, TILE_BLOCKS)
    if padded != nblocks:
        pad = ((0, padded - nblocks), (0, 0), (0, 0))
        cur_b = jnp.pad(cur_b, pad)
        snap_b = jnp.pad(snap_b, pad)
    flags = dirty_diff_blocked(cur_b, snap_b, interpret=ran == "interpret")
    return flags[:nblocks]
