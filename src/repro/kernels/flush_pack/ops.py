"""Public op: fused one-pass diff + pack + checksum of a flat buffer.

``flush_pack`` is the save path's single device pass: everything the
checkpoint epoch needs about a buffer — dirty flags, popcount checksums,
prefix-sum offsets, packed delta blocks, dirty block ids — from one read
of the live bytes. Replaces the staged flush_scan → host flatnonzero →
delta_pack chain.
"""

from __future__ import annotations

import functools
from typing import Literal, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.blocks import TPU_TILE
from repro.kernels.common import as_blocks, blocked_for_tiles, resolve_impl
from repro.kernels.flush_pack.kernel import flush_pack_blocked
from repro.kernels.flush_pack.ref import flush_pack_blocked_ref

Impl = Literal["auto", "pallas", "fused", "interpret", "ref"]


class FlushPack(NamedTuple):
    """Everything one fused device pass yields about a buffer.

    ``flags``: (nblocks,) int32 dirty bitmap vs the snapshot.
    ``counts``: (nblocks,) uint32 per-block popcounts of the live bytes.
    ``offsets``: (nblocks,) int32 exclusive prefix sum of ``flags`` —
    block b's slot in ``packed`` when dirty.
    ``packed``: (nblocks, rows, 128) live-dtype; the first ``total``
    blocks are the dirty blocks in ascending block order (tail zeroed).
    ``index``: (nblocks,) int32; first ``total`` entries are the dirty
    block ids (tail zeroed).
    ``total``: python int dirty-block count (the only host sync).
    """

    flags: jax.Array
    counts: jax.Array
    offsets: jax.Array
    packed: jax.Array
    index: jax.Array
    total: int


@functools.partial(jax.jit, static_argnames=("block_bytes", "impl"))
def flush_pack_device(cur: jax.Array, snap: jax.Array, *, block_bytes: int,
                      impl: str):
    """The whole device side of :func:`flush_pack` as ONE dispatch (the
    oracle is jitted too, so the off-TPU path is also a single fused XLA
    program) → (flags, counts, offsets, packed, index). ``impl`` is a
    resolved implementation: ``"pallas"``, ``"interpret"`` or ``"ref"``."""
    if impl == "ref":
        return flush_pack_blocked_ref(as_blocks(cur, block_bytes)[0],
                                      as_blocks(snap, block_bytes)[0])
    cur_b, nblocks, _ = blocked_for_tiles(cur, block_bytes)
    snap_b, _, _ = blocked_for_tiles(snap, block_bytes)
    outs = flush_pack_blocked(cur_b, snap_b, interpret=impl == "interpret")
    return tuple(o[:nblocks] for o in outs)


def flush_pack(cur: jax.Array, snap: jax.Array, *,
               block_bytes: int = TPU_TILE,
               impl: Impl = "auto") -> FlushPack:
    """Fused diff+pack+checksum of flat ``cur`` vs ``snap`` → FlushPack.

    ``impl`` as in :func:`repro.kernels.common.resolve_impl`: ``"auto"``
    runs the compiled kernel on TPU and the jnp oracle elsewhere;
    ``"pallas"`` (alias ``"fused"`` — the fused kernel IS the pallas
    path) interprets the kernel off the TPU.
    """
    if cur.shape != snap.shape or cur.dtype != snap.dtype:
        raise ValueError("cur and snap must match in shape and dtype")
    flags, counts, off, packed, index = flush_pack_device(
        jnp.asarray(cur), jnp.asarray(snap), block_bytes=block_bytes,
        impl=resolve_impl(impl))
    total = int(off[-1] + flags[-1])
    return FlushPack(flags, counts, off, packed, index, total)
