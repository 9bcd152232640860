"""Public op: popcount checksum of an arbitrary-dtype flat buffer.

Used by the persistence layer as the Zero-log validity word for checkpoint
manifests and WAL records computed on device (the host never has to stream
the data just to checksum it)."""

from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

from repro.core.blocks import TPU_TILE
from repro.kernels.common import (TILE_BLOCKS, as_blocks, pad_blocks_to_tile,
                                  resolve_impl)
from repro.kernels.popcnt_checksum.kernel import popcnt_blocked
from repro.kernels.popcnt_checksum.ref import popcnt_blocked_ref

Impl = Literal["auto", "pallas", "interpret", "ref"]


@functools.partial(jax.jit, static_argnames=("block_bytes", "impl"))
def _popcount_blocks(x: jax.Array, *, block_bytes: int, impl: str) -> jax.Array:
    xb, _ = as_blocks(x, block_bytes)
    nblocks = xb.shape[0]
    if impl == "ref":
        return popcnt_blocked_ref(xb)
    padded = pad_blocks_to_tile(nblocks, TILE_BLOCKS)
    if padded != nblocks:
        xb = jnp.pad(xb, ((0, padded - nblocks), (0, 0), (0, 0)))
    return popcnt_blocked(xb, interpret=impl == "interpret")[:nblocks]


def popcount_blocks(x: jax.Array, *, block_bytes: int = TPU_TILE,
                    impl: Impl = "auto") -> jax.Array:
    """(nblocks,) uint32 per-block popcounts of a flat buffer (one
    dispatch; ``impl`` as in :func:`repro.kernels.common.resolve_impl`)."""
    return _popcount_blocks(jnp.asarray(x), block_bytes=block_bytes,
                            impl=resolve_impl(impl))


def popcount_checksum(x: jax.Array, *, impl: Impl = "auto") -> jax.Array:
    """uint32 scalar: modular popcount checksum (Zero-log validity word).
    Returned value is popcount(x) + 1 (mod 2³²) so 0 always means
    "never written" — the paper's cnt==0 convention."""
    per_block = popcount_blocks(x, impl=impl)
    return (jnp.sum(per_block, dtype=jnp.uint32) + jnp.uint32(1))
