"""Pallas TPU kernel: block-granular dirty bitmap (cur vs snapshot).

This is the on-device realization of the paper's "the page is required to
track modified areas since its last flush" (§3.2.2) — a training loop has no
write interception, so dirtiness is *computed* by diffing live parameters
against the last-flushed snapshot, at TPU-block (4 KiB tile) granularity.

Grid: one program per TILE_BLOCKS blocks. Each program streams two
(TILE_BLOCKS, rows, 128) tiles from HBM into VMEM, reduces "any
word differs" per block on the VPU, and writes a (TILE_BLOCKS, 1) int32
flag vector. Arithmetic intensity is ~1 op/byte ⇒ the kernel is
HBM-bandwidth bound by design; the win over the naive jnp composition is
fusing compare + reduce in one pass (no materialized boolean array in HBM).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import TILE_BLOCKS, as_words, block_reduce


def _dirty_diff_kernel(cur_ref, snap_ref, out_ref):
    neq = (as_words(cur_ref[...]) != as_words(snap_ref[...])).astype(jnp.int32)
    out_ref[...] = block_reduce(neq, jnp.max)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dirty_diff_blocked(cur: jax.Array, snap: jax.Array, *, interpret: bool = False) -> jax.Array:
    """(nblocks, rows, 128) ×2 → (nblocks,) int32 dirty flags (a block is
    dirty when any of its bytes differ).

    ``nblocks`` must be a multiple of TILE_BLOCKS (ops.py pads).
    """
    nblocks = cur.shape[0]
    assert cur.shape == snap.shape
    assert nblocks % TILE_BLOCKS == 0
    spec = pl.BlockSpec((TILE_BLOCKS,) + cur.shape[1:], lambda i: (i, 0, 0))
    out = pl.pallas_call(
        _dirty_diff_kernel,
        grid=(nblocks // TILE_BLOCKS,),
        in_specs=[spec, spec],
        out_specs=pl.BlockSpec((TILE_BLOCKS, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nblocks, 1), jnp.int32),
        interpret=interpret,
    )(cur, snap)
    return out[:, 0]
