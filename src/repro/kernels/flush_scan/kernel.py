"""Pallas TPU kernel: fused checkpoint flush scan (beyond-paper).

The checkpoint save path needs TWO facts per 4 KiB block of live
parameters: is it dirty vs the snapshot (µLog dirty set), and its popcount
(Zero-log page checksums). Both are O(1) flops/byte, i.e. HBM-bandwidth
bound — running them as separate kernels reads the parameter buffer twice.
This kernel computes both in ONE pass (the snapshot is read once too), so
the device-side cost of a delta-checkpoint scan drops from 3 buffer-reads
to 2 — a 1.5× cut of the dominant term of the save path (EXPERIMENTS.md
§Perf, persistence numbers).

Grid: one program per TILE_BLOCKS blocks (compared as int32 words);
outputs per-block (dirty int32, popcount uint32) vectors.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import TILE_BLOCKS, as_words, block_reduce


def _flush_scan_kernel(cur_ref, snap_ref, dirty_ref, cnt_ref):
    cur = as_words(cur_ref[...])
    dirty_ref[...] = block_reduce(
        (cur != as_words(snap_ref[...])).astype(jnp.int32), jnp.max)
    cnt_ref[...] = block_reduce(jax.lax.population_count(cur))


@functools.partial(jax.jit, static_argnames=("interpret",))
def flush_scan_blocked(cur: jax.Array, snap: jax.Array, *,
                       interpret: bool = False):
    """(nblocks, rows, 128) ×2 → ((nblocks,) int32 dirty, (nblocks,) uint32
    popcounts), one pass."""
    nblocks = cur.shape[0]
    assert cur.shape == snap.shape
    assert nblocks % TILE_BLOCKS == 0
    spec = pl.BlockSpec((TILE_BLOCKS,) + cur.shape[1:], lambda i: (i, 0, 0))
    out_spec = pl.BlockSpec((TILE_BLOCKS, 1), lambda i: (i, 0))
    dirty, cnt = pl.pallas_call(
        _flush_scan_kernel,
        grid=(nblocks // TILE_BLOCKS,),
        in_specs=[spec, spec],
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((nblocks, 1), jnp.int32),
            jax.ShapeDtypeStruct((nblocks, 1), jnp.int32),
        ],
        interpret=interpret,
    )(cur, snap)
    return dirty[:, 0], cnt[:, 0].astype(jnp.uint32)
