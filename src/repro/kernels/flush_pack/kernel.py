"""Pallas TPU kernel: fused one-pass flush pipeline (diff+pack+checksum).

The staged save path reads the live parameter buffer from HBM up to three
times — flush_scan (dirty flags + popcounts), delta_pack (gather of dirty
blocks), plus a host round-trip to turn flags into a gather index. This
kernel does all of it in ONE sequential pass: each grid step diffs a tile
of blocks against the snapshot, popcounts the live bytes, extends a
running prefix sum of dirty flags carried in SMEM, and DMAs each dirty
block straight from VMEM to its prefix-sum slot of the packed output in
HBM while the bytes are still on chip. The live buffer is read from HBM
exactly once per save (Wu arXiv:2005.07658: redundant flush passes
dominate PMem cost; Izraelevitz arXiv:1903.05714: PMem read bandwidth is
the scarce resource).

Grid: sequential, one program per TILE_BLOCKS blocks (compared and
popcounted as int32 words, moved in their own dtype).
Flags and popcounts stream out per step; ``packed`` stays in HBM
(``pl.ANY``) and every slot of it receives exactly one block-sized DMA:
dirty block *b* lands at slot ``prefix[b]``, and the k-th clean block
zero-fills slot ``nblocks - 1 - k`` — the clean blocks cover exactly the
tail past the dirty count, so no slot is left unwritten and nothing of
``packed`` is ever resident in VMEM. Offsets and the dirty block ids are
derived from the flags by the same prefix-sum compaction the jnp oracle
uses (a few bytes per block).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import TILE_BLOCKS, as_words, block_reduce
from repro.kernels.flush_pack.ref import compact_index, exclusive_prefix_sum


def _flush_pack_kernel(cur_ref, snap_ref, dirty_ref, cnt_ref, packed_ref,
                       carry_ref, zero_ref, sem):
    i = pl.program_id(0)
    nblocks = packed_ref.shape[0]

    @pl.when(i == 0)
    def _init():
        carry_ref[0] = 0
        zero_ref[...] = jnp.zeros(zero_ref.shape, zero_ref.dtype)

    cur = as_words(cur_ref[...])
    neq = (cur != as_words(snap_ref[...])).astype(jnp.int32)
    dirty_ref[...] = block_reduce(neq, jnp.max)
    cnt_ref[...] = block_reduce(jax.lax.population_count(cur))

    o = carry_ref[0]                       # dirty blocks before this tile
    for b in range(TILE_BLOCKS):
        d = jnp.max(neq[b])
        g = i * TILE_BLOCKS + b

        @pl.when(d != 0)
        def _pack(b=b, o=o):
            pltpu.make_async_copy(cur_ref.at[b], packed_ref.at[o],
                                  sem.at[b]).start()

        @pl.when(d == 0)
        def _zero_tail(b=b, o=o, g=g):
            pltpu.make_async_copy(zero_ref, packed_ref.at[nblocks - 1 - (g - o)],
                                  sem.at[b]).start()

        o = o + d
    carry_ref[0] = o
    # the pipeline refills this input buffer after the step: land the copies
    for b in range(TILE_BLOCKS):
        pltpu.make_async_copy(zero_ref, packed_ref.at[0], sem.at[b]).wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def flush_pack_blocked(cur: jax.Array, snap: jax.Array, *,
                       interpret: bool = False):
    """(nblocks, rows, 128) ×2 → (flags, counts, offsets, packed, index).

    One device pass; see the module docstring. ``nblocks`` must be a
    multiple of TILE_BLOCKS (pad with ``pad_blocks_to_tile`` first —
    zero-padded tails are never dirty, so padding only appends clean
    blocks).
    """
    nblocks = cur.shape[0]
    assert cur.shape == snap.shape and cur.dtype == snap.dtype
    assert nblocks % TILE_BLOCKS == 0
    rows = cur.shape[1:]
    spec = pl.BlockSpec((TILE_BLOCKS,) + rows, lambda i: (i, 0, 0))
    col_spec = pl.BlockSpec((TILE_BLOCKS, 1), lambda i: (i, 0))
    flags, cnt, packed = pl.pallas_call(
        _flush_pack_kernel,
        grid=(nblocks // TILE_BLOCKS,),
        in_specs=[spec, spec],
        out_specs=[col_spec, col_spec, pl.BlockSpec(memory_space=pl.ANY)],
        out_shape=[
            jax.ShapeDtypeStruct((nblocks, 1), jnp.int32),
            jax.ShapeDtypeStruct((nblocks, 1), jnp.int32),
            jax.ShapeDtypeStruct(cur.shape, cur.dtype),
        ],
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32),
                        pltpu.VMEM(rows, cur.dtype),
                        pltpu.SemaphoreType.DMA((TILE_BLOCKS,))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(cur, snap)
    flags = flags[:, 0]
    index, _ = compact_index(flags)
    return (flags, cnt[:, 0].astype(jnp.uint32), exclusive_prefix_sum(flags),
            packed, index)
