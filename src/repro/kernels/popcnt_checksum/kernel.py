"""Pallas TPU kernel: popcount checksum (Zero-logging validity word, §3.3.1).

The paper validates a Zero-log entry by storing the entry's bit population
count next to it: a cache line (here: a 4 KiB TPU block) is either fully
durable or still all-zero, so a dropped block changes the popcount — unless
the block was all-zero, in which case the recovered bytes are identical
anyway. The same argument holds mod 2³²: dropping a block with popcount
0 < c < 2³² always changes the modular sum.

Grid: one program per TILE_BLOCKS blocks; each program popcounts a
(TILE_BLOCKS, rows, 128) tile as int32 words on the VPU
(``lax.population_count``) and emits per-block sums; ops.py does the
final modular reduction. A block's count is at most 8 × block_bytes, so
int32 holds it exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import TILE_BLOCKS, as_words, block_reduce


def _popcnt_kernel(x_ref, out_ref):
    bits = jax.lax.population_count(as_words(x_ref[...]))
    out_ref[...] = block_reduce(bits)


@functools.partial(jax.jit, static_argnames=("interpret",))
def popcnt_blocked(x: jax.Array, *, interpret: bool = False) -> jax.Array:
    """(nblocks, rows, 128) → (nblocks,) uint32 per-block popcounts."""
    nblocks = x.shape[0]
    assert nblocks % TILE_BLOCKS == 0
    out = pl.pallas_call(
        _popcnt_kernel,
        grid=(nblocks // TILE_BLOCKS,),
        in_specs=[pl.BlockSpec((TILE_BLOCKS,) + x.shape[1:],
                               lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((TILE_BLOCKS, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nblocks, 1), jnp.int32),
        interpret=interpret,
    )(x)
    return out[:, 0].astype(jnp.uint32)
