"""Shared geometry for the persistence kernels.

The paper's guideline G1 ("optimize for PMem blocks, not cache lines")
becomes, on TPU: track checkpoint-delta dirtiness at the granularity of a
device-native tile. One float32 (8, 128) VREG tile = 4096 bytes = the
``TPU_TILE`` block. All kernels view a flat parameter buffer as
``(nblocks, rows, 128)`` where ``rows × 128 × itemsize = block_bytes``,
so every block is a whole number of hardware tiles and the MXU/VPU lane
dimension stays 128-aligned.

Inside the Pallas kernels a block's bytes are compared and popcounted as
int32 words (:func:`as_words`, ``block_bytes // 512`` rows of 128): the
checkpoint path hands the kernels raw ``uint8`` leaf bytes, and a 4 KiB
block is then exactly one (8, 128) 32-bit tile. Popcounts and byte
equality do not depend on how bytes are grouped into words, and the TPU
compiler lowers neither reductions over unsigned integers nor multi-axis
reductions, so the kernels reduce int32 one axis at a time
(:func:`block_reduce`). Blocks move (DMA, copies) in their own dtype.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas import tpu as pltpu

from repro.core.blocks import TPU_TILE

LANES = 128

#: blocks per kernel tile along the block axis (VMEM working set:
#: 8 blocks × 4 KiB = 32 KiB per operand — comfortably inside the
#: ~16 MiB VMEM even with double buffering and 3 operands).
TILE_BLOCKS = 8

_UINT_FOR = {4: jnp.uint32, 2: jnp.uint16, 1: jnp.uint8}


def resolve_impl(impl: str) -> str:
    """The implementation a kernel op asked for ``impl`` runs here:
    ``"pallas"`` (compiled for the TPU), ``"interpret"`` (the Pallas
    interpreter) or ``"ref"`` (the jnp oracle).

    ``"auto"`` is the compiled kernel on a TPU and the oracle elsewhere;
    ``"pallas"`` (alias ``"fused"``) asks for the kernel, which off the TPU
    can only be interpreted; ``"interpret"`` forces the interpreter."""
    on_tpu = jax.default_backend() == "tpu"
    if impl == "ref":
        return "ref"
    if impl == "auto":
        return "pallas" if on_tpu else "ref"
    if impl in ("pallas", "fused"):
        return "pallas" if on_tpu else "interpret"
    if impl == "interpret":
        return "interpret"
    raise ValueError(f"unknown kernel impl {impl!r}")


def as_bits(x: jax.Array) -> jax.Array:
    """``x`` bitcast to the unsigned integer of its width — equality on
    it is byte equality (floats would call -0.0 == 0.0 and NaN != NaN)."""
    return jax.lax.bitcast_convert_type(x, _UINT_FOR[x.dtype.itemsize])


def as_words(tile: jax.Array) -> jax.Array:
    """In-kernel: a (..., rows, 128) tile of a 1-, 2- or 4-byte dtype as the
    int32 words that hold its bytes, (..., rows * itemsize // 4, 128) —
    a register reinterpretation, no data movement."""
    return pltpu.bitcast(tile, jnp.int32)


def block_reduce(x: jax.Array, op=jnp.sum) -> jax.Array:
    """In-kernel per-block reduction: (blocks, rows, 128) → (blocks, 1),
    one axis at a time (the only form the TPU compiler lowers)."""
    return op(op(x, axis=1), axis=1, keepdims=True)


def block_rows(dtype, block_bytes: int = TPU_TILE) -> int:
    """Rows of 128 lanes per block for ``dtype``."""
    itemsize = jnp.dtype(dtype).itemsize
    if block_bytes % (LANES * itemsize) != 0:
        raise ValueError(f"block_bytes={block_bytes} not a multiple of "
                         f"{LANES}*{itemsize} for dtype {dtype}")
    return block_bytes // (LANES * itemsize)


def as_blocks(flat: jax.Array, block_bytes: int = TPU_TILE) -> Tuple[jax.Array, int]:
    """Reshape a flat buffer to (nblocks, rows, 128), zero-padding the tail.

    Returns (blocked, original_length). Zero padding is semantically safe
    for every kernel here: padded regions are identical in cur/snap (never
    dirty) and contribute 0 to popcounts.
    """
    flat = flat.reshape(-1)
    rows = block_rows(flat.dtype, block_bytes)
    elems = rows * LANES
    n = flat.shape[0]
    nblocks = -(-n // elems) if n else 1
    padded = nblocks * elems
    if padded != n:
        flat = jnp.pad(flat, (0, padded - n))
    return flat.reshape(nblocks, rows, LANES), n


def from_blocks(blocked: jax.Array, orig_len: int) -> jax.Array:
    """Inverse of :func:`as_blocks`: flatten and drop the zero padding."""
    return blocked.reshape(-1)[:orig_len]


def pad_blocks_to_tile(nblocks: int, tile: int = TILE_BLOCKS) -> int:
    """Round a block count up to the kernel grid's tile multiple."""
    return -(-nblocks // tile) * tile


def blocked_for_tiles(flat: jax.Array, block_bytes: int = TPU_TILE,
                      tile: int = TILE_BLOCKS) -> Tuple[jax.Array, int, int]:
    """``as_blocks`` plus tile-multiple padding along the block axis.

    Returns ``(blocked, nblocks, orig_len)`` where ``blocked`` has a
    first dimension padded up to a multiple of ``tile`` (extra blocks are
    zero, hence clean) and ``nblocks`` is the count BEFORE tile padding —
    slice kernel outputs back to ``[:nblocks]``.
    """
    blocked, orig_len = as_blocks(flat, block_bytes)
    nblocks = blocked.shape[0]
    padded = pad_blocks_to_tile(nblocks, tile)
    if padded != nblocks:
        blocked = jnp.pad(blocked, ((0, padded - nblocks), (0, 0), (0, 0)))
    return blocked, nblocks, orig_len
