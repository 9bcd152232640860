"""Pallas TPU kernel: fused one-pass restore pipeline (verify+scatter+apply).

The staged restore path reads the packed page images up to twice — one
popcount pass to verify each block against its manifest checksum, then a
second pass that copies the verified bytes onto the base image. This
kernel is the inverse of ``flush_pack``: each grid step popcounts ONE
packed block while its bytes are in VMEM and, in the same step, scatters
it to its destination block of the base image — the packed bytes cross
HBM exactly once per restore (Wu arXiv:2005.07658: restart time is
dominated by read-side scan traffic; Izraelevitz arXiv:1903.05714: PMem
read bandwidth is the scarce, thread-scalable resource).

Grid: one program per packed block, destination driven by a
scalar-prefetched index vector (the canonical Pallas TPU scatter, same
shape as ``delta_pack``'s apply kernel). The base image stays in HBM
(``pl.ANY``) aliased into the output, so unreferenced blocks are never
read or copied. Each block's popcount streams out as a lane-broadcast
(1, 1, 128) row — the block shape the TPU tiling accepts for one value
per step; the checksum verdicts are compared outside the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import LANES, as_words, block_reduce


def _apply_unpack_kernel(idx_ref, upd_ref, base_ref, out_ref, cnt_ref):
    # base_ref is aliased into out_ref and never read: the kernel's only
    # job at this grid step is to land the packed block and its count.
    upd = upd_ref[...]
    cnt = block_reduce(jax.lax.population_count(as_words(upd)))  # (1, 1)
    cnt_ref[...] = jnp.broadcast_to(cnt, (1, LANES))[None]
    out_ref[...] = upd


@functools.partial(jax.jit, static_argnames=("interpret",))
def apply_unpack_blocked(base: jax.Array, packed: jax.Array,
                         idx: jax.Array, expected: jax.Array, *,
                         interpret: bool = False):
    """(nblocks, rows, 128) base + (k, rows, 128) packed → (out, ok, counts).

    ``out`` is ``base`` with ``out[idx[i]] = packed[i]`` (in-place via
    aliasing — blocks outside ``idx`` never move); ``ok[i]`` is 1 iff
    block i's popcount equals ``expected[i]``; ``counts[i]`` is the
    actual popcount. ``idx`` must not contain duplicates (each
    destination block written once).
    """
    k = packed.shape[0]
    assert packed.shape[1:] == base.shape[1:] and packed.dtype == base.dtype
    assert idx.shape == (k,) and expected.shape == (k,)
    rows = packed.shape[1:]
    blk = pl.BlockSpec((1,) + rows, lambda i, idx: (i, 0, 0))
    dst = pl.BlockSpec((1,) + rows, lambda i, idx: (idx[i], 0, 0))
    cnt_spec = pl.BlockSpec((1, 1, LANES), lambda i, idx: (i, 0, 0))
    out, cnt = pl.pallas_call(
        _apply_unpack_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(k,),
            in_specs=[blk, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[dst, cnt_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(base.shape, base.dtype),
            jax.ShapeDtypeStruct((k, 1, LANES), jnp.int32),
        ],
        input_output_aliases={2: 0},  # base (after the scalar operand) → out
        interpret=interpret,
    )(idx.astype(jnp.int32), packed, base)
    counts = cnt[:, 0, 0].astype(jnp.uint32)
    ok = (counts == expected.astype(jnp.uint32)).astype(jnp.int32)
    return out, ok, counts
