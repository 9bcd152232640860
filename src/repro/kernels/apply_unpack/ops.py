"""Public op: fused one-pass verify + scatter + apply of packed blocks.

``apply_unpack`` is the restore path's single device pass and the exact
inverse of ``flush_pack``: given a flat base image, a flat run of packed
blocks, their destination block ids and the popcount checksums the
manifest recorded at save time, it verifies every block AND applies it
onto the base in one read of the packed bytes. Replaces the staged
popcount-verify → copy chain (two reads of the restored image).
"""

from __future__ import annotations

import functools
from typing import Literal, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.blocks import TPU_TILE
from repro.kernels.common import LANES, as_blocks, from_blocks, resolve_impl
from repro.kernels.apply_unpack.kernel import apply_unpack_blocked
from repro.kernels.apply_unpack.ref import apply_unpack_blocked_ref

Impl = Literal["auto", "pallas", "fused", "interpret", "ref"]


class ApplyUnpack(NamedTuple):
    """Everything one fused restore pass yields about a buffer.

    ``out``: flat array, same shape/dtype as ``base``, with packed block
    i applied at block ``index[i]`` (all other blocks keep base bytes).
    ``ok``: (k,) int32; 1 iff packed block i's popcount matched
    ``expected[i]`` — the caller discards ``out`` if any verdict fails.
    ``counts``: (k,) uint32 actual popcounts of the packed blocks.
    ``nbad``: python int count of failed verdicts (the only host sync).
    """

    out: jax.Array
    ok: jax.Array
    counts: jax.Array
    nbad: int


@functools.partial(jax.jit, static_argnames=("block_bytes", "impl"))
def apply_unpack_device(base: jax.Array, packed: jax.Array, index: jax.Array,
                        expected: jax.Array, *, block_bytes: int, impl: str):
    """The whole device side of :func:`apply_unpack` as ONE dispatch (the
    oracle is jitted too: popcount+scatter fused by XLA) → (out, ok,
    counts). ``impl`` is a resolved implementation: ``"pallas"``,
    ``"interpret"`` or ``"ref"``."""
    k = index.shape[0]
    packed_b = packed.reshape(k, -1, LANES)
    base_b, orig_len = as_blocks(base, block_bytes)
    idx = index.astype(jnp.int32)
    exp = expected.astype(jnp.uint32)
    if impl == "ref":
        out_b, ok, counts = apply_unpack_blocked_ref(base_b, packed_b, idx, exp)
    else:
        out_b, ok, counts = apply_unpack_blocked(
            base_b, packed_b, idx, exp, interpret=impl == "interpret")
    return from_blocks(out_b, orig_len).reshape(base.shape), ok, counts


def apply_unpack(base: jax.Array, packed: jax.Array, index, expected, *,
                 block_bytes: int = TPU_TILE,
                 impl: Impl = "auto") -> ApplyUnpack:
    """Fused verify+scatter of flat ``packed`` onto flat ``base``.

    ``packed`` holds k consecutive blocks (``k * block_bytes`` bytes);
    ``index`` (k,) names each block's destination block of ``base``
    (duplicate-free); ``expected`` (k,) uint32 holds the popcounts to
    verify against. ``impl`` as in
    :func:`repro.kernels.common.resolve_impl`: ``"auto"`` runs the
    compiled kernel on TPU and the jnp oracle elsewhere; ``"pallas"``
    (alias ``"fused"`` — the fused kernel IS the pallas path) interprets
    the kernel off the TPU.
    """
    if packed.dtype != base.dtype:
        raise ValueError("base and packed must share a dtype")
    elems = block_bytes // base.dtype.itemsize
    if packed.size % elems:
        raise ValueError(
            f"packed ({packed.size} elems) is not whole {block_bytes}-byte "
            f"blocks")
    k = packed.size // elems
    if k == 0:
        return ApplyUnpack(base, jnp.zeros((0,), jnp.int32),
                           jnp.zeros((0,), jnp.uint32), 0)
    out, ok, counts = apply_unpack_device(
        jnp.asarray(base), jnp.asarray(packed),
        jnp.asarray(index, dtype=jnp.int32),
        jnp.asarray(expected, dtype=jnp.uint32),
        block_bytes=block_bytes, impl=resolve_impl(impl))
    nbad = int(k - jnp.sum(ok))
    return ApplyUnpack(out, ok, counts, nbad)
