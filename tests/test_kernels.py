"""Kernel validation: Pallas (interpret mode) vs pure-jnp oracles.

Deterministic checks only; the hypothesis-driven shape/dtype sweeps live in
``test_kernels_props.py`` (skipped without the ``test`` extra)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.common import LANES, TILE_BLOCKS, as_blocks, block_rows, from_blocks
from repro.kernels.delta_pack.kernel import delta_apply_blocked, delta_pack_blocked
from repro.kernels.delta_pack.ref import delta_apply_blocked_ref, delta_pack_blocked_ref
from repro.kernels.delta_pack.ops import apply_delta, pack_delta
from repro.kernels.dirty_diff.kernel import dirty_diff_blocked
from repro.kernels.dirty_diff.ref import dirty_diff_blocked_ref
from repro.kernels.dirty_diff.ops import dirty_blocks
from repro.kernels.popcnt_checksum.kernel import popcnt_blocked
from repro.kernels.popcnt_checksum.ref import popcnt_blocked_ref
from repro.kernels.popcnt_checksum.ops import popcount_blocks, popcount_checksum
from repro.kernels.delta_pack.ops import pack_dirty
from repro.kernels.flush_pack import compact_index, flush_pack

DTYPES = [jnp.float32, jnp.bfloat16, jnp.int8, jnp.uint32]


def rand(rng, shape, dtype):
    x = rng.standard_normal(shape).astype(np.float32)
    if dtype == jnp.uint32:
        return jnp.asarray((x * 1e6).view(np.uint32).reshape(shape))
    if dtype == jnp.int8:
        return jnp.asarray((x * 10).astype(np.int8))
    return jnp.asarray(x).astype(dtype)


# ------------------------------------------------------------- common layout

# --------------------------------------------------------------- dirty_diff

@pytest.mark.parametrize("dtype", DTYPES)
def test_dirty_blocks_op_dtypes(dtype):
    rng = np.random.default_rng(0)
    x = rand(rng, (3000,), dtype)
    y = np.asarray(x).copy()
    y[1234] = np.asarray(rand(rng, (1,), dtype))[0]
    flags_ref = dirty_blocks(x, jnp.asarray(y), impl="ref")
    flags_pal = dirty_blocks(x, jnp.asarray(y), impl="pallas")
    np.testing.assert_array_equal(np.asarray(flags_ref), np.asarray(flags_pal))


def test_dirty_blocks_identical_is_clean():
    x = jnp.arange(10_000, dtype=jnp.float32)
    assert int(dirty_blocks(x, x, impl="pallas").sum()) == 0


# ----------------------------------------------------------- popcnt_checksum

@pytest.mark.parametrize("dtype", DTYPES)
def test_popcount_checksum_properties(dtype):
    rng = np.random.default_rng(1)
    x = rand(rng, (2048,), dtype)
    c_ref = int(popcount_checksum(x, impl="ref"))
    c_pal = int(popcount_checksum(x, impl="pallas"))
    assert c_ref == c_pal
    assert c_ref != 0, "checksum of written data must be nonzero (cnt==0 = never written)"
    # zero buffer => checksum exactly 1 (popcount 0 + 1)
    assert int(popcount_checksum(jnp.zeros(512, dtype), impl="pallas")) == 1
    # dropping a block changes the checksum (Zero-log validity argument)
    y = np.asarray(as_blocks(x)[0]).copy()
    if np.unpackbits(y[0].view(np.uint8) if y.dtype == np.uint8 else y[0].view(np.uint8)).sum() > 0:
        y[0] = 0
        c_dropped = int(popcount_checksum(jnp.asarray(y).reshape(-1), impl="ref"))
        assert c_dropped != c_ref


# ---------------------------------------------------------------- delta pack

@pytest.mark.parametrize("dtype", DTYPES)
def test_delta_roundtrip_restores_buffer(dtype):
    """pack(cur) applied onto snap reproduces cur exactly — the µLog replay
    invariant the checkpoint layer relies on."""
    rng = np.random.default_rng(2)
    snap = rand(rng, (9000,), dtype)
    cur = np.asarray(snap).copy()
    dirty_positions = [0, 4097, 8000]
    for p in dirty_positions:
        cur[p] = np.asarray(rand(rng, (1,), dtype))[0]
    cur = jnp.asarray(cur)
    for impl in ("ref", "pallas"):
        flags = dirty_blocks(cur, snap, impl=impl)
        idx = jnp.asarray(np.flatnonzero(np.asarray(flags)).astype(np.int32))
        delta = pack_delta(cur, idx, impl=impl)
        restored = apply_delta(snap, delta, idx, impl=impl)
        np.testing.assert_array_equal(np.asarray(restored), np.asarray(cur))


def test_apply_delta_preserves_clean_blocks():
    rng = np.random.default_rng(3)
    base = rand(rng, (64, 8, LANES), jnp.float32)
    upd = rand(rng, (2, 8, LANES), jnp.float32)
    idx = jnp.asarray([5, 60], dtype=jnp.int32)
    out = delta_apply_blocked(base, upd, idx, interpret=True)
    out_np, base_np = np.asarray(out), np.asarray(base)
    clean = [b for b in range(64) if b not in (5, 60)]
    np.testing.assert_array_equal(out_np[clean], base_np[clean])
    np.testing.assert_array_equal(out_np[[5, 60]], np.asarray(upd))


# ----------------------------------------------------------- flush_scan

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
def test_flush_scan_consistent_with_separate_kernels(dtype):
    """Fused scan == dirty_diff + popcount_blocks composed (any dtype)."""
    from repro.kernels.flush_scan import flush_scan
    rng = np.random.default_rng(5)
    snap = rand(rng, (5000,), dtype)
    cur = np.asarray(snap).copy()
    cur[123] = np.asarray(rand(rng, (1,), dtype))[0]
    cur = jnp.asarray(cur)
    d, c = flush_scan(cur, snap, impl="pallas")
    d2 = dirty_blocks(cur, snap, impl="ref")
    c2 = popcount_blocks(cur, impl="ref")
    np.testing.assert_array_equal(np.asarray(d), np.asarray(d2))
    np.testing.assert_array_equal(np.asarray(c), np.asarray(c2))


# ----------------------------------------------------------- flush_pack

def _dirtied(rng, snap, positions):
    """Copy of ``snap`` with new random values at ``positions``."""
    cur = np.asarray(snap).copy()
    for p in positions:
        cur[p] = np.asarray(rand(rng, (1,), snap.dtype))[0]
    return jnp.asarray(cur)


def _assert_flush_pack_equal(a, b):
    assert a.total == b.total
    for f in ("flags", "counts", "offsets", "packed", "index"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)), err_msg=f)


@pytest.mark.parametrize("dtype", DTYPES)
def test_flush_pack_ref_vs_pallas_dtypes(dtype):
    """The fused Pallas kernel (interpret mode) matches the jnp oracle on
    every FlushPack field, for every checkpointable dtype."""
    rng = np.random.default_rng(7)
    snap = rand(rng, (9000,), dtype)
    cur = _dirtied(rng, snap, [0, 4097, 8000])
    _assert_flush_pack_equal(flush_pack(cur, snap, impl="pallas"),
                             flush_pack(cur, snap, impl="ref"))


@pytest.mark.parametrize("block_bytes", [4096, 8192, 16384])
@pytest.mark.parametrize("n", [4096, 5000, 13000])
def test_flush_pack_block_sizes_and_ragged_tails(block_bytes, n):
    """Parity across block sizes and buffer lengths that are not block
    (or grid-tile) multiples — the zero-padded tail must never read as
    dirty or perturb the prefix-sum offsets."""
    rng = np.random.default_rng(block_bytes + n)
    snap = rand(rng, (n,), jnp.float32)
    cur = _dirtied(rng, snap, [1, n // 2, n - 1])
    fp_pal = flush_pack(cur, snap, block_bytes=block_bytes, impl="pallas")
    fp_ref = flush_pack(cur, snap, block_bytes=block_bytes, impl="ref")
    _assert_flush_pack_equal(fp_pal, fp_ref)
    nblocks = -(-n * 4 // block_bytes)
    assert fp_pal.flags.shape[0] == nblocks
    assert 1 <= fp_pal.total <= 3
    # offsets are the exclusive prefix sum of the flags
    f = np.asarray(fp_pal.flags)
    np.testing.assert_array_equal(np.asarray(fp_pal.offsets),
                                  np.cumsum(f) - f)


@pytest.mark.parametrize("impl", ["ref", "pallas", "fused"])
def test_flush_pack_all_clean_and_all_dirty(impl):
    """The two extremes: identical buffers pack nothing (flags, packed
    and index all zero); fully-rewritten buffers pack every block in
    ascending order, so ``packed`` is just the blocked live buffer."""
    rng = np.random.default_rng(11)
    snap = rand(rng, (6000,), jnp.float32)
    clean = flush_pack(snap, snap, impl=impl)
    assert clean.total == 0
    assert int(np.asarray(clean.flags).sum()) == 0
    assert not np.asarray(clean.packed).any()
    assert not np.asarray(clean.index).any()

    cur = rand(rng, (6000,), jnp.float32)   # independent draw: all blocks differ
    full = flush_pack(cur, snap, impl=impl)
    nblocks = full.flags.shape[0]
    assert full.total == nblocks
    np.testing.assert_array_equal(np.asarray(full.flags), np.ones(nblocks))
    np.testing.assert_array_equal(np.asarray(full.index),
                                  np.arange(nblocks, dtype=np.int32))
    np.testing.assert_array_equal(np.asarray(full.packed),
                                  np.asarray(as_blocks(cur)[0]))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
def test_flush_pack_matches_staged_oracles(dtype):
    """One fused pass == the staged chain composed: dirty_diff flags,
    popcnt checksums, flatnonzero compaction, delta_pack gather."""
    rng = np.random.default_rng(13)
    snap = rand(rng, (7000,), dtype)
    cur = _dirtied(rng, snap, [5, 2048, 6999])
    fp = flush_pack(cur, snap, impl="pallas")
    flags = dirty_blocks(cur, snap, impl="ref")
    counts = popcount_blocks(cur, impl="ref")
    idx = np.flatnonzero(np.asarray(flags)).astype(np.int32)
    delta = pack_delta(cur, jnp.asarray(idx), impl="ref")
    np.testing.assert_array_equal(np.asarray(fp.flags), np.asarray(flags))
    np.testing.assert_array_equal(np.asarray(fp.counts), np.asarray(counts))
    assert fp.total == idx.size
    np.testing.assert_array_equal(np.asarray(fp.index[: fp.total]), idx)
    np.testing.assert_array_equal(np.asarray(fp.packed[: fp.total]),
                                  np.asarray(delta))
    # ...and the packed delta replays: apply onto snap reproduces cur
    restored = apply_delta(snap, fp.packed[: fp.total],
                           fp.index[: fp.total], impl="ref")
    np.testing.assert_array_equal(np.asarray(restored), np.asarray(cur))


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_dirty_is_a_byte_difference(impl):
    """-0.0 == 0.0 as floats but not as bytes: a checkpoint that skipped
    the block would restore the wrong bytes. Kernel and oracle agree."""
    snap = jnp.zeros(4096, jnp.float32)
    cur = snap.at[1500].set(-0.0)
    want = [0, 1, 0, 0]
    fp = flush_pack(cur, snap, impl=impl)
    assert fp.total == 1
    np.testing.assert_array_equal(np.asarray(fp.flags), want)
    np.testing.assert_array_equal(
        np.asarray(dirty_blocks(cur, snap, impl=impl)), want)


def test_resolve_impl_names_what_runs():
    """Off the TPU "auto" is the oracle and a request for the kernel is
    the interpreter; nothing silently changes what a report records."""
    from repro.kernels.common import resolve_impl
    on_tpu = jax.default_backend() == "tpu"
    kernel = "pallas" if on_tpu else "interpret"
    assert resolve_impl("auto") == ("pallas" if on_tpu else "ref")
    assert resolve_impl("pallas") == resolve_impl("fused") == kernel
    assert resolve_impl("interpret") == "interpret"
    assert resolve_impl("ref") == "ref"
    with pytest.raises(ValueError):
        resolve_impl("staged")


def test_compact_index_matches_flatnonzero():
    """On-device prefix-sum compaction == np.flatnonzero, including the
    empty, full, and single-flag patterns."""
    for pattern in ([0] * 16, [1] * 16, [0] * 15 + [1], [1] + [0] * 15,
                    [0, 1, 1, 0, 1, 0, 0, 1], [1, 0] * 8):
        flags = jnp.asarray(pattern, dtype=jnp.int32)
        index, total = compact_index(flags)
        k = int(total)
        want = np.flatnonzero(np.asarray(pattern))
        assert k == want.size
        np.testing.assert_array_equal(np.asarray(index[:k]), want)


# ----------------------------------------------------------- apply_unpack

def _unpack_case(rng, n, dtype, k):
    """A restore-shaped case: ``k`` packed blocks scattered over an
    ``n``-element base, plus their true per-block popcounts."""
    from repro.kernels.apply_unpack import block_popcounts
    base = rand(rng, (n,), dtype)
    nblocks = as_blocks(base)[0].shape[0]
    idx = rng.choice(nblocks, size=min(k, nblocks), replace=False)
    idx = np.sort(idx).astype(np.int32)
    rows = block_rows(dtype)
    packed = rand(rng, (idx.size, rows, LANES), dtype)
    expected = np.asarray(block_popcounts(packed))
    return base, packed, jnp.asarray(idx), jnp.asarray(expected)


@pytest.mark.parametrize("dtype", DTYPES)
def test_apply_unpack_ref_vs_pallas_dtypes(dtype):
    from repro.kernels.apply_unpack import apply_unpack
    rng = np.random.default_rng(19)
    base, packed, idx, exp = _unpack_case(rng, 9000, dtype, 3)
    res_ref = apply_unpack(base, packed, idx, exp, impl="ref")
    res_pal = apply_unpack(base, packed, idx, exp, impl="pallas")
    assert res_ref.nbad == 0 and res_pal.nbad == 0
    np.testing.assert_array_equal(np.asarray(res_pal.out),
                                  np.asarray(res_ref.out))
    np.testing.assert_array_equal(np.asarray(res_pal.counts),
                                  np.asarray(res_ref.counts))
    np.testing.assert_array_equal(np.asarray(res_pal.ok),
                                  np.asarray(res_ref.ok))


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_apply_unpack_inverts_flush_pack(impl):
    """The restore kernel is flush_pack's inverse: scatter the packed
    dirty blocks onto the snapshot and the live buffer reappears,
    checksum-verified against flush_pack's own per-block counts."""
    from repro.kernels.apply_unpack import apply_unpack
    rng = np.random.default_rng(23)
    snap = rand(rng, (9000,), jnp.float32)
    cur = _dirtied(rng, snap, [0, 4097, 8000])
    fp = flush_pack(cur, snap, impl="ref")
    k = fp.total
    exp = np.asarray(fp.counts)[np.asarray(fp.index[:k])]
    res = apply_unpack(snap, fp.packed[:k], fp.index[:k],
                       jnp.asarray(exp), impl=impl)
    assert res.nbad == 0
    np.testing.assert_array_equal(np.asarray(res.out), np.asarray(cur))


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_apply_unpack_detects_corruption(impl):
    """A wrong expected count flags exactly the corrupted block; the
    scatter still lands (the caller discards the whole result)."""
    from repro.kernels.apply_unpack import apply_unpack
    rng = np.random.default_rng(29)
    base, packed, idx, exp = _unpack_case(rng, 8192, jnp.float32, 4)
    bad = jnp.asarray(np.asarray(exp) + np.array([0, 1, 0, 0], np.uint32))
    res = apply_unpack(base, packed, idx, bad, impl=impl)
    assert res.nbad == 1
    np.testing.assert_array_equal(np.asarray(res.ok),
                                  np.array([1, 0, 1, 1], np.int32))


def test_apply_unpack_clean_blocks_preserved():
    """Blocks outside the scatter index keep the base bytes exactly."""
    from repro.kernels.apply_unpack import apply_unpack
    rng = np.random.default_rng(31)
    base, packed, idx, exp = _unpack_case(rng, 9000, jnp.float32, 2)
    res = apply_unpack(base, packed, idx, exp, impl="pallas")
    out_b = np.asarray(as_blocks(jnp.asarray(res.out))[0])
    base_b = np.asarray(as_blocks(jnp.asarray(base))[0])
    touched = set(int(i) for i in np.asarray(idx))
    clean = [b for b in range(base_b.shape[0]) if b not in touched]
    np.testing.assert_array_equal(out_b[clean], base_b[clean])


def test_apply_unpack_empty_and_ragged():
    """k == 0 is a no-op; a base whose length is not a block multiple
    round-trips through the padded blocked form unchanged."""
    from repro.kernels.apply_unpack import apply_unpack
    rng = np.random.default_rng(37)
    base = rand(rng, (5000,), jnp.float32)      # ragged: 5000 * 4 % 4096 != 0
    empty = apply_unpack(base, jnp.zeros((0,), jnp.float32),
                         jnp.zeros((0,), jnp.int32),
                         jnp.zeros((0,), jnp.uint32))
    assert empty.nbad == 0
    np.testing.assert_array_equal(np.asarray(empty.out), np.asarray(base))
    b2, packed, idx, exp = _unpack_case(rng, 5000, jnp.float32, 2)
    res = apply_unpack(b2, packed, idx, exp, impl="pallas")
    assert res.out.shape == b2.shape and res.nbad == 0


def test_pack_dirty_shares_compaction():
    """delta_pack's flag-driven entry point (the staged fallback) uses
    the same on-device compaction — no host flatnonzero — and agrees
    with the explicit-index pack_delta."""
    rng = np.random.default_rng(17)
    snap = rand(rng, (8192,), jnp.float32)
    cur = _dirtied(rng, snap, [100, 3000, 8000])
    flags = dirty_blocks(cur, snap, impl="ref")
    delta, idx, k = pack_dirty(cur, flags, impl="ref")
    want_idx = np.flatnonzero(np.asarray(flags)).astype(np.int32)
    assert k == want_idx.size
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_array_equal(
        np.asarray(delta),
        np.asarray(pack_delta(cur, jnp.asarray(want_idx), impl="ref")))
