"""Unit tests for the PMem functional model (cache/WC semantics, crash)."""

import numpy as np
import pytest

from repro.core import FlushKind, PMem


def test_store_visible_but_not_durable():
    pm = PMem(4096)
    pm.store(0, b"hello")
    assert bytes(pm.load(0, 5)) == b"hello"
    assert bytes(pm.durable_view()[:5]) == b"\x00" * 5


def test_persist_makes_durable():
    pm = PMem(4096)
    pm.store(128, b"abc")
    pm.persist(128, 3)
    assert bytes(pm.durable_view()[128:131]) == b"abc"


def test_streaming_store_durable_only_after_sfence():
    pm = PMem(4096)
    pm.store(0, b"xyz", streaming=True)
    assert bytes(pm.durable_view()[:3]) == b"\x00" * 3
    pm.sfence()
    assert bytes(pm.durable_view()[:3]) == b"xyz"


def test_flush_stages_data_at_flush_time():
    """A store after flush but before sfence is NOT covered (§3.1)."""
    pm = PMem(4096)
    pm.store(0, b"A")
    pm.flush(0, 1)
    pm.store(0, b"B")        # dirty again, not staged
    pm.sfence()
    assert bytes(pm.durable_view()[:1]) == b"A"
    assert bytes(pm.load(0, 1)) == b"B"  # program order still sees B


def test_crash_drops_unflushed_lines():
    pm = PMem(4096)
    pm.store(0, b"keep")
    pm.persist(0, 4)
    pm.store(64, b"lost")
    img = pm.crash(evict=lambda li: False)
    assert bytes(img.durable[:4]) == b"keep"
    assert bytes(img.durable[64:68]) == b"\x00" * 4
    assert 1 in img.dropped_lines


def test_crash_may_evict_unflushed_lines():
    """Spontaneous eviction is legal: an unflushed store MAY survive."""
    pm = PMem(4096)
    pm.store(64, b"evicted")
    img = pm.crash(evict=lambda li: True)
    assert bytes(img.durable[64:71]) == b"evicted"


def test_barrier_counting():
    pm = PMem(4096)
    pm.sfence()                       # nothing pending: not a barrier
    assert pm.stats.barriers == 0
    pm.store(0, b"x")
    pm.persist(0, 1)
    assert pm.stats.barriers == 1
    pm.store(0, b"y", streaming=True)
    pm.sfence()
    assert pm.stats.barriers == 2


def test_write_combining_block_accounting():
    pm = PMem(4096)
    # 4 lines of one 256B block committed together -> 1 block write
    pm.store(0, bytes(256), streaming=True)
    pm.sfence()
    assert pm.stats.blocks_written == 1
    assert pm.stats.partial_block_writes == 0
    # a single line commits as a partial block write
    pm.store(1024, bytes(64), streaming=True)
    pm.sfence()
    assert pm.stats.blocks_written == 2
    assert pm.stats.partial_block_writes == 1


def test_same_line_flush_detection():
    pm = PMem(4096)
    for _ in range(4):
        pm.store(0, b"z")
        pm.persist(0, 1)
    assert pm.stats.same_line_flushes == 3


def test_file_backed_region(tmp_path):
    p = str(tmp_path / "region.pmem")
    pm = PMem(4096, path=p)
    pm.store(10, b"disk", streaming=True)
    pm.sfence()
    pm.fsync()
    pm2 = PMem(4096, path=p)
    assert bytes(pm2.load(10, 4)) == b"disk"


def test_bounds_checking():
    pm = PMem(128)
    with pytest.raises(ValueError):
        pm.store(120, b"123456789")
    with pytest.raises(ValueError):
        pm.load(-1, 4)


# ------------------------------------- file-backed: in-place durable reads

def _file_region(tmp_path, content=b""):
    """A 16 KiB file-backed region whose file already holds ``content``
    at offset 4096, as a pool file left by an earlier process would."""
    p = str(tmp_path / "region.pmem")
    pm = PMem(16384, path=p)
    if content:
        pm.store(4096, content, streaming=True)
        pm.sfence()
    pm.fsync()
    return p


def test_durable_inplace_is_read_only_and_follows_persist(tmp_path):
    pm = PMem(16384, path=_file_region(tmp_path))
    view = pm.durable_inplace()
    with pytest.raises(ValueError):
        view[0] = 1
    with pytest.raises(ValueError):
        pm.durable_inplace(128, 64)[0] = 1
    pm.store(128, b"abc")
    pm.persist(128, 3)
    # the view aliases the image: the later persist shows through it
    assert bytes(view[128:131]) == b"abc"
    assert bytes(pm.durable_inplace(128, 3)) == b"abc"
    assert pm.durable_copy_bytes == 0
    with pytest.raises(ValueError):
        pm.durable_inplace(16380, 8)


def test_durable_view_stays_a_snapshot(tmp_path):
    pm = PMem(16384, path=_file_region(tmp_path))
    snap = pm.durable_view()
    pm.store(0, b"new", streaming=True)
    pm.sfence()
    assert bytes(snap[:3]) == b"\x00" * 3
    assert bytes(pm.durable_view()[:3]) == b"new"
    snap[0] = 9                                  # a snapshot is the caller's
    assert bytes(pm.durable_inplace(0, 1)) == b"n"
    sl = pm.durable_slice(0, 3)
    pm.store(0, b"xyz", streaming=True)
    pm.sfence()
    assert bytes(sl) == b"new"
    assert pm.durable_copy_bytes == 2 * pm.size + 3


def test_open_reads_the_file_without_copying_it(tmp_path):
    p = _file_region(tmp_path, b"from the file")
    pm = PMem(16384, path=p)
    assert pm.durable_copy_bytes == 0
    assert bytes(pm.load(4096, 13)) == b"from the file"
    assert bytes(pm.durable_inplace(4096, 13)) == b"from the file"


def test_unpersisted_store_is_logical_only_and_commit_reaches_both(tmp_path):
    pm = PMem(16384, path=_file_region(tmp_path, b"old"))
    pm.store(4096 + 64, b"dirty")                 # same OS page as "old"
    assert bytes(pm.load(4096 + 64, 5)) == b"dirty"
    assert bytes(pm.durable_inplace(4096 + 64, 5)) == b"\x00" * 5
    pm.store(4096 + 128, b"committed", streaming=True)
    pm.sfence()
    pm.store(8192, b"other page", streaming=True)
    pm.sfence()
    for off, data in ((4096, b"old"), (4096 + 64, b"dirty"),
                      (4096 + 128, b"committed"), (8192, b"other page")):
        assert bytes(pm.load(off, len(data))) == data
    for off, data in ((4096, b"old"), (4096 + 128, b"committed"),
                      (8192, b"other page")):
        assert bytes(pm.durable_inplace(off, len(data))) == data
    # a reopen sees exactly the durable bytes
    pm2 = PMem(16384, path=pm.path)
    assert np.array_equal(pm2.load(0, pm2.size), pm.durable_inplace())


def test_crash_and_memset_leave_logical_equal_to_durable(tmp_path):
    pm = PMem(16384, path=_file_region(tmp_path, b"kept"))
    pm.store(4096 + 64, b"dropped")              # dirty, never flushed
    pm.store(12288, b"evicted")                  # dirty, survives the crash
    img = pm.crash(evict=lambda li: li == 12288 // pm.geometry.cache_line)
    assert np.array_equal(pm.load(0, pm.size), img.durable)
    assert np.array_equal(pm._logical, pm.durable_inplace())
    assert bytes(pm.load(4096, 4)) == b"kept"
    assert bytes(pm.load(4096 + 64, 7)) == b"\x00" * 7
    assert bytes(pm.load(12288, 7)) == b"evicted"
    pm.store(0, b"before zeroing")
    pm.memset_zero()
    assert not pm._logical.any()
    assert np.array_equal(pm._logical, pm.durable_inplace())
    pm.store(100, b"after", streaming=True)
    pm.sfence()
    assert bytes(pm.load(100, 5)) == bytes(pm.durable_inplace(100, 5)) \
        == b"after"
