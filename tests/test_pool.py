"""Pool/handle API tests: durable directory round-trip, crash-safe region
allocation, LogHandle recovery parity with the legacy classes, and the
PersistentKV-on-pool YCSB smoke.

The hypothesis eviction-subset property for mid-allocation crashes lives in
``test_pool_props.py`` (skipped without the ``test`` extra).
"""

import numpy as np
import pytest

from repro.core import KVConfig, LOG_TECHNIQUES, LogConfig, PMem, PersistentKV
from repro.core.directory import KIND_LOG, KIND_RAW, RegionDirectory
from repro.pool import Pool

SIZE = 1 << 20


# ------------------------------------------------------------ directory

def test_directory_roundtrip_in_memory():
    pool = Pool.create(None, SIZE)
    log = pool.log("wal", capacity=1 << 16, technique="zero")
    pages = pool.pages("heap", npages=4, page_size=1024)
    raw = pool.raw("root", nbytes=128)
    log.append(b"alpha")
    log.append(b"beta")
    pages.flush(1, np.full(1024, 7, dtype=np.uint8))
    raw.store(0, b"rootrec", streaming=True)
    raw.persist(0, 7)

    before = {n: (r.kind, r.base, r.length, r.meta)
              for n, r in pool.regions().items()}
    pool.pmem.crash(evict=lambda li: False)   # drop every in-flight line

    pool2 = Pool.open(pmem=pool.pmem)
    after = {n: (r.kind, r.base, r.length, r.meta)
             for n, r in pool2.regions().items()}
    assert after == before
    log2 = pool2.log("wal")
    assert log2.recovered.entries == [b"alpha", b"beta"]
    assert (pool2.pages("heap").read_page(1) == 7).all()
    assert bytes(pool2.raw("root").load(0, 7)) == b"rootrec"


def test_directory_roundtrip_file_backed(tmp_path):
    path = str(tmp_path / "pool.pmem")
    pool = Pool.create(path, SIZE)
    log = pool.log("wal", capacity=1 << 14, technique="classic",
                   cfg=LogConfig(pad_to_line=True))
    log.append(b"persisted")
    pool.fsync()
    regions = {n: (r.base, r.length) for n, r in pool.regions().items()}

    pool2 = Pool.open(path)                    # geometry from the superblock
    assert pool2.geometry == pool.geometry
    assert {n: (r.base, r.length) for n, r in pool2.regions().items()} == regions
    log2 = pool2.log("wal")                    # technique from the directory
    assert log2.technique == "classic"
    assert log2.recovered.entries == [b"persisted"]
    log2.append(b"more")
    assert log2.recover().entries == [b"persisted", b"more"]


def test_open_unformatted_region_fails(tmp_path):
    pm = PMem(SIZE)
    with pytest.raises(ValueError):
        Pool.open(pmem=pm)
    with pytest.raises(FileNotFoundError):
        Pool.open("/nonexistent/pool.pmem")
    # an existing file with a bad superblock is corruption, NOT absence —
    # a try/except FileNotFoundError → create() fallback must not fire
    bad = str(tmp_path / "bad.pmem")
    open(bad, "wb").write(b"\x12" * 4096)
    with pytest.raises(ValueError, match="torn superblock"):
        Pool.open(bad)


def test_attach_refuses_legacy_durable_data():
    """Formatting over a pre-pool durable image would zero its head —
    attach must refuse instead (the shim path is for zeroed regions)."""
    pm = PMem(SIZE)
    pm.store(0, b"legacy log entry data", streaming=True)
    pm.sfence()
    with pytest.raises(ValueError, match="refusing to format"):
        Pool.attach(pm)


def test_legacy_wal_fresh_constructor_resets_existing_region():
    """Legacy recover=False on an existing region means 'fresh WAL', not
    'silently resume the previous generation'."""
    from repro.persistence.wal import StepRecord, TrainWAL

    pm = PMem(TrainWAL.capacity_for(100))
    pm.memset_zero()
    wal = TrainWAL(pm, 0, pm.size)
    wal.commit_step(StepRecord(1, 0, (0, 0), 0.5, 0.1, 1.0))
    fresh = TrainWAL(pm, 0, pm.size)             # recover=False
    assert fresh.records == [] and fresh.last is None
    recovered = TrainWAL(pm, 0, pm.size, recover=True)
    assert recovered.records == []               # old generation gone


def test_open_never_destroys_data(tmp_path):
    """Read paths must refuse, never truncate or reformat."""
    path = str(tmp_path / "pool.pmem")
    pool = Pool.create(path, SIZE)
    pool.log("wal", capacity=4096).append(b"precious")
    pool.fsync()

    # truncated file: refuse to open (PMem would otherwise recreate it)
    with open(path, "r+b") as f:
        f.truncate(SIZE // 2)
    with pytest.raises(ValueError, match="refusing"):
        Pool.open(path)
    assert open(path, "rb").read(8) != b"\x00" * 8   # bytes untouched

    # a non-pool file is someone's data: open_or_create must not format it
    other = str(tmp_path / "notapool.bin")
    open(other, "wb").write(b"user data, not a pool")
    with pytest.raises(ValueError, match="refusing"):
        Pool.open_or_create(other, SIZE)
    assert open(other, "rb").read() == b"user data, not a pool"


def test_open_rejects_capacity_larger_than_region():
    pool = Pool.create(None, SIZE)
    pool.log("wal", capacity=4096)
    with pytest.raises(ValueError, match="cannot grow"):
        pool.log("wal", capacity=1 << 16)
    # asking for less (or nothing) is fine
    assert pool.log("wal", capacity=1024).capacity == 4096


def test_wal_open_uses_stored_technique():
    """Reopening a classic/header WAL without naming the technique must
    work — the directory record decides (regression: the open path used
    to force the zero default and raise)."""
    from repro.persistence.wal import StepRecord

    pool = Pool.create(None, SIZE)
    wal = pool.wal("steps", capacity_steps=50, technique="classic")
    wal.commit_step(StepRecord(1, 0, (0, 0), 0.5, 0.1, 1.0))
    pool.pmem.crash(evict=lambda li: False)
    wal2 = Pool.open(pmem=pool.pmem).wal("steps")     # no technique arg
    assert wal2.technique == "classic"
    assert wal2.last.step == 1
    # a bigger capacity request on reopen is a config error, not a silent
    # undersized region
    with pytest.raises(ValueError, match="cannot grow"):
        Pool.open(pmem=pool.pmem).wal("steps", capacity_steps=10_000)


def test_allocation_errors():
    pool = Pool.create(None, 1 << 16, max_regions=2)
    pool.raw("a", nbytes=256)
    with pytest.raises(ValueError):
        pool.raw("a", nbytes=512)            # wrong: grows an existing region
    with pytest.raises(ValueError):
        pool.directory.allocate("a", KIND_RAW, 256)   # duplicate name
    with pytest.raises(RuntimeError):
        pool.raw("too-big", nbytes=1 << 20)  # exceeds the pool
    pool.raw("b", nbytes=256)
    with pytest.raises(RuntimeError):
        pool.raw("c", nbytes=256)            # directory full (max_regions=2)


def test_handle_conflicts_with_directory_record():
    pool = Pool.create(None, SIZE)
    pool.log("l", capacity=4096, technique="zero")
    pool.pages("p", npages=2, page_size=1024)
    with pytest.raises(ValueError):
        pool.log("l", technique="classic")
    with pytest.raises(TypeError):
        pool.pages("l")                      # kind mismatch
    with pytest.raises(ValueError):
        pool.pages("p", npages=3)


# ------------------------------------------------- crash-safe allocation

def _committed_log_image(pool):
    rec = pool.regions()["a"]
    return pool.pmem.durable_view()[rec.base : rec.base + rec.length].copy()


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("prob", [0.0, 0.5, 1.0])
def test_crash_mid_allocation_preserves_existing(seed, prob):
    """A crash between *place* and *commit* of a new region leaves every
    previously committed region bit-exact and the new name absent."""
    pool = Pool.create(None, SIZE)
    log = pool.log("a", capacity=1 << 14, technique="zero")
    for i in range(8):
        log.append(bytes([i + 1]) * 33)
    img_a = _committed_log_image(pool)

    d = pool.directory
    rec, slot = d._place("b", KIND_LOG, 1 << 14, (2, 1, 1, 0))
    d._initialize(rec)                        # zeroing done, entry NOT committed
    pool.pmem.crash(rng=np.random.default_rng(seed), evict_prob=prob)

    pool2 = Pool.open(pmem=pool.pmem)
    assert "b" not in pool2.regions()
    assert np.array_equal(_committed_log_image(pool2), img_a)
    rec2 = pool2.log("a").recover()
    assert rec2.entries == [bytes([i + 1]) * 33 for i in range(8)]
    # the claimed space is reusable after the crash
    log_b = pool2.log("b", capacity=1 << 14)
    log_b.append(b"fresh")
    assert log_b.recover().entries == [b"fresh"]


@pytest.mark.parametrize("seed", [0, 5, 9])
def test_crash_during_entry_commit_is_atomic(seed):
    """Crash with the entry line stored but not fenced: spontaneous
    eviction may or may not make it durable — either way region "a" is
    intact and "b" is either absent or a valid empty region."""
    pool = Pool.create(None, SIZE)
    log = pool.log("a", capacity=1 << 14, technique="zero")
    for i in range(5):
        log.append(bytes([i + 1]) * 20)
    img_a = _committed_log_image(pool)

    d = pool.directory
    rec, slot = d._place("b", KIND_LOG, 1 << 14, (2, 1, 1, 0))
    d._initialize(rec)
    # store the entry line but crash before the fence of _commit()
    import repro.core.directory as directory_mod
    entry = directory_mod._ENTRY.pack(b"b", rec.kind, rec.generation,
                                      rec.base, rec.length, *rec.meta)
    pool.pmem.store(d._entry_off(slot), entry, streaming=True)
    pool.pmem.crash(rng=np.random.default_rng(seed), evict_prob=0.5)

    pool2 = Pool.open(pmem=pool.pmem)
    assert np.array_equal(_committed_log_image(pool2), img_a)
    if "b" in pool2.regions():
        got = pool2.regions()["b"]
        assert (got.base, got.length) == (rec.base, rec.length)
        assert pool2.log("b").recovered.entries == []   # valid, empty


# --------------------------------------------- LogHandle recovery parity

@pytest.mark.parametrize("technique", ["classic", "header", "zero"])
@pytest.mark.parametrize("padded", [True, False])
def test_log_handle_parity_with_legacy_classes(technique, padded):
    """The unified LogHandle must behave exactly like the legacy class it
    wraps: same barrier count per append and identical recovery."""
    payloads = [bytes([i + 1]) * (5 + 11 * i) for i in range(9)]
    cfg = LogConfig(pad_to_line=padded)

    pool = Pool.create(None, SIZE)
    h = pool.log("log", capacity=1 << 15, technique=technique, cfg=cfg)
    before = pool.stats.barriers
    for p in payloads:
        h.append(p)
    cls = LOG_TECHNIQUES[technique]
    assert pool.stats.barriers - before == len(payloads) * cls.BARRIERS_PER_APPEND
    assert h.barriers_per_append == cls.BARRIERS_PER_APPEND

    pool.pmem.crash(evict=lambda li: False)
    h2 = Pool.open(pmem=pool.pmem).log("log")
    assert h2.recovered.entries == payloads
    assert h2.recovered.lsns == list(range(1, len(payloads) + 1))

    # cross-check: the legacy classmethod recovery at the region base sees
    # exactly what the handle reports
    rec = cls.recover(pool.pmem, h2.base, h2.length, h2.cfg)
    assert rec.entries == h2.recovered.entries
    assert rec.tail == h2.tail      # writer resumed exactly at the durable tail

    # and appends continue with correct LSNs after recovery
    h2.append(b"after-crash")
    assert h2.recover().entries == payloads + [b"after-crash"]


@pytest.mark.parametrize("technique", ["classic", "header", "zero"])
@pytest.mark.parametrize("case", ["clean", "torn_tail", "empty"])
def test_file_backed_log_reopen_recovers_entries_tail_and_lsn(
        tmp_path, technique, case):
    """Reopening a log from its pool file (recovery reads the durable
    image in place) finds the same entries, tail and next LSN the writer
    had: a torn last append — its first line durable, the rest lost —
    is not recovered, and an empty log reopens empty."""
    path = str(tmp_path / "log.pmem")
    pool = Pool.create(path, SIZE)
    h = pool.log("log", capacity=1 << 15, technique=technique)
    payloads = [] if case == "empty" else \
        [bytes([i + 1]) * (5 + 23 * i) for i in range(6)]
    for p in payloads:
        h.append(p)
    tail, next_lsn = h.tail, h.next_lsn
    if case == "torn_tail":
        pm = pool.pmem
        first_line = (h.base + tail) // pm.geometry.cache_line
        pm.sfence = lambda: None          # the append's fence never runs
        h.append(b"t" * 200)              # spans several cache lines
        del pm.sfence
        pm.crash(evict=lambda li: li == first_line)
        assert pm.durable_inplace(h.base + tail, 16).any()  # really torn
    pool.fsync()

    h2 = Pool.open(path).log("log")
    assert h2.recovered.entries == payloads
    assert h2.recovered.lsns == list(range(1, len(payloads) + 1))
    assert (h2.tail, h2.next_lsn) == (tail, next_lsn)
    assert (h2.recovered.tail, h2.recovered.next_lsn) == (tail, next_lsn)
    assert h2.pool.pmem.durable_copy_bytes < h2.length
    h2.append(b"after reopen")
    h2.pool.fsync()
    h3 = Pool.open(path).log("log")
    assert h3.recovered.entries == payloads + [b"after reopen"]
    assert h3.next_lsn == next_lsn + 1


def test_log_handle_reset_starts_new_generation():
    pool = Pool.create(None, SIZE)
    h = pool.log("log", capacity=1 << 14, technique="zero")
    h.append(b"old")
    h.reset()
    assert h.next_lsn == 1
    h.append(b"new")
    assert h.recover().entries == [b"new"]


def test_handle_stats_delta_view():
    pool = Pool.create(None, SIZE)
    h = pool.log("log", capacity=1 << 14, technique="zero")
    h.reset_stats()
    h.append(b"x" * 40)
    s = h.stats()
    assert s.barriers == 1
    assert s.nt_store_bytes > 0


# ------------------------------------------------------ KV-on-pool smoke

def test_kv_on_pool_ycsb_smoke():
    """YCSB-style 100%-write workload through pool.kv: puts survive auto
    checkpoints and an arbitrary-eviction crash."""
    cfg = KVConfig(npages=4, page_size=1024, value_size=64,
                   log_capacity=1 << 13, technique="zero")
    pool = Pool.create(None, PersistentKV.region_bytes(cfg))
    kv = pool.kv("store", cfg)
    rng = np.random.default_rng(42)
    expected = {}
    for i in range(300):                      # overflows the 8 KiB WAL
        k = int(rng.integers(0, cfg.nkeys))
        v = bytes([(i + j) % 256 for j in range(64)])
        kv.put(k, v)
        expected[k] = v
    pool.pmem.crash(rng=np.random.default_rng(0), evict_prob=0.5)

    kv2 = Pool.open(pmem=pool.pmem).kv("store", cfg)
    for k, v in expected.items():
        assert kv2.get(k) == v

    # no caller-visible raw offsets: all three engine regions are named
    names = set(Pool.open(pmem=pool.pmem).regions())
    assert {"store.root", "store.pages", "store.wal"} <= names


def test_kv_legacy_shim_still_works():
    """The old (pmem, cfg) constructor is a shim over Pool.attach."""
    cfg = KVConfig(npages=4, page_size=1024, value_size=64,
                   log_capacity=1 << 13)
    pm = PMem(PersistentKV.region_bytes(cfg))
    pm.memset_zero()
    kv = PersistentKV(pm, cfg)
    kv.put(3, bytes(range(64)))
    pm.crash(evict=lambda li: False)
    kv2 = PersistentKV.open(pm, cfg)
    assert kv2.get(3) == bytes(range(64))


# --------------------------------------------------------- TrainWAL/pool

def test_train_wal_on_pool_roundtrip():
    from repro.persistence.wal import StepRecord

    pool = Pool.create(None, SIZE)
    wal = pool.wal("steps", capacity_steps=100)
    for s in range(6):
        wal.commit_step(StepRecord(s + 1, s * 64, (s, s + 1), float(s), 0.1, 1.0))
    pool.pmem.crash(evict=lambda li: False)
    wal2 = Pool.open(pmem=pool.pmem).wal("steps")
    assert wal2.last.step == 6
    assert wal2.last.rng_key == (5, 6)
    assert wal2.barriers_per_step() == 1
