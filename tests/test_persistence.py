"""Persistence-layer tests: checkpoint save/restore/delta, WAL, async
flusher overlap, elastic re-sharding, crash consistency of the manifest."""

import numpy as np
import pytest

from repro.core import PMem
from repro.persistence import (
    AsyncFlusher,
    CheckpointConfig,
    CheckpointManager,
    StepRecord,
    TrainWAL,
    assemble_global,
    reshard_state,
)
from repro.persistence.restore import slice_state

# 128 KiB pages (32 × 4 KiB dirty-tracking lines, 8 × 16 KiB write blocks):
# large enough that the hybrid policy has a real µLog-vs-CoW tradeoff.
CFG = CheckpointConfig(page_size=128 * 1024, manifest_capacity=1 << 16)


def make_state(seed=0, scale=1.0):
    rng = np.random.default_rng(seed)
    return {
        "w_embed": (rng.standard_normal((512, 64)) * scale).astype(np.float32),
        "w_out": (rng.standard_normal((64, 512)) * scale).astype(np.float32),
        "step_count": np.array([7], dtype=np.int64),
    }


# ------------------------------------------------------------- checkpoint

def test_save_restore_roundtrip(tmp_path):
    m = CheckpointManager(str(tmp_path / "s0.pmem"), CFG)
    state = make_state(0)
    m.save(100, state)
    m2 = CheckpointManager(str(tmp_path / "s0.pmem"), CFG)
    step, got = m2.restore()
    assert step == 100
    for k in state:
        np.testing.assert_array_equal(got[k], state[k])


def test_multiple_saves_restore_latest(tmp_path):
    m = CheckpointManager(str(tmp_path / "s0.pmem"), CFG)
    for i, seed in enumerate([1, 2, 3]):
        m.save(i, make_state(seed))
    step, got = CheckpointManager(str(tmp_path / "s0.pmem"), CFG).restore()
    assert step == 2
    np.testing.assert_array_equal(got["w_embed"], make_state(3)["w_embed"])


def test_delta_save_uses_mulog_for_sparse_change(tmp_path):
    """Shadow-slot deltas: a µLog delta must cover the change since v-1
    (union of the last two saves' dirty sets), so the FIRST sparse save
    after a full rewrite still takes CoW; the SECOND sparse save in a row
    takes the µLog path."""
    m = CheckpointManager(str(tmp_path / "s0.pmem"), CFG)
    r0 = m.save(0, make_state(0))
    assert r0.pages_cow == r0.pages_total  # first save: all CoW
    m.save(1, make_state(1))               # full rewrite: CoW, shadows set
    state2 = {k: v.copy() for k, v in make_state(1).items()}
    state2["w_embed"][0, 0] += 1.0
    r2 = m.save(2, state2)                 # sparse, but union w/ full dirt
    assert r2.pages_clean >= r2.pages_total - 2
    assert r2.pages_cow >= 1
    state3 = {k: v.copy() for k, v in state2.items()}
    state3["w_embed"][0, 1] += 1.0
    r3 = m.save(3, state3)                 # sparse twice in a row → µLog
    assert r3.pages_mulog >= 1, "sparse change should take the µLog path"
    assert r3.blocks_written < r2.blocks_written or r3.pages_mulog >= 1
    # restore gives exactly state3
    step, got = CheckpointManager(str(tmp_path / "s0.pmem"), CFG).restore()
    assert step == 3
    for k in state3:
        np.testing.assert_array_equal(got[k], state3[k])


def test_clean_pages_are_skipped(tmp_path):
    m = CheckpointManager(str(tmp_path / "s0.pmem"), CFG)
    state = make_state(0)
    m.save(0, state)
    r = m.save(1, state)          # identical state
    assert r.pages_clean == r.pages_total
    assert r.pages_cow == 0 and r.pages_mulog == 0
    step, got = CheckpointManager(str(tmp_path / "s0.pmem"), CFG).restore()
    assert step == 1
    np.testing.assert_array_equal(got["w_embed"], state["w_embed"])


def test_restore_then_continue_saving(tmp_path):
    m = CheckpointManager(str(tmp_path / "s0.pmem"), CFG)
    m.save(0, make_state(0))
    m.save(1, make_state(1))
    m2 = CheckpointManager(str(tmp_path / "s0.pmem"), CFG)
    step, got = m2.restore()
    assert step == 1
    m2.save(2, make_state(2))
    step3, got3 = CheckpointManager(str(tmp_path / "s0.pmem"), CFG).restore()
    assert step3 == 2
    np.testing.assert_array_equal(got3["w_out"], make_state(2)["w_out"])


def test_manifest_commit_is_single_barrier(tmp_path):
    """The checkpoint commit point (manifest append) = ONE barrier (Zero)."""
    m = CheckpointManager(str(tmp_path / "s0.pmem"), CFG)
    state = make_state(0)
    m.save(0, state)
    before = m.pmem.stats.barriers
    m.manifest.append(b'{"probe": true}')
    assert m.pmem.stats.barriers - before == 1


def test_crash_before_manifest_commit_restores_previous(tmp_path):
    """Pages of save N+1 flushed, but manifest not committed → restore N.
    This is the shadow-slot guarantee: save N's pages are never touched
    while manifest N is the last committed one."""
    m = CheckpointManager(str(tmp_path / "s0.pmem"), CFG)
    s0, s1 = make_state(10), make_state(11)
    m.save(0, s0)
    # replicate save(1) page flushing WITHOUT the manifest append
    for name in sorted(s1):
        per_page, buf, _counts = m._dirty_lines_per_page(name, s1[name])
        pages = m._leaf_pages[name]
        from repro.persistence.checkpoint import SaveReport
        rep = SaveReport(step=1)
        for i, pid in enumerate(pages):
            lo = i * CFG.page_size
            page = np.zeros(CFG.page_size, dtype=np.uint8)
            chunk = buf[lo : lo + CFG.page_size]
            page[: chunk.size] = chunk
            dirty = set(range(CFG.blocks_per_page)) if per_page is None else per_page.get(i, set())
            if dirty or per_page is None:
                m._flush_page(pid, page, sorted(dirty), per_page is None, rep)
    m.pmem.fsync()
    # crash: drop every in-flight line (nothing was mid-flush anyway)
    m.pmem.crash(evict=lambda li: False)
    step, got = CheckpointManager(str(tmp_path / "s0.pmem"), CFG).restore()
    assert step == 0
    for k in s0:
        np.testing.assert_array_equal(got[k], s0[k])


@pytest.mark.parametrize("mulog_crash", [False, True])
def test_file_backed_restore_reads_the_pool_in_place(tmp_path, mulog_crash):
    """A fresh manager restores a file-backed checkpoint byte for byte
    while copying less than one pool's worth out of the durable image:
    the logs, slot headers, µlogs and pages are read where they lie. With
    a valid µlog left by a crash between its write and its apply, the
    adopt's page-store open still replays it through the in-place read."""
    import os
    from repro.core.pageflush import _SLOT_HDR
    path = str(tmp_path / "s0.pmem")
    m = CheckpointManager(path, CFG)
    m.save(0, make_state(0))
    m.save(1, make_state(1))
    state = {k: v.copy() for k, v in make_state(1).items()}
    state["w_embed"][0, 0] += 1.0
    m.save(2, state)
    if mulog_crash:
        # the µLog steps of a delta onto page 0's shadow slot — invalidate,
        # write, validate — then a crash before the apply
        pid = m._leaf_pages["w_embed"][0]
        shadow, pvn = m._shadow[pid], m.store.table[pid][1]
        lines = [0, 3]
        data = np.full((len(lines), CFG.geometry.cache_line), 0xA5,
                       dtype=np.uint8)
        ml = m.store.mulogs[0]
        ml.invalidate()
        ml.write(pvn + 1, lines, data, target_slot=shadow)
        ml.validate(pid)
        m.pmem.crash(evict=lambda li: False)
        assert ml.read_durable() is not None
    m.pmem.fsync()

    m2 = CheckpointManager(path, CFG)
    step, got = m2.restore()
    assert step == 2
    for k in state:
        assert got[k].dtype == state[k].dtype
        assert got[k].tobytes() == state[k].tobytes()
    r = m2.last_restore
    assert r.pool_copy_bytes < os.path.getsize(path)
    if mulog_crash:
        # the replay reached the shadow slot: its header and lines
        layout = m2._layout
        hdr = _SLOT_HDR.unpack_from(m2.pmem.durable_inplace(),
                                    layout.slot_off(shadow))
        assert hdr == (pid, pvn + 1)
        cl = CFG.geometry.cache_line
        for li in lines:
            off = layout.slot_data_off(shadow) + li * cl
            assert (m2.pmem.durable_inplace(off, cl) == 0xA5).all()
    # and the restored manager saves on
    m2.save(3, make_state(3))
    step3, got3 = CheckpointManager(path, CFG).restore()
    assert step3 == 3
    np.testing.assert_array_equal(got3["w_out"], make_state(3)["w_out"])


def test_fused_and_staged_pipelines_agree_end_to_end(tmp_path):
    """The fused flush_pack scan and the staged chain route every page
    identically (same CoW/µLog/clean split), restore byte-identical
    state — and the fused save reads the live bytes once where staged
    reads them up to three times, which engine_time_ns must credit."""
    import dataclasses
    reports = {}
    for impl in ("fused", "staged"):
        cfg = dataclasses.replace(CFG, kernel_impl=impl)
        m = CheckpointManager(str(tmp_path / f"{impl}.pmem"), cfg)
        m.save(0, make_state(0))
        m.save(1, make_state(1))               # full rewrite
        s2 = {k: v.copy() for k, v in make_state(1).items()}
        s2["w_embed"][0, 0] += 1.0             # sparse delta save
        reports[impl] = m.save(2, s2)
        step, got = CheckpointManager(str(tmp_path / f"{impl}.pmem"), cfg).restore()
        assert step == 2
        for k in s2:
            np.testing.assert_array_equal(got[k], s2[k])
    rf, rs = reports["fused"], reports["staged"]
    assert (rf.pages_cow, rf.pages_mulog, rf.pages_clean) == \
        (rs.pages_cow, rs.pages_mulog, rs.pages_clean)
    assert rf.blocks_written == rs.blocks_written
    # the tentpole claim: ≥2x fewer device bytes read per delta save
    assert rs.scan_read_bytes >= 2 * rf.scan_read_bytes > 0
    assert rf.scan_ns < rs.scan_ns
    assert rf.modeled_ns < rs.modeled_ns


def test_fused_full_rewrite_when_delta_disabled(tmp_path):
    """delta=False: every save takes the full-rewrite path, and the scan
    accounting is exactly one popcount pass over the live bytes."""
    import dataclasses
    cfg = dataclasses.replace(CFG, delta=False, kernel_impl="fused")
    m = CheckpointManager(str(tmp_path / "s0.pmem"), cfg)
    state = make_state(4)
    m.save(0, state)
    r = m.save(1, state)                       # identical state: still CoW
    assert r.pages_cow == r.pages_total and r.pages_mulog == 0
    assert r.scan_read_bytes == r.bytes_logical
    step, got = CheckpointManager(str(tmp_path / "s0.pmem"), cfg).restore()
    assert step == 1
    for k in state:
        np.testing.assert_array_equal(got[k], state[k])


@pytest.mark.parametrize("impl", ["auto", "ref", "fused", "interpret",
                                  "staged"])
def test_reports_record_the_scan_that_ran(tmp_path, impl):
    """SaveReport and RestoreReport name what ran the scans — compiled
    pallas, interpret, ref or staged — not the configured request."""
    import dataclasses
    import jax
    kernel = "pallas" if jax.default_backend() == "tpu" else "interpret"
    want = {"auto": "pallas" if kernel == "pallas" else "ref", "ref": "ref",
            "fused": kernel, "interpret": "interpret", "staged": "staged"}
    cfg = dataclasses.replace(CFG, kernel_impl=impl)
    path = str(tmp_path / "s.pmem")
    m = CheckpointManager(path, cfg)
    assert m.save(0, make_state(0)).kernel_impl == want[impl]   # full
    assert m.save(1, make_state(1)).kernel_impl == want[impl]   # delta
    r = CheckpointManager(path, cfg)
    assert r.restore()[0] == 1
    assert r.last_restore.kernel_impl == want[impl]


def test_page_size_must_be_whole_dirty_units():
    """Pages are whole 4 KiB dirty-tracking units (the fused restore
    kernel verifies them as whole int32 tiles): anything else is refused
    up front instead of switching the restore to another path."""
    with pytest.raises(ValueError, match="page_size"):
        CheckpointConfig(page_size=4096 + 128)
    with pytest.raises(ValueError, match="page_size"):
        CheckpointConfig(page_size=0)


# -------------------------------------------------------------------- WAL

def test_wal_zero_single_barrier_per_step():
    pm = PMem(TrainWAL.capacity_for(100))
    pm.memset_zero()
    wal = TrainWAL(pm, 0, pm.size, technique="zero")
    before = pm.stats.barriers           # pool setup cost is off the path
    for s in range(20):
        wal.commit_step(StepRecord(s, s * 256, (1, 2), 1.5, 0.1, 1.0))
    assert pm.stats.barriers - before == 20
    assert wal.barriers_per_step() == 1


@pytest.mark.parametrize("technique,barriers", [("classic", 2), ("header", 2)])
def test_wal_baselines_cost_more(technique, barriers):
    pm = PMem(TrainWAL.capacity_for(100))
    pm.memset_zero()
    wal = TrainWAL(pm, 0, pm.size, technique=technique)
    before = pm.stats.barriers
    for s in range(10):
        wal.commit_step(StepRecord(s, s, (0, 0), 0.0, 0.0, 1.0))
    assert pm.stats.barriers - before == 10 * barriers


def test_wal_recovery_resume_point():
    pm = PMem(TrainWAL.capacity_for(100))
    pm.memset_zero()
    wal = TrainWAL(pm, 0, pm.size)
    for s in range(7):
        wal.commit_step(StepRecord(s, s * 1024, (s, s + 1), float(s), 0.5, 2.0))
    pm.crash(evict=lambda li: False)
    wal2 = TrainWAL(pm, 0, pm.size, recover=True)
    assert wal2.last.step == 6
    assert wal2.last.data_cursor == 6 * 1024
    assert wal2.last.rng_key == (6, 7)
    # appends continue after recovery
    wal2.commit_step(StepRecord(7, 7 * 1024, (7, 8), 7.0, 0.5, 2.0))
    wal3 = TrainWAL(pm, 0, pm.size, recover=True)
    assert [r.step for r in wal3.records] == list(range(8))


# ---------------------------------------------------------------- flusher

def test_async_flusher_overlap_and_order(tmp_path):
    m = CheckpointManager(str(tmp_path / "s0.pmem"), CFG)
    fl = AsyncFlusher(m, max_pending=2)
    states = [make_state(s) for s in range(4)]
    for i, st in enumerate(states):
        fl.submit(i, st)
    reports = fl.close()
    assert [r.step for r in reports] == [0, 1, 2, 3]
    step, got = CheckpointManager(str(tmp_path / "s0.pmem"), CFG).restore()
    assert step == 3
    np.testing.assert_array_equal(got["w_embed"], states[3]["w_embed"])


def test_async_flusher_staging_isolates_mutation(tmp_path):
    """Training may mutate the live state right after submit(); the staged
    copy must be what lands on disk."""
    m = CheckpointManager(str(tmp_path / "s0.pmem"), CFG)
    fl = AsyncFlusher(m)
    state = make_state(1)
    snapshot = {k: v.copy() for k, v in state.items()}
    fl.submit(0, state)
    state["w_embed"][:] = -1.0    # mutate immediately
    fl.close()
    _, got = CheckpointManager(str(tmp_path / "s0.pmem"), CFG).restore()
    np.testing.assert_array_equal(got["w_embed"], snapshot["w_embed"])


# ----------------------------------------------------------------- elastic

def test_slice_assemble_roundtrip():
    g = make_state(5)
    shards = slice_state(g, 4)
    states = [s for s, _ in shards]
    specs = [sp for _, sp in shards]
    back = assemble_global(states, specs)
    for k in g:
        np.testing.assert_array_equal(back[k], g[k])


def test_elastic_reshard_4_to_2(tmp_path):
    """4 shard regions on disk → restore → re-shard to 2 (elastic shrink)."""
    g = make_state(9)
    shards = slice_state(g, 4)
    for i, (st, spec) in enumerate(shards):
        mgr = CheckpointManager(str(tmp_path / f"s{i}.pmem"), CFG, shard_id=i)
        mgr.save(50, st)
    # recover all shards, assemble, re-shard
    states, specs = [], []
    for i, (_, spec) in enumerate(shards):
        mgr = CheckpointManager(str(tmp_path / f"s{i}.pmem"), CFG, shard_id=i)
        step, st = mgr.restore()
        assert step == 50
        states.append(st)
        specs.append(spec)
    global_state = assemble_global(states, specs)
    new_shards = reshard_state(global_state, 2)
    assert len(new_shards) == 2
    merged = assemble_global([s for s, _ in new_shards], [sp for _, sp in new_shards])
    for k in g:
        np.testing.assert_array_equal(merged[k], g[k])
