"""Deterministic crash-fuzz regression corpus — tier-1, no hypothesis.

PR 2 and PR 3 shipped their strongest correctness evidence as hypothesis
crash properties, which skip wherever the ``test`` extra is not
installed (this container included) — so the crash arguments were only
ever exercised locally. This corpus fixes that: a checked-in seed list,
distilled once from the hypothesis suites' strategy spaces (every
technique, lane count x group commit, crash stage, failpoint protocol
point, and eviction/keep probability including the 0.0/1.0 extremes),
replayed through the *same* property bodies (``tests/corpus_runner.py``)
that ``@given`` randomizes. No imports beyond numpy/pytest — these run
(not skip) in a bare environment, and a seed that ever finds a bug
should be appended here as a permanent regression.
"""

import pytest

from corpus_runner import (
    run_cache_crash,
    run_cache_restore_crash,
    run_ckpt_fused_crash,
    run_cluster_crash,
    run_restore_fused_crash,
    run_generation_spill_crash,
    run_kv_crash,
    run_multilog_crash,
    run_page_spill_crash,
    run_pool_alloc_crash,
    run_serve_crash,
)


def _ops(seed: int, n: int, nkeys: int = 64):
    """Deterministic (key, value-seed) op list: a tiny LCG expansion of
    one corpus seed (no RNG imports, bit-exact everywhere)."""
    x, out = seed & 0x7FFFFFFF, []
    for _ in range(n):
        x = (1103515245 * x + 12345) & 0x7FFFFFFF
        out.append((x % nkeys, (x >> 7) % (10 ** 6)))
    return out


# ============================================================== KV engine
# (technique, ops-seed, n_ops, ckpt_every, crash-seed, evict_prob)

KV_CORPUS = [
    ("classic", 1, 24, 0, 101, 0.0),
    ("classic", 2, 40, 7, 202, 0.4),
    ("classic", 3, 17, 13, 303, 1.0),
    ("header", 4, 24, 0, 404, 1.0),
    ("header", 5, 40, 7, 505, 0.0),
    ("header", 6, 33, 13, 606, 0.4),
    ("zero", 7, 24, 0, 707, 0.4),
    ("zero", 8, 40, 13, 808, 1.0),
    ("zero", 9, 1, 0, 909, 0.0),          # single put, nothing durable yet
    ("zero", 10, 39, 7, 1010, 0.4),       # crash right before a checkpoint
]


@pytest.mark.parametrize("technique,ops_seed,n,ckpt,seed,prob", KV_CORPUS)
def test_kv_crash_corpus(technique, ops_seed, n, ckpt, seed, prob):
    run_kv_crash(technique, _ops(ops_seed, n), ckpt, seed, prob)


# ============================================================== MultiLog
# (technique, lanes, group_commit, n_entries, commit_after, seed, prob)

MULTILOG_CORPUS = [
    ("zero", 1, 1, 12, {3, 7}, 11, 0.3),
    ("zero", 2, 8, 40, {19}, 22, 0.7),
    ("zero", 3, 4, 25, set(), 33, 0.5),
    ("zero", 5, 9, 40, {0, 39}, 44, 1.0),
    ("zero", 4, 2, 31, {5, 17, 29}, 55, 0.0),
    ("classic", 2, 3, 20, {9}, 66, 0.7),
    ("classic", 4, 8, 40, set(), 77, 0.3),
    ("classic", 3, 1, 7, {2}, 88, 1.0),
    ("header", 2, 5, 26, {13}, 99, 0.3),
    ("header", 5, 7, 40, {11, 31}, 111, 0.5),
    ("header", 4, 4, 0, set(), 122, 0.7),   # empty log recovers empty
]


@pytest.mark.parametrize(
    "technique,lanes,gc,n,commits,seed,prob", MULTILOG_CORPUS)
def test_multilog_crash_corpus(technique, lanes, gc, n, commits, seed, prob):
    run_multilog_crash(technique, lanes, gc, n, commits, seed, prob)


# ====================================================== pool allocation
# (n_entries, payload, crash_stage, seed, prob)

POOL_CORPUS = [
    (0, b"a", "placed", 7, 0.5),
    (3, b"pool-payload", "placed", 14, 1.0),
    (6, b"x" * 120, "initialized", 21, 0.0),
    (2, b"\x00\xff" * 30, "initialized", 28, 0.75),
    (4, b"entry", "entry_stored", 35, 0.0),     # entry line dropped
    (4, b"entry", "entry_stored", 42, 1.0),     # entry line survives
    (1, b"q" * 64, "entry_stored", 49, 0.5),
    (5, b"\xaa" * 33, "entry_stored", 56, 0.25),
]


@pytest.mark.parametrize("n,payload,stage,seed,prob", POOL_CORPUS)
def test_pool_alloc_crash_corpus(n, payload, stage, seed, prob):
    run_pool_alloc_crash(n, payload, stage, seed, prob)


# ============================================== crash-during-spill (WAL)
# (lanes, gen_sets, group_commit, per_gen, crash_step, seed,
#  pmem_prob, ssd_keep) — crash steps 1..4 land on each failpoint of the
# first generation drain (ssd_written / ssd_flushed / mapped / retired);
# larger steps land in later drains or never fire.

GEN_SPILL_CORPUS = [
    (1, 2, 1, [3], 1, 1001, 0.5, 0.5),
    (2, 2, 2, [4, 6], 2, 1002, 1.0, 0.0),
    (3, 3, 1, [2, 5, 9], 3, 1003, 0.0, 1.0),
    (4, 2, 5, [12, 1], 4, 1004, 0.5, 1.0),
    (2, 3, 3, [7, 7, 7], 6, 1005, 1.0, 0.5),
    (1, 3, 1, [1, 1, 1, 1, 1], 9, 1006, 0.5, 0.0),
    (3, 2, 4, [10, 3, 8], 11, 1007, 0.0, 0.0),
    (2, 2, 1, [5], 40, 1008, 1.0, 1.0),     # no crash: full drain path
]


@pytest.mark.parametrize(
    "lanes,gen_sets,gc,per_gen,step,seed,pprob,skeep", GEN_SPILL_CORPUS)
def test_generation_spill_crash_corpus(lanes, gen_sets, gc, per_gen, step,
                                       seed, pprob, skeep):
    run_generation_spill_crash(lanes, gen_sets, gc, per_gen, step, seed,
                               pprob, skeep)


# ============================================= crash-during-spill (pages)
# (nslots, writes-seed, n_writes, crash_step, seed, pmem_prob, ssd_keep)

PAGE_SPILL_CORPUS = [
    (3, 11, 40, 1, 2001, 0.5, 0.5),
    (3, 12, 24, 2, 2002, 1.0, 0.0),
    (4, 13, 40, 3, 2003, 0.0, 1.0),
    (4, 14, 33, 5, 2004, 0.5, 1.0),
    (5, 15, 40, 8, 2005, 1.0, 0.5),
    (6, 16, 16, 13, 2006, 0.0, 0.0),
    (3, 17, 40, 21, 2007, 0.5, 0.0),
    (5, 18, 9, 60, 2008, 1.0, 1.0),         # no crash: clean epochs
]


@pytest.mark.parametrize(
    "nslots,wseed,n,step,seed,pprob,skeep", PAGE_SPILL_CORPUS)
def test_page_spill_crash_corpus(nslots, wseed, n, step, seed, pprob, skeep):
    writes = [(k % 16, v % 256) for k, v in _ops(wseed, n, nkeys=16)]
    run_page_spill_crash(nslots, writes, step, seed, pprob, skeep)


# ============================================ DRAM cache (buffer manager)
# (frames, admit_k, ops-seed, n_ops, epoch_every, crash_step, seed,
#  pmem_prob, ssd_keep) — the op stream mixes ~1/3 writes over pids 0-7
# with reads over pids 0-15 (see _cache_ops), so dirty frames sit pending
# write-back and k-touch promotions are in flight when the failpoint
# fires; crash steps land on eviction points (ssd_written / ssd_flushed /
# mapped) and on mid-promotion (promoted), plus no-crash full runs. Each
# case runs TWICE — warm cache and frames=0 — and asserts identical
# recovered state (see corpus_runner.run_cache_crash).

def _cache_ops(seed: int, n: int):
    """Deterministic read/write stream (same LCG discipline as _ops):
    writes confined to 8 pids so an epoch's dirty set stays within the
    frame budget; reads range over all 16 pids."""
    x, out = seed & 0x7FFFFFFF, []
    for _ in range(n):
        x = (1103515245 * x + 12345) & 0x7FFFFFFF
        if (x >> 3) % 3 == 0:
            out.append(("w", (x >> 5) % 8, x % 256))
        else:
            out.append(("r", (x >> 5) % 16, 0))
    return out


CACHE_CORPUS = [
    (8, 2, 21, 48, 6, 1, 3001, 0.5, 0.5),
    (8, 2, 22, 48, 6, 2, 3002, 1.0, 0.0),
    (6, 1, 23, 40, 5, 3, 3003, 0.0, 1.0),     # promote-on-first-access
    (8, 3, 24, 60, 6, 4, 3004, 0.5, 1.0),     # crash lands mid-promotion
    (6, 2, 25, 48, 8, 7, 3005, 1.0, 0.5),
    (8, 4, 26, 64, 6, 11, 3006, 0.0, 0.0),    # high admission threshold
    (8, 2, 27, 36, 6, 60, 3007, 0.5, 0.5),    # no crash: full clean run
    (16, 1, 28, 48, 4, 5, 3008, 1.0, 1.0),    # every page fits a frame
]


@pytest.mark.parametrize(
    "frames,admit_k,oseed,n,epoch,step,seed,pprob,skeep", CACHE_CORPUS)
def test_cache_crash_corpus(frames, admit_k, oseed, n, epoch, step, seed,
                            pprob, skeep):
    run_cache_crash(frames, admit_k, _cache_ops(oseed, n), epoch, step,
                    seed, pprob, skeep)


# ================================== restore after dirty eviction (cache)
# (frames, admit_k, epoch_every, n_evict_writes, crash_step, seed,
#  pmem_prob, ssd_keep) — a write burst past the frame budget parks
# clock-evicted dirty images in the flush queue, then a snapshot restore
# invalidates the cache and rewrites only PART of the page table: the
# untouched pids are protected against stale-image resurrection solely
# by invalidate()'s parked-image purge. Crash steps land in the baseline
# drain, the restore drain, and the post-restore drain, plus no-crash
# full runs; each case runs warm and frames=0 and must recover identical
# state with phase-B bytes never resurfacing (see
# corpus_runner.run_cache_restore_crash).

CACHE_RESTORE_CORPUS = [
    (8, 2, 6, 24, 99, 4099, 0.5, 0.5),     # no crash: full restore cycle
    (8, 2, 6, 24, 3, 4003, 0.5, 0.5),      # crash in the baseline drain
    (8, 2, 6, 24, 12, 4012, 1.0, 0.0),     # crash in the restore drain
    (8, 2, 6, 24, 20, 4020, 0.0, 1.0),     # crash post-restore drain
    (6, 1, 4, 32, 9, 4109, 0.5, 1.0),      # promote-on-first-access
    (6, 3, 8, 24, 16, 4216, 1.0, 0.5),     # high admission threshold
    (16, 2, 6, 24, 99, 4399, 0.0, 0.0),    # every page fits a frame
]


@pytest.mark.parametrize(
    "frames,admit_k,epoch,nwrites,step,seed,pprob,skeep",
    CACHE_RESTORE_CORPUS)
def test_cache_restore_crash_corpus(frames, admit_k, epoch, nwrites, step,
                                    seed, pprob, skeep):
    run_cache_restore_crash(frames, admit_k, epoch, nwrites, step, seed,
                            pprob, skeep)


# ============================================ crash-mid-fused-flush (ckpt)
# (sparse-positions, crash_step, crash-seed, evict_prob) — arms a
# failpoint on the checkpoint flush queue so the µLog save's epoch drain
# dies after crash_step-1 page flushes, then runs the SAME scenario under
# kernel_impl="fused" and "staged" and asserts byte-identical recovery
# (see corpus_runner.run_ckpt_fused_crash). Positions index a 512 KiB
# float32 leaf split into 128 KiB pages (32768 elements each); the huge
# step is the no-crash control.

CKPT_FUSED_CORPUS = [
    ((0, 40000), 1, 5001, 0.5),      # die on the first page flush
    ((0, 40000), 2, 5002, 1.0),      # second flush, every line evicted
    ((13000,), 1, 5003, 0.0),        # single dirty page, nothing evicted
    ((5, 70000, 131071), 2, 5004, 0.4),   # three pages dirty
    ((0, 40000), 60, 5005, 0.5),     # no crash: clean fused µLog save
]


@pytest.mark.parametrize("positions,step,seed,prob", CKPT_FUSED_CORPUS)
def test_ckpt_fused_crash_corpus(tmp_path, positions, step, seed, prob):
    run_ckpt_fused_crash(str(tmp_path), positions, step, seed, prob)


# ============================================ crash-mid-fused-restore
# (sparse-positions, crash_step, crash-seed, evict_prob) — the restore
# direction of the fused-kernel corpus above: the device crashes with an
# arbitrary eviction subset, then the restore itself dies after
# crash_step-1 per-leaf apply dispatches (apply_unpack under
# kernel_impl="fused", the verify-then-copy chain under "staged").
# Restore is read-only, so the aborted attempt must leave the durable
# cut untouched and a fresh manager recovers the committed step
# byte-identically under BOTH impls (see
# corpus_runner.run_restore_fused_crash). The state has three leaves,
# so steps 1-3 land mid-manifest-entry; the huge step is the no-crash
# control.

RESTORE_FUSED_CORPUS = [
    ((0, 40000), 1, 6001, 0.5),          # die on the first leaf apply
    ((13000,), 2, 6002, 1.0),            # mid-entry, every line evicted
    ((5, 70000, 131071), 3, 6003, 0.0),  # last leaf of the entry
    ((0, 40000), 60, 6004, 0.4),         # no crash: clean restore control
]


@pytest.mark.parametrize("positions,step,seed,prob", RESTORE_FUSED_CORPUS)
def test_restore_fused_crash_corpus(tmp_path, positions, step, seed, prob):
    run_restore_fused_crash(str(tmp_path), positions, step, seed, prob)


# ============================================ crash-mid-request-batch
# (n_requests, workload-seed, crash_step, crash-seed, evict_prob,
#  admission, slo_us) — crash steps land on ``req_applied`` /
# ``batch_commit`` failpoints of the serving frontend (two tenants,
# two-lane group-commit WALs each); admitted-but-uncommitted requests
# must recover as if shed (see corpus_runner.run_serve_crash). The
# tight-SLO case serves with real shedding in flight; the huge-step
# case is the no-crash control.

SERVE_CORPUS = [
    (40, 1, 3, 4101, 0.5, True, 500.0),     # crash in the first batch
    (40, 2, 33, 4102, 1.0, True, 500.0),    # mid-run, nothing evicted
    (40, 3, 57, 4103, 0.0, True, 500.0),    # late, everything evicted
    (40, 4, 21, 4104, 0.4, True, 0.05),     # shedding active at crash
    (32, 5, 26, 4105, 0.7, False, 500.0),   # admission off: pure queueing
    (24, 6, 999, 4106, 0.5, True, 500.0),   # no crash: full clean run
]


@pytest.mark.parametrize(
    "n,wseed,step,seed,prob,admission,slo", SERVE_CORPUS)
def test_serve_crash_corpus(n, wseed, step, seed, prob, admission, slo):
    run_serve_crash(n, wseed, step, seed, prob,
                    admission=admission, slo_us=slo)


# ================================================== crash-mid-reshard
# (nshards, new_nshards, n_ops, ckpt_every, crash_step, crash-seed,
#  evict_prob, tiered, ssd_keep) — crash steps land on the router's
# view-change failpoints. The step numbers below were chosen against
# the deterministic failpoint traces of each scenario (seed 12345 LCG
# workload): the checkpointed 2→3 grow migrates one range as
#   1 view:started · 2 copy:page · 3 flush:done · 4 own:committed ·
#   5 invalidate:done · 6 view:committed,
# the 4→2 shrink moves two ranges (steps 2-6 first range incl. a
# copy:wal, 7-10 second), and the never-checkpointed 2→4 grow ships
# WAL records only (steps 2-13 copy:wal). Each case asserts
# exactly-old-owner or exactly-new-owner recovery per range (never
# both/neither), last-committed-value reads, convergence on resume
# with only unflipped ranges re-moved, and durably scrubbed sources
# (see corpus_runner.run_cluster_crash).

CLUSTER_CORPUS = [
    (2, 3, 40, 10, 2, 7101, 0.5, False, 1.0),   # mid-copy: page image shipped
    (2, 3, 40, 10, 3, 7102, 1.0, False, 1.0),   # after target flush, pre-own
    (2, 3, 40, 10, 4, 7103, 0.0, False, 1.0),   # at the ownership flip
    (2, 3, 40, 10, 5, 7104, 0.5, False, 1.0),   # after source invalidation
    (4, 2, 48, 10, 6, 7105, 0.5, False, 1.0),   # range 1 flipped, range 2 not
    (4, 2, 48, 10, 9, 7106, 1.0, False, 1.0),   # mid-second-range ownership
    (2, 4, 48, 0, 7, 7107, 0.5, False, 1.0),    # mid-WAL-only copy stream
    (2, 4, 48, 0, 15, 7108, 0.0, False, 1.0),   # second range's flush step
    (3, 4, 48, 8, 4, 7109, 0.5, True, 0.5),     # tiered source, own flip
    (3, 4, 48, 8, 5, 7110, 0.5, True, 0.0),     # tiered, SSD loses all
    (2, 3, 40, 10, 99, 7111, 0.5, False, 1.0),  # no crash: clean control
    # a page spilled at pvn 5 left a stale pvn-4 header in a released PMem
    # slot; recovery tables it, and the reads' promotions must not spill
    # that old image over the newer SSD copy (the last put was lost)
    (3, 4, 48, 8, 1, 4, 0.0, True, 0.0),        # tiered, crash at view start
    (3, 4, 48, 8, 99, 4, 0.0, True, 1.0),       # tiered, no crash in reshard
]


@pytest.mark.parametrize(
    "nsh,new,n,ckpt,step,seed,prob,tiered,skeep", CLUSTER_CORPUS)
def test_cluster_crash_corpus(nsh, new, n, ckpt, step, seed, prob,
                              tiered, skeep):
    run_cluster_crash(nsh, new, n, ckpt, step, seed, prob,
                      tiered=tiered, ssd_keep=skeep)


# Concurrent driver: the same view-change protocol, but width ranges
# flighted per stage-interleaved batch — so one crash step lands with
# 2+ ranges at MIXED protocol stages (one range's ownership already
# flipped while its batch-mate is still pre-own, both mid-copy, etc.).
# Steps below index the deterministic width>1 failpoint traces: the
# 4→2 shrink batches both moving ranges (2-3 copy:page, 4-5 copy:wal,
# 6-7 flush:done, 8-9 own:committed, 10-11 invalidate:done); the 4→1
# drain moves four ranges as a batch of three (2-15) then one (16-21);
# the never-checkpointed 2→4 grow ships a batched WAL-only stream
# (2-11 copy:wal, then 12-17 flush/own/invalidate pairs). Same
# invariants as the serial corpus — exactly-old-XOR-exactly-new per
# range, committed reads, resume convergence at the same width,
# scrubbed sources — because batching never reorders one range's own
# copy → flush → own → invalidate sequence.

CLUSTER_WIDTH_CORPUS = [
    (4, 2, 48, 10, 3, 7301, 0.5, 2),   # batch of 2, both mid-page-copy
    (4, 2, 48, 10, 9, 7302, 1.0, 2),   # range A flipped, batch-mate not
    (4, 2, 48, 10, 11, 7303, 0.0, 2),  # both owned, one not invalidated
    (2, 4, 48, 0, 7, 7304, 0.5, 2),    # mid batched WAL-only stream
    (4, 1, 48, 10, 5, 7305, 0.5, 3),   # batch of 3 at three copy stages
    (4, 1, 48, 10, 11, 7306, 0.5, 3),  # 2 of 3 flipped inside one batch
    (4, 1, 48, 10, 17, 7307, 1.0, 3),  # second batch mid-copy
    (4, 1, 48, 10, 99, 7308, 0.5, 3),  # no crash: clean width=3 control
]


@pytest.mark.parametrize(
    "nsh,new,n,ckpt,step,seed,prob,width", CLUSTER_WIDTH_CORPUS)
def test_cluster_width_crash_corpus(nsh, new, n, ckpt, step, seed, prob,
                                    width):
    run_cluster_crash(nsh, new, n, ckpt, step, seed, prob, width=width)


# Stale-WAL fence: crash mid-copy AFTER copy:wal replayed committed
# source records into the migration target's WAL, reopen (the scrub
# must checkpoint the target, truncating that residue), then overwrite
# the still-moving ranges' keys and checkpoint their owners — source
# WALs empty, the new values live only in page images — resume, and
# crash + reopen once more. Without the fence the target's leftover
# records replay over the newer images on that second restart and
# revert committed writes (run_cluster_crash resume_interleave arm).
# The never-checkpointed rows ship WAL records only, so any mid-copy
# step lands inside the copy:wal stream; the ckpt=10 rows mix page
# images and WAL records.
CLUSTER_STALE_WAL_CORPUS = [
    (2, 4, 48, 0, 3, 7201, 0.5, False, 1.0),    # early in the WAL stream
    (2, 4, 48, 0, 9, 7202, 0.0, False, 1.0),    # deep in the WAL stream
    (2, 4, 48, 0, 15, 7203, 0.5, False, 1.0),   # past one range's flip
    (2, 3, 40, 10, 3, 7204, 0.5, False, 1.0),   # images + WAL tail mixed
    (4, 2, 48, 10, 5, 7205, 0.5, False, 1.0),   # shrink, first range mid-copy
    (3, 4, 48, 8, 3, 7206, 0.5, True, 1.0),     # tiered source mid-copy
]


@pytest.mark.parametrize(
    "nsh,new,n,ckpt,step,seed,prob,tiered,skeep", CLUSTER_STALE_WAL_CORPUS)
def test_cluster_stale_wal_corpus(nsh, new, n, ckpt, step, seed, prob,
                                  tiered, skeep):
    run_cluster_crash(nsh, new, n, ckpt, step, seed, prob,
                      tiered=tiered, ssd_keep=skeep, resume_interleave=True)


def test_cluster_stale_wal_concurrent_driver():
    # the stale-WAL-residue scenario under the width=2 driver: the
    # crash-interrupted batched copy leaves records in TWO targets' WALs
    # at once, and the reopen scrub must fence both before the
    # interleaved overwrites + width=2 resume + second restart
    run_cluster_crash(2, 4, 48, 0, 7, 7309, 0.5,
                      width=2, resume_interleave=True)
