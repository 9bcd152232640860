"""End-to-end behaviour tests: the paper's primitives carrying a simulated
training job through crashes — WAL + delta checkpoints + recovery combine to
exactly-once step semantics."""

import dataclasses

import numpy as np
import pytest

from repro.core import PMem
from repro.persistence import (
    CheckpointConfig,
    CheckpointManager,
    StepRecord,
    TrainWAL,
)

CFG = CheckpointConfig(page_size=128 * 1024, manifest_capacity=1 << 16)


def fake_train_state(step: int, nparam: int = 1 << 15):
    """Deterministic 'parameters' after `step` optimizer updates."""
    rng = np.random.default_rng(42)
    base = rng.standard_normal(nparam).astype(np.float32)
    return {"params": base * (1.0 + 0.01 * step)}


def fake_update(state, step):
    base = fake_train_state(0)["params"] / 1.0
    return {"params": fake_train_state(step)["params"]}


class MiniTrainer:
    """A training loop skeleton wired to the persistence stack the way
    launch/train.py does it (checkpoint every k steps, WAL every step)."""

    def __init__(self, ckpt_path, wal_pmem, ckpt_every=5):
        self.manager = CheckpointManager(ckpt_path, CFG)
        self.wal = TrainWAL(wal_pmem, 0, wal_pmem.size)
        self.ckpt_every = ckpt_every

    def run(self, state, start_step, n_steps, crash_at=None):
        for step in range(start_step, start_step + n_steps):
            if crash_at is not None and step == crash_at:
                return state, step  # simulate process death mid-run
            state = fake_update(state, step + 1)
            self.wal.commit_step(StepRecord(
                step + 1, (step + 1) * 4096, (0, step + 1),
                float(1.0 / (step + 1)), 0.1, 1.0))
            if (step + 1) % self.ckpt_every == 0:
                self.manager.save(step + 1, state)
        return state, start_step + n_steps


def test_train_crash_resume_exact_state(tmp_path):
    wal_pm = PMem(TrainWAL.capacity_for(1000))
    wal_pm.memset_zero()
    t = MiniTrainer(str(tmp_path / "ckpt.pmem"), wal_pm, ckpt_every=5)
    state = fake_train_state(0)
    t.manager.save(0, state)

    # run 12 steps then 'crash' (checkpoints at 5, 10; WAL through 12)
    state, _ = t.run(state, 0, 12)
    wal_pm.crash(evict=lambda li: False)

    # --- restart ---
    m2 = CheckpointManager(str(tmp_path / "ckpt.pmem"), CFG)
    ckpt_step, restored = m2.restore()
    assert ckpt_step == 10
    np.testing.assert_array_equal(restored["params"],
                                  fake_train_state(10)["params"])
    wal2 = TrainWAL(wal_pm, 0, wal_pm.size, recover=True)
    assert wal2.last.step == 12          # WAL is ahead of the checkpoint
    assert wal2.last.data_cursor == 12 * 4096
    # deterministic replay: fast-forward from ckpt_step to wal.last.step
    replay_state = dict(restored)
    for s in range(ckpt_step, wal2.last.step):
        replay_state = fake_update(replay_state, s + 1)
    np.testing.assert_array_equal(replay_state["params"],
                                  fake_train_state(12)["params"])


def test_wal_and_checkpoint_disagree_gracefully(tmp_path):
    """Crash right after a checkpoint but before its WAL record would be
    an ordering bug; our ordering (WAL first, checkpoint after) means the
    WAL step is always >= checkpoint step."""
    wal_pm = PMem(TrainWAL.capacity_for(1000))
    wal_pm.memset_zero()
    t = MiniTrainer(str(tmp_path / "ckpt.pmem"), wal_pm, ckpt_every=3)
    state = fake_train_state(0)
    t.manager.save(0, state)
    state, _ = t.run(state, 0, 7)
    wal_pm.crash(evict=lambda li: False)
    m2 = CheckpointManager(str(tmp_path / "ckpt.pmem"), CFG)
    ckpt_step, _ = m2.restore()
    wal2 = TrainWAL(wal_pm, 0, wal_pm.size, recover=True)
    assert wal2.last.step >= ckpt_step


def test_repeated_crash_recovery_cycles(tmp_path):
    """Several crash/restart cycles in a row never lose committed work."""
    wal_pm = PMem(TrainWAL.capacity_for(1000))
    wal_pm.memset_zero()
    path = str(tmp_path / "ckpt.pmem")
    t = MiniTrainer(path, wal_pm, ckpt_every=2)
    state = fake_train_state(0)
    t.manager.save(0, state)
    step = 0
    for cycle in range(3):
        state, step = t.run(state, step, 4)
        wal_pm.crash(rng=np.random.default_rng(cycle), evict_prob=0.5)
        m = CheckpointManager(path, CFG)
        ckpt_step, restored = m.restore()
        wal = TrainWAL(wal_pm, 0, wal_pm.size, recover=True)
        assert ckpt_step % 2 == 0 and ckpt_step <= step
        assert wal.last.step == step
        np.testing.assert_array_equal(
            restored["params"], fake_train_state(ckpt_step)["params"])
        # resume from the recovered checkpoint + WAL replay
        state = dict(restored)
        for s in range(ckpt_step, step):
            state = fake_update(state, s + 1)
        t = MiniTrainer(path, wal_pm, ckpt_every=2)
        t.manager.restore()
        t.wal = TrainWAL(wal_pm, 0, wal_pm.size, recover=True)


def test_trainer_resume_refuses_a_checkpoint_that_does_not_restore(tmp_path):
    """A run directory whose checkpoint pool holds no committed checkpoint
    is an error on resume, never a silent fresh start."""
    from repro.launch.train import Trainer, TrainerConfig
    tc = TrainerConfig(arch="mamba2-130m", reduced=True, steps=2, batch=2,
                       seq=16, ckpt_every=1, out=str(tmp_path),
                       async_flush=False)
    # the pool exists, but its first save never committed a manifest
    CheckpointManager(str(tmp_path / "ckpt.pmem"), CFG)._build(
        {"w": np.zeros(1024, np.float32)})
    with pytest.raises(RuntimeError, match="no checkpoint that restores"):
        Trainer(tc)
    assert Trainer(dataclasses.replace(tc, resume=False)).start_step == 0
