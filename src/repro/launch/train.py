"""End-to-end training driver.

Wires together: model (any --arch), synthetic resumable data pipeline,
AdamW, and the paper's persistence stack — Zero-log WAL committed every
step (ONE durability barrier on the critical path), hybrid CoW/µLog delta
checkpoints flushed asynchronously every --ckpt-every steps, crash
recovery on restart (checkpoint + WAL fast-forward = exactly-once steps).

CPU-runnable: reduced configs train a real model for hundreds of steps
(examples/train_tinyllama.py). Full configs train on one TPU chip where
they fit: ``chip_smoke.py`` runs mamba2-130m at its published widths
through save → kill → resume.

  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --reduced --steps 200 --batch 8 --seq 128 --out /tmp/run1
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_reduced
from repro.data import SyntheticPipeline
from repro.pool import Pool
from repro.launch.compile_cache import use_compile_cache
from repro.launch.steps import build_train_step
from repro.models import init_params
from repro.optim import AdamWConfig, adamw_init
from repro.persistence import (
    AsyncFlusher,
    CheckpointConfig,
    CheckpointManager,
    StepRecord,
    TrainWAL,
)
from repro.spans import compiles, span


def flatten_state(tree, prefix="") -> Dict[str, np.ndarray]:
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        out[key] = np.asarray(leaf)
    return out


def unflatten_like(template, flat: Dict[str, np.ndarray]):
    paths = jax.tree_util.tree_flatten_with_path(template)[0]
    treedef = jax.tree_util.tree_structure(template)
    leaves = []
    for path, leaf in paths:
        key = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        arr = flat[key]
        leaves.append(jnp.asarray(arr).astype(leaf.dtype).reshape(leaf.shape))
    return jax.tree_util.tree_unflatten(treedef, leaves)


@dataclasses.dataclass
class TrainerConfig:
    arch: str = "tinyllama-1.1b"
    reduced: bool = True
    steps: int = 100
    batch: int = 8
    seq: int = 128
    ckpt_every: int = 20
    out: str = "/tmp/repro_run"
    wal_capacity_steps: int = 100_000
    lr: float = 3e-4
    remat: bool = True
    resume: bool = True
    async_flush: bool = True
    # repro.io engine: >1 stripes the WAL over that many zero-log lanes
    # and amortizes `wal_group_commit` steps per persistency barrier
    wal_lanes: int = 1
    wal_group_commit: int = 1
    # >= 2 runs the step WAL on a generation ring: every checkpoint rolls
    # (seals) the live generation and the spill tier retires it to SSD in
    # the same cadence, so the WAL's PMem footprint stays at
    # gen_sets x capacity_steps instead of growing for the whole run
    # (capacity_steps is then per generation — size it to the checkpoint
    # cadence, not the run length)
    wal_gen_sets: int = 1


class Trainer:
    """The training loop and its persistence. Spans (:mod:`repro.spans`)
    mark its phases: ``trainer.build`` (the constructor, with
    ``trainer.wal_open`` and, on resume, the checkpoint's ``ckpt.restore``
    and ``trainer.upload``), and per step ``train.step``,
    ``train.wal_commit`` and, at a checkpoint, ``ckpt.stage``."""

    def __init__(self, tc: TrainerConfig) -> None:
        with span("trainer.build"):
            self._build(tc)

    def _build(self, tc: TrainerConfig) -> None:
        self.tc = tc
        os.makedirs(tc.out, exist_ok=True)
        self.cfg = get_reduced(tc.arch) if tc.reduced else get_config(tc.arch)
        self.pipeline = SyntheticPipeline(self.cfg, tc.batch, tc.seq)
        self.step_fn = jax.jit(build_train_step(
            self.cfg, AdamWConfig(lr=tc.lr), remat=tc.remat,
            total_steps=max(tc.steps, 100)))
        # --- persistence ------------------------------------------------
        with span("trainer.wal_open"):
            wal_path = os.path.join(tc.out, "wal.pmem")
            wal_bytes = TrainWAL.capacity_for(tc.wal_capacity_steps,
                                              lanes=tc.wal_lanes,
                                              gen_sets=tc.wal_gen_sets)
            if tc.wal_gen_sets > 1:
                wal_bytes += 1 << 16   # spill-map double buffer + head regions
            self.wal_pool = Pool.open_or_create(wal_path, wal_bytes)
            self.wal_pmem = self.wal_pool.pmem
            self.wal = self.wal_pool.wal(
                "train_wal", capacity_steps=tc.wal_capacity_steps,
                lanes=tc.wal_lanes, group_commit=tc.wal_group_commit,
                gen_sets=tc.wal_gen_sets)
            self.wal_spill = None
            if self.wal.generational:
                # the ring needs a retirement path: sealed step generations
                # move to SSD at the checkpoint cadence (the durable retired
                # watermark keeps every generation recoverable from exactly
                # one tier), bounding the WAL's PMem footprint for good
                from repro.core.ssd import SSD
                from repro.tier import SpillScheduler
                self.wal_pool.attach_ssd(SSD(1 << 26))
                self.wal_spill = SpillScheduler(self.wal_pool, name="twsp",
                                                map_capacity=1 << 14)
                self.wal.log.attach_spill(self.wal_spill)
        self.manager = CheckpointManager(
            os.path.join(tc.out, "ckpt.pmem"),
            CheckpointConfig(page_size=128 * 1024))
        self.flusher = AsyncFlusher(self.manager) if tc.async_flush else None

        self.start_step = 0
        params = opt_state = None
        ckpt_path = os.path.join(tc.out, "ckpt.pmem")
        if tc.resume and os.path.exists(ckpt_path) \
                and os.path.getsize(ckpt_path) > 0:
            # a checkpoint file that does not restore is an error, never a
            # silent fresh start over the run's committed state
            try:
                step, flat = self.manager.restore()
            except (FileNotFoundError, RuntimeError) as e:
                raise RuntimeError(
                    f"{ckpt_path} holds no checkpoint that restores ({e}); "
                    f"remove it or set resume=False to start afresh") from e
            tmpl_p = jax.eval_shape(lambda k: init_params(self.cfg, k),
                                    jax.random.key(0))
            tmpl_o = jax.eval_shape(adamw_init, tmpl_p)
            np_params = {k[2:]: v for k, v in flat.items() if k.startswith("p/")}
            np_opt = {k[2:]: v for k, v in flat.items() if k.startswith("o/")}
            with span("trainer.upload") as sp:
                params = unflatten_like(tmpl_p, np_params)
                opt_state = unflatten_like(tmpl_o, np_opt)
                sp.add(h2d_bytes=sum(v.nbytes for v in flat.values()))
            self.start_step = step
            print(f"[train] restored checkpoint @ step {step}")
            if self.wal.last is not None and self.wal.last.step > step:
                print(f"[train] WAL ahead at step {self.wal.last.step}; "
                      f"fast-forwarding data cursor")
                self.start_step = step  # deterministic replay from ckpt
        if params is None:
            params = init_params(self.cfg, jax.random.key(0))
            opt_state = adamw_init(params)
        self.params, self.opt_state = params, opt_state

    def _ckpt_state(self) -> Dict[str, np.ndarray]:
        """The state to checkpoint, fetched to the host (``ckpt.stage``)."""
        with span("ckpt.stage") as sp:
            flat = {f"p/{k}": v for k, v in flatten_state(self.params).items()}
            flat.update({f"o/{k}": v
                         for k, v in flatten_state(self.opt_state).items()})
            sp.add(d2h_bytes=sum(v.nbytes for v in flat.values()))
        return flat

    def run(self, crash_at: Optional[int] = None) -> Dict[str, Any]:
        tc = self.tc
        losses = []
        t_start = time.time()
        for step in range(self.start_step, tc.steps):
            if crash_at is not None and step == crash_at:
                # simulated process death: no cleanup, no final flush
                return {"crashed_at": step, "losses": losses}
            with span("train.step", step=step) as sp:
                before = compiles()
                batch = {k: jnp.asarray(v)
                         for k, v in self.pipeline.batch_at(step).items()}
                self.params, self.opt_state, metrics = self.step_fn(
                    self.params, self.opt_state, batch)
                loss = float(metrics["loss"])
                sp.add(compiles=compiles() - before)
            losses.append(loss)
            # WAL commit: ONE barrier on the critical path (Zero logging);
            # with group commit enabled, steps buffer and the barrier is
            # amortized per batch (crash loses at most a replayable tail)
            with span("train.wal_commit"):
                self.wal.commit_step(StepRecord(
                    step + 1, step + 1, (0, 0), loss,
                    float(metrics["grad_norm"]), 1.0, time.time_ns()),
                    sync=tc.wal_group_commit <= 1)
            if (step + 1) % tc.ckpt_every == 0:
                state = self._ckpt_state()
                if self.flusher is not None:
                    self.flusher.submit(step + 1, state)
                else:
                    self.manager.save(step + 1, state)
                if self.wal.generational:
                    # checkpoint-cadence truncation: seal the live step
                    # generation and retire it through the spill tier —
                    # it stays recoverable (PMem until the drain's map
                    # record + watermark commit, SSD after), but its
                    # ring slot frees for reuse instead of the step WAL
                    # only ever truncating at restart
                    self.wal.roll()
                    self.wal_spill.drain()
        self.wal.flush()   # drain any group-commit-buffered steps
        if self.flusher is not None:
            reports = self.flusher.wait()
        else:
            reports = []
        wall = time.time() - t_start
        return {
            "steps": tc.steps - self.start_step,
            "wall_s": wall,
            "losses": losses,
            "first_loss": losses[0] if losses else None,
            "last_loss": losses[-1] if losses else None,
            "wal_barriers_per_step": self.wal.barriers_per_step(),
            "ckpt_reports": [dataclasses.asdict(r) for r in reports][-3:],
        }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--out", default="/tmp/repro_run")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--no-resume", action="store_true")
    args = ap.parse_args()
    use_compile_cache()
    tc = TrainerConfig(arch=args.arch, reduced=args.reduced, steps=args.steps,
                       batch=args.batch, seq=args.seq,
                       ckpt_every=args.ckpt_every, out=args.out, lr=args.lr,
                       resume=not args.no_resume)
    report = Trainer(tc).run()
    print(json.dumps({k: v for k, v in report.items() if k != "losses"},
                     indent=1, default=str))
    losses = report["losses"]
    if losses:
        k = max(1, len(losses) // 10)
        print(f"loss: first10={np.mean(losses[:k]):.4f} "
              f"last10={np.mean(losses[-k:]):.4f}")


if __name__ == "__main__":
    main()
