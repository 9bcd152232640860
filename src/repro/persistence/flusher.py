"""Asynchronous checkpoint flushing, overlapped with training.

Guideline G5 ("performance-critical code should prefer DRAM … buffer writes
in a DRAM cache") becomes: the training loop *stages* device state to host
memory (a cheap device→host copy) and returns to compute immediately; a
bounded background pool (guideline G4: over-saturating the durable tier
degrades throughput, so writer concurrency is capped) runs the actual
CoW/µLog flushing off the critical path.

Lane model (repro.io engine): the flusher runs **one worker lane per
checkpoint shard**. A single manager keeps the original contract — saves
serialized in submission order. A list of managers (one per shard of the
host's state) flushes the shards concurrently, which is exactly the
paper's multi-threaded page-flush setting (Fig. 5(b)): each shard's
:class:`CheckpointManager` batches its own pages through a
:class:`~repro.io.FlushQueue` epoch, and the per-shard worker count is
the engine's active-lane count.

Ordering contract: saves for a given shard are serialized in submission
order (a single worker per shard region); ``wait()`` drains everything —
the train loop calls it before intentionally stopping, and the WAL makes
any un-flushed tail recoverable anyway.

The flusher owns no layout: each :class:`CheckpointManager` manages its
shard through its own :class:`repro.pool.Pool` (manifest + pages regions),
so the worker threads only ever call ``manager.save``.
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from repro.persistence.checkpoint import (
    CheckpointConfig,
    CheckpointManager,
    SaveReport,
)
from repro.spans import span

__all__ = ["AsyncFlusher"]


class AsyncFlusher:
    """Background flusher: one worker lane per checkpoint shard."""

    def __init__(self,
                 managers: Union[CheckpointManager, Sequence[CheckpointManager]],
                 *, max_pending: int = 2,
                 sockets: Optional[int] = None,
                 cache_frames: Optional[int] = None,
                 cache_admit_k: Optional[int] = None,
                 kernel_impl: Optional[str] = None) -> None:
        """``sockets`` (when > 1) interleaves the shards' home sockets
        round-robin across the host's NUMA sockets, so each shard's
        worker lane flushes near-socket instead of funneling every
        shard's pages through socket 0. Only shards that have not yet
        built their pool (first save pending) and did not pin a socket
        themselves (``CheckpointConfig.socket``) are moved; a shard
        config still at the single-socket default also has the topology
        propagated into it (its pool is created ``sockets``-wide —
        without that the home assignment would clamp back to 0).

        ``cache_frames`` / ``cache_admit_k`` likewise propagate a
        host-level DRAM budget into every shard config still at its
        default: the flusher's aggregate staging DRAM is
        ``lanes × cache_frames × page_size``, bounded regardless of the
        state size — per-shard snapshot frames are the shard pool's
        :class:`~repro.cache.BufferManager` (``pool.cache``), not an
        unbounded host-RAM mirror. Shards whose pools are already built
        or whose configs pin their own values keep them.

        ``kernel_impl`` propagates a scan dispatch (e.g. ``"fused"`` or
        ``"staged"``) into every shard config still at ``"auto"``, and
        it governs BOTH directions: each worker lane's saves run the
        one-pass ``flush_pack`` kernel (or the staged A/B chain), and
        each shard's ``restore``/``adopt`` runs the one-pass
        ``apply_unpack`` verify+assemble (or the staged
        verify-then-copy loop) — see ``CheckpointConfig.kernel_impl``."""
        if isinstance(managers, CheckpointManager):
            managers = [managers]
        self.managers: List[CheckpointManager] = list(managers)
        if not self.managers:
            raise ValueError("AsyncFlusher needs at least one manager")
        import dataclasses
        if sockets is not None and sockets > 1:
            for i, mgr in enumerate(self.managers):
                if mgr.pool is not None or mgr.cfg.socket is not None:
                    continue
                if mgr.cfg.sockets == 1:
                    mgr.cfg = dataclasses.replace(mgr.cfg,
                                                  sockets=int(sockets))
                mgr.home_socket = i % mgr.cfg.sockets
        if cache_frames is not None or cache_admit_k is not None:
            for mgr in self.managers:
                if mgr.pool is not None:
                    continue
                kw = {}
                if cache_frames is not None and mgr.cfg.cache_frames is None:
                    kw["cache_frames"] = int(cache_frames)
                if cache_admit_k is not None \
                        and mgr.cfg.cache_admit_k == CheckpointConfig.cache_admit_k:
                    kw["cache_admit_k"] = int(cache_admit_k)
                if kw:
                    mgr.cfg = dataclasses.replace(mgr.cfg, **kw)
        if kernel_impl is not None:
            for mgr in self.managers:
                if mgr.pool is None and mgr.cfg.kernel_impl == "auto":
                    mgr.cfg = dataclasses.replace(mgr.cfg,
                                                  kernel_impl=str(kernel_impl))
        #: first shard's manager — kept for the single-shard call sites
        self.manager = self.managers[0]
        self._queues: List["queue.Queue"] = [
            queue.Queue(maxsize=max_pending) for _ in self.managers
        ]
        self._reports: List[List[SaveReport]] = [[] for _ in self.managers]
        self.errors: List[BaseException] = []
        self._workers = [
            threading.Thread(target=self._run, args=(i,), daemon=True)
            for i in range(len(self.managers))
        ]
        for w in self._workers:
            w.start()

    @property
    def lanes(self) -> int:
        return len(self.managers)

    @property
    def reports(self) -> List[SaveReport]:
        """All completed saves: submission order within a shard; across
        shards, ordered by (step, shard)."""
        if len(self._reports) == 1:
            return list(self._reports[0])
        merged = [
            (r.step, shard, r)
            for shard, reps in enumerate(self._reports) for r in reps
        ]
        return [r for _, _, r in sorted(merged, key=lambda t: (t[0], t[1]))]

    def _run(self, lane: int) -> None:
        q = self._queues[lane]
        while True:
            item = q.get()
            if item is None:
                q.task_done()
                return
            step, state = item
            try:
                self._reports[lane].append(self.managers[lane].save(step, state))
            except BaseException as e:  # surfaced on wait()
                self.errors.append(e)
            finally:
                q.task_done()

    @staticmethod
    def stage(state: Dict[str, Any]) -> Dict[str, np.ndarray]:
        """Device→host staging copy (the only synchronous cost). Must be a
        real copy: the training loop mutates the live buffers immediately
        after submit(). The ``flusher.stage`` span counts its ``bytes``."""
        with span("flusher.stage") as sp:
            staged = {k: np.array(v, copy=True) for k, v in state.items()}
            sp.add(bytes=sum(v.nbytes for v in staged.values()))
        return staged

    def submit(self, step: int, state: Dict[str, Any], *, shard: int = 0) -> None:
        """Stage and enqueue one shard's save; blocks only if that shard
        already has ``max_pending`` saves in flight (back-pressure instead
        of unbounded host RAM). The wait for a slot is the
        ``flusher.queue_wait`` span, with the queue's ``depth`` at entry."""
        staged = self.stage(state)
        q = self._queues[shard]
        with span("flusher.queue_wait", depth=q.qsize(), shard=shard):
            q.put((step, staged))

    def submit_all(self, step: int, states: Sequence[Dict[str, Any]]) -> None:
        """Stage and enqueue one save per shard (lane-parallel flush)."""
        if len(states) != len(self.managers):
            raise ValueError(
                f"{len(states)} shard states for {len(self.managers)} managers")
        for shard, state in enumerate(states):
            self.submit(step, state, shard=shard)

    def wait(self) -> List[SaveReport]:
        for q in self._queues:
            q.join()
        if self.errors:
            raise self.errors[0]
        return self.reports

    def restore_all(self, *, verify: bool = True):
        """Restore every shard (drains in-flight saves first) and return
        ``(step, states)`` — the common committed step and one state
        dict per shard. Each shard restores through its own manager, so
        the per-shard ``kernel_impl`` (fused ``apply_unpack`` vs staged)
        and restore accounting (``manager.last_restore``) apply
        shard-by-shard. Raises if the shards disagree on the newest
        committed step — a torn multi-shard save (submit_all + wait
        makes this impossible in normal operation)."""
        self.wait()
        steps, states = [], []
        for mgr in self.managers:
            step, state = mgr.restore(verify=verify)
            steps.append(step)
            states.append(state)
        if len(set(steps)) != 1:
            raise RuntimeError(
                f"shards restored different steps {steps}: torn "
                f"multi-shard checkpoint")
        return steps[0], states

    def close(self) -> List[SaveReport]:
        for q in self._queues:
            q.put(None)
        for q in self._queues:
            q.join()
        for w in self._workers:
            w.join(timeout=60)
        if self.errors:
            raise self.errors[0]
        return self.reports
