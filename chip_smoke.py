#!/usr/bin/env python3
"""Chip smoke run: mamba2-130m trains, checkpoints, is killed and resumes on one TPU.

Drives the training-checkpoint path through its normal entry points —
``repro.launch.train.Trainer``, ``AsyncFlusher`` → ``CheckpointManager.save``
(the compiled ``popcount_blocks`` on the first, full save and the compiled
``flush_pack`` on every delta save), a real SIGKILL, and
``CheckpointManager.restore`` (the compiled ``apply_unpack``) — with the
published mamba2-130m config (24 layers, d_model 768, vocab 50432) at batch
8 × seq 2048, random weights from ``jax.random.key(0)`` and synthetic data.

  a. train from step 0, checkpointing every ``ckpt_every`` steps: a full
     save, then delta saves. Keep a host copy of the state at the last
     checkpoint and the loss of every step.
  b. train on past that checkpoint and commit the next step to the WAL,
     then die by SIGKILL.
  c. a fresh process restores the checkpoint (``start_step > 0``) and
     trains on to ``steps``, replaying the steps the crash lost.
  d. check: the restored state is bit-identical to the host copy; the
     replayed losses equal the pre-crash ones; every save and restore ran
     the compiled Pallas kernels; and for every leaf of one delta save and
     one restore the kernels' outputs equal the jnp oracles run on the chip.

Each phase runs in a child process, one after the other, under a parent
that never imports JAX, so exactly one process holds the chip at a time.
Pool files (several GB) live in ``.chip_smoke/`` of the checkout and are
removed at the end.

    python chip_smoke.py

The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``;
any failed phase, or a backend that is not a TPU, exits nonzero without it.
"""

from __future__ import annotations

import json
import math
import shutil
import signal
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

#: the chip run: published mamba2-130m widths and depth, one chip
CHIP_JOB = dict(
    arch="mamba2-130m", reduced=False, batch=8, seq=2048,
    steps=6, ckpt_every=2, crash_after=5,
    expect_impl="pallas", require_tpu=True,
)

#: seconds the whole run may take, kept under a 1200-second budget
DEADLINE_S = 1100
#: a child's exit code when JAX finds no TPU
EXIT_NO_TPU = 3


class SmokeFailure(RuntimeError):
    """A phase failed; the message says which and why."""


def _say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


# ----------------------------------------------------------------- parent

def smoke(work: Path, **overrides) -> dict:
    """Run phases a–d in two child processes, one after the other, with
    their pools under ``work``; return the resumed run's results. Raises
    :class:`SmokeFailure` when any phase fails."""
    job = dict(CHIP_JOB, **overrides, work=str(work))
    work.mkdir(parents=True, exist_ok=True)
    job_path = work / "job.json"
    job_path.write_text(json.dumps(job))
    deadline = time.monotonic() + DEADLINE_S
    child = [sys.executable, str(Path(__file__).resolve()), "--phase"]

    # a + b: train to the crash point, then die by SIGKILL
    ready = work / "train.ready"
    proc = subprocess.Popen(child + ["train", str(job_path)])
    try:
        while not ready.exists():
            if proc.poll() is not None:
                raise SmokeFailure(_exit_reason("train", proc.returncode))
            if time.monotonic() > deadline:
                raise SmokeFailure("train: out of time before the crash point")
            time.sleep(0.2)
        proc.send_signal(signal.SIGKILL)
        proc.wait()
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if proc.returncode != -signal.SIGKILL:
        raise SmokeFailure(f"train: exited {proc.returncode} instead of "
                           f"dying by SIGKILL")
    _say(f"phase b: train process killed by SIGKILL after the WAL commit "
         f"of step {job['crash_after']}")

    # c + d: a fresh process resumes on the same directory and checks
    try:
        rc = subprocess.run(child + ["resume", str(job_path)],
                            timeout=max(1.0, deadline - time.monotonic())
                            ).returncode
    except subprocess.TimeoutExpired:
        raise SmokeFailure("resume: out of time") from None
    if rc:
        raise SmokeFailure(_exit_reason("resume", rc))
    return json.loads((work / "resume.json").read_text())


def _exit_reason(phase: str, rc: int) -> str:
    if rc == EXIT_NO_TPU:
        return f"{phase}: JAX finds no TPU"
    return f"{phase}: exited {rc}"


def main() -> int:
    if len(sys.argv) == 4 and sys.argv[1] == "--phase":
        job = json.loads(Path(sys.argv[3]).read_text())
        return {"train": phase_train, "resume": phase_resume}[sys.argv[2]](job)
    if len(sys.argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    if not (SRC / "repro").is_dir():
        print("chip_smoke.py: no src/repro next to this script — run it from "
              "a checkout of the repository", file=sys.stderr)
        return 2
    work = ROOT / ".chip_smoke"
    shutil.rmtree(work, ignore_errors=True)
    try:
        result = smoke(work)
    except SmokeFailure as e:
        print(f"chip_smoke.py: FAILED — {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps({"ok": True, "device": result["device"]}))
    return 0


# --------------------------------------------------------------- children

def _open_jax(job: dict):
    """Import JAX in a child: source path, compile cache, and the device
    check that comes before any phase."""
    sys.path.insert(0, str(SRC))
    import jax

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    dev = jax.devices()[0]
    if job["require_tpu"] and dev.platform != "tpu":
        print(f"chip_smoke.py: JAX finds no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        sys.exit(EXIT_NO_TPU)
    return jax


def _trainer(job: dict):
    from repro.launch.train import Trainer, TrainerConfig

    return Trainer(TrainerConfig(
        arch=job["arch"], reduced=job["reduced"], steps=job["steps"],
        batch=job["batch"], seq=job["seq"], ckpt_every=job["ckpt_every"],
        out=str(Path(job["work"]) / "run"), async_flush=True,
        wal_capacity_steps=1024))


def _steps(jax, trainer, start: int, stop: int):
    """Train steps [start, stop) one ``Trainer.run`` call each, so every
    step's wall time ends in ``block_until_ready``. A step whose number
    is a checkpoint step includes staging that save for the flusher (its
    stall on the step loop). Returns (losses, seconds per step)."""
    losses, secs = [], []
    for s in range(start, stop):
        trainer.start_step = s
        t0 = time.perf_counter()
        out = trainer.run(crash_at=s + 1)
        jax.block_until_ready((trainer.params, trainer.opt_state))
        secs.append(time.perf_counter() - t0)
        losses += out["losses"]
    return losses, secs


def _host_bytes(state: dict) -> dict:
    import numpy as np

    return {k: np.ascontiguousarray(v).view(np.uint8).reshape(-1)
            for k, v in state.items()}


def _device_info(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _peak_bytes(jax):
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _check_impl(reports, what: str, expect: str) -> None:
    ran = sorted({r.kernel_impl for r in reports})
    if ran != [expect]:
        raise SmokeFailure(f"{what} ran {ran}, expected only {expect!r}")


def _steps_line(label: str, first: int, secs, losses) -> str:
    if not all(math.isfinite(x) for x in losses):
        raise SmokeFailure(f"{label}: non-finite losses {losses}")
    return f"{label}: " + ", ".join(
        f"step {first + i} {s:.3f} s loss {x:.6f}"
        for i, (s, x) in enumerate(zip(secs, losses)))


def phase_train(job: dict) -> int:
    """Phases a and b (child 1): returns only by being killed."""
    import numpy as np

    jax = _open_jax(job)
    work, k, crash = Path(job["work"]), job["ckpt_every"], job["crash_after"]
    last_ckpt = crash // k * k
    if not 0 < last_ckpt < crash < job["steps"]:
        raise SmokeFailure(f"crash_after={crash} is not past a checkpoint "
                           f"and before the last step")
    _say(f"device {_device_info(jax)}")
    t0 = time.perf_counter()
    trainer = _trainer(job)
    if trainer.start_step != 0:
        raise SmokeFailure("train: the run directory was not fresh")
    _say(f"phase a: Trainer built in {time.perf_counter() - t0:.1f} s "
         f"({job['arch']}, batch {job['batch']} x seq {job['seq']})")
    losses, secs = _steps(jax, trainer, 0, last_ckpt)
    _say(_steps_line("phase a (first includes compile)", 0, secs, losses))
    t0 = time.perf_counter()
    reports = trainer.flusher.wait()
    _say(f"phase a: waited {time.perf_counter() - t0:.1f} s for the flusher "
         f"after step {last_ckpt}")
    _check_impl(reports, "saves", job["expect_impl"])
    if [r.step for r in reports] != list(range(k, last_ckpt + 1, k)):
        raise SmokeFailure(f"saves committed steps {[r.step for r in reports]}")
    for r in reports:
        _say(f"phase a: save @ step {r.step}: {r.bytes_logical} B, "
             f"{r.pages_cow} CoW / {r.pages_mulog} µLog / {r.pages_clean} "
             f"clean pages, kernels {r.kernel_impl}; modeled (not measured) "
             f"PMem time {r.modeled_ns / 1e6:.1f} ms")
    np.savez(work / "ckpt_state.npz", **_host_bytes(trainer._ckpt_state()))

    more, secs_b = _steps(jax, trainer, last_ckpt, crash)
    if trainer.wal.last is None or trainer.wal.last.step != crash:
        raise SmokeFailure("train: the WAL did not commit the crash step")
    _say(_steps_line("phase b", last_ckpt, secs_b, more))
    (work / "train.json").write_text(json.dumps({
        "losses": losses + more, "last_ckpt": last_ckpt,
        "peak_bytes_in_use": _peak_bytes(jax)}))
    (work / "train.ready").touch()
    while True:                      # wait here for the SIGKILL
        signal.pause()


def phase_resume(job: dict) -> int:
    """Phases c and d (child 2)."""
    import numpy as np

    jax = _open_jax(job)
    work = Path(job["work"])
    before = json.loads((work / "train.json").read_text())
    last_ckpt, crash = before["last_ckpt"], job["crash_after"]

    t0 = time.perf_counter()
    trainer = _trainer(job)
    restore_s = time.perf_counter() - t0
    start_step = trainer.start_step
    if start_step <= 0:
        raise SmokeFailure("resume: started fresh instead of restoring")
    if start_step != last_ckpt:
        raise SmokeFailure(f"resume: restored step {start_step}, "
                           f"expected {last_ckpt}")
    rr = trainer.manager.last_restore
    _check_impl([rr], "restore", job["expect_impl"])
    _say(f"phase c: restored step {rr.step} ({rr.pages_total} pages, "
         f"kernels {rr.kernel_impl}) in {restore_s:.1f} s wall")

    # d1: the restored state is the state that was saved, bit for bit
    restored = _host_bytes(trainer._ckpt_state())
    with np.load(work / "ckpt_state.npz") as saved:
        if set(saved.files) != set(restored):
            raise SmokeFailure("restore: leaf names differ from the saved state")
        bad = [k for k in saved.files
               if not np.array_equal(saved[k], restored[k])]
    if bad:
        raise SmokeFailure(f"restore: {len(bad)} leaves differ, e.g. {bad[0]}")
    _say(f"phase d: restored state bit-identical to the saved host copy "
         f"({sum(v.size for v in restored.values())} bytes, "
         f"{len(restored)} leaves)")

    # c: train on to the end, replaying the steps the crash lost
    losses, secs = _steps(jax, trainer, last_ckpt, job["steps"])
    _say(_steps_line("phase c (first includes compile; the last waits for "
                     "the final save)", last_ckpt, secs, losses))
    replayed = losses[: crash - last_ckpt]
    if replayed != before["losses"][last_ckpt:crash]:
        raise SmokeFailure(f"replayed losses {replayed} != pre-crash "
                           f"{before['losses'][last_ckpt:crash]}")
    _say(f"phase d: replayed losses equal the pre-crash ones: {replayed}")
    reports = trainer.flusher.wait()
    _check_impl(reports, "saves", job["expect_impl"])
    _say(f"phase c: saves after resume at steps {[r.step for r in reports]}, "
         f"kernels {sorted({r.kernel_impl for r in reports})}")

    # d2: kernels == oracles, on the device, for every leaf of the last
    # delta save (live state vs the restored snapshot) and of a restore
    t0 = time.perf_counter()
    nleaves = _check_kernels(_host_bytes(trainer._ckpt_state()), restored,
                             trainer.manager.cfg)
    _say(f"phase d: flush_pack, popcount_blocks and apply_unpack equal the "
         f"jnp oracles on all {nleaves} leaves ({time.perf_counter() - t0:.1f} s)")
    device = _device_info(jax)
    (work / "resume.json").write_text(json.dumps({
        "device": device, "start_step": start_step,
        "peak_bytes_in_use": [before["peak_bytes_in_use"], _peak_bytes(jax)]}))
    _say(f"peak_bytes_in_use: train {before['peak_bytes_in_use']}, "
         f"resume {_peak_bytes(jax)}")
    return 0


def _check_kernels(live: dict, snap: dict, cfg) -> int:
    """Each leaf through the compiled kernels and through their jnp oracles
    on the same device, at the checkpoint manager's geometry: flush_pack
    and popcount_blocks over 4 KiB blocks (save), apply_unpack over whole
    pages (restore). Any difference raises."""
    import jax.numpy as jnp

    from repro.kernels.apply_unpack import apply_unpack
    from repro.kernels.flush_pack import flush_pack
    from repro.kernels.popcnt_checksum import popcount_blocks

    block, page = cfg.geometry.cache_line, cfg.page_size

    def same(a, b, what, name):
        if not bool(jnp.array_equal(a, b)):       # compared on the device
            raise SmokeFailure(f"{what} kernel != oracle on leaf {name}")

    for name in sorted(live):
        cur, old = jnp.asarray(live[name]), jnp.asarray(snap[name])
        fk = flush_pack(cur, old, block_bytes=block, impl="pallas")
        fo = flush_pack(cur, old, block_bytes=block, impl="ref")
        if fk.total != fo.total:
            raise SmokeFailure(f"flush_pack total {fk.total} != {fo.total} "
                               f"on leaf {name}")
        for field in ("flags", "counts", "offsets", "packed", "index"):
            same(getattr(fk, field), getattr(fo, field),
                 f"flush_pack.{field}", name)
        same(popcount_blocks(cur, block_bytes=block, impl="pallas"),
             popcount_blocks(cur, block_bytes=block, impl="ref"),
             "popcount_blocks", name)
        k = -(-live[name].size // page)
        pages = jnp.pad(old, (0, k * page - old.size))
        want = popcount_blocks(pages, block_bytes=page, impl="ref")
        base = jnp.zeros_like(pages)
        idx = jnp.arange(k, dtype=jnp.int32)
        ak = apply_unpack(base, pages, idx, want, block_bytes=page,
                          impl="pallas")
        ao = apply_unpack(base, pages, idx, want, block_bytes=page, impl="ref")
        if ak.nbad or ao.nbad:
            raise SmokeFailure(f"apply_unpack rejected a page of leaf {name}")
        for field in ("out", "ok", "counts"):
            same(getattr(ak, field), getattr(ao, field),
                 f"apply_unpack.{field}", name)
    return len(live)


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:       # raised inside a child phase
        print(f"chip_smoke.py: FAILED — {e}", file=sys.stderr)
        sys.exit(1)
