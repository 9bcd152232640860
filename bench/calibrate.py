#!/usr/bin/env python3
"""Readings that the check's limits are set from, at a cell's own size.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,... \
        [--control-seeds 11,12,13] [--fault-seeds 11,12,13] [--out FILE]

For each seed, in one process that holds the chip: the program trains the
cell's first ``checked_steps`` steps through ``Trainer.run`` exactly as a
run's set-up does, the plain reference trains them in float32, and
``check.readings`` compares the two — the lower readings. On the control
seeds the reference computed with every matrix product's operands in
float8 (e4m3) stands in the program's place — the control, which must read
far above. On the fault seeds the program runs with half of each batch's
rows left out (``drive._HalfBatch``). A state left unchanged reads 1 on
``update_gap`` by its definition and needs no run; a flipped byte is
caught by an exact comparison.

Prints one JSON line per seed and kind, and appends them to ``--out``.
The benchmark's own runs never run this; ``PERF.md`` gives the readings
each limit was set from. Exits nonzero without a TPU.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

from run import ROOT, load_cell, open_chips  # noqa: E402


def program_readings(drive, cfg, traffic, seed, out, fault=None):
    d = drive.Driver(cfg, traffic, seed, 0.0, out, t_start=T_START,
                     fault=fault)
    trainer = d.start_trainer()
    for s in range(d.checked):
        d.checked_step(trainer, d.run.spans, s)
    del trainer
    gc.collect()
    shutil.rmtree(out, ignore_errors=True)
    return d, d.run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    faults = {int(s) for s in args.fault_seeds.split(",") if s}

    cell, cfg, traffic, _, _ = load_cell(args.workload)
    open_chips(cell["chips"])
    import jax.numpy as jnp

    import check
    import drive

    out = ROOT / ".bench_run" / "calibrate"
    lines = []

    def emit(kind, seed, values, seconds):
        line = dict(kind=kind, workload=args.workload, seed=seed,
                    seconds=seconds, **{k: float(v) for k, v in values.items()})
        lines.append(line)
        print(json.dumps(line), flush=True)

    for seed in seeds:
        t0 = time.perf_counter()
        d, run = program_readings(drive, cfg, traffic, seed, out)
        t1 = time.perf_counter()
        batches = d.feed.batches(d.checked)
        ref = d.ref.train_readings(cfg, seed, batches)
        t2 = time.perf_counter()
        emit("program", seed, check.readings(run, ref), [t1 - t0, t2 - t1])
        if seed in control:
            t0 = time.perf_counter()
            low = d.ref.train_readings(cfg, seed, batches,
                                       compute_dtype=jnp.float8_e4m3fn)
            emit("control_fp8", seed,
                 check.readings(types.SimpleNamespace(**low), ref),
                 [time.perf_counter() - t0])
        if seed in faults:
            t0 = time.perf_counter()
            _, bad = program_readings(drive, cfg, traffic, seed, out,
                                      fault="half_batch")
            emit("fault_half_batch", seed, check.readings(bad, ref),
                 [time.perf_counter() - t0])
    if args.out:
        with open(args.out, "a") as f:
            for line in lines:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
