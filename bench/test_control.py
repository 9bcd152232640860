"""The control at a size a test run can hold: the reference computed with
every matrix product's operands in float8 (e4m3), put in the program's
place, must come out not correct under the configuration's limits, while
the program itself, on the same seeds, comes out correct.

The control at the cells' own size runs on the chip through
``calibrate.py``; ``PERF.md`` gives those readings.

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_control.py
"""

import time
import types

import jax.numpy as jnp
import pytest

from conftest import reduced_cell

import check  # noqa: E402
import drive  # noqa: E402

SEEDS = [7, 2**31 + 99, 123456789]


def _passes(values, limits):
    return all(v <= limits[k] for k, v in values.items())


@pytest.mark.parametrize("seed", SEEDS)
def test_control_fails_where_the_program_passes(tmp_path, seed):
    cfg, traffic = reduced_cell("m2-130m.async.ckpt4")
    d = drive.Driver(cfg, traffic, seed, 0.0, tmp_path / "run",
                     t_start=time.perf_counter())
    trainer = d.start_trainer()
    for s in range(d.checked):
        d.checked_step(trainer, d.run.spans, s)
    del trainer
    batches = d.feed.batches(d.checked)
    ref = d.ref.train_readings(cfg, seed, batches)
    low = d.ref.train_readings(cfg, seed, batches,
                               compute_dtype=jnp.float8_e4m3fn)
    program = check.readings(d.run, ref)
    control = check.readings(types.SimpleNamespace(**low), ref)
    assert _passes(program, cfg["limits"]), program
    assert not _passes(control, cfg["limits"]), control
