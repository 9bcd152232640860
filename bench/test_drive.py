"""CPU rehearsal of both traffic loops (train with async and with sync
saves), of the check that decides ``correct``, and of ``run.py``'s
refusals.

Runs the harness as ``run.py`` does, minus the look for a chip, at the
reduced Mamba-2 on the CPU (the kernels' jnp oracles run in place of the
Pallas kernels there). Shows that operations are counted whole, that a
checkpoint restores bit for bit, that each fault the cells can have makes
``correct`` false, and that ``run.py`` exits nonzero, printing no result,
without a TPU or without the program.

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_drive.py
"""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from conftest import HERE, ROOT, reduced_cell

import check  # noqa: E402
import drive  # noqa: E402

TRAIN, RESUME = "m2-130m.async.ckpt4", "m2-130m.sync.resume"
SYNC_TRAIN = "m2-130m.sync.ckpt8"          # saves on the loop, no flusher
SEED = 2**31 + 4242        # larger than 32 signed bits hold
#: more seeds for the fault whose reading depends on the data
HALF_BATCH_SEEDS = (SEED, 7, 123456789, 2**33 + 5)


def _run(cell, tmp_path, seconds=2.0, fault=None, seed=SEED):
    cfg, traffic = reduced_cell(cell)
    d = drive.Driver(cfg, traffic, seed, seconds, tmp_path / "run",
                     t_start=time.perf_counter(), fault=fault)
    run = getattr(d, traffic["loop"])()
    checks = check.compare(d, run)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    return d, run, checks, correct


@pytest.mark.parametrize("cell", [TRAIN, SYNC_TRAIN])
def test_train_loop_counts_whole_intervals(tmp_path, cell):
    d, run, checks, correct = _run(cell, tmp_path)
    k = d.traffic["ckpt_every"]
    lo, hi = run.window
    steps = run.spans.within("step", lo, hi)
    assert run.ops == len(steps) > 0
    assert run.ops % k == 0                      # whole intervals only
    assert run.ops >= k * d.traffic["min_ops"]
    assert steps[-1].t1 <= hi and (steps[-1].step + 1) % k == 0
    assert steps[0].t0 - lo < 0.05               # no gap at the opening
    # saves ran in the window too: on the flusher's thread, or with sync
    # saves each inside the step that ends its interval
    saves = [s for s in run.spans.items
             if s.name == "save" and lo <= s.t1 <= hi]
    assert saves
    if not d.cfg["deployment"]["async_flush"]:
        assert len(saves) == run.ops // k
        for sv in saves:
            assert any(st.t0 <= sv.t0 and sv.t1 <= st.t1
                       and st.step + 1 == sv.step for st in steps)
    assert checks["restore_bytes_differing"]["value"] == 0
    assert checks["restored_step_behind"]["value"] == 0
    assert correct, checks


def test_resume_loop_counts_whole_resumes(tmp_path):
    d, run, checks, correct = _run(RESUME, tmp_path, seconds=0.5)
    lo, hi = run.window
    ops = run.spans.within("resume", lo, hi)
    assert run.ops == len(ops) >= 1
    for op in ops:                               # build, then one step
        inner = [s for s in run.spans.items
                 if s.name in ("build", "step") and op.t0 <= s.t0 <= op.t1]
        assert [s.name for s in inner] == ["build", "step"]
        assert inner[1].step == d.traffic["saved_steps"]
    assert len(run.losses) == d.checked
    assert correct, checks


@pytest.mark.parametrize("cell", [TRAIN, SYNC_TRAIN])
def test_traced_train_run_reads_the_host_side_metrics(tmp_path, cell):
    import readers

    cfg, traffic = reduced_cell(cell)
    d = drive.Driver(cfg, traffic, SEED, 1.0, tmp_path / "run",
                     t_start=time.perf_counter(), trace_dir=tmp_path / "trace")
    run = d.train()
    run.trace = readers.TRACE.Trace.load(str(tmp_path / "trace"))
    run.summary = readers.TRACE.summarize(run.trace)
    assert run.save_dirty_blocks                  # counted for the saves kept
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    mine = [m for m in spec["per_layer"] if cell in m["workloads"]]
    got = readers.read(run, mine, "TPU v5 lite")
    # every metric listed for the cell reads a number; the CPU has no TPU
    # plane, so only the trace's readers find nothing there
    for m in mine:
        if m["source"] != "device_trace":
            assert got[m["name"]]["value"] > 0, m["name"]
    assert 0 < got["mfu.save"]["value"] < 100
    assert "flush_pack_roofline" not in got
    # staging reads every save; the queue's wait reads submits, which sync
    # saves have none of
    assert readers.value(run, "stage.stall_s") > 0
    assert (readers.value(run, "flusher.wait_s") is None) == (
        cell == SYNC_TRAIN)


def test_trainer_takes_the_configured_manifest_capacity(tmp_path):
    cfg, traffic = reduced_cell(TRAIN)
    d = drive.Driver(cfg, traffic, SEED, 1.0, tmp_path / "run",
                     t_start=time.perf_counter())
    t = d.new_trainer()
    want = cfg["deployment"]["manifest_capacity"]
    assert t.manager.cfg.manifest_capacity == want
    drive.check_program_matches(t, cfg)
    cfg["deployment"]["manifest_capacity"] = want // 2
    with pytest.raises(RuntimeError, match="manifest_capacity"):
        drive.check_program_matches(t, cfg)


@pytest.mark.parametrize("cell,fault,seed", [
    (TRAIN, "unchanged_state", SEED), (TRAIN, "flip_byte", SEED),
    (RESUME, "unchanged_state", SEED), (RESUME, "flip_byte", SEED),
    (SYNC_TRAIN, "unchanged_state", SEED), (SYNC_TRAIN, "flip_byte", SEED),
] + [(TRAIN, "half_batch", s) for s in HALF_BATCH_SEEDS])
def test_each_fault_makes_the_run_incorrect(tmp_path, cell, fault, seed):
    _, _, checks, correct = _run(cell, tmp_path, seconds=0.5, fault=fault,
                                 seed=seed)
    assert not correct, checks


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_run_py_refuses_without_a_tpu(tmp_path):
    proc = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload", TRAIN,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=dict(_cpu_env(), JAX_COMPILATION_CACHE_DIR=str(
            tmp_path / "cache")),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_run_py_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", TRAIN, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_cpu_env(), capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_benchmark_json_names_files_that_exist():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        model = drive.reference_model(json.loads((ROOT / c["file"]).read_text()))
        for piece in ("PROGRAM", "train_flops_per_token", "REDUCED"):
            assert hasattr(model, piece), (c["name"], piece)
    for w in spec["workloads"]:
        assert (HERE / "traffic" / f"{w['traffic']}.json").is_file()
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert (HERE / "metrics" / f"{m['name']}.py").is_file()


def test_dirty_blocks_counts_each_changed_block_once():
    import numpy as np

    new = {"a": np.zeros(3 * 1024 + 10, np.float32),     # 3 blocks + a tail
           "b": np.zeros(7, np.uint8)}
    old = {k: v.copy() for k, v in new.items()}
    assert drive.dirty_blocks(new, old) == 0
    old["a"][[0, 1, 2048, 3 * 1024 + 9]] = 1.0           # blocks 0, 2, tail
    old["b"][3] = 1
    assert drive.dirty_blocks(new, old) == 4
