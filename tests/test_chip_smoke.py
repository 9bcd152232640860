"""``chip_smoke.py`` off the chip: its save → kill → resume → compare
sequence at a reduced mamba2-130m, and its refusal to run without a TPU.

The sequence test calls the same :func:`smoke` the script's ``main`` calls,
steered from here to the reduced config, the CPU backend, and the jnp
oracle that ``kernel_impl="auto"`` runs off the TPU (the kernel-vs-oracle
phase then runs the kernels in interpret mode).
"""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cpu_env(tmp_path) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.pop("PYTHONPATH", None)          # the script finds src/ itself
    env.pop("XLA_FLAGS", None)
    return env


def test_smoke_save_kill_resume_reduced(chip_smoke, tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    work = tmp_path / "work"
    result = chip_smoke.smoke(work, reduced=True, batch=2, seq=32,
                              expect_impl="ref", require_tpu=False)
    assert result["start_step"] == 4           # restored, not fresh
    # one device: nothing on the path imported the dry-run, which asks
    # XLA for 512 host devices as it is imported
    assert result["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    # the first child died by SIGKILL after committing step 5 to its WAL
    assert json.loads((work / "train.json").read_text())["last_ckpt"] == 4


def test_smoke_refuses_a_backend_without_tpu(tmp_path):
    proc = subprocess.run([sys.executable, str(SCRIPT)], cwd=tmp_path,
                          env=_cpu_env(tmp_path), capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr
    assert not (ROOT / ".chip_smoke").exists()


def test_smoke_refuses_to_run_outside_a_checkout(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    proc = subprocess.run([sys.executable, str(lone)], cwd=tmp_path,
                          env=_cpu_env(tmp_path), capture_output=True,
                          text=True, timeout=60)
    assert proc.returncode != 0
    assert proc.stdout == ""


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "checkout"])
def test_compile_cache_placement(tmp_path, from_env):
    """The entry points' cache is $JAX_COMPILATION_CACHE_DIR when set, and
    otherwise the checkout's fixed .jax_cache — never a per-run path."""
    env = _cpu_env(tmp_path)
    if not from_env:
        del env["JAX_COMPILATION_CACHE_DIR"]
    env["PYTHONPATH"] = str(ROOT / "src")
    probe = ("import jax\n"
             "from repro.launch.compile_cache import use_compile_cache\n"
             "print(use_compile_cache())\n"
             "print(jax.config.jax_compilation_cache_dir)\n")
    out = subprocess.run([sys.executable, "-c", probe], env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True).stdout.split()
    want = str(tmp_path / "jax_cache") if from_env else str(ROOT / ".jax_cache")
    assert out == [want, want]
