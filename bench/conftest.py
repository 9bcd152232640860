"""Shared pieces of the benchmark's own tests (not part of the repo's
tier-1 suite, which collects ``tests/`` only).

The tests run the harness on the CPU at the reduced Mamba-2 the repo's
CPU tests use; ``reduced_cell`` gives a cell's configuration and traffic
at that size, with the limits of the real configuration."""

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
for p in (str(HERE), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: the program's reduced mamba2 (``configs/mamba2_130m.py: reduced()``)
REDUCED = dict(d_model=64, n_layer=4, d_state=16,
               nheads=4, headdim=16, vocab_size=512,
               pad_vocab_size_multiple=16, chunk_size=16, batch=2, seq=64)


def reduced_cell(name: str):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in spec["workloads"]}[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = json.loads((ROOT / conf["file"]).read_text())
    cfg.update(REDUCED)
    traffic = json.loads(
        (HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    return cfg, traffic


@pytest.fixture(autouse=True)
def _cpu_only(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
