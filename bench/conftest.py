"""Shared pieces of the benchmark's own tests (not part of the repo's
tier-1 suite, which collects ``tests/`` only).

The tests run the harness on the CPU at a reduced model: ``reduced_cell``
gives a cell's configuration and traffic with the model's sizes from its
reference module's ``REDUCED`` (the program's own reduced configuration),
a short batch and sequence, and the limits of the real configuration."""

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
for p in (str(HERE), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import drive  # noqa: E402

#: the rehearsal's batch and sequence length
SHORT = dict(batch=2, seq=64)


def reduced_cell(name: str):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in spec["workloads"]}[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = json.loads((ROOT / conf["file"]).read_text())
    cfg.update(drive.reference_model(cfg).REDUCED, **SHORT)
    traffic = json.loads(
        (HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    return cfg, traffic


@pytest.fixture(autouse=True)
def _cpu_only(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
