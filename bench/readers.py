"""Finds and runs the reader of each metric, ``bench/metrics/<name>.py``.

A reader is a module with ``read(run)``: it takes the metric from the
run's spans, counters and trace (``drive.Run``), and returns a number, or
None where the run holds nothing for it to read; the metric is then left
out of the result. ``run.peaks`` (the chip's row of ``peaks.json``),
``run.work`` (``work.py``), ``run.model`` (the configuration's reference
module) and :func:`value` (another metric of the same run) are there for
readers that need them.
"""

from __future__ import annotations

from pathlib import Path
from typing import Dict, List, Optional

from drive import load_module, reference_model

BENCH = Path(__file__).resolve().parent
TRACE = load_module(BENCH / "trace.py", "bench_trace")
WORK = load_module(BENCH / "work.py", "bench_work")

_readers: Dict[str, object] = {}


def reader(name: str):
    if name not in _readers:
        _readers[name] = load_module(BENCH / "metrics" / f"{name}.py",
                                     f"bench_metric_{name.replace('.', '_')}")
    return _readers[name]


def value(run, name: str) -> Optional[float]:
    return reader(name).read(run)


def read(run, specs: List[Dict], device_kind: str) -> Dict[str, Dict]:
    """``{name: {"value": v, "unit": u}}`` of each metric in ``specs`` that
    its reader finds something to read for."""
    run.peaks = WORK.peaks(device_kind)
    run.work = WORK
    run.model = reference_model(run.cfg)
    run.trace_lib = TRACE
    out = {}
    for spec in specs:
        v = value(run, spec["name"])
        if v is not None:
            out[spec["name"]] = {"value": v, "unit": spec["unit"]}
    return out
