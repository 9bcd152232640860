#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as one JSON line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell, its configuration
(``bench/configs/``) and its traffic mix (``bench/traffic/<mix>.json``)
are found by name from ``BENCHMARK.json``; a per-layer metric is read by
``bench/metrics/<metric>.py``. Set-up makes the weights and tokens from
the seed and warms every shape the window uses; the window then runs for
``--seconds`` (whole operations, see ``drive.py``); after it, what the
window produced is compared with the plain reference (``check.py``).

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window and the harness's spans. The last lines on standard error, and the
result's last key ``checks``, give each number compared beside its limit.

Exits nonzero without printing a result when JAX finds no TPU, or fewer
chips than the cell asks for, or no program beside the benchmark.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

#: exit codes
EXIT_USAGE, EXIT_NO_CHIP = 2, 3


def load_cell(name: str):
    """(workload, config, traffic, end_to_end, per_layer) of cell ``name``
    from BENCHMARK.json."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"run.py: no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return cell, cfg, traffic, mine(spec["end_to_end"]), mine(spec["per_layer"])


def open_chips(chips: int):
    """Import JAX with the checkout's compile cache and return the devices;
    exits when they are not ``chips`` TPUs or more."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"run.py: the cell needs {chips} TPU chip(s); JAX finds "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        sys.exit(EXIT_NO_CHIP)
    return devs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print("run.py: no program (src/repro) beside the benchmark",
              file=sys.stderr)
        return EXIT_USAGE
    cell, cfg, traffic, e2e, per_layer = load_cell(args.workload)
    devs = open_chips(cell["chips"])

    import check
    import drive
    import readers

    out = ROOT / ".bench_run"
    shutil.rmtree(out, ignore_errors=True)
    out.mkdir()
    trace_dir = out / "trace" if args.trace else None
    try:
        d = drive.Driver(cfg, traffic, args.seed, args.seconds, out / "run",
                         t_start=T_START, trace_dir=trace_dir)
        run = getattr(d, traffic["loop"])()
        if trace_dir is not None:
            t0 = time.perf_counter()
            run.trace = readers.TRACE.Trace.load(str(trace_dir))
            run.summary = readers.TRACE.summarize(run.trace)
            drive.log(f"trace read: {time.perf_counter() - t0:.3f} s, "
                      f"{len(run.trace.ops)} device operations")
            for mod, sec in sorted(run.summary.module_seconds.items(),
                                   key=lambda kv: -kv[1])[:12]:
                drive.log(f"device time of module {mod}: {sec!r} s")
        checks = check.compare(d, run)
    finally:
        shutil.rmtree(out, ignore_errors=True)

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
              "attempted": run.ops, "failed": run.failed}
    if args.trace:
        result["metrics"] = readers.read(run, per_layer, devs[0].device_kind)
        device.update(busy_s=run.summary.busy_s, window_s=run.summary.window_s)
        result["device"] = device
        result["breakdown"] = readers.TRACE.breakdown(run.summary)
    else:
        result["metrics"] = readers.read(run, e2e, devs[0].device_kind)
        result["device"] = device
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
