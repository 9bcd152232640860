#!/usr/bin/env python3
"""The program's own spans in a profiler trace: phases, self times, and
idle gaps named by the phase the loop was in.

The program marks its phases with host annotations named ``repro:<name>``
(``repro.spans``), on the trace's clock and with their stats (bytes moved,
step, queue depth). ``trace.py`` reduces the device lines and the
harness's ``bench:`` spans; this module adds, from the same file:

- ``load``: the program's spans and the harness's, each with its thread
  (the plane's line) and, for program spans, its stats;
- ``self_ns``: a span's duration less what its nested spans on the same
  thread cover;
- ``gaps``: the device's idle gaps of ``trace.summarize``, each labelled by
  the innermost program span open at its midpoint on the loop's thread
  (the thread that holds ``bench:window``), with `` / `` and the span that
  another thread opened later, if one is open (``flusher.queue_wait /
  ckpt.save.epoch``). Where no program span is open on the loop's thread
  the label is ``trace.summarize``'s own: on a trace without program
  spans the gaps are exactly those.
- ``operations``: per harness ``save@<step>`` or ``build`` span inside the
  window, the self time of each phase of the program's top span in it.

    python3 bench/program_trace.py <trace dir or .xplane.pb> [--top N]

prints the relabelled gaps and each operation's phases as one JSON object.
"""

from __future__ import annotations

import argparse
import bisect
import dataclasses
import glob
import json
import os
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence

BENCH = Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from drive import load_module  # noqa: E402

T = sys.modules.get("bench_trace") or load_module(BENCH / "trace.py",
                                                  "bench_trace")

PROGRAM_PREFIX = "repro:"

#: the phases each operation's top span is made of
OPERATIONS = {
    "save": ("ckpt.save", ("ckpt.save.snapshot", "ckpt.save.scan",
                           "ckpt.save.build", "ckpt.save.epoch",
                           "ckpt.save.commit")),
    "build": ("trainer.build", ("trainer.wal_open", "ckpt.restore.open",
                                "ckpt.restore.scan", "ckpt.restore.adopt",
                                "trainer.upload")),
}


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: int                # ns
    end: int                  # ns
    line: str = ""            # "<plane>#<line index>": one host thread
    args: Dict[str, Any] = dataclasses.field(default_factory=dict,
                                             compare=False)


@dataclasses.dataclass
class ProgramTrace:
    trace: Any                # trace.Trace: device operations, harness spans
    program: List[Span]       # repro: spans, start order
    harness: List[Span]       # bench: spans with their lines, start order
    loop_line: str            # the line of bench:window

    @classmethod
    def load(cls, path: str, **trace_kw) -> "ProgramTrace":
        from jax.profiler import ProfileData

        if os.path.isdir(path):
            found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                              recursive=True)
            if not found:
                raise FileNotFoundError(f"no .xplane.pb under {path}")
            path = max(found, key=os.path.getmtime)
        program, harness = [], []
        for plane in ProfileData.from_file(path).planes:
            for i, line in enumerate(plane.lines):
                where = f"{plane.name}#{i}"
                for ev in line.events:
                    name = ev.name
                    if name.startswith(PROGRAM_PREFIX):
                        out, name = program, name[len(PROGRAM_PREFIX):]
                        args = dict(ev.stats)
                    elif name.startswith(T.SPAN_PREFIX):
                        out, name = harness, name[len(T.SPAN_PREFIX):]
                        args = {}
                    else:
                        continue
                    start = int(ev.start_ns)
                    out.append(Span(name, start, start + int(ev.duration_ns),
                                    where, args))
        program.sort(key=lambda s: s.start)
        harness.sort(key=lambda s: s.start)
        loop = next((s.line for s in harness if s.name == "window"), "")
        return cls(T.Trace.load(path, **trace_kw), program, harness, loop)


def self_ns(span: Span, spans: Sequence[Span]) -> int:
    """``span``'s duration less what the other spans of ``spans`` nested
    in it on its line cover (``spans`` in start order)."""
    starts = [s.start for s in spans]
    lo = bisect.bisect_left(starts, span.start)
    hi = bisect.bisect_right(starts, span.end)
    inner = [(s.start, s.end) for s in spans[lo:hi]
             if s is not span and s.line == span.line and s.end <= span.end]
    return span.end - span.start - T.covered_ns(inner)


def label(pt: ProgramTrace, t: int) -> str:
    """The name of the moment ``t``: see the module's docstring."""
    open_ = [s for s in pt.program if s.start <= t < s.end]
    mine = [s for s in open_ if s.line == pt.loop_line]
    if not mine:
        return T.open_span(pt.trace.spans, t)
    first = max(mine, key=lambda s: s.start)
    later = [s for s in open_
             if s.line != pt.loop_line and s.start > first.start]
    if not later:
        return first.name
    return f"{first.name} / {max(later, key=lambda s: s.start).name}"


def gaps(pt: ProgramTrace, top: Optional[int] = 10) -> List[tuple]:
    """``trace.summarize``'s longest idle gaps of the first device plane,
    labelled by :func:`label`."""
    trace = pt.trace
    lo, hi = trace.window()
    planes = sorted({op.plane for op in trace.ops})
    idle = []
    if planes:
        busy = T.union(T.clip([(o.start, o.end) for o in trace.ops
                               if o.plane == planes[0]], lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    idle.sort(key=lambda g: g[0] - g[1])
    return [(label(pt, (a + b) // 2), (b - a) / 1e9) for a, b in idle[:top]]


def operations(pt: ProgramTrace, op: str) -> List[Dict[str, Any]]:
    """Per harness ``op`` span wholly inside the window that holds one
    program top span: its seconds, the top span's seconds and stats, and
    the self seconds of each program span name inside the top span."""
    top_name, _ = OPERATIONS[op]
    lo, hi = pt.trace.window()
    out = []
    for h in pt.harness:
        if h.name.partition("@")[0] != op or not (
                lo <= h.start and h.end <= hi):
            continue
        tops = [s for s in pt.program if s.name == top_name
                and h.start <= s.start and s.end <= h.end]
        if len(tops) != 1:
            continue
        t = tops[0]
        inside = [s for s in pt.program if s.line == t.line
                  and t.start <= s.start and s.end <= t.end]
        phases: Dict[str, float] = {}
        for s in inside:
            phases[s.name] = phases.get(s.name, 0.0) + self_ns(s, inside) / 1e9
        out.append({"op": h.name, "seconds": (h.end - h.start) / 1e9,
                    "top_seconds": (t.end - t.start) / 1e9,
                    "stats": dict(t.args), "self_seconds": phases})
    return out


def coverage(op: str, entry: Dict[str, Any]) -> float:
    """The share of the harness's operation that the top span's phases
    cover: the save's five phases over ``ckpt.save``; for a build, the
    WAL's opening, the restore's three phases and the upload over the
    harness's ``build``."""
    _, names = OPERATIONS[op]
    covered = sum(entry["self_seconds"].get(n, 0.0) for n in names)
    whole = entry["top_seconds"] if op == "save" else entry["seconds"]
    return covered / whole if whole > 0 else 0.0


def report(pt: ProgramTrace, top: int = 10) -> Dict[str, Any]:
    out: Dict[str, Any] = {"gaps": [[k, v] for k, v in gaps(pt, top)]}
    for op in OPERATIONS:
        ops = operations(pt, op)
        for e in ops:
            e["coverage"] = coverage(op, e)
        out[op] = ops
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path")
    ap.add_argument("--top", type=int, default=10)
    args = ap.parse_args(argv)
    print(json.dumps(report(ProgramTrace.load(args.path), args.top)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
