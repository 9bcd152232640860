"""Reduce a profiler trace to what the per-layer metrics read.

A run with ``--trace 1`` records its measured window with
``jax.profiler``; the window and the harness's own calls into the program
appear as host annotations named ``bench:<span>``. From the trace this
module takes:

- the device operations (on TPU the ``XLA Ops`` line of each
  ``/device:TPU:n`` plane), each tagged with the XLA module (jitted
  program) running around it, from the plane's ``XLA Modules`` line;
- the harness spans, on the host's clock, which the trace shares with the
  device lines.

``summarize`` turns them into the device's busy time and the window's
length, the time of each operation and module, and the longest idle gaps,
each named by the harness span that was open in it.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Callable, Dict, List, Optional, Sequence, Tuple

SPAN_PREFIX = "bench:"


@dataclasses.dataclass(frozen=True)
class Interval:
    name: str
    start: int           # ns
    end: int             # ns
    plane: str = ""
    module: str = ""


def tpu_ops(plane: str, line: str) -> bool:
    return plane.startswith("/device:TPU") and line == "XLA Ops"


def tpu_modules(plane: str, line: str) -> bool:
    return plane.startswith("/device:TPU") and line == "XLA Modules"


@dataclasses.dataclass
class Trace:
    ops: List[Interval]
    spans: List[Interval]

    @classmethod
    def load(cls, path: str, *,
             is_op: Callable[[str, str], bool] = tpu_ops,
             is_module: Callable[[str, str], bool] = tpu_modules) -> "Trace":
        """Read one ``.xplane.pb`` file, or the newest one under a
        directory that ``jax.profiler`` wrote."""
        from jax.profiler import ProfileData

        if os.path.isdir(path):
            found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                              recursive=True)
            if not found:
                raise FileNotFoundError(f"no .xplane.pb under {path}")
            path = max(found, key=os.path.getmtime)
        data = ProfileData.from_file(path)
        ops, spans = [], []
        for plane in data.planes:
            modules: List[Interval] = []
            plane_ops: List[Interval] = []
            for line in plane.lines:
                take_op = is_op(plane.name, line.name)
                take_mod = is_module(plane.name, line.name)
                for ev in line.events:
                    start = int(ev.start_ns)
                    iv = Interval(ev.name, start, start + int(ev.duration_ns),
                                  plane.name)
                    if take_op:
                        plane_ops.append(iv)
                    elif take_mod:
                        modules.append(iv)
                    elif ev.name.startswith(SPAN_PREFIX):
                        spans.append(dataclasses.replace(
                            iv, name=ev.name[len(SPAN_PREFIX):]))
            ops += _tag_modules(plane_ops, modules)
        ops.sort(key=lambda iv: iv.start)
        spans.sort(key=lambda iv: iv.start)
        return cls(ops, spans)

    def window(self) -> Tuple[int, int]:
        """The measured window: the ``window`` span, else the trace's
        extent."""
        for s in self.spans:
            if s.name == "window":
                return s.start, s.end
        every = self.ops + self.spans
        if not every:
            raise ValueError("the trace holds no events")
        return min(i.start for i in every), max(i.end for i in every)


def _tag_modules(ops: List[Interval], modules: List[Interval]) -> List[Interval]:
    if not modules:
        return ops
    modules = sorted(modules, key=lambda m: m.start)
    starts = [m.start for m in modules]
    out = []
    for op in ops:
        i = bisect.bisect_right(starts, op.start) - 1
        name = ""
        if i >= 0 and modules[i].end >= op.end:
            name = modules[i].name
        out.append(dataclasses.replace(op, module=name))
    return out


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Sorted, merged intervals."""
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def covered_ns(intervals) -> int:
    return sum(b - a for a, b in union(intervals))


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                 # mean over the device planes
    op_seconds: Dict[str, float]  # by ``op_label``, all planes
    module_seconds: Dict[str, float]
    gaps: List[Tuple[str, float]]  # longest idle gaps, by open span


def summarize(trace: Trace, *, top: int = 10) -> Summary:
    lo, hi = trace.window()
    planes = sorted({op.plane for op in trace.ops})
    busy, op_s, mod_s = [], {}, {}
    for plane in planes:
        ivs = clip([(o.start, o.end) for o in trace.ops if o.plane == plane],
                   lo, hi)
        busy.append(covered_ns(ivs) / 1e9)
    for o in trace.ops:
        a, b = max(o.start, lo), min(o.end, hi)
        if b <= a:
            continue
        label = op_label(o)
        op_s[label] = op_s.get(label, 0.0) + (b - a) / 1e9
        if o.module:
            mod_s[o.module] = mod_s.get(o.module, 0.0) + (b - a) / 1e9
    gaps = []
    if planes:
        first = union(clip([(o.start, o.end) for o in trace.ops
                            if o.plane == planes[0]], lo, hi))
        edges = [lo] + [x for iv in first for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((open_span(trace.spans, (a + b) // 2), (b - a) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return Summary(window_s=(hi - lo) / 1e9,
                   busy_s=sum(busy) / len(busy) if busy else 0.0,
                   op_seconds=op_s, module_seconds=mod_s, gaps=gaps[:top])


def op_label(op: Interval) -> str:
    """``<module>:<operation>``: the jitted program's name without its
    fingerprint, and the operation's HLO name without the instruction's
    text that TPU events carry (``%while.6 = (s32[], ...) while(...)``)."""
    name = op.name.partition(" = ")[0].lstrip("%")
    module = op.module.partition("(")[0]
    return f"{module}:{name}" if module else name


def open_span(spans: Sequence[Interval], t: int) -> str:
    """The innermost harness span (the latest to start) open at ``t``,
    other than the window itself; ``idle`` where none is."""
    best: Optional[Interval] = None
    for s in spans:
        if s.start <= t < s.end and s.name != "window":
            if best is None or s.start >= best.start:
                best = s
    return best.name.partition("@")[0] if best else "idle"


def module_time(trace: Trace, match: Sequence[str], *,
                within: Optional[Sequence[Interval]] = None) -> float:
    """Seconds of the device operations that run inside an XLA module
    whose name contains one of ``match``; with ``within``, only those that
    start inside one of those spans."""
    total = 0
    for o in trace.ops:
        if not any(m in o.module for m in match):
            continue
        if within is not None and not any(s.start <= o.start < s.end
                                          for s in within):
            continue
        total += o.end - o.start
    return total / 1e9


def breakdown(summary: Summary, top: int = 10) -> Dict[str, list]:
    ops = sorted(summary.op_seconds.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in summary.gaps[:top]]}
