"""Named phase spans and byte counters on the profiler's clock.

``with span("ckpt.save.epoch", into=phases): ...`` marks one phase of the
program. Under ``jax.profiler`` (``start_trace``/``stop_trace``, or a
profiler server) each span is a host event named ``repro:<name>`` in the
trace, on the same clock as the device's lines, carrying its stats (the
keyword arguments, and the counts ``Span.add`` attaches at exit, such as
``h2d_bytes``). With the profiler off a span only times itself
(``time.perf_counter``) and, given ``into``, adds its self seconds — its
duration less the time its child spans cover on the same thread — to
``into[name]``; that is how ``SaveReport.phase_s`` is filled.

While a trace is on, each span that ends is also kept in a bounded,
process-wide list (:func:`records`), the same spans and stats the trace
holds, for readers inside the process. :func:`compiles` counts the
backend compiles and compile-cache loads a thread has run.

JAX is imported on first use, so modules that use spans import without it.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Any, Deque, Dict, List, Optional

__all__ = ["PREFIX", "Record", "Span", "compiles", "records", "span"]

#: name prefix of the program's spans in a profiler trace
PREFIX = "repro:"
#: spans kept by :func:`records`, newest last
RECORD_LIMIT = 1 << 16
#: the ``jax.monitoring`` event that times each backend compile or
#: compile-cache load (``compile_or_get_cached``)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_local = threading.local()          # .stack: open spans; .compiles: count
_records: Deque["Record"] = collections.deque(maxlen=RECORD_LIMIT)
_annotation: Any = None             # jax.profiler.TraceAnnotation, or False
_listening = False
_listen_lock = threading.Lock()


@dataclasses.dataclass(frozen=True)
class Record:
    """One span that ended while a profiler trace was on."""

    name: str
    thread: int                      # threading.get_ident() of its thread
    t0: float                        # time.perf_counter() at entry
    t1: float                        # ... and at exit
    self_s: float                    # t1 - t0 less its child spans
    stats: Dict[str, Any]


def _annotation_type():
    global _annotation
    if _annotation is None:
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:
            TraceAnnotation = False
        _annotation = TraceAnnotation
    return _annotation


class Span:
    """An open phase; see :func:`span`. ``seconds`` holds its duration
    once it has ended."""

    __slots__ = ("name", "into", "stats", "seconds", "_t0", "_child_s",
                 "_ann")

    def __init__(self, name: str, into: Optional[Dict[str, float]],
                 stats: Dict[str, Any]) -> None:
        self.name, self.into, self.stats = name, into, stats
        self.seconds = 0.0
        self._child_s = 0.0
        self._ann = None

    def add(self, **counts: Any) -> None:
        """Attach counts known only at exit (ignored with no trace on)."""
        if self._ann is not None:
            self.stats.update(counts)
            self._ann.set_metadata(**counts)

    def __enter__(self) -> "Span":
        ann = _annotation_type()
        if ann and ann.is_enabled():
            self._ann = ann(PREFIX + self.name, **self.stats)
            self._ann.__enter__()
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        stack.append(self)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        self.seconds = t1 - self._t0
        stack = _local.stack
        stack.pop()
        if stack:
            stack[-1]._child_s += self.seconds
        self_s = self.seconds - self._child_s
        if self.into is not None:
            self.into[self.name] = self.into.get(self.name, 0.0) + self_s
        if self._ann is not None:
            self._ann.__exit__(*exc)
            _records.append(Record(self.name, threading.get_ident(),
                                   self._t0, t1, self_s, self.stats))


def span(name: str, *, into: Optional[Dict[str, float]] = None,
         **stats: Any) -> Span:
    """A context manager that marks one phase named ``name``: a
    ``repro:<name>`` event with ``stats`` in a running profiler trace,
    and its self seconds added to ``into[name]`` where ``into`` is given."""
    return Span(name, into, stats)


def records() -> List[Record]:
    """The spans that ended while a profiler trace was on (the newest
    ``RECORD_LIMIT``), oldest first."""
    return list(_records)


def _on_duration(event: str, duration: float, **kw: Any) -> None:
    if event == COMPILE_EVENT:
        _local.compiles = getattr(_local, "compiles", 0) + 1


def compiles() -> int:
    """Backend compiles and compile-cache loads run on the calling thread
    since the first call of this function in the process."""
    global _listening
    if not _listening:
        with _listen_lock:
            if not _listening:
                import jax.monitoring
                jax.monitoring.register_event_duration_secs_listener(
                    _on_duration)
                _listening = True
    return getattr(_local, "compiles", 0)
