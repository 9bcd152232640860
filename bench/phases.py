"""Per-operation phase times and byte counts from the program's spans.

While a profiler trace is on, the program (``repro.spans``) keeps each
span that ends: its name, thread, times on the host's clock, self seconds
and stats, the same spans and stats its trace holds as ``repro:<name>``.
The readers of the ``save.*`` and ``restore.*`` metrics take them from
there. An operation is one of the harness's ``save`` or ``build`` spans
that lies wholly inside the window; its program spans are those on the
thread of the one top span (``ckpt.save``, ``trainer.build``) inside it,
within that span. A program without spans keeps no records: the readers
then return None.
"""

from __future__ import annotations

from typing import Callable, List, Optional


def records() -> list:
    try:
        from repro.spans import records as program_records
    except ImportError:
        return []
    return program_records()


def per_op(run, op: str, top: str,
           value: Callable[[List], float]) -> Optional[float]:
    """Mean over the window's ``op`` spans of ``value(inside)``, where
    ``inside`` is the program spans of the operation (its ``top`` span
    included); None where no operation holds exactly one ``top`` span."""
    recs = records()
    if not recs:
        return None
    values = []
    for s in run.spans.within(op, *run.window):
        tops = [r for r in recs
                if r.name == top and s.t0 <= r.t0 and r.t1 <= s.t1]
        if len(tops) != 1:
            continue
        t = tops[0]
        values.append(value([r for r in recs if r.thread == t.thread
                             and t.t0 <= r.t0 and r.t1 <= t.t1]))
    return sum(values) / len(values) if values else None


def self_seconds(*names: str) -> Callable[[List], float]:
    """Self seconds of the spans named ``names`` in one operation."""
    return lambda inside: sum(r.self_s for r in inside if r.name in names)


def megabytes(stat: str, *names: str) -> Callable[[List], float]:
    """``stat`` of the spans named ``names`` in one operation, in MB."""
    return lambda inside: sum(r.stats.get(stat, 0) for r in inside
                              if r.name in names) / 1e6


def per_save(run, value) -> Optional[float]:
    return per_op(run, "save", "ckpt.save", value)


def per_resume(run, value) -> Optional[float]:
    return per_op(run, "build", "trainer.build", value)
