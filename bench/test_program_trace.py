"""Checks of the program-span reduction (``program_trace.py``) and of the
``save.*`` and ``restore.*`` readers (``phases.py``, ``metrics/``).

Hand-made spans and records, where every number can be worked out on
paper; ``testdata/cpu_window.xplane.pb`` (see ``test_trace.py``), a trace
without program spans, on which the gaps must come out exactly as
``trace.summarize`` gives them; and ``testdata/cpu_ckpt.xplane.pb``, which
:func:`record` wrote on the CPU: inside ``bench:window``, three jitted
matmuls in ``bench:step@i``, then three ``AsyncFlusher.submit`` calls of
a 1.2 MB state to a flusher with one queue slot, each in ``bench:submit``,
whose saves run in ``bench:save@<step>`` on the flusher's thread, so the
third submit waits in ``flusher.queue_wait`` while a save runs.

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_program_trace.py
    JAX_PLATFORMS=cpu python bench/test_program_trace.py   # re-record
"""

import sys
import types
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import phases  # noqa: E402
import program_trace as P  # noqa: E402
import readers  # noqa: E402

T = P.T
CKPT = HERE / "testdata" / "cpu_ckpt.xplane.pb"


def _cpu_ops(plane, line):
    return plane == "/host:CPU" and line.startswith("tf_XLA")


# ----------------------------------------------------------- hand-made

def _pt(ops, harness, program, loop="L"):
    iv = T.Interval
    trace = T.Trace(ops=[iv(*o) for o in ops],
                    spans=[iv(s.name, s.start, s.end) for s in harness])
    return P.ProgramTrace(trace, sorted(program, key=lambda s: s.start),
                          harness, loop)


def test_self_time_subtracts_what_nests_on_the_same_line():
    S = P.Span
    spans = [S("save", 0, 100, "F"), S("scan", 10, 30, "F"),
             S("inner", 12, 20, "F"), S("build", 30, 40, "F"),
             S("step", 20, 90, "L")]       # another thread: not nested
    spans.sort(key=lambda s: s.start)
    own = {s.name: P.self_ns(s, spans) for s in spans}
    # save: 100 less scan and build (10-40); scan: 20 less inner's 8
    assert own == {"save": 70, "scan": 12, "inner": 8, "build": 10,
                    "step": 70}


def test_gap_labels_name_the_loops_phase_and_the_other_threads():
    S = P.Span
    harness = [S("window", 0, 100, "L"), S("submit@4", 10, 60, "L"),
               S("step@5", 70, 100, "L"), S("save@4", 5, 95, "F")]
    program = [S("flusher.queue_wait", 20, 60, "L"),
               S("ckpt.save", 5, 95, "F"),
               S("ckpt.save.epoch", 30, 50, "F"),
               S("ckpt.save.build", 8, 18, "F")]
    # device busy 0-10, 18-22, 50-55, 62-70, 90-100
    ops = [("x", 0, 10, "d0"), ("x", 18, 22, "d0"), ("x", 50, 55, "d0"),
           ("x", 62, 70, "d0"), ("x", 90, 100, "d0")]
    pt = _pt(ops, harness, program)
    got = P.gaps(pt)
    assert got == [
        # 22-50, midpoint 36: the loop waits, the epoch started later
        ("flusher.queue_wait / ckpt.save.epoch", pytest.approx(28e-9)),
        # 70-90, midpoint 80: no program span on the loop's thread
        ("step", pytest.approx(20e-9)),
        # 10-18, midpoint 14: only the harness's submit on the loop's
        # thread, though the flusher builds pages
        ("submit", pytest.approx(8e-9)),
        # 55-62, midpoint 58: the save's own span started before the wait
        ("flusher.queue_wait", pytest.approx(7e-9))]
    assert P.gaps(pt, top=1) == got[:1]


def test_operations_and_their_coverage():
    S = P.Span
    harness = [S("window", 0, 1000, "L"), S("save@8", 100, 600, "F"),
               S("save@12", 700, 1100, "F")]   # ends after the window
    program = [S("ckpt.save", 110, 590, "F", {"h2d_bytes": 7}),
               S("ckpt.save.scan", 120, 300, "F"),
               S("ckpt.save.epoch", 300, 560, "F"),
               S("train.step", 100, 500, "L"),
               S("ckpt.save", 710, 1090, "F")]
    pt = _pt([], harness, program)
    ops = P.operations(pt, "save")
    assert len(ops) == 1
    e = ops[0]
    assert e["op"] == "save@8" and e["stats"] == {"h2d_bytes": 7}
    assert e["seconds"] == pytest.approx(500e-9)
    assert e["self_seconds"] == {"ckpt.save": pytest.approx(40e-9),
                                 "ckpt.save.scan": pytest.approx(180e-9),
                                 "ckpt.save.epoch": pytest.approx(260e-9)}
    assert P.coverage("save", e) == pytest.approx(440 / 480)


# ----------------------------------------------------------- recorded

def test_a_trace_without_program_spans_gives_todays_gaps():
    path = str(HERE / "testdata" / "cpu_window.xplane.pb")
    pt = P.ProgramTrace.load(path, is_op=_cpu_ops)
    trace = T.Trace.load(path, is_op=_cpu_ops)
    assert pt.program == []
    assert [(s.name, s.start, s.end) for s in pt.trace.spans] == [
        (s.name, s.start, s.end) for s in trace.spans]
    assert [s.name for s in pt.harness] == [
        "window", "step@0", "step@1", "step@2", "save@3"]
    for top in (3, 10, 10**6):
        assert P.gaps(pt, top) == T.summarize(trace, top=top).gaps
    assert P.gaps(pt)[0][0] == "save"
    assert P.operations(pt, "save") == []      # no program span in it


def test_recorded_program_spans_and_their_stats():
    pt = P.ProgramTrace.load(str(CKPT), is_op=_cpu_ops)
    names = [s.name for s in pt.program]
    for n in ("flusher.stage", "flusher.queue_wait", "ckpt.save",
              *P.OPERATIONS["save"][1]):
        assert n in names, n
    saves = [s for s in pt.program if s.name == "ckpt.save"]
    assert [s.args["step"] for s in saves] == [4, 5, 6]
    # each leaf and its snapshot went up: a delta save of 1.2 MB
    assert all(s.args["h2d_bytes"] == 2 * 1_200_000 for s in saves)
    assert all(s.args["leaves"] == 2 for s in saves)
    assert all(s.line != pt.loop_line for s in saves)
    waits = [s for s in pt.program if s.name == "flusher.queue_wait"]
    assert [s.line for s in waits] == [pt.loop_line] * 3
    assert [s.args["depth"] for s in waits] == [0, 0, 1]
    ops = P.operations(pt, "save")
    assert [e["op"] for e in ops] == ["save@4", "save@5", "save@6"]
    for e in ops:
        assert P.coverage("save", e) > 0.95
        assert e["top_seconds"] <= e["seconds"]
    # while the third submit waits, the gaps are named by its wait
    assert any(g.startswith("flusher.queue_wait") for g, _ in P.gaps(pt))
    assert P.report(pt)["save"][0]["coverage"] == P.coverage("save", ops[0])


# ------------------------------------------------------------ readers

def _record(name, thread, t0, t1, self_s=None, **stats):
    return types.SimpleNamespace(name=name, thread=thread, t0=t0, t1=t1,
                                 self_s=t1 - t0 if self_s is None else self_s,
                                 stats=stats)


def _run(spans, window):
    import drive

    s = drive.Spans()
    s.items = [drive.Span(n, a, b) for n, a, b in spans]
    return types.SimpleNamespace(spans=s, window=window)


def test_save_readers_on_hand_made_records(monkeypatch):
    R = _record
    recs = [
        # save 1: thread 2; a step on thread 1 overlaps it
        R("ckpt.save", 2, 10.0, 19.0, 0.5, h2d_bytes=2_000_000),
        R("ckpt.save.snapshot", 2, 10.0, 11.0),
        R("ckpt.save.snapshot", 2, 11.0, 11.5),
        R("ckpt.save.scan", 2, 11.5, 13.5),
        R("ckpt.save.build", 2, 13.5, 15.5),
        R("ckpt.save.epoch", 2, 15.5, 18.0),
        R("ckpt.save.commit", 2, 18.0, 18.5),
        R("ckpt.save.scan", 1, 12.0, 13.0),          # not this save's
        # save 2
        R("ckpt.save", 2, 20.0, 28.0, 0.0, h2d_bytes=4_000_000),
        R("ckpt.save.snapshot", 2, 20.0, 20.5),
        R("ckpt.save.scan", 2, 20.5, 21.5),
        R("ckpt.save.build", 2, 21.5, 22.5),
        R("ckpt.save.epoch", 2, 22.5, 27.0),
        R("ckpt.save.commit", 2, 27.0, 28.0),
        # a save that ends after the window
        R("ckpt.save", 2, 29.0, 40.0, h2d_bytes=9_000_000),
        R("ckpt.save.epoch", 2, 29.0, 40.0),
    ]
    monkeypatch.setattr(phases, "records", lambda: recs)
    run = _run([("window", 5.0, 30.0), ("save", 9.5, 19.5),
                ("save", 19.8, 28.2), ("save", 28.5, 40.5)], (5.0, 30.0))
    want = {"save.snapshot_s": (1.5 + 0.5) / 2, "save.scan_s": 1.5,
            "save.build_s": 1.5, "save.epoch_s": (2.5 + 4.5) / 2,
            "save.commit_s": 0.75, "save.h2d_mb": 3.0}
    for name, v in want.items():
        assert readers.value(run, name) == pytest.approx(v), name


def test_restore_readers_on_hand_made_records(monkeypatch):
    R = _record
    recs = [
        R("trainer.build", 1, 1.0, 11.0, 0.2),
        R("trainer.wal_open", 1, 1.0, 1.5),
        R("ckpt.restore", 1, 1.5, 9.0, 0.1, h2d_bytes=2_600_000_000),
        R("ckpt.restore.open", 1, 1.5, 2.5),
        R("ckpt.restore.scan", 1, 2.5, 5.0),
        R("ckpt.restore.scan", 1, 5.0, 6.5),
        R("ckpt.restore.adopt", 1, 6.5, 9.0),
        R("trainer.upload", 1, 9.0, 10.8, h2d_bytes=1_300_000_000),
    ]
    monkeypatch.setattr(phases, "records", lambda: recs)
    run = _run([("window", 0.0, 20.0), ("build", 0.9, 11.1)], (0.0, 20.0))
    want = {"restore.open_s": 1.0, "restore.scan_s": 4.0,
            "restore.adopt_s": 2.5, "restore.upload_s": 1.8,
            "restore.h2d_mb": 3900.0}
    for name, v in want.items():
        assert readers.value(run, name) == pytest.approx(v), name


def test_readers_read_nothing_from_a_program_without_spans(monkeypatch):
    monkeypatch.setattr(phases, "records", lambda: [])
    run = _run([("window", 0.0, 20.0), ("build", 1.0, 11.0),
                ("save", 2.0, 3.0)], (0.0, 20.0))
    for name in ("save.snapshot_s", "save.h2d_mb", "restore.scan_s",
                 "restore.h2d_mb"):
        assert readers.value(run, name) is None


def test_the_records_come_from_the_program_when_it_has_spans():
    from repro import spans

    assert phases.records() == spans.records()


# ------------------------------------------------------------ recorder

def record(out: Path) -> None:
    """Writes ``testdata/cpu_ckpt.xplane.pb`` (see the module's
    docstring) through ``drive.Spans``, as a traced run does."""
    import glob
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    import drive
    from repro.persistence import (AsyncFlusher, CheckpointConfig,
                                   CheckpointManager)

    spans = drive.Spans(traced=True)
    mgr = CheckpointManager(None, CheckpointConfig(page_size=64 * 1024))
    orig = mgr.save
    mgr.save = lambda step, state: spans.record("save", orig, step, state,
                                                step=step)
    fl = AsyncFlusher(mgr, max_pending=1)
    rng = np.random.default_rng(0)
    state = {"a": rng.standard_normal(200_000).astype(np.float32),
             "b": rng.standard_normal(100_000).astype(np.float32)}
    f = jax.jit(lambda x: jnp.sin(x) @ x.T)
    x = jnp.ones((512, 512))
    f(x).block_until_ready()
    fl.submit(0, state)                # the full save, and every compile
    fl.submit(1, state)
    fl.wait()

    def body():
        for i in range(3):
            spans.record("step", lambda: f(x).block_until_ready(), step=i)
        for step in (4, 5, 6):
            state["a"][step] += 1.0
            spans.record("submit", fl.submit, step, state, step=step)
        fl.wait()

    tmp = tempfile.mkdtemp()
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            spans.record("window", body)
        finally:
            jax.profiler.stop_trace()
        fl.close()
        found, = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
        shutil.copy(found, out)
    finally:
        shutil.rmtree(tmp)


if __name__ == "__main__":
    sys.path.insert(0, str(HERE.parent / "src"))
    record(CKPT)
