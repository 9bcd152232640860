"""Phase spans and byte counters (``repro.spans``) and where the program
puts them: the save, the restore, the flusher and the step loop."""

import dataclasses
import glob
import threading
import time

import numpy as np
import pytest

from repro import spans
from repro.persistence import CheckpointConfig, CheckpointManager
from repro.spans import span

CFG = CheckpointConfig(page_size=128 * 1024, manifest_capacity=1 << 16)
SAVE_PHASES = {"ckpt.save.snapshot", "ckpt.save.scan", "ckpt.save.build",
               "ckpt.save.epoch", "ckpt.save.commit"}
RESTORE_PHASES = {"ckpt.restore.open", "ckpt.restore.scan",
                  "ckpt.restore.adopt"}


def make_state(seed):
    rng = np.random.default_rng(seed)
    return {"w": rng.standard_normal((300, 512)).astype(np.float32),
            "b": rng.standard_normal(1000).astype(np.float32),
            "n": np.array([seed], dtype=np.int64)}


class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: records what a span
    hands the profiler."""

    enabled = False
    made = []

    def __init__(self, name, **stats):
        assert self.enabled, "a span built an annotation with no trace on"
        self.name, self.stats, self.metadata = name, stats, {}
        _FakeAnnotation.made.append(self)

    @classmethod
    def is_enabled(cls):
        return cls.enabled

    def set_metadata(self, **kw):
        self.metadata.update(kw)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def fake_profiler(monkeypatch):
    monkeypatch.setattr(spans, "_annotation", _FakeAnnotation)
    monkeypatch.setattr(_FakeAnnotation, "made", [])
    monkeypatch.setattr(_FakeAnnotation, "enabled", False)
    return _FakeAnnotation


def test_into_takes_self_seconds_less_the_children(fake_profiler):
    into = {}
    with span("outer", into=into) as outer:
        time.sleep(0.02)
        with span("inner", into=into) as a:
            time.sleep(0.02)
        with span("inner", into=into) as b:
            with span("leaf") as c:      # a grandchild counts in b only
                time.sleep(0.01)

        def other_thread():              # not a child: another thread
            with span("elsewhere", into=into):
                time.sleep(0.02)
        t = threading.Thread(target=other_thread)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
    assert into["inner"] == pytest.approx(
        a.seconds + b.seconds - c.seconds, rel=1e-9)
    assert into["outer"] == pytest.approx(
        outer.seconds - a.seconds - b.seconds, rel=1e-9)
    assert 0.02 <= into["outer"] <= outer.seconds - 0.03
    assert into["elsewhere"] >= 0.02
    assert "leaf" not in into


def test_no_trace_skips_the_stats(fake_profiler):
    n = len(spans.records())
    with span("quiet", step=3) as sp:
        sp.add(h2d_bytes=10)
    assert fake_profiler.made == [] and sp.seconds > 0
    assert len(spans.records()) == n


def test_a_trace_gets_the_stats_and_the_records(fake_profiler):
    fake_profiler.enabled = True
    with span("outer", step=7) as sp:
        with span("inner"):
            pass
        sp.add(h2d_bytes=2**33 + 1)
    outer, inner = fake_profiler.made
    assert (outer.name, outer.stats) == ("repro:outer", {"step": 7})
    assert outer.metadata == {"h2d_bytes": 2**33 + 1}
    assert inner.name == "repro:inner"
    got = spans.records()[-2:]
    assert [r.name for r in got] == ["inner", "outer"]
    assert got[1].stats == {"step": 7, "h2d_bytes": 2**33 + 1}
    assert got[1].t0 <= got[0].t0 <= got[0].t1 <= got[1].t1
    assert got[1].self_s == pytest.approx(
        (got[1].t1 - got[1].t0) - (got[0].t1 - got[0].t0))
    assert got[0].thread == threading.get_ident()


def _profile(tmp_path, fn):
    """Runs ``fn`` under the JAX profiler → (its result, the repro spans of
    the trace as (name, stats) in start order)."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                      recursive=True)
    events = [(ev.start_ns, ev.name[len(spans.PREFIX):], dict(ev.stats))
              for plane in ProfileData.from_file(path).planes
              for line in plane.lines for ev in line.events
              if ev.name.startswith(spans.PREFIX)]
    return out, [(n, st) for _, n, st in sorted(events, key=lambda e: e[0])]


@pytest.mark.parametrize("impl", ["auto", "staged"])
def test_save_and_restore_phases_in_a_profiler_trace(tmp_path, impl):
    cfg = dataclasses.replace(CFG, kernel_impl=impl)
    path = str(tmp_path / "s.pmem")
    states = [make_state(1), make_state(2)]
    nbytes = sum(v.nbytes for v in states[0].values())
    pages = sum(-(-v.nbytes // cfg.page_size) for v in states[0].values())

    def work():
        m = CheckpointManager(path, cfg)
        full, delta = m.save(1, states[0]), m.save(2, states[1])
        r = CheckpointManager(path, cfg)
        step, _ = r.restore()
        return full, delta, r.last_restore, step

    (full, delta, rest, step), events = _profile(tmp_path, work)
    assert step == 2
    saves = [st for n, st in events if n == "ckpt.save"]
    assert [st["step"] for st in saves] == [1, 2]
    # the full save uploads each leaf, the delta save each leaf and its
    # snapshot; a fused restore uploads packed pages and a zero base of
    # the same size, plus an int32 block id and a uint32 checksum a page
    assert saves[0]["h2d_bytes"] == full.h2d_bytes == nbytes
    assert saves[1]["h2d_bytes"] == delta.h2d_bytes == 2 * nbytes
    assert saves[1]["leaves"] == 3
    assert saves[1]["pages_dirty"] == delta.pages_total - delta.pages_clean
    fused_h2d = 2 * pages * cfg.page_size + 8 * pages
    restore, = [st for n, st in events if n == "ckpt.restore"]
    assert restore["h2d_bytes"] == rest.h2d_bytes == (
        0 if impl == "staged" else fused_h2d)
    assert restore["step"] == 2 and restore["entries_tried"] == 1
    # every phase of the delta save of step 2, and of the restore, is in
    # the trace: one snapshot, scan and build per leaf
    i = [n for n, _ in events].index("ckpt.save", 1)
    j = [n for n, _ in events].index("ckpt.restore")
    names = [n for n, _ in events[i:j]]
    for phase in ("ckpt.save.snapshot", "ckpt.save.scan", "ckpt.save.build"):
        assert names.count(phase) == 3
    assert names.count("ckpt.save.epoch") == names.count(
        "ckpt.save.commit") == 1
    scans = [st for n, st in events[i:j] if n == "ckpt.save.scan"]
    assert sum(st["h2d_bytes"] for st in scans) == 2 * nbytes
    assert all(st["blocks_dirty"] > 0 for st in scans)
    names = [n for n, _ in events[j:]]
    assert names.count("ckpt.restore.scan") == 3
    assert names.count("ckpt.restore.open") == names.count(
        "ckpt.restore.adopt") == 1
    # the reports carry the measured phases with no profiler needed
    for rep in (full, delta):
        assert set(rep.phase_s) == SAVE_PHASES
        assert 0 < sum(rep.phase_s.values()) <= rep.wall_s
    assert set(rest.phase_s) == RESTORE_PHASES
    assert 0 < sum(rest.phase_s.values()) <= rest.wall_s
    assert delta.d2h_bytes > 0 and rest.d2h_bytes >= (
        0 if impl == "staged" else pages * cfg.page_size)


def test_trainer_step_loop_spans(tmp_path, fake_profiler):
    from repro.launch.train import Trainer, TrainerConfig

    fake_profiler.enabled = True
    tc = TrainerConfig(arch="mamba2-130m", reduced=True, steps=2, batch=2,
                       seq=16, ckpt_every=1, out=str(tmp_path))
    t = Trainer(tc)
    me = threading.get_ident()
    t0 = time.perf_counter()
    t.run()
    got = [r for r in spans.records() if r.t0 >= t0]
    steps = [r for r in got if r.name == "train.step" and r.thread == me]
    assert [r.stats["step"] for r in steps] == [0, 1]
    assert steps[0].stats["compiles"] > 0 and steps[1].stats["compiles"] == 0
    waits = [r for r in got if r.name == "flusher.queue_wait"]
    assert len(waits) == 2 and all(
        r.stats["depth"] >= 0 and r.stats["shard"] == 0 for r in waits)
    staged = [r for r in got if r.name == "ckpt.stage"]
    copies = [r for r in got if r.name == "flusher.stage"]
    assert len(staged) == len(copies) == 2
    assert staged[0].stats["d2h_bytes"] == copies[0].stats["bytes"] > 0
    saves = [r for r in got if r.name == "ckpt.save"]
    assert len(saves) == 2 and all(r.thread != me for r in saves)
    assert len([r for r in got if r.name == "train.wal_commit"]) == 2

    # a resume: the build holds the WAL's opening, the restore and the
    # upload of every restored byte
    t0 = time.perf_counter()
    t = Trainer(tc)
    assert t.start_step == 2
    got = [r for r in spans.records() if r.t0 >= t0]
    build, = [r for r in got if r.name == "trainer.build"]
    inner = {r.name: r for r in got if r.name in (
        "trainer.wal_open", "ckpt.restore", "trainer.upload")}
    assert len(inner) == 3 and all(
        build.t0 <= r.t0 <= r.t1 <= build.t1 for r in inner.values())
    assert inner["trainer.upload"].stats["h2d_bytes"] == sum(
        v.nbytes for v in t._ckpt_state().values())
