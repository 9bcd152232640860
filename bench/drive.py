"""The benchmark's one traffic driver, steered by a traffic file.

A traffic file (``bench/traffic/<mix>.json``) names a ``loop`` and its
parameters; this module runs it against the program through its normal
entry points: ``repro.launch.train.Trainer`` and its ``run``, the
flusher's ``submit`` and the manager's ``save`` and ``restore``, which
run the compiled ``flush_pack`` and ``apply_unpack``.

``train``   closed loop, one step at a time (``Trainer.run(crash_at=s+1)``,
            so ``run`` never drains the flusher), a checkpoint every
            ``ckpt_every`` steps: submitted to the flusher, or, with sync
            saves (no flusher), saved on the loop before the next step
            runs. The window is made of whole checkpoint
            intervals, at least ``min_ops`` of them: it starts one while
            ``--seconds`` have not passed and closes when the last one
            ends.
``resume``  set-up trains ``saved_steps`` steps and saves; each operation
            of the window builds a fresh ``Trainer`` over that directory,
            which restores the checkpoint, runs its first step and is
            dropped. Operations start while ``--seconds`` have not passed.

Both make their weights and tokens from the seed (``models/<reference>``
and :class:`TokenFeed`), hand them to the program, and keep what the
check after the window compares with the plain reference.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import sys
import threading
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


#: where a configuration's reference module lies, by its ``reference``
MODELS = BENCH / "models"


def reference_model(cfg: Dict):
    """The configuration's reference module, ``models/<reference>.py``.
    Besides the plain reference (``make_weights``, ``train_readings``) it
    holds what the harness needs to know of the architecture:
    ``PROGRAM`` (``{field of the program's ModelConfig: (the file's key,
    the built model's attribute held to it, or None)}``),
    ``train_flops_per_token`` and the CPU rehearsal's sizes ``REDUCED``."""
    name = f"bench_ref_{cfg['reference']}"
    if name in sys.modules:
        return sys.modules[name]
    return load_module(MODELS / f"{cfg['reference']}.py", name)


# ------------------------------------------------------------------ spans

@dataclasses.dataclass
class Span:
    name: str
    t0: float
    t1: float
    step: Optional[int] = None

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class Spans:
    """Host spans of the harness's calls into the program, on the host's
    clock; with ``traced`` each also goes into the profiler's trace as
    ``bench:<name>`` (``bench:<name>@<step>`` where it has a step)."""

    def __init__(self, traced: bool = False) -> None:
        self.traced = traced
        self.items: List[Span] = []
        self._lock = threading.Lock()

    def record(self, name: str, fn: Callable, *args, step=None, **kw):
        ann = None
        if self.traced:
            import jax
            label = f"bench:{name}" + ("" if step is None else f"@{step}")
            ann = jax.profiler.TraceAnnotation(label)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            return fn(*args, **kw)
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            with self._lock:
                self.items.append(Span(name, t0, t1, step))

    def within(self, name: str, lo: float, hi: float) -> List[Span]:
        with self._lock:
            return [s for s in self.items
                    if s.name == name and lo <= s.t0 and s.t1 <= hi]


# ----------------------------------------------------------------- tokens

class TokenFeed:
    """The token stream, a pure function of (seed, step): a mix of
    zipf-distributed and uniform ids over the unpadded vocabulary, every
    row different. Stands in for the trainer's pipeline (``batch_at``)."""

    def __init__(self, cfg: Dict, traffic: Dict, seed: int) -> None:
        self.batch, self.seq = cfg["batch"], cfg["seq"]
        self.vocab = cfg["vocab_size"]
        self.zipf_a = traffic["tokens"]["zipf_a"]
        self.uniform_share = traffic["tokens"]["uniform_share"]
        self.seed = seed

    def batch_at(self, cursor: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng([self.seed, cursor])
        shape = (self.batch, self.seq)
        z = rng.zipf(self.zipf_a, size=shape) % self.vocab
        u = rng.integers(0, self.vocab, size=shape)
        toks = np.where(rng.random(shape) < self.uniform_share, u, z)
        toks = toks.astype(np.int32)
        labels = np.concatenate(
            [toks[:, 1:], np.full((self.batch, 1), -1, np.int32)], axis=1)
        return {"tokens": toks, "labels": labels}

    def batches(self, n: int):
        return [(b["tokens"], b["labels"])
                for b in map(self.batch_at, range(n))]


# ------------------------------------------------------------- the program

def program_arch(cfg: Dict) -> str:
    """The configuration's model, entered in the program's registry of
    architectures (``repro.configs``: a module with ``CONFIG``) under a
    name of its own, which ``Trainer`` then builds: ``cfg['arch']``'s
    entry with the fields the file states (its reference module's
    ``PROGRAM``), and no head padding for a tensor-parallel split the
    one-chip cell has not."""
    import types

    from repro.configs import get_config

    name = "bench_" + cfg["name"].replace("-", "_").replace(".", "_")
    mod = types.ModuleType(f"repro.configs.{name}")
    mod.CONFIG = dataclasses.replace(
        get_config(cfg["arch"]), name=cfg["name"], tp_heads_multiple=1,
        **{f: cfg[k] for f, (k, _) in reference_model(cfg).PROGRAM.items()})
    sys.modules[mod.__name__] = mod
    return name


def trainer_config(cfg: Dict, traffic: Dict, out: Path):
    from repro.launch.train import TrainerConfig

    dep = cfg["deployment"]
    return TrainerConfig(
        arch=program_arch(cfg), reduced=False,
        steps=cfg["optimizer"]["total_steps"], batch=cfg["batch"],
        seq=cfg["seq"], ckpt_every=traffic["ckpt_every"], out=str(out),
        lr=cfg["optimizer"]["lr"], async_flush=dep["async_flush"],
        wal_capacity_steps=dep["wal_capacity_steps"])


def check_program_matches(trainer, cfg: Dict) -> Dict[str, Any]:
    """The configuration file states what runs: refuse a program whose
    model, page size or flusher differs from it. The model's keys, and the
    attribute of the built model each is read from, are its reference
    module's ``PROGRAM``. Returns what was compared, by key."""
    m = trainer.cfg
    dep = cfg["deployment"]
    table = reference_model(cfg).PROGRAM
    vocab, pad = (cfg[table[f][0]] for f in ("vocab_size", "vocab_pad"))
    fl = trainer.flusher
    ran = {k: getattr(m, attr) for k, attr in table.values() if attr}
    want = {k: cfg[k] for k, attr in table.values() if attr}
    ran.update(padded_vocab=m.padded_vocab,
               page_size=trainer.manager.cfg.page_size,
               manifest_capacity=trainer.manager.cfg.manifest_capacity,
               async_flush=fl is not None,
               max_pending=None if fl is None else fl._queues[0].maxsize)
    want.update(padded_vocab=-(-vocab // pad) * pad,
                page_size=dep["page_size"],
                manifest_capacity=dep["manifest_capacity"],
                async_flush=dep["async_flush"],
                max_pending=dep["max_pending"])
    bad = {k: (ran[k], want[k]) for k in want if ran[k] != want[k]}
    if bad:
        raise RuntimeError(f"the program runs another model or deployment "
                           f"than the configuration states (ran, stated): "
                           f"{bad}")
    return want


def set_weights(trainer, weights) -> None:
    import jax

    ref = jax.tree.structure(weights)
    if jax.tree.structure(trainer.params) != ref or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(trainer.params), jax.tree.leaves(weights))):
        raise RuntimeError("the seed's weights do not match the program's "
                           "parameter tree")
    trainer.params = weights


class SaveGate:
    """Wraps the manager's ``save`` on the instance: times every save
    (``save`` spans, on the thread that runs it), keeps the state each
    completed save was given, and after :meth:`close` lets the save in
    flight finish and skips those not yet started, as a crash would."""

    def __init__(self, manager, spans: Spans, keep: int) -> None:
        self._orig = manager.save
        self._spans = spans
        self._keep = keep
        self._cond = threading.Condition()
        self._closed = False
        self._inflight = 0
        self.completed: List[list] = []         # [step, state, t0, t1]
        manager.save = self.save

    def save(self, step, state):
        with self._cond:
            if self._closed:
                return None
            self._inflight += 1
        t0 = time.perf_counter()
        try:
            rep = self._spans.record("save", self._orig, step, state, step=step)
        finally:
            with self._cond:
                self._inflight -= 1
                self._cond.notify_all()
        with self._cond:
            self.completed.append([step, state, t0, time.perf_counter()])
            for old in self.completed[:-self._keep]:
                old[1] = None                   # let the state go
        return rep

    def close(self, timeout: float) -> None:
        with self._cond:
            self._closed = True
            if not self._cond.wait_for(lambda: self._inflight == 0, timeout):
                raise RuntimeError("the save in flight did not finish")


def instrument(trainer, spans: Spans, keep: int) -> SaveGate:
    """Timed wrappers on the instance attributes the step loop calls."""
    orig_ckpt = trainer._ckpt_state
    trainer._ckpt_state = lambda: spans.record("ckpt_state", orig_ckpt)
    fl = trainer.flusher
    if fl is not None:
        orig_stage, orig_submit = fl.stage, fl.submit
        fl.stage = lambda state: spans.record("stage", orig_stage, state)
        fl.submit = lambda step, state, **kw: spans.record(
            "submit", orig_submit, step, state, step=step, **kw)
    return SaveGate(trainer.manager, spans, keep)


def train_step(trainer, spans: Spans, s: int) -> float:
    """Step ``s`` through ``Trainer.run``; the span ends in
    ``block_until_ready`` of the new state."""
    import jax

    def one():
        trainer.start_step = s
        out = trainer.run(crash_at=s + 1)
        jax.block_until_ready((trainer.params, trainer.opt_state))
        return out["losses"][0]

    return spans.record("step", one, step=s)


def host_leaves(tree, prefix: str = "") -> Dict[str, np.ndarray]:
    """Every leaf on the host, keyed by ``prefix`` + its path as 'a/b/c'
    (the names the trainer gives the leaves of a checkpoint)."""
    import jax

    return {prefix + "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                              for k in path): np.asarray(leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def leaf_norms(tree) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp

    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(str(getattr(k, "key", k)) for k in path)
        out[key] = jnp.linalg.norm(leaf.astype(jnp.float32).reshape(-1))
    return {k: float(v) for k, v in jax.device_get(out).items()}


def state_bytes_differing(got: Dict[str, Any], want: Dict[str, Any]) -> int:
    """Bytes in which a restored state differs from the state saved; a
    leaf missing on either side counts whole."""
    diff = 0
    for k in set(got) | set(want):
        if k not in got or k not in want:
            diff += np.asarray(got.get(k, want.get(k))).nbytes
            continue
        a = np.ascontiguousarray(np.asarray(got[k])).view(np.uint8).reshape(-1)
        b = np.ascontiguousarray(np.asarray(want[k])).view(np.uint8).reshape(-1)
        if a.size != b.size:
            diff += max(a.size, b.size)
        else:
            diff += int(np.count_nonzero(a != b))
    return diff


def dirty_blocks(new: Dict[str, Any], old: Dict[str, Any],
                 block: int = 4096) -> int:
    """4 KiB blocks of the flat leaves in which ``new`` differs from
    ``old``, compared as 8-byte words (a leaf's ragged tail is one block)."""
    n = 0
    for k in new:
        a = np.ascontiguousarray(new[k]).view(np.uint8).reshape(-1)
        b = np.ascontiguousarray(old[k]).view(np.uint8).reshape(-1)
        full = a.size - a.size % block
        if full:
            wa = a[:full].view(np.uint64).reshape(-1, block // 8)
            wb = b[:full].view(np.uint64).reshape(-1, block // 8)
            n += int(np.count_nonzero((wa != wb).any(axis=1)))
        if full < a.size:
            n += int((a[full:] != b[full:]).any())
    return n


# -------------------------------------------------------------------- run

@dataclasses.dataclass
class Run:
    """What one run measured and kept for its metrics and its check."""

    cfg: Dict
    traffic: Dict
    seed: int
    spans: Spans
    window: tuple = (0.0, 0.0)          # host clock
    ops: int = 0                          # operations in the window
    failed: int = 0
    setup_s: float = 0.0
    losses: List[float] = dataclasses.field(default_factory=list)
    grad_norms: Dict[str, float] = dataclasses.field(default_factory=dict)
    change_norms: Dict[str, float] = dataclasses.field(default_factory=dict)
    restore_bytes_differing: Optional[int] = None
    restored_step_behind: Optional[int] = None
    leaf_nbytes: List[int] = dataclasses.field(default_factory=list)
    save_dirty_blocks: Dict[int, int] = dataclasses.field(default_factory=dict)
    memory_peak_bytes: Optional[int] = None
    phases: Dict[str, float] = dataclasses.field(default_factory=dict)
    trace: Any = None                     # trace.Trace of a traced run
    summary: Any = None                   # trace.Summary of it


def _memory_peak() -> Optional[int]:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class Driver:
    def __init__(self, cfg: Dict, traffic: Dict, seed: int, seconds: float,
                 out: Path, *, t_start: float, trace_dir: Optional[Path] = None,
                 fault: Optional[str] = None) -> None:
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.seconds, self.out, self.t_start = seconds, out, t_start
        self.trace_dir = trace_dir
        self.fault = fault
        self.run = Run(cfg, traffic, seed, Spans(traced=trace_dir is not None))
        self.feed = TokenFeed(cfg, traffic, seed)
        self.ref = reference_model(cfg)
        self.checked = traffic["checked_steps"]
        self._reference = None

    # -- common pieces -------------------------------------------------

    def phase(self, name: str, t0: float) -> None:
        self.run.phases[name] = time.perf_counter() - t0
        log(f"{name}: {self.run.phases[name]:.3f} s")

    def new_trainer(self):
        """A ``Trainer`` with the configuration's manifest capacity: the
        trainer builds its manager with the default, and the manager makes
        its pool at the first save, so the capacity is set before that."""
        from repro.launch.train import Trainer

        t = Trainer(trainer_config(self.cfg, self.traffic, self.out))
        t.manager.cfg = dataclasses.replace(
            t.manager.cfg,
            manifest_capacity=self.cfg["deployment"]["manifest_capacity"])
        t.pipeline = self.feed
        return t

    def start_trainer(self):
        """A fresh trainer over an empty directory, with the seed's
        weights; its first ``checked_steps`` steps are the ones the
        reference follows."""
        import jax

        t0 = time.perf_counter()
        trainer = self.new_trainer()
        check_program_matches(trainer, self.cfg)
        self.initial = self.ref.make_weights(self.cfg, self.seed)
        jax.block_until_ready(self.initial)
        set_weights(trainer, self.initial)
        self.phase("trainer built, weights made", t0)
        return trainer

    def checked_step(self, trainer, spans: Spans, s: int) -> float:
        """One of the first steps: keeps what the check reads — each loss,
        the first gradient from the moments after step 0, and every
        leaf's change once the checked steps are done."""
        import jax

        if self.fault == "unchanged_state" and s < self.checked:
            before = (trainer.params, trainer.opt_state)
        if self.fault == "half_batch" and s < self.checked:
            feed = trainer.pipeline
            trainer.pipeline = _HalfBatch(feed)
        loss = train_step(trainer, spans, s)
        if self.fault == "half_batch" and s < self.checked:
            trainer.pipeline = feed
        if self.fault == "unchanged_state" and s < self.checked:
            trainer.params, trainer.opt_state = before
        if s < self.checked:
            self.run.losses.append(loss)
        if s == 0:
            b1 = self.cfg["optimizer"]["b1"]
            self.run.grad_norms = {
                k: v / (1 - b1)
                for k, v in leaf_norms(trainer.opt_state["m"]).items()}
        if s == self.checked - 1:
            self.run.change_norms = leaf_norms(jax.tree.map(
                lambda a, b: a.astype("float32") - b.astype("float32"),
                trainer.params, self.initial))
            self.initial = None
        return loss

    def start_reference(self) -> None:
        """Starts the plain reference over the checked steps on a thread of
        its own. Called once the window has closed, its memory peak is read
        and the trainer is freed: the reference then runs on the device
        while the host-bound restore check runs on this thread."""
        box: Dict[str, Any] = {}

        def work():
            try:
                box["readings"] = self.ref.train_readings(
                    self.cfg, self.seed, self.feed.batches(self.checked))
            except BaseException as e:
                box["error"] = e

        thread = threading.Thread(target=work, name="reference")
        thread.start()
        self._reference = (thread, box)

    def reference_readings(self) -> Dict:
        if self._reference is None:
            self.start_reference()
        thread, box = self._reference
        t0 = time.perf_counter()
        thread.join()
        self.phase("reference joined", t0)
        if "error" in box:
            raise box["error"]
        return box["readings"]

    def trace_on(self):
        if self.trace_dir is None:
            return
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)

    def trace_off(self):
        if self.trace_dir is None:
            return
        import jax

        jax.profiler.stop_trace()

    def window(self, op: Callable[[], int]) -> None:
        """Operations start while ``seconds`` have not passed, and until the
        traffic's ``min_ops`` have run; the window closes when the last one
        ends. ``op`` returns how many units (steps, resumes) it ran."""
        self.run.setup_s = time.perf_counter() - self.t_start
        self.trace_on()
        spans = self.run.spans

        def body():
            t0, n = time.perf_counter(), 0
            while (n < self.traffic["min_ops"]
                   or time.perf_counter() - t0 < self.seconds):
                self.run.ops += op()
                n += 1

        try:
            spans.record("window", body)
        finally:
            self.trace_off()
        w = spans.within("window", 0, float("inf"))[-1]
        self.run.window = (w.t0, w.t1)
        log(f"window: {w.seconds:.3f} s, {self.run.ops} operations")

    # -- train ---------------------------------------------------------

    def train(self) -> Run:
        import jax

        k = self.traffic["ckpt_every"]
        spans = self.run.spans
        trainer = self.start_trainer()
        gate = instrument(trainer, spans,
                          keep=6 if self.trace_dir is not None else 1)
        s = 0
        t0 = time.perf_counter()
        while s < max(self.checked, k * self.traffic["warm_saves"]):
            if s < self.checked:
                self.checked_step(trainer, spans, s)
            else:
                train_step(trainer, spans, s)
            s += 1
        if trainer.flusher is not None:
            trainer.flusher.wait()
        self.phase(f"steps 0-{s - 1} and the first "
                   f"{self.traffic['warm_saves']} saves, drained", t0)
        t0 = time.perf_counter()
        for _ in range(self.traffic["fill_intervals"] * k):
            train_step(trainer, spans, s)
            s += 1
        self.phase(f"{self.traffic['fill_intervals']} intervals filling the "
                   f"flusher's queue", t0)

        def interval():
            nonlocal s
            for _ in range(k):
                train_step(trainer, spans, s)
                s += 1
            return k

        self.window(interval)
        lo, hi = self.run.window
        log("saves that ended in the window (s): " + ", ".join(
            f"{sp.seconds:.3f}" for sp in spans.items
            if sp.name == "save" and lo <= sp.t1 <= hi))
        t0 = time.perf_counter()
        gate.close(timeout=300)
        if trainer.flusher is not None:
            trainer.flusher.close()      # skips the saves still queued
        self.phase("save in flight at the close finished", t0)
        self.run.memory_peak_bytes = _memory_peak()
        done = [c for c in gate.completed if c[1] is not None]
        newest_step, newest_state = done[-1][0], done[-1][1]
        self.run.leaf_nbytes = [np.asarray(v).nbytes
                                for v in newest_state.values()]
        if self.trace_dir is not None:
            t0 = time.perf_counter()
            for (s0, a, _, _), (s1, b, _, _) in zip(done, done[1:]):
                self.run.save_dirty_blocks[s1] = dirty_blocks(b, a)
            self.phase("dirty blocks of the retained saves counted", t0)
        path = trainer.manager.path
        page = trainer.manager.cfg.page_size
        del trainer, gate
        gc.collect()
        self.start_reference()

        # the newest checkpoint acknowledged restores bit for bit
        from repro.persistence import CheckpointConfig, CheckpointManager

        t0 = time.perf_counter()
        rstep, rstate = CheckpointManager(
            path, CheckpointConfig(page_size=page)).restore()
        if self.fault == "flip_byte":
            _flip_byte(rstate)
        self.run.restored_step_behind = newest_step - rstep
        self.run.restore_bytes_differing = state_bytes_differing(
            rstate, newest_state)
        self.phase(f"checkpoint @ step {rstep} restored and compared "
                   f"(newest acknowledged: {newest_step})", t0)
        del rstate, newest_state, done
        gc.collect()
        return self.run

    # -- resume --------------------------------------------------------

    def resume(self) -> Run:
        import jax

        spans = self.run.spans
        saved = self.traffic["saved_steps"]
        trainer = self.start_trainer()
        gate = instrument(trainer, spans, keep=1)
        t0 = time.perf_counter()
        for s in range(saved):
            self.checked_step(trainer, spans, s)
        step, saved_state, _, _ = gate.completed[-1]
        if step != saved:
            raise RuntimeError(f"set-up saved step {step}, not {saved}")
        self.run.leaf_nbytes = [np.asarray(v).nbytes
                                for v in saved_state.values()]
        del trainer, gate
        self.initial = None
        gc.collect()
        self.phase(f"steps 0-{saved - 1}, checkpoint @ {saved} saved", t0)
        kept = {}

        def resume_op():
            t = spans.record("build", self.new_trainer)
            if t.start_step != saved:
                raise RuntimeError(f"resumed at step {t.start_step}, "
                                   f"not {saved}")
            if not kept:
                kept["restored"] = (t.params, t.opt_state)
            t.pipeline = self.feed
            kept["loss"] = train_step(t, spans, saved)
            kept["params"] = t.params
            return 1

        t0 = time.perf_counter()
        for _ in range(self.traffic["warm_resumes"]):
            spans.record("resume", resume_op)
            kept.clear()
        gc.collect()
        self.phase(f"{self.traffic['warm_resumes']} warm-up resume", t0)

        def op():
            return spans.record("resume", resume_op)

        self.window(op)
        self.run.memory_peak_bytes = _memory_peak()
        t0 = time.perf_counter()
        params, opt_state = kept["restored"]
        restored = dict(host_leaves(params, "p/"),
                        **host_leaves(opt_state, "o/"))
        if len(self.run.losses) < self.checked:
            self.run.losses.append(kept["loss"])
        initial = self.ref.make_weights(self.cfg, self.seed)
        after = kept["params"]
        if self.fault == "unchanged_state":
            after = initial
        self.run.change_norms = leaf_norms(jax.tree.map(
            lambda a, b: a.astype("float32") - b.astype("float32"),
            after, initial))
        del kept, initial, after, params, opt_state
        gc.collect()
        self.start_reference()
        if self.fault == "flip_byte":
            _flip_byte(restored)
        self.run.restore_bytes_differing = state_bytes_differing(
            restored, saved_state)
        self.run.restored_step_behind = 0
        del restored, saved_state
        gc.collect()
        self.phase("restored state compared", t0)
        return self.run


class _HalfBatch:
    """A planted fault: the second half of the batch's rows left out (their
    labels -1), so the loss is the mean over the rest."""

    def __init__(self, feed) -> None:
        self.feed = feed

    def batch_at(self, cursor):
        b = dict(self.feed.batch_at(cursor))
        lab = b["labels"].copy()
        lab[lab.shape[0] // 2:] = -1
        b["labels"] = lab
        return b


def _flip_byte(state: Dict[str, Any]) -> None:
    """A planted fault: one byte of a restored state altered where the
    restore produced it."""
    k = sorted(state)[0]
    a = np.ascontiguousarray(state[k]).view(np.uint8).reshape(-1).copy()
    a[a.size // 2] ^= 0xFF
    state[k] = a.view(np.asarray(state[k]).dtype).reshape(np.shape(state[k]))
