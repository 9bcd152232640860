"""Checks of the trace reduction (``trace.py``).

One check runs on intervals made by hand, where every number can be worked
out on paper; the other on ``testdata/cpu_window.xplane.pb``, a trace
that ``jax.profiler`` recorded on the CPU: three jitted ``sin(x) @ x.T``
calls of 512 × 512, each inside a ``bench:step@i`` annotation, then a
50 ms sleep inside ``bench:save@3``, all inside ``bench:window``. The CPU
has no device plane, so there the XLA threads of the host stand in for it.

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_trace.py
"""

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from drive import load_module  # noqa: E402

T = load_module(HERE / "trace.py", "bench_trace")


def _cpu_ops(plane, line):
    return plane == "/host:CPU" and line.startswith("tf_XLA")


def test_hand_made_intervals():
    iv = T.Interval
    trace = T.Trace(
        ops=[iv("a", 0, 10, "d0", "jit_f"), iv("b", 5, 20, "d0", "jit_f"),
             iv("c", 30, 40, "d0", "jit_g"), iv("a", 0, 50, "d1", "jit_f")],
        spans=[iv("window", 0, 60), iv("save@8", 18, 35),
               iv("step@9", 38, 60)])
    s = T.summarize(trace)
    assert s.window_s == pytest.approx(60e-9)
    # d0 busy 0-20 and 30-40 (30 ns), d1 0-50 (50 ns): the mean is 40 ns
    assert s.busy_s == pytest.approx(40e-9)
    # d0's gaps: 20-30 inside the save, 40-60 inside the step
    assert s.gaps == [("step", pytest.approx(20e-9)),
                      ("save", pytest.approx(10e-9))]
    assert s.op_seconds["jit_f:a"] == pytest.approx(60e-9)
    assert T.module_time(trace, ["jit_f"]) == pytest.approx(75e-9)
    # only c (30-40) starts inside the save (18-35)
    assert T.module_time(trace, ["jit_f"], within=[trace.spans[1]]) == 0.0
    assert T.module_time(trace, ["jit_g"],
                         within=[trace.spans[1]]) == pytest.approx(10e-9)
    assert T.breakdown(s)["idle_gaps"][0] == ["step", pytest.approx(20e-9)]


def test_modules_tag_the_operations_inside_them():
    iv = T.Interval
    ops = [iv("x", 2, 4), iv("y", 12, 30)]
    mods = [iv("jit_f(1)", 0, 10), iv("jit_g(2)", 10, 20)]
    tagged = T._tag_modules(ops, mods)
    assert [o.module for o in tagged] == ["jit_f(1)", ""]   # y overruns


def test_operations_are_labelled_by_program_and_hlo_name():
    iv = T.Interval
    op = iv("%while.6 = (s32[]{:T(128)}, bf16[8,2048]) while(%tuple.2)",
            0, 1, "d0", "jit_train_step(2842806738831132740)")
    assert T.op_label(op) == "jit_train_step:while.6"
    assert T.op_label(iv("dot_general.3", 0, 1)) == "dot_general.3"


def test_recorded_cpu_trace():
    trace = T.Trace.load(str(HERE / "testdata" / "cpu_window.xplane.pb"),
                         is_op=_cpu_ops)
    names = [s.name for s in trace.spans]
    assert names == ["window", "step@0", "step@1", "step@2", "save@3"]
    s = T.summarize(trace)
    lo, hi = trace.window()
    assert s.window_s == pytest.approx((hi - lo) / 1e9)
    assert 0.05 < s.window_s < 0.2
    # the matmuls ran; the sleep kept the host's XLA threads idle
    assert any(k.startswith("dot_general") for k in s.op_seconds)
    assert 0 < s.busy_s < s.window_s - 0.045
    assert s.gaps[0][0] == "save" and s.gaps[0][1] >= 0.045


def test_a_missing_trace_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        T.Trace.load(str(tmp_path))
