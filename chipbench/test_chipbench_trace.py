"""The trace reduction, on hand-counted intervals and on a small trace
recorded on the CPU."""
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import devtrace


def test_union_and_gaps_hand_counted():
    # busy: [10, 30) and [25, 40) overlap -> [10, 40); [50, 55) nested
    # in [50, 60); [70, 80) runs past the window's end at 75
    iv = [(25, 40), (10, 30), (50, 60), (52, 55), (70, 80)]
    assert devtrace.merge(iv) == [(10, 40), (50, 60), (70, 80)]
    assert devtrace.covered(iv, 0, 75) == 30 + 10 + 5
    assert devtrace.gaps(iv, 0, 75) == [(0, 10), (40, 50), (60, 70)]
    assert devtrace.covered(iv, 12, 14) == 2
    assert devtrace.gaps(iv, 12, 14) == []
    assert devtrace.gaps([], 3, 9) == [(3, 9)]
    assert devtrace.covered([], 3, 9) == 0


def test_gap_labels_take_the_innermost_host_span():
    host = [("call", 0, 100), ("upload", 5, 15), ("drain", 60, 90),
            ("other", 200, 300)]
    gaps = [(0, 4), (6, 10), (40, 50), (70, 80), (150, 160)]
    assert devtrace.label_gaps(gaps, host) == {
        "call": 4 + 10, "upload": 4, "drain": 10, "no host span": 10}


class _Ev:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _Profile:
    def __init__(self, planes):
        self.planes = planes


def test_trace_reduction_on_hand_made_planes():
    ops = [_Ev("%fusion.1 = f32[8] fusion(x)", 100, 50),
           _Ev("%fusion.1 = f32[8] fusion(x)", 200, 100),
           _Ev("%copy.2 = f32[8] copy(y)", 380, 40)]
    pd = _Profile([
        _Plane("/device:TPU:0", [
            _Line("XLA Modules", [_Ev("jit_step(1)", 90, 350),
                                  _Ev("jit_probe(2)", 600, 30)]),
            _Line("XLA Ops", ops)]),
        _Plane("/device:TPU:1", [_Line("XLA Ops", [_Ev("%x = y", 0, 10)])]),
        _Plane("/host:CPU", [
            _Line("python", [_Ev(devtrace.WINDOW, 100, 300),
                             _Ev("upload", 150, 40), _Ev("drain", 310, 60)]),
            _Line("worker", [_Ev("Busy", 150, 300)])]),
    ])
    t = devtrace.Trace(pd, ("/device:TPU:", "XLA Ops"),
                       ("/device:TPU:", "XLA Modules"), n_chips=1)
    assert t.window == (100, 400)
    assert t.window_s == pytest.approx(300e-9)
    # busy in [100, 400): [100,150) + [200,300) + [380,400) = 170 ns
    assert t.busy_s() == pytest.approx(170e-9)
    assert t.module_s("jit_probe") == [pytest.approx(30e-9)]
    assert t.top_ops() == [["fusion.1", pytest.approx(150e-9)],
                           ["copy.2", pytest.approx(20e-9)]]
    # gaps [150,200) under "upload" (mid 175), [300,380) under "drain"
    # (mid 340); the worker thread's span does not label them
    assert t.idle_gaps() == [["drain", pytest.approx(80e-9)],
                             ["upload", pytest.approx(50e-9)]]
    two = devtrace.Trace(pd, ("/device:TPU:", "XLA Ops"),
                         ("/device:TPU:", "XLA Modules"), n_chips=2)
    assert two.busy_s() == pytest.approx((170e-9 + 0) / 2)


def test_reduction_of_a_trace_recorded_on_the_cpu(tmp_path):
    """Three device calls with 30 ms of host sleep between them: the
    CPU's op events are found inside the window, busy + idle is the
    window, and the sleeps are idle time labelled by their host span."""
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128), jnp.float32)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(devtrace.WINDOW):
            for _ in range(3):
                f(x).block_until_ready()
                with jax.profiler.TraceAnnotation("sleep"):
                    time.sleep(0.03)
    finally:
        jax.profiler.stop_trace()
    t = devtrace.load(str(tmp_path), "cpu", 1,
                      ops=("/host:CPU", "tf_XLAPjRtCpuClient"),
                      modules=("/host:CPU", "none"))
    assert t.window is not None and t.window_s >= 0.09
    busy = t.busy_s()
    assert 0 < busy < t.window_s - 0.09 + 1e-3
    idle = dict(t.idle_gaps())
    assert idle["sleep"] >= 0.09 - 3e-3
    assert sum(idle.values()) == pytest.approx(t.window_s - busy, rel=1e-6)
    assert t.top_ops()
