"""The reduction from a profiler trace to the per-layer numbers.

``data/tiny_window.xplane.pb`` was recorded on one TPU v5 lite chip: a
jitted ``while_loop`` of 50 (512 x 512) products and tanh, run three
times inside the ``bench.traced_window`` annotation, with the Python
tracer off (65 KB).
"""
import importlib.util

import pytest

from conftest import BENCH

FIXTURE = BENCH / "tests" / "data" / "tiny_window.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    spec = importlib.util.spec_from_file_location("bench_trace", BENCH / "trace.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_union_and_gaps_by_hand(trace):
    iv = [(20, 30), (0, 10), (5, 15)]
    assert trace.union_length(iv) == 25
    assert trace.gaps(iv, -5, 40) == [(-5, 0), (15, 20), (30, 40)]
    assert trace.gaps(iv, 2, 12) == []
    assert trace.union_length([]) == 0


def test_op_name(trace):
    assert trace.op_name("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop") == "fusion.3"


def test_reduce_recorded_trace(trace):
    r = trace.reduce_trace(str(FIXTURE))
    assert r["chips"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    seconds, executions = r["modules"]["jit_tiny"]
    assert executions >= 2 and 0 < seconds <= r["busy_s"]
    # busy time and the idle gaps tile the window
    idle = sum(s for _, s in r["idle_gaps"])
    assert idle + r["busy_s"] == pytest.approx(r["window_s"], rel=1e-9)
    assert all(not name.startswith("%") for name, _ in r["ops"])
    assert r["ops"][0][0] == "while"


def test_trace_without_window_is_refused(trace):
    with pytest.raises(RuntimeError, match="annotation"):
        trace.reduce_trace(str(FIXTURE), window="no.such.window")
