"""The trace reduction on a small trace recorded on a TPU v5 lite: three
calls of one jitted program (a 1024x1024 bf16 product), each inside a
``bench.step`` span and followed by a ``bench.idle`` sleep, all inside a
``bench.window`` span.

In this trace the device's clock runs about a millisecond behind the
host's, so the first call's program lies before the window's opening and is
left out: two programs remain inside the window."""

import pytest

from bench.lib import trace as tr
from bench.tests.tiny_cells import ROOT

TRACE = ROOT / "bench" / "tests" / "data" / "tiny_v5e.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return tr.reduce_trace(TRACE)


def test_window_and_busy(red):
    assert red["window_s"] == pytest.approx(61440876e-9 - 48514637e-9)
    assert red["n_devices"] == 1
    assert red["programs_n"] == {"jit_work": 2}
    # busy is the union of op intervals: each call's ops, back to back,
    # cover all but a 2 ns gap and the 3 us edges of its program
    assert 0 < red["busy_s"] <= red["programs_s"]["jit_work"]
    assert red["busy_s"] == pytest.approx(red["programs_s"]["jit_work"],
                                          rel=0.01)


def test_ops_by_stable_name_sum_to_busy(red):
    assert set(red["ops_s"]) == {"jit_work/fusion:fusion",
                                 "jit_work/copy-start:copy-start",
                                 "jit_work/copy-done:copy-done"}
    assert sum(red["ops_s"].values()) == pytest.approx(red["busy_s"],
                                                       rel=0.01)


def test_idle_gaps_go_to_the_open_host_span(red):
    idle = red["idle_by_span_s"]
    assert set(idle) == {"bench.idle"}
    assert sum(idle.values()) == pytest.approx(
        red["window_s"] - red["busy_s"], abs=1e-9)


def test_breakdown_lists_the_largest_first(red):
    b = tr.breakdown(red)
    assert b["device_ops"][0][0] == "jit_work/fusion:fusion"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert tr.program_time(red, "jit_work") == red["programs_s"]["jit_work"]
    assert tr.op_time(red, "jit_work", r"^fusion:") == red["ops_s"][
        "jit_work/fusion:fusion"]


@pytest.mark.parametrize("text,name", [
    ("%while.4 = (s32[]{:T(128)}, bf16[16,1,3072]{2,0,1:T(8,128)(2,1)S(1)})"
     " while((s32[]{:T(128)}) %tuple.1)", "while:while"),
    ("%closed_call.13 = bf16[16,2,12,128]{3,2,1,0:T(8,128)(2,1)S(1)} "
     "custom-call(s32[16,256]{1,0:T(8,128)S(1)} %copy-done)",
     "custom-call:closed_call"),
    ("%constant_dynamic-slice_fusion.13 = bf16[1,4098]{1,0:T(8,128)} "
     "fusion(bf16[30]{0} %p)", "fusion:constant_dynamic-slice_fusion"),
    ("fusion.7", "fusion"),
])
def test_stable_op_names(text, name):
    assert tr.stable_op(text) == name


def test_self_time_leaves_out_nested_ops():
    ev = [(0, 100, "while"), (10, 30, "a"), (40, 90, "b"), (50, 60, "c"),
          (200, 210, "d")]
    assert tr.self_times(ev) == [100 - 20 - 50, 20, 50 - 10, 10, 10]
