"""The reduction from a trace to numbers, on hand-made lines and on a small
trace recorded on a TPU v5e."""

import os

import pytest

from benchmark.lib import xplane
from benchmark.lib.xplane import Event

from helpers import TESTS


def test_union_and_busy():
    events = [Event("a", 0, 10), Event("b", 5, 20), Event("c", 30, 40)]
    assert xplane.union((e.start, e.end) for e in events) == [(0, 20), (30, 40)]
    assert xplane.busy_ns(events) == 30


def test_subtract():
    assert xplane.subtract([(0, 100)], [(10, 20), (50, 60)]) == [
        (0, 10), (20, 50), (60, 100)
    ]
    assert xplane.subtract([(0, 10), (20, 30)], [(5, 25)]) == [(0, 5), (25, 30)]
    assert xplane.subtract([(0, 10)], []) == [(0, 10)]


def test_nesting_gives_self_time():
    # a while loop holding two body operations, then a fusion
    events = [
        Event("while", 0, 100), Event("body.1", 10, 40),
        Event("body.2", 50, 90), Event("fusion", 100, 130),
    ]
    assert xplane.self_times(events) == {
        "while": 30.0, "body.1": 30.0, "body.2": 40.0, "fusion": 30.0
    }


def test_exposed_collective_time_two_line_example():
    # One chip's operations: the all-reduce runs 100..160; a fusion covers
    # 100..130 of it (overlapped), 130..160 nothing else runs (exposed).
    ops = [
        Event("fusion.1", 0, 100),
        Event("all-reduce-start.1", 100, 160),
        Event("fusion.2", 100, 130),
        Event("fusion.3", 160, 200),
    ]
    assert xplane.exposed_collective_ns(ops) == 30
    # A second chip where the collective is wholly hidden.
    hidden = [Event("all-reduce.7", 10, 20), Event("fusion.1", 5, 15),
              Event("fusion.2", 15, 30)]
    assert xplane.exposed_collective_ns(hidden) == 0
    # A loop that holds the collective is its container, not cover.
    looped = [Event("while", 0, 50), Event("all-reduce.7", 10, 20),
              Event("fusion", 20, 45)]
    assert xplane.exposed_collective_ns(looped) == 10


def test_idle_gaps_are_named_after_the_host_span_that_covers_them():
    ops = [Event("step", 0, 40), Event("step", 60, 100), Event("step", 150, 200)]
    host = [Event("bench.dispatch", 38, 62), Event("bench.wait", 95, 160)]
    gaps = xplane.idle_gaps(ops, (0, 220), host)
    assert gaps == [
        ["bench.wait", 50e-9], ["bench.dispatch", 20e-9], ["(none)", 20e-9]
    ]


def test_summarize_hand_made_trace():
    trace = {
        "/device:TPU:0": {
            xplane.OPS_LINE: [Event("fusion", 0, 50), Event("all-reduce", 50, 60)],
            xplane.MODULES_LINE: [Event("jit_step", 0, 60)],
        },
        "/device:TPU:1": {
            xplane.OPS_LINE: [Event("fusion", 0, 30)],
            xplane.MODULES_LINE: [Event("jit_step", 0, 30)],
        },
        "/host:CPU": {"main": [Event("bench.window", 0, 100),
                               Event("bench.wait", 60, 100)]},
    }
    s = xplane.summarize(trace)
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["busy_s"] == pytest.approx((60 + 30) / 2 * 1e-9)
    assert s["chips"][0]["exposed_collective_s"] == pytest.approx(10e-9)
    assert s["chips"][0]["programs"]["jit_step"]["count"] == 1
    assert s["device_ops"][0][0] == "fusion"
    assert s["idle_gaps"][0][0] == "bench.wait"


def test_no_device_operation_gives_nothing():
    assert xplane.summarize({"/host:CPU": {"main": [Event("x", 0, 1)]}}) is None


RECORDED = os.path.join(TESTS, "data", "tpu_v5e_two_steps.xplane.pb.gz")


def test_short_name_keeps_name_shape_and_opcode():
    hlo = ("%select_and_scatter.29 = bf16[5376,84,84,16]{0,3,2,1:T(8,128)} "
           "select-and-scatter(bf16[5376,84,84,16]{0,3,2,1} %x), window={}")
    assert xplane.short_name(hlo) == (
        "select_and_scatter.29 = bf16[5376,84,84,16]{0,3,2,1:T(8,128)} "
        "select-and-scatter"
    )
    assert xplane.short_name("plain") == "plain"
    assert xplane.short_name(
        "%fusion.1 = (f32[]{:T(128)}, bf16[3,3]{1,0:T(4,128)(2,1)}) "
        "fusion(bf16[5]{0} %a), kind=kOutput"
    ) == "fusion.1 = f32[]{:T(128)},... fusion"
    assert xplane.COLLECTIVE.match("%all-reduce-start.3 = f32[] all-reduce-start()")


def test_recorded_tpu_trace():
    """A trace of a few steps of the tiny rehearsal learner, recorded on one
    TPU v5e chip: the planes and lines are where the reduction looks."""
    trace = xplane.load(RECORDED)
    assert xplane.device_planes(trace) == ["/device:TPU:0"]
    lines = trace["/device:TPU:0"]
    assert lines[xplane.OPS_LINE] and lines[xplane.MODULES_LINE]
    s = xplane.summarize(trace)
    assert 0 < s["busy_s"] <= s["window_s"]
    steps = max(p["count"] for p in s["chips"][0]["programs"].values())
    assert steps >= 2
    # self times add up to the busy union (one line, nested by containment)
    assert sum(s["chips"][0]["op_self_s"].values()) == pytest.approx(
        s["chips"][0]["busy_s"], rel=1e-6
    )
    assert any(name.startswith("bench.") for name, _ in s["idle_gaps"])
