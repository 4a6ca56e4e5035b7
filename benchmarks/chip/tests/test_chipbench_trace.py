"""The trace reduction on a small trace slice kept beside this file: busy
union, idle gaps named by the host span they fall in, program executions
and the device ops that took most time."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
sys.path[:0] = [CHIP]

import devtrace  # noqa: E402

with open(os.path.join(HERE, "data", "trace_slice.json")) as f:
    SLICE = json.load(f)
EXPECT = SLICE.pop("expect")
SLICE.pop("source")


def test_busy_is_the_union_of_op_intervals():
    got = devtrace.reduce(SLICE, chips=1, span_names=("bench.resolve",))
    assert got["window_s"] == pytest.approx(EXPECT["window_s"], rel=1e-12)
    assert got["busy_s"] == pytest.approx(EXPECT["busy_s"], rel=1e-12)


def test_programs_and_kernels_by_name():
    got = devtrace.reduce(SLICE, chips=1)
    for name, (seconds, count) in EXPECT["programs"].items():
        assert got["programs"][name][1] == count
        assert got["programs"][name][0] == pytest.approx(seconds, rel=1e-12)
    # the fused GEMM+LIF kernel runs as ``closed_call``; its events are
    # ranked among the device ops like any other op
    ops = dict(got["device_ops"])
    assert ops["closed_call"] == pytest.approx(EXPECT["kernel_s"],
                                               rel=1e-12)


def test_idle_gaps_are_named_by_their_host_span():
    got = devtrace.reduce(SLICE, chips=1, span_names=("bench.resolve",))
    assert [g[0] for g in got["idle_gaps"]] == EXPECT["gap_names"]
    assert [g[1] for g in got["idle_gaps"]] == pytest.approx(
        EXPECT["gap_s"], rel=1e-12)


def test_mean_over_chips_and_no_device_is_an_error():
    two = devtrace.reduce(SLICE, chips=2)
    assert two["busy_s"] == pytest.approx(EXPECT["busy_s_two_chips"],
                                          rel=1e-12)
    with pytest.raises(ValueError, match="no TPU"):
        devtrace.reduce({"planes": [p for p in SLICE["planes"]
                                    if not p["name"].startswith("/device")]},
                        chips=1)

