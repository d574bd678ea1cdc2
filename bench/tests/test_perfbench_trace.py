"""The trace reduction of the chip benchmark, on a small recorded
trace whose numbers are worked out by hand."""

import os

import numpy as np
import pytest

from perfbench_util import BENCH, load

T = load("trace.py", "bench_trace")
EVENTS = T.read(os.path.join(BENCH, "tests", "data", "trace_small.json"))
DEV = "/device:TPU:0"


def test_busy_union_and_window():
    red = T.reduce(EVENTS)
    # window: first call's start (1000) to last call's end (9000)
    assert red["window_s"] == pytest.approx(8000e-9)
    assert red["calls"] == 2
    assert red["call_s"] == pytest.approx([4000e-9, 3000e-9])
    # [2000, 3500] + [7000, 7500] + [8000, 8800]: overlapping ops count once
    assert red["busy_s"][DEV] == pytest.approx(2800e-9)
    assert red["busy_in_calls_s"][DEV] == pytest.approx(2800e-9)


def test_idle_gaps_labelled_by_host_span():
    red = T.reduce(EVENTS)
    idle = dict(red["idle_gaps"])
    # [1000, 2000] falls in the shortest covering span, PjitFunction;
    # [3500, 7000], [7500, 8000] and [8800, 9000] only in "outer"
    assert idle == pytest.approx({"PjitFunction(run)": 1000e-9,
                                  "outer": 4200e-9})
    assert sum(idle.values()) + red["busy_s"][DEV] == pytest.approx(
        red["window_s"])


def test_gap_labels_without_host_spans():
    ev = dict(EVENTS, host=[])
    idle = dict(T.reduce(ev)["idle_gaps"])
    assert idle == pytest.approx({"bench.call (no host span)": 1700e-9,
                                  "between calls": 3500e-9})


def test_kernel_and_collective_sums_by_name():
    red = T.reduce(EVENTS)
    assert red["op_s"][DEV] == pytest.approx({
        "fusion.1 f32[70,2]": 1000e-9,
        "replay_blocks.1 (f32[70,8192,128]": 1000e-9,
        "all-reduce.1 f32[4]": 500e-9, "fusion.2 s32[2660]": 800e-9})
    assert red["collective_s"][DEV] == pytest.approx(500e-9)
    assert T.op_seconds(red, lambda n: n.startswith("fusion")) == \
        pytest.approx(1800e-9)
    assert T.kernel_s(red, "replay") == pytest.approx(1000e-9)
    assert T.kernel_s(red, "charge_sim") == 0.0
    assert red["device_ops"][0][0].startswith(("fusion.1", "replay_blocks"))


def test_op_names_from_instruction_text():
    text = ('%replay_blocks.3 = (f32[70,8192,128]{2,1,0:T(8,128)}, f32[70,'
            '1,128]) custom-call(%a, %b), custom_call_target="tpu_custom_'
            'call"')
    assert T.op_name(text) == "replay_blocks.3 (f32[70,8192,128]{2,1,0:T(8,128)},"
    assert T.kernel_match("replay")(T.op_name(text))
    assert not T.kernel_match("charge_sim")(T.op_name(text))
    assert T.op_name("copy.1") == "copy.1"


def test_metric_helpers():
    red = T.reduce(EVENTS)
    # idle: 1 - 2800 / 8000
    assert T.idle_share(red) == pytest.approx(65.0)
    # calls 7000 ns in all, 2800 busy inside them, over 2 calls
    assert T.host_s_per_call(red) == pytest.approx(2100e-9)


def test_interval_helpers():
    ms, me = T.merge([0, 1, 5, 9], [2, 3, 6, 9], 0, 10)
    assert list(ms) == [0, 5] and list(me) == [3, 6]
    ms, me = T.merge([0, 1], [2, 3], 1, 10)
    assert list(ms) == [1] and list(me) == [3]
    gs, ge = T.gap_arrays(*T.merge([2], [3], 0, 5), 0, 5)
    assert list(gs) == [0, 3] and list(ge) == [2, 5]
    below = T.covered_below(np.array([0.0, 5.0]), np.array([3.0, 6.0]),
                            np.array([-1.0, 2.0, 4.0, 5.5, 9.0]))
    assert list(below) == [0.0, 2.0, 3.0, 3.5, 4.0]


def test_no_calls_reduce_to_nothing():
    assert T.reduce({"calls": [], "host": [], "devices": {}}) == {}
