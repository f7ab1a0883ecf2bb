"""``trace_reduce`` on two small recorded traces (cut from chip runs of PR 25:
the first three requests of a traced ``val175-commit-stream`` run, the first
two of a ``val10k-commit-stream`` run) and on a hand-made one."""

import os

import pytest

from benchmarks import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "testdata")
KERNEL = (r" tpu_custom_call$",)


def test_hand_made_planes():
    planes = [
        ("/device:TPU:0", [
            ("XLA Ops", [("k", 100, 50), ("k", 200, 100), ("c", 320, 10),
                         ("k", 1000, 100), ("k", 2000, 10)]),
            ("XLA Modules", [("m", 0, 5000)]),
        ]),
        ("/host:CPU", [("python3", [("request", 90, 300), ("request", 400, 800),
                                    ("other", 0, 1)])]),
    ]
    t = tr.reduce_planes(planes)
    assert t.window_s == pytest.approx(1110e-9)  # 90 .. 1200
    assert t.busy_s == pytest.approx(260e-9)  # the event at 2000 is outside
    assert t.op_seconds == {"k": pytest.approx(250e-9), "c": pytest.approx(10e-9)}
    assert t.op_counts == {"k": 3, "c": 1}
    assert t.requests == 2 and t.chips == 1
    gaps = t.idle_gaps
    assert gaps["inside a request, before its first device operation"] == \
        pytest.approx(610e-9)  # 90-100 and 400-1000
    assert gaps["inside a request, between its device operations"] == \
        pytest.approx(70e-9)  # 150-200, 300-320
    assert gaps["inside a request, after its last device operation"] == \
        pytest.approx(160e-9)  # 330-390, 1100-1200
    assert gaps["between requests"] == pytest.approx(10e-9)  # 390-400
    assert sum(gaps.values()) + t.busy_s == pytest.approx(t.window_s)


def test_nothing_to_read_gives_nothing():
    assert tr.reduce_planes([]) is None
    assert tr.reduce_planes([("/device:TPU:0", [("XLA Ops", [("k", 1, 1)])])]) is None
    assert tr.reduce_planes([("/host:CPU", [("t", [("request", 1, 5)])])]) is None


def test_short_name():
    text = ('%_pallas_core.1 = s32[1,8192]{1,0:T(1,128)S(1)} custom-call(s32[20,8192]'
            '{1,0} %concatenate.4), custom_call_target="tpu_custom_call", x={}')
    assert tr.short_name(text) == "_pallas_core.1 s32[1,8192] tpu_custom_call"
    assert tr.short_name("%fusion.3 = (s32[1,128]{1,0}, s32[1,128]{1,0}) fusion(...)") \
        == "fusion.3 s32[1,128]"
    assert tr.short_name("no hlo here") == "no hlo here"


@pytest.mark.parametrize("name, window_s, busy_s, requests, kernel_s, kernels, top", [
    ("val175_3req", 0.03653774, 0.000835905, 3, 0.000792524, 3,
     "_pallas_core.1 s32[1,128] tpu_custom_call"),
    ("val10k_2req", 0.443316718, 0.038024326, 2, 0.037934846, 4,
     "_pallas_core.1 s32[1,8192] tpu_custom_call"),
])
def test_recorded_trace(name, window_s, busy_s, requests, kernel_s, kernels, top):
    t = tr.reduce_file(os.path.join(DATA, name + ".xplane.pb"))
    assert t.window_s == pytest.approx(window_s, rel=1e-9)
    assert t.busy_s == pytest.approx(busy_s, rel=1e-9)
    assert t.requests == requests and t.chips == 1
    seconds, count = t.seconds_of(KERNEL)
    assert seconds == pytest.approx(kernel_s, rel=1e-9) and count == kernels
    assert t.top_ops(1)[0][0] == top
    assert sum(t.idle_gaps.values()) + t.busy_s == pytest.approx(t.window_s)
    assert len(t.top_ops(10)) == 10 and len(t.top_gaps(10)) == 4
    idle_pct = 100 * (1 - t.busy_s / t.window_s)
    assert 90 < idle_pct < 100
