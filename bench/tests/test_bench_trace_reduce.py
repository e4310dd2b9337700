"""The trace reduction: device-busy union, idle share, top operations,
idle gaps by host activity and collective time, on hand-built events
and on a small trace recorded once on a TPU v5e."""

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
import trace_reduce as tr  # noqa: E402

TPU_TRACE = os.path.join(HERE, "data", "small_tpu.xplane.pb")


def naive_busy(events, lo, hi):
    """Busy nanoseconds by marking every covered nanosecond."""
    mark = np.zeros(hi - lo, bool)
    for _, s, e in events:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            mark[a - lo:b - lo] = True
    return int(mark.sum())


def test_union_of_overlapping_intervals():
    iv = np.array([[0, 10], [5, 15], [20, 30], [21, 22], [30, 31]])
    np.testing.assert_array_equal(tr.merged(iv),
                                  [[0, 15], [20, 31]])
    assert tr.union_length(iv) == 26
    assert tr.union_length(np.zeros((0, 2), np.int64)) == 0


def test_reduce_by_hand():
    dev = {"/device:TPU:0": [("a", 100, 200), ("b", 150, 300),
                             ("all-reduce.1", 280, 400), ("a", 500, 600)],
           "/device:TPU:1": [("a", 100, 600)]}
    host = [("bench.traced", 0, 1000), ("segment.retire", 400, 500),
            ("segment.stage", 600, 1000)]
    out = tr.reduce_trace(dev, host, 0, 1000)
    # device 0 busy [100, 400) + [500, 600) = 400; device 1: 500
    assert out["busy_s"] == pytest.approx((400 + 500) / 2 / 1e9)
    assert out["window_s"] == pytest.approx(1e-6)
    assert out["devices"] == 2
    # idle: dev0 [0,100) traced, [400,500) retire, [600,1000) stage;
    # dev1 [0,100) traced, [600,1000) stage
    gaps = dict(out["idle_gaps"])
    assert gaps["segment.stage"] == pytest.approx(800 / 2 / 1e9)
    assert gaps["bench.traced"] == pytest.approx(200 / 2 / 1e9)
    assert gaps["segment.retire"] == pytest.approx(100 / 2 / 1e9)
    ops = dict(out["device_ops"])
    assert ops["a"] == pytest.approx((100 + 100 + 500) / 2 / 1e9)
    assert out["device_ops"][0][0] == "a"
    # the all-reduce runs [280, 400); [280, 300) overlaps op b
    assert out["collective_s"] == pytest.approx(120 / 2 / 1e9)
    assert out["collective_exposed_s"] == pytest.approx(100 / 2 / 1e9)


def test_window_clips_operations():
    dev = {"d": [("x", 0, 100), ("y", 90, 250)]}
    out = tr.reduce_trace(dev, [], 50, 200)
    assert out["busy_s"] == pytest.approx(150 / 1e9)
    assert dict(out["device_ops"])["x"] == pytest.approx(50 / 1e9)


def test_empty_inputs_are_errors():
    with pytest.raises(ValueError):
        tr.reduce_trace({"d": [("x", 0, 1)]}, [], 5, 5)
    with pytest.raises(ValueError):
        tr.reduce_trace({}, [], 0, 10)


def test_recorded_tpu_trace():
    dev, host = tr.read_events(TPU_TRACE)
    assert list(dev) == ["/device:TPU:0"]
    ops = dev["/device:TPU:0"]
    assert len(ops) == 84
    assert all(name.startswith("jit__lambda/") for name, _, _ in ops)
    lo, hi = tr.find_span(host, "bench.traced")
    out = tr.reduce_trace(dev, host, lo, hi)
    assert out["busy_s"] * 1e9 == pytest.approx(naive_busy(ops, lo, hi),
                                                abs=1)
    assert 0 < out["busy_s"] < out["window_s"]
    assert out["window_s"] == pytest.approx((hi - lo) / 1e9)
    names = [n for n, _ in out["idle_gaps"]]
    assert set(names) <= {"bench.traced", "bench.advance"} | {
        n for n, _, _ in host}
    secs = [v for _, v in out["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    idle = sum(v for _, v in out["idle_gaps"])
    assert idle == pytest.approx(out["window_s"] - out["busy_s"], rel=1e-6)
