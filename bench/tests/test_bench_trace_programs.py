"""Device time by program and by named phase: module events of a trace
recorded once on a TPU v5e, nested operations' self time by hand, the
phase scopes read from a segment program's compiled text, and the
retire step's span metrics in a traced CPU rehearsal."""

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)
import bench_tiny  # noqa: E402
import trace_programs as tp  # noqa: E402
import trace_reduce as tr  # noqa: E402

TPU_TRACE = os.path.join(HERE, "data", "small_tpu.xplane.pb")


def test_device_programs_on_recorded_tpu_trace():
    mods = tp.read_modules(TPU_TRACE)
    assert list(mods) == ["/device:TPU:0"]
    evs = mods["/device:TPU:0"]
    assert len(evs) == 6 and {n for n, _, _ in evs} == {"jit__lambda"}
    dev, host = tr.read_events(TPU_TRACE)
    lo, hi = tr.find_span(host, "bench.traced")
    out = tp.device_programs(mods, lo, hi)
    assert [n for n, _ in out] == ["jit__lambda"]
    want = sum(max(0, min(e, hi) - max(s, lo)) for _, s, e in evs)
    assert out[0][1] * 1e9 == pytest.approx(want, abs=1)
    # every op runs inside a program: programs hold the busy time
    busy = tr.reduce_trace(dev, host, lo, hi)["busy_s"]
    assert busy <= out[0][1] <= (hi - lo) / 1e9


def test_device_programs_by_hand():
    mods = {"d0": [("jit_a", 0, 100), ("jit_b", 100, 150),
                   ("jit_a", 180, 300)],
            "d1": [("jit_a", 50, 250)]}
    out = dict(tp.device_programs(mods, 20, 200))
    # d0: a 80 + 20, b 50; d1: a 150; averaged over two devices
    assert out["jit_a"] == pytest.approx((100 + 150) / 2 / 1e9)
    assert out["jit_b"] == pytest.approx(50 / 2 / 1e9)
    assert tp.device_programs({}, 0, 10) == []


def test_self_time_subtracts_nested_ops():
    iv = np.array([[0, 100], [10, 30], [40, 90], [50, 60], [100, 120]])
    np.testing.assert_array_equal(tp.self_times(iv),
                                  [30, 20, 40, 10, 20])
    # order of the input does not matter
    np.testing.assert_array_equal(tp.self_times(iv[::-1]),
                                  [20, 10, 40, 20, 30])


def test_device_scopes_by_hand():
    g = "jit_segment_gated"
    dev = {"/device:TPU:0": [
        (f"{g}/while.1", 0, 100), (f"{g}/fusion.2", 10, 30),
        (f"{g}/fusion.3", 40, 90), (f"{g}/copy-start.4", 50, 60),
        ("jit_hist_gather/fusion.1", 100, 120),
        (f"{g}/fusion.5", 130, 140)]}
    scopes = {g: {"fusion.2": "flush_forward", "fusion.3": "pong",
                  "fusion.5": "stats"}}
    out = dict(tp.device_scopes(dev, 0, 95, scopes))
    # the window cuts the while to [0, 95): 95 - 20 - 50 of its own
    assert out == {
        "pong": pytest.approx(40 / 1e9),
        "flush_forward": pytest.approx(20 / 1e9),
        tp.UNSCOPED: pytest.approx((25 + 10) / 1e9)}
    full = dict(tp.device_scopes(dev, 0, 200, scopes))
    assert full["stats"] == pytest.approx(10 / 1e9)
    assert sum(full.values()) == pytest.approx(110 / 1e9)   # no gather


def test_op_scopes_take_the_innermost_phase():
    text = ("HloModule jit_segment_gated, is_scheduled=true\n"
            '  %fusion.7 = s32[4]{0} fusion(%p), kind=kLoop, '
            'metadata={op_name="jit(segment_gated)/while/body/pong/'
            'stats/add"}\n'
            '  ROOT %copy.2 = s32[4]{0} copy(%fusion.7), '
            'metadata={op_name="jit(segment_gated)/flush_forward/min"}\n'
            "  %tuple.1 = (s32[4]{0}) tuple(%copy.2)\n")
    assert tp.op_scopes([text]) == {
        "jit_segment_gated": {"fusion.7": "stats",
                              "copy.2": "flush_forward"}}


def test_op_scopes_of_a_compiled_segment_program():
    from repro.core.vecsim import churn_scenario
    from repro.core.vecsim.shard.driver import ShardedStepper
    from repro.obs.spans import EngineObs

    scn = churn_scenario(3, 64)
    st = ShardedStepper(scn, scn.m_total, n_devices=1, seg_len=8,
                        scan="on", obs=EngineObs(histograms=True))
    while not st.done:
        st.advance()
    texts = st.program_texts()
    table = tp.op_scopes(texts)
    assert list(table) == ["jit_segment_gated"]
    found = set(table["jit_segment_gated"].values())
    assert {"fold", "removals", "additions", "broadcasts", "deliveries",
            "pong", "flush_forward", "stats", "retire_reduce"} <= found
    for name in table["jit_segment_gated"]:
        assert f"%{name} = " in texts[0] or f" {name} = " in texts[0]


@pytest.mark.parametrize("cell", [c for c, _, _ in bench_tiny.CELLS])
def test_traced_rehearsal_reads_the_retire_split(tmp_path, cell):
    root = bench_tiny.make_root(str(tmp_path))
    rc, res, err = bench_tiny.run(root, cell, seed=2147483648 + 29,
                                  trace=1)
    assert rc == 0, err
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["retire_fold_ms_per_round"] > 0
    assert m["retire_gather_ms_per_round"] > 0
    assert res["metrics"]["retire_fold_ms_per_round"]["unit"] == "ms/round"
    # both are parts of the segment pipeline's own host steps
    assert (m["retire_fold_ms_per_round"] + m["retire_gather_ms_per_round"]
            < m["segment_host_ms_per_round"])
