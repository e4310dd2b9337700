"""The program's telemetry inside a JAX profiler trace: recorded spans
land on the trace's host plane, the sharded retire step records its
parts and the bytes it pulls, and the segment programs carry their
names and phase scopes into the compiled text a trace is read against.
"""

import glob
import os
import re
import sys
import time
from dataclasses import replace

import jax
import numpy as np
import pytest

from repro.core.vecsim import churn_scenario, static_scenario
from repro.core.vecsim.live import LiveLoop
from repro.core.vecsim.shard.driver import ShardedStepper
from repro.core.vecsim.shard.spanner import PHASE_SCOPES, SEGMENT_PROGRAMS
from repro.core.vecsim.sim import SERIES_FIELDS
from repro.obs.hist import NB
from repro.obs.spans import _MAX_DEPTH, NULL_RECORDER, EngineObs, \
    SpanRecorder

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
sys.path.insert(0, BENCH)
import trace_reduce  # noqa: E402

RETIRE_PARTS = ("segment.retire.pull", "segment.retire.gather",
                "segment.retire.fold", "segment.retire.apply")


def _host_spans(trace_dir):
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert files, "the profiler wrote no trace"
    _, host = trace_reduce.read_events(files[0])
    out = {}
    for name, s, e in host:
        out.setdefault(name, []).append((s, e))
    return out


def test_recorded_spans_land_in_the_profiler_trace(tmp_path):
    rec = SpanRecorder(capacity=8)
    outer, inner = rec.name("obs.outer"), rec.name("obs.inner")
    deep = rec.name("obs.deep")
    null = NULL_RECORDER.name("obs.null")
    depth = _MAX_DEPTH + 3
    with jax.profiler.trace(str(tmp_path)):
        rec.begin(outer)
        rec.begin(inner)
        time.sleep(0.002)
        rec.end()
        rec.end()
        NULL_RECORDER.begin(null)
        time.sleep(0.001)
        NULL_RECORDER.end()
        # past the fixed-depth stack the ring records nothing, but the
        # profiler's annotations still open and close in step
        for _ in range(depth):
            rec.begin(deep)
        for _ in range(depth):
            rec.end()
    assert rec.depth == 0 and not rec._open
    rec.end()                      # an unmatched end stays harmless
    assert rec.depth == 0 and not rec._open
    spans = _host_spans(str(tmp_path))
    (o0, o1), = spans["obs.outer"]
    (i0, i1), = spans["obs.inner"]
    assert o0 <= i0 < i1 <= o1 and i1 - i0 >= 2_000_000
    assert len(spans["obs.deep"]) == depth
    assert "obs.null" not in spans
    # the recorder's own ring is unchanged: the two spans, then full
    assert [e["name"] for e in rec.events()][:2] == ["obs.inner",
                                                     "obs.outer"]
    assert rec.n == 8 and rec.dropped > 0


def _live(spans: bool):
    """A tiny served run on the sharded engine under link churn."""
    base = churn_scenario(5, 256, k=6, m_app=3, n_adds=32, n_rms=32,
                          churn_window=64)
    scn = replace(base, bcast_round=np.empty(0, np.int32),
                  bcast_origin=np.empty(0, np.int32)).validate()
    obs = EngineObs(histograms=True, spans=spans)
    loop = LiveLoop(scn, 64, engine="sharded", backend="jax", devices=1,
                    scan="on", seg_len=8, collect="aggregate",
                    arrivals="poisson", admission="defer", rate=1.0,
                    messages=160, seed=6, obs=obs)
    st, cw = loop.stepper, loop.cw
    seen = dict(gather_bytes=0, freed=0)
    take, free = st._take, cw.free_cols

    def spy_take(a, c, b):
        seen["gather_bytes"] += st.n_pad * len(c)
        return take(a, c, b)

    def spy_free(cols):
        seen["freed"] += len(cols)
        return free(cols)

    st._take, cw.free_cols = spy_take, spy_free
    rep = loop.run()
    return loop, obs, rep, seen


def test_retire_parts_and_counters_in_a_live_sharded_run():
    loop, obs, rep, seen = _live(spans=True)
    evs = obs.spans.events()
    assert obs.spans.depth == 0 and obs.spans.dropped == 0
    retires = [(e["t0_ns"], e["t0_ns"] + e["dur_ns"]) for e in evs
               if e["name"] == "segment.retire"]
    parts = [e for e in evs if e["name"] in RETIRE_PARTS]
    assert {e["name"] for e in parts} == set(RETIRE_PARTS)

    def inside(t0, t1=None):
        t1 = t0 if t1 is None else t1
        return [k for k, (a, b) in enumerate(retires) if a <= t0 and t1 <= b]

    for e in parts:
        assert inside(e["t0_ns"], e["t0_ns"] + e["dur_ns"]), e
    # a retire step that freed columns pulled the aggregates and
    # recycled the planes; one that folded a histogram gathered it
    for counter, want in (("retire.columns", {"segment.retire.pull",
                                              "segment.retire.apply"}),
                          ("retire.hist_bytes", set(RETIRE_PARTS))):
        marks = [e for e in evs if e["name"] == counter]
        assert marks and all(e["kind"] == "counter" for e in marks)
        steps = {k for e in marks for k in inside(e["t0_ns"])}
        assert len(steps) == len(marks)
        for k in steps:
            a, b = retires[k]
            names = {e["name"] for e in parts if a <= e["t0_ns"] < b}
            assert want <= names, (counter, names)
    hist = [e["value"] for e in evs if e["name"] == "retire.hist_bytes"]
    cols = [e["value"] for e in evs if e["name"] == "retire.columns"]
    assert sum(hist) == seen["gather_bytes"] > 0
    assert sum(cols) == seen["freed"] > 0
    assert rep.delivered_messages == 160


def test_retire_spans_change_no_result():
    runs = [_live(spans=s) for s in (False, True)]
    (l0, o0, r0, s0), (l1, o1, r1, s1) = runs
    assert o0.spans is NULL_RECORDER and o0.spans.events() == []
    np.testing.assert_array_equal(l0.stepper.series, l1.stepper.series)
    np.testing.assert_array_equal(l0.stepper.deliv_count,
                                  l1.stepper.deliv_count)
    np.testing.assert_array_equal(l0.stepper.deliv_round_sum,
                                  l1.stepper.deliv_round_sum)
    np.testing.assert_array_equal(o0.latency_hist, o1.latency_hist)
    assert s0 == s1


def _fold_run(monkeypatch, on_device: bool, run: str):
    """One churn run with the latency histogram folded on the chosen
    path; the mesh-platform predicate is what selects it."""
    from repro.core.vecsim.shard import driver
    monkeypatch.setattr(driver, "_folds_on_device", lambda mesh: on_device)
    if run == "live":
        loop, obs, _, _ = _live(spans=True)
        st = loop.stepper
        res = None
    else:
        scn = churn_scenario(3, 64)
        # recycling: four columns short, so retired columns are reused
        w = scn.m_total if run == "stepper" else scn.m_total - 4
        obs = EngineObs(histograms=True, spans=True)
        st = ShardedStepper(scn, w, n_devices=1, seg_len=8, scan="on",
                            obs=obs)
        while not st.done:
            st.advance()
        res = st.finish()
    assert st.fold_on_device is on_device
    assert obs.spans.depth == 0 and obs.spans.dropped == 0
    return st, obs, res


@pytest.mark.parametrize("run", ["stepper", "stepper-recycling", "live"])
def test_device_fold_matches_host_fold(monkeypatch, run):
    """The histogram folded on the mesh (an accelerator mesh's path)
    equals the host fold (a CPU mesh's path) byte for byte, changes no
    result, and pulls only the (NB,) int64 totals per folding retire."""
    host_st, host_obs, host_res = _fold_run(monkeypatch, False, run)
    dev_st, dev_obs, dev_res = _fold_run(monkeypatch, True, run)
    assert host_obs.latency_hist.sum() > 0
    np.testing.assert_array_equal(host_obs.latency_hist,
                                  dev_obs.latency_hist)
    for key in ("series", "deliv_count", "deliv_round_sum", "bcast_done",
                "expired"):
        np.testing.assert_array_equal(getattr(host_st, key),
                                      getattr(dev_st, key), err_msg=key)
    assert (host_st.lat_sum, host_st.lat_cnt) == (dev_st.lat_sum,
                                                  dev_st.lat_cnt)
    if host_res is not None:
        assert host_res.stats == dev_res.stats
        np.testing.assert_array_equal(host_res.delivered, dev_res.delivered)
        assert host_res.peak_live == dev_res.peak_live
    evs = dev_obs.spans.events()
    folds = [e for e in evs if e["name"] == "segment.retire.fold"]
    pulled = [e["value"] for e in evs if e["name"] == "retire.hist_bytes"]
    assert folds and len(pulled) == len(folds)
    assert pulled == [NB * 8] * len(folds)
    host_pulled = [e["value"] for e in host_obs.spans.events()
                   if e["name"] == "retire.hist_bytes"]
    assert len(host_pulled) == len(folds)
    assert min(host_pulled) >= host_st.n_pad * 8


def _op_name_components(text):
    out = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        out.update(op_name.split("/"))
    return out


def _module(text):
    return re.search(r"^HloModule\s+([^\s,]+)", text, re.M).group(1)


def _run_stepper(scn, seg_len=8):
    st = ShardedStepper(scn, scn.m_total, n_devices=1, seg_len=seg_len,
                        scan="on", obs=EngineObs(histograms=True))
    while not st.done:
        st.advance()
    st.finish()
    return st


def test_segment_programs_carry_names_and_phase_scopes():
    import trace_programs
    # the benchmark reads device time by these names
    assert trace_programs.SEGMENT_PROGRAMS == tuple(
        f"jit_{name}" for name in SEGMENT_PROGRAMS)
    assert trace_programs.PHASE_SCOPES == PHASE_SCOPES
    churn = churn_scenario(3, 64)
    # crashes too, so the gated body traces every one of its phases
    churn = replace(churn, crash_round=np.array([5, 9], np.int32),
                    crash_pid=np.array([7, 40], np.int32)).validate()
    st = _run_stepper(churn)
    texts = st.program_texts()
    assert [_module(t) for t in texts] == ["jit_segment_gated"]
    gated = {"fold", "removals", "additions", "crashes", "broadcasts",
             "deliveries", "pong", "flush_forward", "stats",
             "retire_reduce"}
    assert gated <= set(PHASE_SCOPES)
    assert gated <= _op_name_components(texts[0])

    static = _run_stepper(static_scenario(3, 64))
    texts = static.program_texts()
    assert [_module(t) for t in texts] == ["jit_segment_fast"]
    fast = {"convert", "fold", "broadcasts", "deliveries", "stats",
            "flush_forward", "retire_reduce"}
    assert fast <= _op_name_components(texts[0])


def test_retire_and_gather_programs_are_named():
    from repro.core.vecsim.shard.spanner import shard_column_gather, \
        shard_hist_runner
    st = _run_stepper(churn_scenario(3, 64))
    w = st.w
    cols = np.zeros(8, np.int32)
    no = np.zeros(w, bool)
    with jax.enable_x64(True):
        lowered = {
            "jit_hist_gather": st._take.lower(st.state[1], cols, cols),
            "jit_hist_fold": shard_hist_runner(1).jitted.lower(
                st.state[1], np.full(w, -1, np.int32)),
            "jit_column_gather": shard_column_gather().lower(st.state[1],
                                                             cols),
            "jit_retire_reduce": st.reduce_run.jitted.lower(
                st.state, np.full(w, -1, np.int32), st.rounds_dev),
            "jit_retire_apply": st.apply_run.jitted.lower(st.state, no,
                                                          no, no),
        }
        for want, low in lowered.items():
            assert _module(low.compile().as_text()) == want


def test_stepped_path_reports_its_program():
    scn = churn_scenario(3, 64)
    st = ShardedStepper(scn, scn.m_total, n_devices=1, seg_len=8,
                        scan="off")
    st.advance()
    assert [_module(t) for t in st.program_texts()] == [
        "jit_segment_stepped"]


def _pong_counters(scn):
    obs = EngineObs(histograms=False, spans=True)
    st = ShardedStepper(scn, scn.m_total, n_devices=1, seg_len=8,
                        scan="on", obs=obs)
    while not st.done:
        st.advance()
    st.finish()
    evs = obs.spans.events()
    got = {name: [e for e in evs if e["name"] == name]
           for name in ("segment.dispatch", "pong.queries", "pong.chunks")}
    return st, got


def test_pong_counters_per_gated_dispatch():
    """Every dispatch of the gated body records how many candidate slots
    its pong query asked and how many chunks it ran; the slots asked
    bound the pongs that fired.  With no link additions there is no
    pong phase and no counter."""
    st, got = _pong_counters(churn_scenario(3, 64))
    n = len(got["segment.dispatch"])
    assert n > 1
    for name in ("pong.queries", "pong.chunks"):
        assert len(got[name]) == n, name
        assert all(e["kind"] == "counter" for e in got[name])
    queries = sum(e["value"] for e in got["pong.queries"])
    chunks = sum(e["value"] for e in got["pong.chunks"])
    pongs = int(st.series[:, SERIES_FIELDS.index("pongs")].sum())
    assert queries >= pongs > 0
    assert chunks > 0

    _, got = _pong_counters(static_scenario(3, 64))
    assert got["segment.dispatch"]
    assert not got["pong.queries"] and not got["pong.chunks"]


def _frontier_counters(scn, w, seg_len):
    obs = EngineObs(histograms=False, spans=True)
    st = ShardedStepper(scn, w, n_devices=1, seg_len=seg_len, scan="on",
                        collect="full", obs=obs)
    while not st.done:
        st.advance()
    res = st.finish()
    evs = obs.spans.events()
    got = {name: [e for e in evs if e["name"] == name]
           for name in ("segment.dispatch", "frontier.columns",
                        "frontier.full_rounds")}
    return res, got


def _wave_columns(delivered):
    """Sum over rounds of the messages (app and ping) delivered in that
    round somewhere: each one's distinct delivery rounds."""
    return sum(len(np.unique(col[col >= 0])) for col in delivered.T)


def test_frontier_counters_per_gated_dispatch():
    """Every gated dispatch records the columns its frontier exchange
    found with a new delivery, summed over its rounds, and the rounds it
    ran at full width; the columns add up to the run's distinct
    (message, round) deliveries.  A window of at most 128 columns runs
    every round at full width.  At W=512, one round per dispatch, dense
    waves run at 128, at 256 and at full width, which a round takes
    exactly when more than 256 columns are active."""
    from vecsim_cases import build

    scn = churn_scenario(3, 64)
    assert scn.m_total <= 128
    res, got = _frontier_counters(scn, scn.m_total, 8)
    n = len(got["segment.dispatch"])
    assert n > 1
    for name in ("frontier.columns", "frontier.full_rounds"):
        assert len(got[name]) == n, name
        assert all(e["kind"] == "counter" for e in got[name])
    assert sum(e["value"] for e in got["frontier.full_rounds"]) \
        == scn.rounds
    assert sum(e["value"] for e in got["frontier.columns"]) \
        == _wave_columns(res.delivered) > 0

    scn = build("burst512", 7, 64)
    res, got = _frontier_counters(scn, scn.m_total, 1)
    cols = np.array([e["value"] for e in got["frontier.columns"]])
    full = np.array([e["value"] for e in got["frontier.full_rounds"]])
    assert len(cols) == len(full) == scn.rounds
    assert cols.sum() == _wave_columns(res.delivered)
    np.testing.assert_array_equal(full, cols > 256)
    assert ((cols > 0) & (cols <= 128)).any()
    assert ((cols > 128) & (cols <= 256)).any()
    assert (cols > 256).any()
