"""Telemetry subsystem suite (repro.obs; DESIGN.md §2.10).

The unified-telemetry claims, each tested directly:

  * the log-bucket function is a single integer-comparison contract:
    the numpy reference, the jax reduction tail and the brute-force
    layout spec all agree on every boundary value;
  * hist-derived percentiles are *exact* nearest-rank percentiles for
    latencies < 16 rounds and bucket-lower-bound approximations above;
  * the on-device delivery-latency histogram cross-validates against
    the exact event simulator's per-delivery latencies, at N ∈
    {64, 256}, windowed (numpy/jax/pallas) and sharded scan="on" — and
    telemetry on vs off leaves every engine result byte-identical;
  * a live run's histogram equals the host-side rebucketing of its own
    delivered matrix (queueing delay included), and the report's
    percentiles are the histogram's;
  * the span recorder is leak-checked (depth returns to 0), bounded
    (overflow counts into ``dropped``), and its null twin is free;
  * backpressure events are well-formed: one ``backpressure`` instant
    per caught ``WindowOverflowError``, carrying the blocking round;
  * the segment stager's upload-skip accounting matches its content
    cache semantics (satellite: stager coverage);
  * both export sinks round-trip: schema-versioned JSONL metrics
    reject foreign files, Chrome trace JSON is Perfetto-loadable
    (``traceEvents`` with X/i/C/M phases);
  * every committed ``BENCH_*.json`` loads through the shared
    versioned report reader with the kind its filename claims;
  * ``repro.core.metrics`` still works but warns
    ``LegacyEntryPointWarning`` on import (satellite: shim).
"""

import importlib
import json
import math
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.api import ObsSpec, RunSpec, SpecError, TrafficSpec, WindowSpec
from repro.api import run as api_run
from repro.core.vecsim import crossval as _crossval
from repro.core.vecsim import execute_windowed, static_scenario
from repro.core.vecsim.live import LiveLoop
from repro.core.vecsim.shard import execute_sharded
from repro.obs.hist import (NB, bucket_index_jnp, bucket_index_np,
                            bucket_lower_bounds, hist_np, merge_hists,
                            percentiles_from_hist)
from repro.obs.report import (BENCH_SCHEMA_VERSION, load_bench_report,
                              write_bench_report)
from repro.obs.sinks import (SINKS, load_metrics_jsonl, write_chrome_trace,
                             write_metrics_chrome, write_metrics_jsonl)
from repro.obs.spans import NULL_RECORDER, EngineObs, SpanRecorder

REPO_ROOT = Path(__file__).resolve().parents[1]


# --------------------------------------------------------------------- #
# Bucket layout: the integer contract
# --------------------------------------------------------------------- #
def _ref_bucket(v: int) -> int:
    """Brute-force transcription of the DESIGN §2.10 layout table."""
    if v < 16:
        return max(v, 0)
    for j in range(15):
        if (1 << (4 + j)) <= v < (1 << (5 + j)):
            return min(16 + j, NB - 1)
    return NB - 1


_EDGES = sorted({0, 1, 15, 16, 17, 31, 32, 33, 63, 64, 1023, 1024,
                 (1 << 19) - 1, 1 << 19, (1 << 20) + 7}
                | {(1 << k) + d for k in range(4, 20) for d in (-1, 0, 1)})


def test_bucket_layout_matches_spec_table():
    got = bucket_index_np(_EDGES)
    want = [_ref_bucket(v) for v in _EDGES]
    assert got.tolist() == want
    # negative sentinels clamp to bucket 0 (callers mask them out)
    assert bucket_index_np([-1, -7]).tolist() == [0, 0]


def test_bucket_index_jnp_matches_numpy():
    import jax.numpy as jnp
    values = np.array(_EDGES + list(range(0, 200)), np.int64)
    np.testing.assert_array_equal(
        np.asarray(bucket_index_jnp(jnp.asarray(values))),
        bucket_index_np(values))


def test_bucket_lower_bounds_are_bucket_minima():
    lo = bucket_lower_bounds()
    assert lo.shape == (NB,)
    for i, b in enumerate(lo):
        assert bucket_index_np([int(b)])[0] == i
        if i:  # one below the bound lands in an earlier bucket
            assert bucket_index_np([int(b) - 1])[0] == i - 1


def test_hist_np_and_merge():
    a = np.array([0, 3, 3, 15, 16, 40, -1])   # -1 masked out
    b = np.array([3, 1 << 10])
    ha, hb = hist_np(a), hist_np(b)
    assert int(ha.sum()) == 6 and int(hb.sum()) == 2
    np.testing.assert_array_equal(merge_hists([ha, hb]),
                                  hist_np(np.concatenate([a, b])))


def _nearest_rank(values, q):
    v = np.sort(np.asarray(values))
    return v[max(1, math.ceil(q / 100.0 * len(v))) - 1]


def test_percentiles_exact_below_16_and_bucketed_above():
    rng = np.random.default_rng(0)
    small = rng.integers(0, 16, size=500)      # every steady-state run
    p = percentiles_from_hist(hist_np(small), (50.0, 99.0, 99.9))
    assert p == [float(_nearest_rank(small, q)) for q in (50.0, 99.0, 99.9)]
    # above 16 the read-out is the lower bound of the nearest-rank
    # value's bucket — bucketing is monotone, so it commutes with ranks
    big = rng.integers(0, 5000, size=500)
    lo = bucket_lower_bounds()
    for q, hp in zip((50.0, 99.0, 99.9),
                     percentiles_from_hist(hist_np(big), (50.0, 99.0, 99.9))):
        assert hp == float(lo[bucket_index_np([_nearest_rank(big, q)])[0]])


def test_percentiles_empty_hist_is_nan():
    out = percentiles_from_hist(np.zeros(NB, np.int64), (50.0, 99.0))
    assert len(out) == 2 and all(math.isnan(x) for x in out)


def test_shard_hist_runner_matches_host_fold():
    """The on-device histogram (cumulative threshold counts, psum'd)
    is byte-identical to hist_np over the same gathered latencies —
    the parity contract that lets the sharded driver fold host-side on
    CPU meshes and on-device on accelerator meshes interchangeably."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core.vecsim.shard.spanner import shard_hist_runner, \
        shard_mesh

    rng = np.random.default_rng(7)
    n, w = 96, 12
    delivered = rng.integers(-1, 1 << 12, size=(n, w)).astype(np.int32)
    # one reference round per column: columns left out (base -1), a
    # sentinel-high base, a base past every delivery, and normal bases
    base = np.array([0, -1, 1, 5, -1, 2, -1, 40, -1, -1, 9000, 1],
                    np.int32)
    mesh = shard_mesh(1)
    dev = jax.device_put(delivered, NamedSharding(mesh, PartitionSpec("shard")))
    got = np.asarray(shard_hist_runner(1)(dev, base))
    assert got.dtype == np.int64 and got.shape == (NB,)
    da = delivered.astype(np.int64)
    valid = (da >= 0) & (base >= 0)[None, :]
    want = hist_np((da - base[None, :].astype(np.int64))[valid])
    assert want.sum() > 0
    np.testing.assert_array_equal(got, want)


# --------------------------------------------------------------------- #
# Span recorder: bounded, leak-checked, null twin free
# --------------------------------------------------------------------- #
def test_span_recorder_events_and_depth():
    rec = SpanRecorder(capacity=16)
    outer, inner = rec.name("outer"), rec.name("inner")
    assert rec.name("outer") == outer      # interning is idempotent
    rec.begin(outer)
    rec.begin(inner)
    assert rec.depth == 2
    rec.end()
    rec.instant(rec.name("mark"), 7.0)
    rec.counter(rec.name("gauge"), 3.5)
    rec.end()
    assert rec.depth == 0 and rec.dropped == 0
    evs = rec.events()
    assert [e["kind"] for e in evs] == ["span", "instant", "counter",
                                       "span"]
    assert [e["name"] for e in evs] == ["inner", "mark", "gauge", "outer"]
    assert all(e["dur_ns"] >= 0 for e in evs if e["kind"] == "span")
    # inner span closed first, but outer opened first
    assert evs[3]["t0_ns"] <= evs[0]["t0_ns"]
    assert evs[1]["value"] == 7.0 and evs[2]["value"] == 3.5


def test_span_recorder_overflow_counts_dropped():
    rec = SpanRecorder(capacity=2)
    mark = rec.name("m")
    for _ in range(5):
        rec.instant(mark)
    assert rec.n == 2 and rec.dropped == 3
    assert len(rec.events()) == 2


def test_null_recorder_is_inert():
    assert not NULL_RECORDER.enabled
    NULL_RECORDER.begin(NULL_RECORDER.name("x"))
    NULL_RECORDER.end()
    NULL_RECORDER.instant(0, 1.0)
    assert NULL_RECORDER.depth == 0 and NULL_RECORDER.events() == []


def test_engine_obs_accumulators():
    obs = EngineObs(histograms=True, spans=True, span_capacity=8)
    obs.add_hist(hist_np([1, 2]))
    obs.gauge("g", 4)
    obs.count("c")
    obs.count("c", 2)
    assert int(obs.latency_hist.sum()) == 2
    assert obs.gauges == {"g": [4]} and obs.counters == {"c": 3}
    assert obs.spans.enabled
    off = EngineObs(histograms=False)
    off.add_hist(hist_np([1]))
    assert int(off.latency_hist.sum()) == 0   # disabled: fold is a no-op
    assert off.spans is NULL_RECORDER


# --------------------------------------------------------------------- #
# Histogram cross-validation against the exact event simulator
# --------------------------------------------------------------------- #
_EXACT_CACHE: dict = {}


def _scn(n):
    return static_scenario(1, n, k=4, m_app=8)


def _exact_latencies(n) -> np.ndarray:
    """Per-delivery latency multiset from the exact replay: delivery
    time minus the same message's broadcast time, rounded (exact sim
    times carry float epsilon; latencies are integral rounds)."""
    if n not in _EXACT_CACHE:
        net = _crossval.run_exact(_scn(n))
        t_bcast, lat = {}, []
        for t, kind, pid, msg in net.trace:
            if kind == "broadcast":
                t_bcast[(pid, msg.counter)] = t
            elif kind == "deliver":
                lat.append(t - t_bcast[(msg.origin, msg.counter)])
        _EXACT_CACHE[n] = np.rint(np.asarray(lat)).astype(np.int64)
    return _EXACT_CACHE[n]


def _run_engine(engine, backend, scn, obs):
    if engine == "windowed":
        return execute_windowed(scn, 32, backend=backend, collect="full",
                                obs=obs)
    return execute_sharded(scn, 32, n_devices=1, seg_len=8, scan="on",
                           collect="full", backend=backend, obs=obs)


# pallas kept to N=64: the fused-kernel bucketing is identical code at
# any N, and the interpret-mode run dominates suite wall-time otherwise
_MATRIX = [("windowed", "numpy", 64), ("windowed", "jax", 64),
           ("windowed", "pallas", 64), ("sharded", "jax", 64),
           ("windowed", "numpy", 256), ("windowed", "jax", 256),
           ("sharded", "jax", 256)]


@pytest.mark.parametrize("engine,backend,n", _MATRIX)
def test_latency_hist_crossvalidates_exact_engine(engine, backend, n):
    scn = _scn(n)
    obs = EngineObs(histograms=True, spans=True)
    on = _run_engine(engine, backend, scn, obs)
    off = _run_engine(engine, backend, scn, None)

    # telemetry on vs off: byte-identical results
    np.testing.assert_array_equal(on.delivered, off.delivered)
    np.testing.assert_array_equal(on.series, off.series)
    assert on.deliv_count.tolist() == off.deliv_count.tolist()
    assert on.stats == off.stats

    # the on-device histogram is the exact engine's latency multiset
    exact = _exact_latencies(n)
    np.testing.assert_array_equal(obs.latency_hist, hist_np(exact))
    assert int(obs.latency_hist.sum()) == len(exact)

    # hist-derived percentiles == bucketed exact nearest-rank
    lo = bucket_lower_bounds()
    qs = (50.0, 99.0, 99.9)
    for q, hp in zip(qs, percentiles_from_hist(obs.latency_hist, qs)):
        assert hp == float(lo[bucket_index_np([_nearest_rank(exact, q)])[0]])

    # piggyback/occupancy gauges rode along; no span leaked
    assert len(obs.gauges["piggyback_bytes"]) > 0
    assert len(obs.gauges["window_occupancy"]) > 0
    assert obs.spans.depth == 0


# --------------------------------------------------------------------- #
# Live mode: histogram == rebucketed delivered matrix, report percentiles
# --------------------------------------------------------------------- #
def _live_run(obs, **kw):
    scn = static_scenario(5, 48, k=4, m_app=0)
    loop = LiveLoop(scn, 64, engine="windowed", backend="numpy",
                    collect="full", arrivals="poisson", rate=4.0,
                    messages=192, seed=3, obs=obs, **kw)
    return loop, loop.run()


def test_live_hist_matches_delivered_matrix():
    obs = EngineObs(histograms=True, spans=True)
    loop, rep = _live_run(obs)
    _, rep_off = _live_run(EngineObs(histograms=False))

    # telemetry on vs off: identical serving outcome
    assert rep.admitted == rep_off.admitted
    assert rep.delivered_messages == rep_off.delivered_messages
    np.testing.assert_array_equal(rep.result.series, rep_off.result.series)
    np.testing.assert_array_equal(rep.result.deliv_count,
                                  rep_off.result.deliv_count)

    # live latency base is the submission round: the histogram must be
    # the host-side rebucketing of the delivered matrix itself
    m_bc = len(rep.submit_round)
    d = rep.result.delivered[:, :m_bc]
    lat = (d - rep.submit_round[None, :])[d >= 0]
    np.testing.assert_array_equal(obs.latency_hist, hist_np(lat))

    # the report's percentiles are the histogram's
    p50, p99, p999 = percentiles_from_hist(obs.latency_hist,
                                           (50.0, 99.0, 99.9))
    assert (rep.p50, rep.p99, rep.p999) == (p50, p99, p999)

    # tick spans recorded, nothing leaked
    names = {e["name"] for e in obs.spans.events()}
    assert {"tick", "tick.ingest", "tick.admit", "tick.advance"} <= names
    assert obs.spans.depth == 0 and obs.spans.dropped == 0


# --------------------------------------------------------------------- #
# Satellite: backpressure events are well-formed, no span leaks
# --------------------------------------------------------------------- #
def test_backpressure_events_well_formed():
    obs = EngineObs(histograms=True, spans=True)
    scn = static_scenario(3, 32, k=3, m_app=0)
    loop = LiveLoop(scn, 8, engine="windowed", backend="numpy",
                    seg_len=4, admission="admit", rate=16.0,
                    messages=256, seed=2, obs=obs)
    rep = loop.run()
    assert rep.overflow_catches > 0, "admit policy should hit overflow"
    bp = [e for e in obs.spans.events() if e["name"] == "backpressure"]
    assert all(e["kind"] == "instant" for e in bp)
    # one instant per caught overflow, mirrored by the counter
    assert len(bp) == rep.overflow_catches
    assert obs.counters["backpressure_events"] == rep.overflow_catches
    # each carries the blocking round: an integer inside the run bound
    for e in bp:
        assert e["value"] == int(e["value"])
        assert 0 <= e["value"] <= rep.bound
    # the exception path closed every span it opened
    assert obs.spans.depth == 0
    # ingest accounting stays consistent under sustained backpressure
    assert (rep.admitted + rep.unserved + rep.shed_queue
            + rep.shed_policy == rep.offered)


# --------------------------------------------------------------------- #
# Satellite: segment stager upload-skip accounting
# --------------------------------------------------------------------- #
def test_stager_content_cache_accounting():
    from repro.core.vecsim.shard.driver import _SegmentStager
    st = _SegmentStager(None, None, seg_len=4, rounds=16,
                        put=lambda a: np.asarray(a))
    a = np.arange(6, dtype=np.int32)
    st._stage("x", a.copy())
    assert (st.uploads, st.skips) == (1, 0)
    st._stage("x", a.copy())               # identical content: skip
    assert (st.uploads, st.skips) == (1, 1)
    b = a.copy()
    b[0] = 99
    st._stage("x", b)                      # mutated content: re-upload
    assert (st.uploads, st.skips) == (2, 1)
    # the cache stores a *copy*: mutating the staged source afterwards
    # must not poison the comparison for the next identical segment
    c = np.arange(6, dtype=np.int32)
    st._stage("y", c)
    c[:] = 7
    st._stage("y", np.arange(6, dtype=np.int32))
    assert (st.uploads, st.skips) == (3, 2)


def test_stager_counters_surface_through_obs():
    obs = EngineObs(histograms=True)
    execute_sharded(_scn(64), 32, n_devices=1, seg_len=8, scan="on",
                    obs=obs)
    # a static run has quiescent segments: the sentinel planes re-use
    assert obs.counters["stager_uploads"] > 0
    assert obs.counters["stager_skips"] > 0


# --------------------------------------------------------------------- #
# Sinks: JSONL metrics round-trip + Chrome trace JSON validity
# --------------------------------------------------------------------- #
def _sample_doc():
    return dict(run={"engine": "windowed", "n": 64},
                summary={"latency_p50": 4.0, "wall_seconds": 0.25},
                latency_hist=hist_np([1, 2, 2, 40]),
                gauges={"window_occupancy": [3.0, 5.0]},
                counters={"stager_uploads": 7})


def test_metrics_jsonl_round_trip(tmp_path):
    path = str(tmp_path / "m.jsonl")
    write_metrics_jsonl(path, _sample_doc())
    doc = load_metrics_jsonl(path)
    assert doc["run"]["engine"] == "windowed"
    assert doc["summary"]["latency_p50"] == 4.0
    np.testing.assert_array_equal(doc["latency_hist"],
                                  hist_np([1, 2, 2, 40]))
    assert doc["gauges"]["window_occupancy"] == [3.0, 5.0]
    assert doc["counters"]["stager_uploads"] == 7


def test_metrics_jsonl_rejects_foreign_files(tmp_path):
    alien = tmp_path / "alien.jsonl"
    alien.write_text('{"schema": "someone.else", "version": 1}\n')
    with pytest.raises(ValueError, match="not a repro.obs.metrics"):
        load_metrics_jsonl(str(alien))
    stale = tmp_path / "stale.jsonl"
    stale.write_text('{"schema": "repro.obs.metrics", "version": 99}\n')
    with pytest.raises(ValueError, match="version"):
        load_metrics_jsonl(str(stale))
    (tmp_path / "empty.jsonl").write_text("")
    with pytest.raises(ValueError, match="empty"):
        load_metrics_jsonl(str(tmp_path / "empty.jsonl"))


def test_chrome_trace_json_is_loadable(tmp_path):
    rec = SpanRecorder(capacity=16)
    rec.begin(rec.name("segment.dispatch"))
    rec.end()
    rec.instant(rec.name("backpressure"), 12.0)
    rec.counter(rec.name("queue"), 3.0)
    path = str(tmp_path / "trace.json")
    write_chrome_trace(path, rec, run_args={"engine": "windowed"})
    with open(path) as fh:
        doc = json.load(fh)
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    phases = [e["ph"] for e in evs]
    assert phases.count("M") >= 2 and "X" in phases and "i" in phases
    assert "C" in phases
    span = next(e for e in evs if e["ph"] == "X")
    assert span["name"] == "segment.dispatch" and span["dur"] >= 0
    assert all(e["ts"] >= 0 for e in evs if "ts" in e)   # rebased to t0
    inst = next(e for e in evs if e["ph"] == "i")
    assert inst["s"] == "t" and inst["args"]["value"] == 12.0
    # satellite (S10): span-name families land on *named* thread tracks
    # so the trace reads without the code open
    threads = {e["tid"]: e["args"]["name"] for e in evs
               if e["ph"] == "M" and e["name"] == "thread_name"}
    assert threads[span["tid"]] == "segment pipeline"
    assert threads[inst["tid"]] == "serving loop"


def test_chrome_metrics_sink(tmp_path):
    path = str(tmp_path / "metrics.json")
    SINKS["chrome-trace"].write(path, _sample_doc())
    with open(path) as fh:
        doc = json.load(fh)
    counters = [e for e in doc["traceEvents"] if e["ph"] == "C"]
    # satellite (S10): counter tracks are engine-prefixed so gauge
    # series from different engines never collide into one track
    assert {e["name"] for e in counters} == {"windowed/window_occupancy",
                                             "windowed/stager_uploads"}
    # a sharded doc additionally carries the device count in the prefix
    sharded = _sample_doc()
    sharded["run"] = {"engine": "sharded", "n": 64, "devices": 4}
    SINKS["chrome-trace"].write(path, sharded)
    with open(path) as fh:
        doc = json.load(fh)
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "C"}
    assert names == {"sharded[d4]/window_occupancy",
                     "sharded[d4]/stager_uploads"}


def test_sinks_registry_exposed_by_api():
    from repro.api import SINKS as api_sinks
    assert set(SINKS) == {"jsonl", "chrome-trace"}
    for key in SINKS:
        assert api_sinks.get(key).write is SINKS[key].write


# --------------------------------------------------------------------- #
# Spec layer + API end-to-end export
# --------------------------------------------------------------------- #
def test_obs_spec_validates_eagerly():
    with pytest.raises(SpecError, match="obs.sink"):
        RunSpec(n=16, obs=ObsSpec(sink="nope")).validate()
    with pytest.raises(SpecError, match="span_capacity"):
        RunSpec(n=16, obs=ObsSpec(span_capacity=0)).validate()
    with pytest.raises(SpecError, match="histograms"):
        RunSpec(n=16, obs=ObsSpec(histograms="yes")).validate()


def test_obs_spec_round_trips_through_dict():
    spec = RunSpec(n=64, obs=ObsSpec(histograms=True, spans=True,
                                     sink="chrome-trace"))
    assert RunSpec.from_dict(spec.to_dict()) == spec
    assert RunSpec.from_dict(json.loads(json.dumps(spec.to_dict()))) == spec


def _api_spec(obs):
    return RunSpec(engine="windowed", backend="numpy", n=48,
                   traffic=TrafficSpec(messages=16),
                   window=WindowSpec(window=48), obs=obs)


def test_api_run_exports_trace_and_metrics(tmp_path):
    trace = str(tmp_path / "t.json")
    metrics = str(tmp_path / "m.jsonl")
    rep = api_run(_api_spec(ObsSpec(trace_out=trace, metrics_out=metrics)))
    assert rep.obs is not None and rep.obs.spans.depth == 0
    # extras carry the histogram-derived percentiles
    total = int(rep.obs.latency_hist.sum())
    assert rep.extras["latency_hist_total"] == total > 0
    p50 = percentiles_from_hist(rep.obs.latency_hist, (50.0,))[0]
    assert rep.extras["latency_p50"] == p50
    # the metrics file round-trips and matches the in-memory histogram
    doc = load_metrics_jsonl(metrics)
    np.testing.assert_array_equal(doc["latency_hist"], rep.obs.latency_hist)
    assert doc["summary"]["latency_p50"] == p50
    # the trace file is Chrome-trace JSON with the segment span taxonomy
    with open(trace) as fh:
        tdoc = json.load(fh)
    names = {e["name"] for e in tdoc["traceEvents"] if e["ph"] == "X"}
    assert {"segment.dispatch", "segment.retire"} <= names


def test_api_obs_disabled_is_none_and_identical():
    on = api_run(_api_spec(ObsSpec(histograms=True)))
    off = api_run(_api_spec(ObsSpec(histograms=False)))
    assert off.obs is None and "latency_p50" not in off.extras
    assert on.extras["latency_p50"] > 0
    np.testing.assert_array_equal(on.result.series, off.result.series)
    np.testing.assert_array_equal(on.result.deliv_count,
                                  off.result.deliv_count)
    assert on.stats == off.stats


# --------------------------------------------------------------------- #
# Satellite: shared bench-report schema
# --------------------------------------------------------------------- #
def test_bench_report_round_trip(tmp_path):
    path = str(tmp_path / "BENCH_x.json")
    out = write_bench_report(path, "scale", {"n": 64, "kind": "ignored"})
    assert out["schema_version"] == BENCH_SCHEMA_VERSION
    assert out["kind"] == "scale"          # writer owns the stamp
    doc = load_bench_report(path, kind="scale")
    assert doc == out and doc["n"] == 64
    with pytest.raises(ValueError, match="kind"):
        load_bench_report(path, kind="serve")
    with pytest.raises(ValueError, match="unknown bench kind"):
        write_bench_report(path, "nope", {})


def test_bench_report_version_policy(tmp_path):
    legacy = tmp_path / "legacy.json"
    legacy.write_text('{"n": 8}')          # pre-schema snapshots load
    assert load_bench_report(str(legacy), kind="scale")["n"] == 8
    future = tmp_path / "future.json"
    future.write_text('{"schema_version": 99, "kind": "scale"}')
    with pytest.raises(ValueError, match="schema_version"):
        load_bench_report(str(future))


def test_every_committed_bench_snapshot_loads():
    paths = sorted(REPO_ROOT.glob("BENCH_*.json"))
    assert paths, "expected committed BENCH_*.json snapshots"
    for path in paths:
        kind = path.stem[len("BENCH_"):]
        doc = load_bench_report(str(path), kind=kind)
        assert doc["schema_version"] == BENCH_SCHEMA_VERSION, path.name


# --------------------------------------------------------------------- #
# Satellite: the repro.core.metrics legacy shim warns
# --------------------------------------------------------------------- #
def test_legacy_metrics_entry_point_warns():
    from repro.core.types import LegacyEntryPointWarning
    sys.modules.pop("repro.core.metrics", None)
    with pytest.warns(LegacyEntryPointWarning):
        mod = importlib.import_module("repro.core.metrics")
    import repro.obs.graphs as graphs
    # the shim re-exports the real implementations, not copies
    assert mod.mean_shortest_path is graphs.mean_shortest_path
    assert mod.safe_graph is graphs.safe_graph
    assert mod.overhead_per_message is graphs.overhead_per_message
