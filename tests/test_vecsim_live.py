"""Live serving front-door suite (DESIGN.md §2.9).

The serving loop's correctness claims, each tested directly:

  * the overflow raise is *state-clean*: ``ColumnWindow.activate``
    detects the blocking round before any assignment, so a caught
    ``WindowOverflowError`` leaves the window byte-identical and
    re-enterable (the catch-and-defer backpressure path relies on it);
  * a live run is a *scheduler*, not a new engine: the finally-admitted
    schedule replayed pre-scripted through the same engine reproduces
    the live run's delivered matrix, series and stats byte-for-byte
    (windowed, and the scanned sharded path on every dynamics of
    ``vecsim_cases.BUILDERS`` under each arrival process and admission
    policy);
  * the served sharded path's ``collect="aggregate"`` (what the chip
    benchmark runs) reports exactly what ``collect="full"`` does;
  * the capacity-blind ``admit`` policy drives the engine into overflow
    and the loop serves every message anyway (catch, withdraw, requeue,
    retry — zero loss);
  * rounds-to-delivery latency (queueing delay included) cross-validates
    against the exact event simulator on the admitted schedule, at N ∈
    {64, 256} and under churn, through the windowed engine and the
    scanned sharded one: the mean over per-message mean delivery
    rounds, and the p50/p99 as per-delivery histogram read-outs
    (repro.obs, DESIGN.md §2.10);
  * the ingest accounting identity holds under shedding;
  * the spec/registry surface validates eagerly and the discovery
    listing describes every arrivals/admission entry.
"""

import io
from dataclasses import replace
from functools import lru_cache

import numpy as np
import pytest

from repro.api import LiveSpec, MetricsSpec, RunSpec, SpecError
from repro.api import run as api_run
from repro.core.vecsim import crossval as _crossval
from repro.core.vecsim.live import (LiveColumnWindow, LiveLoop,
                                    build_arrivals)
from repro.core.vecsim.scenario import churn_scenario, static_scenario
from repro.core.vecsim.stream import (ColumnWindow, WindowOverflowError,
                                      execute_windowed)
from repro.obs.hist import hist_np, percentiles_from_hist
from vecsim_cases import BUILDERS


def _base(seed, n, **kw):
    return static_scenario(seed, n, k=4, m_app=0, **kw)


def _served(scn):
    """``scn`` with its scripted broadcasts stripped: a live base whose
    traffic comes only from the arrival process."""
    return replace(scn, bcast_round=np.empty(0, np.int32),
                   bcast_origin=np.empty(0, np.int32)).validate()


def _live_engine(engine):
    """LiveLoop engine arguments: the windowed numpy engine, or the
    scanned sharded one the chip benchmark serves through."""
    if engine == "windowed":
        return dict(engine="windowed", backend="numpy")
    return dict(engine="sharded", devices=1, scan="on")


def _replay(engine, scn, window):
    """Replay an admitted schedule pre-scripted through ``engine``."""
    if engine == "windowed":
        return execute_windowed(scn, window, backend="numpy", seg_len=32)
    from repro.core.vecsim.shard import execute_sharded
    return execute_sharded(scn, window, n_devices=1, scan="on",
                           seg_len=32)


# --------------------------------------------------------------------- #
# Satellite 1: the overflow raise leaves the window untouched
# --------------------------------------------------------------------- #
def _window_snapshot(cw):
    return dict(
        slot_msg=cw.slot_msg.copy(), slot_birth=cw.slot_birth.copy(),
        slot_app=cw.slot_app.copy(), bc_live_slot=cw.bc_live_slot.copy(),
        add_live_slot=cw.add_live_slot.copy(),
        bc_round=cw.bc_round.copy(), bc_origin=cw.bc_origin.copy(),
        next_bc=cw.next_bc, next_add=cw.next_add, m_bc=cw.m_bc,
        peak_live=cw.peak_live)


def test_overflow_raise_is_state_clean():
    scn = static_scenario(3, 32, k=4, m_app=64)
    cw = ColumnWindow(scn, 4)
    t, err, snap = 0, None, None
    for _ in range(scn.rounds * 2):
        snap = _window_snapshot(cw)
        try:
            t = cw.activate(t, min(t + 8, scn.rounds))
        except WindowOverflowError as exc:
            err = exc
            break
    else:
        pytest.fail("expected the 4-column window to overflow")
    after = _window_snapshot(cw)
    for key, before in snap.items():
        if isinstance(before, np.ndarray):
            np.testing.assert_array_equal(
                before, after[key], err_msg=f"{key} mutated by the raise")
        else:
            assert before == after[key], f"{key} mutated by the raise"
    # re-enterable: the same call raises the same way, and with room
    # freed the window proceeds (nothing was half-assigned)
    with pytest.raises(WindowOverflowError) as again:
        cw.activate(t, min(t + 8, scn.rounds))
    assert again.value.round == err.round
    assert err.round <= t


def test_overflow_seg_len_invariant_after_catch():
    # the blocking round reported must not depend on how the caller
    # segments time (the live loop retries with the same seg boundaries)
    scn = static_scenario(5, 32, k=4, m_app=48)
    rounds = []
    for seg in (4, 8, 16):
        cw = ColumnWindow(scn, 4)
        t = 0
        try:
            for _ in range(scn.rounds * 2):
                t = cw.activate(t, min(t + seg, scn.rounds))
        except WindowOverflowError as exc:
            rounds.append(exc.round)
    assert len(set(rounds)) == 1, rounds


# --------------------------------------------------------------------- #
# Live window: append / withdraw mechanics
# --------------------------------------------------------------------- #
def test_live_window_append_and_withdraw():
    scn = _base(1, 16)
    cw = LiveColumnWindow(scn, 8, capacity=10, per_round_cap=2)
    ids = cw.append_broadcasts(np.array([1, 1, 2], np.int32),
                               np.array([3, 4, 5], np.int32))
    assert ids.tolist() == [0, 1, 2] and cw.m_bc == 3
    with pytest.raises(ValueError):   # unsorted batch
        cw.append_broadcasts(np.array([5, 4], np.int32),
                             np.array([0, 1], np.int32))
    with pytest.raises(ValueError):   # behind the admitted stream
        cw.append_broadcasts(np.array([1], np.int32),
                             np.array([9], np.int32))
    rounds, origins = cw.withdraw_unactivated()
    assert rounds.tolist() == [1, 1, 2] and origins.tolist() == [3, 4, 5]
    assert cw.m_bc == 0
    # positions recycle
    ids = cw.append_broadcasts(np.array([4], np.int32),
                               np.array([7], np.int32))
    assert ids.tolist() == [0]
    with pytest.raises(ValueError):   # capacity
        cw.append_broadcasts(np.full(10, 9, np.int32),
                             np.arange(10, dtype=np.int32))
    with pytest.raises(ValueError):   # live base must be broadcast-free
        LiveColumnWindow(static_scenario(1, 16, m_app=2), 8,
                         capacity=4, per_round_cap=1)


def test_arrival_processes():
    for kind in ("poisson", "bursty", "diurnal"):
        rounds, origins = build_arrivals(kind, 3, 32, 4.0, 500)
        assert len(rounds) == len(origins) == 500
        assert (np.diff(rounds) >= 0).all(), kind
        assert origins.min() >= 0 and origins.max() < 32
    with pytest.raises(KeyError):
        build_arrivals("nope", 0, 8, 1.0, 10)


# --------------------------------------------------------------------- #
# Tentpole: live == pre-scripted replay, byte for byte
# --------------------------------------------------------------------- #
def _assert_replay_identical(rep, res2):
    r1 = rep.result
    np.testing.assert_array_equal(r1.series, res2.series)
    np.testing.assert_array_equal(r1.deliv_count, res2.deliv_count)
    np.testing.assert_array_equal(r1.deliv_round_sum,
                                  res2.deliv_round_sum)
    np.testing.assert_array_equal(r1.expired, res2.expired)
    np.testing.assert_array_equal(r1.bcast_done, res2.bcast_done)
    if r1.delivered is not None and res2.delivered is not None:
        np.testing.assert_array_equal(r1.delivered, res2.delivered)
    assert r1.stats == res2.stats


@pytest.mark.parametrize("arrivals,admission", [
    ("poisson", "defer"), ("bursty", "admit"), ("diurnal", "defer"),
])
def test_live_byte_identity_windowed(arrivals, admission):
    scn = _base(3, 64)
    loop = LiveLoop(scn, 16, engine="windowed", backend="numpy",
                    arrivals=arrivals, admission=admission,
                    rate=4.0, messages=200, queue_cap=4096, seed=7,
                    arrival_params=dict(period=64, duty=0.5))
    rep = loop.run()
    assert rep.admitted == 200 and rep.delivered_messages == 200
    res2 = execute_windowed(rep.scenario, 16, backend="numpy", seg_len=32)
    _assert_replay_identical(rep, res2)


# The served sharded grid: every dynamics of the differential suites
# (their scripted broadcasts stripped) under every arrival process and
# both admitting policies.  The window is small enough that ``admit``
# overflows and is caught on several dynamics, and large enough that no
# base's own pings overflow it.
SERVE_N, SERVE_W, SERVE_M, SERVE_SEG = 32, 24, 40, 8
SERVE_GRID = [(name, arrivals, admission) for name in BUILDERS
              for arrivals in ("poisson", "bursty", "diurnal")
              for admission in ("defer", "admit")]


@lru_cache(maxsize=None)
def _live_sharded(name, arrivals, admission, collect):
    """One served sharded run of the grid: its report and the latency
    histogram it merged (cached; two tests read each full run)."""
    loop = LiveLoop(_served(BUILDERS[name](5, SERVE_N)), SERVE_W,
                    seg_len=SERVE_SEG, collect=collect,
                    arrivals=arrivals, admission=admission, rate=4.0,
                    messages=SERVE_M, queue_cap=512, seed=7,
                    **_live_engine("sharded"))
    return loop.run(), loop.obs.latency_hist.copy()


def _undeliverable(scn):
    """Admitted broadcasts whose origin crashed at or before their
    round: they are never delivered."""
    crashed = dict(zip(scn.crash_pid.tolist(), scn.crash_round.tolist()))
    return sum(1 for r, o in zip(scn.bcast_round.tolist(),
                                 scn.bcast_origin.tolist())
               if o in crashed and crashed[o] <= r)


@pytest.mark.parametrize("name,arrivals,admission", SERVE_GRID)
def test_live_byte_identity_sharded_scan(name, arrivals, admission):
    rep, _ = _live_sharded(name, arrivals, admission, "full")
    assert rep.admitted == SERVE_M
    assert (rep.delivered_messages
            == rep.admitted - _undeliverable(rep.scenario))
    _assert_replay_identical(rep, _replay("sharded", rep.scenario,
                                          SERVE_W))


@pytest.mark.parametrize("name,arrivals,admission", SERVE_GRID)
def test_live_sharded_aggregate_matches_full(name, arrivals, admission):
    full, hist_full = _live_sharded(name, arrivals, admission, "full")
    agg, hist_agg = _live_sharded(name, arrivals, admission, "aggregate")
    assert full.result.delivered is not None
    assert agg.result.delivered is None
    assert hist_full.sum() > 0
    np.testing.assert_array_equal(hist_agg, hist_full)
    assert (agg.p50, agg.p99) == (full.p50, full.p99)
    np.testing.assert_array_equal(agg.result.series, full.result.series)
    assert agg.result.stats == full.result.stats
    assert ((agg.admitted, agg.delivered_messages)
            == (full.admitted, full.delivered_messages))
    np.testing.assert_array_equal(agg.submit_round, full.submit_round)
    np.testing.assert_array_equal(agg.result.deliv_count,
                                  full.result.deliv_count)
    np.testing.assert_array_equal(agg.result.deliv_round_sum,
                                  full.result.deliv_round_sum)


def test_admit_policy_catches_overflow_and_loses_nothing():
    scn = _base(3, 64)
    loop = LiveLoop(scn, 12, engine="windowed", backend="numpy",
                    arrivals="bursty", admission="admit",
                    rate=8.0, messages=300, queue_cap=4096, seed=11,
                    arrival_params=dict(period=64, duty=0.5))
    rep = loop.run()
    assert rep.overflow_catches > 0, \
        "capacity-blind admission never hit the window"
    assert rep.admitted == 300 and rep.delivered_messages == 300
    assert rep.shed_queue == 0 and rep.shed_policy == 0
    # the overflow-driven trajectory is still a pure schedule
    res2 = execute_windowed(rep.scenario, 12, backend="numpy", seg_len=32)
    _assert_replay_identical(rep, res2)


def test_shed_accounting_identity():
    scn = _base(9, 32)
    loop = LiveLoop(scn, 8, engine="windowed", backend="numpy",
                    arrivals="bursty", admission="shed",
                    rate=16.0, messages=400, queue_cap=32, seed=3,
                    arrival_params=dict(period=32, duty=0.5))
    rep = loop.run()
    assert rep.shed_queue + rep.shed_policy > 0
    assert (rep.admitted + rep.shed_queue + rep.shed_policy
            + rep.unserved == rep.offered)
    assert rep.delivered_messages == rep.admitted
    res2 = execute_windowed(rep.scenario, 8, backend="numpy", seg_len=32)
    _assert_replay_identical(rep, res2)


# --------------------------------------------------------------------- #
# Satellite 3: latency accounting vs the exact event simulator
# --------------------------------------------------------------------- #
def _exact_mean_delivery_rounds(adm, seed):
    """Per-admitted-message mean delivery round from the exact engine's
    trace (its delivery times are whole rounds on these scenarios)."""
    net = _crossval.run_exact(adm, seed=seed, protocol="pc")
    sums = {}
    counts = {}
    for t, kind, _pid, m in net.trace:
        if kind != "deliver":
            continue
        key = (m.origin, m.counter)
        sums[key] = sums.get(key, 0.0) + t
        counts[key] = counts.get(key, 0) + 1
    # message j -> (origin, counter): counters increment per origin in
    # round order, and (origin, round) pairs are unique
    order = np.argsort(adm.bcast_round, kind="stable")
    seen = {}
    mean = np.full(adm.m_app, np.nan)
    for j in order:
        o = int(adm.bcast_origin[j])
        seen[o] = seen.get(o, 0) + 1
        key = (o, seen[o])
        if key in counts:
            mean[j] = sums[key] / counts[key]
    return mean


def _exact_delivery_latencies(adm, submit, seed):
    """Per-*delivery* latency multiset (delivery round minus submission
    round) from the exact engine's trace — the quantity the on-device
    histogram buckets, and since PR 9 the source of the report's
    p50/p99/p99.9 (exact times carry float epsilon, hence the rint)."""
    net = _crossval.run_exact(adm, seed=seed, protocol="pc")
    order = np.argsort(adm.bcast_round, kind="stable")
    seen, sub = {}, {}
    for j in order:
        o = int(adm.bcast_origin[j])
        seen[o] = seen.get(o, 0) + 1
        sub[(o, seen[o])] = int(submit[j])
    lat = [t - sub[(m.origin, m.counter)]
           for t, kind, _pid, m in net.trace if kind == "deliver"]
    return np.rint(np.asarray(lat)).astype(np.int64)


@pytest.mark.parametrize("engine", ["windowed", "sharded"])
@pytest.mark.parametrize("n,messages", [(64, 150), (256, 300)])
def test_latency_crossval_vs_exact(n, messages, engine):
    scn = _base(21, n)
    loop = LiveLoop(scn, max(16, n // 4), arrivals="poisson",
                    admission="defer", rate=4.0, messages=messages,
                    queue_cap=1 << 14, seed=5, **_live_engine(engine))
    rep = loop.run()
    assert rep.delivered_messages == messages
    mean = _exact_mean_delivery_rounds(rep.scenario, seed=5)
    assert not np.isnan(mean).any()
    lat = mean - rep.submit_round
    assert rep.mean_latency_rounds == pytest.approx(float(lat.mean()))
    # p50/p99 are per-delivery histogram read-outs (repro.obs): they
    # must equal the same read-out over the exact engine's latencies
    lat_del = _exact_delivery_latencies(rep.scenario, rep.submit_round,
                                        seed=5)
    p50, p99 = percentiles_from_hist(hist_np(lat_del), (50.0, 99.0))
    assert (rep.p50, rep.p99) == (p50, p99)


@pytest.mark.parametrize("engine", ["windowed", "sharded"])
def test_latency_crossval_churn_during_serving(engine):
    scn = _served(churn_scenario(17, 64, k=5, m_app=6, n_adds=5, n_rms=4))
    loop = LiveLoop(scn, 24, arrivals="poisson", admission="defer",
                    rate=3.0, messages=120, queue_cap=1 << 12, seed=29,
                    **_live_engine(engine))
    rep = loop.run()
    assert rep.delivered_messages == 120
    mean = _exact_mean_delivery_rounds(rep.scenario, seed=29)
    assert not np.isnan(mean).any()
    lat_del = _exact_delivery_latencies(rep.scenario, rep.submit_round,
                                        seed=29)
    p50, p99 = percentiles_from_hist(hist_np(lat_del), (50.0, 99.0))
    assert (rep.p50, rep.p99) == (p50, p99)
    # and the delivered multiset itself matches the exact engine
    _assert_replay_identical(rep, _replay(engine, rep.scenario, 24))


# --------------------------------------------------------------------- #
# API surface: mode="live" through the front door
# --------------------------------------------------------------------- #
def test_api_live_mode_end_to_end():
    spec = RunSpec(
        mode="live", engine="windowed", backend="numpy", n=64, seed=2,
        live=LiveSpec(arrivals="poisson", rate=4.0, messages=100,
                      queue_cap=1024, slo_p99=1e9),
        metrics=MetricsSpec(oracle=True, crossval=True))
    rep = api_run(spec)
    assert rep.live is not None and rep.live.slo_ok is True
    assert rep.oracle.ok and rep.crossval_ok
    assert rep.m_app == 100 and rep.delivered_frac == 1.0
    assert rep.extras["serve_admitted"] == 100
    d = rep.to_dict()
    assert d["live"]["p99"] == rep.live.p99


def test_live_spec_validation():
    with pytest.raises(SpecError):
        RunSpec(mode="serve").validate()
    with pytest.raises(SpecError, match="live.arrivals"):
        RunSpec(mode="live", live=LiveSpec(arrivals="nope")).validate()
    with pytest.raises(SpecError, match="admission"):
        RunSpec(mode="live", live=LiveSpec(admission="nope")).validate()
    with pytest.raises(SpecError, match="engine"):
        RunSpec(mode="live", engine="exact").validate()
    with pytest.raises(SpecError, match="messages"):
        RunSpec(mode="live", live=LiveSpec(messages=0)).validate()
    with pytest.raises(SpecError, match="per_round_cap"):
        RunSpec(mode="live", n=8,
                live=LiveSpec(per_round_cap=9)).validate()
    with pytest.raises(SpecError, match="snapshot"):
        RunSpec(mode="live",
                metrics=MetricsSpec(snapshot=4)).validate()
    # JSON round-trip carries the live section
    spec = RunSpec.from_dict({"mode": "live",
                              "live": {"arrivals": "bursty",
                                       "rate": 2.5}}).validate()
    assert spec.live.arrivals == "bursty" and spec.live.rate == 2.5
    assert RunSpec.from_dict(spec.to_dict()) == spec


def test_discovery_lists_live_registries():
    from contextlib import redirect_stdout

    from repro.api.__main__ import print_registries
    buf = io.StringIO()
    with redirect_stdout(buf):
        print_registries()
    out = buf.getvalue()
    assert "arrivals (live mode):" in out
    assert "admission (live mode):" in out
    for line in out.splitlines():
        if line.startswith("  "):
            key_desc = line.strip().split(None, 1)
            if key_desc[0].startswith("test_"):
                # other suites register description-less throwaway
                # entries (e.g. test_api's register-to-extend checks)
                continue
            assert len(key_desc) == 2 and key_desc[1], \
                f"registry entry missing description: {line!r}"
