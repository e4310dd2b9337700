"""Device-sharded engine: byte-identity with the windowed engine at
every device count, the ISSUE acceptance matrix (N ∈ {64, 256} on
1/2/4 host devices, churn/crash/gating scenarios included), overflow
and horizon parity, the api front door, and per-device-aware engine
auto-selection.

Single-device runs execute in-process (the default test environment has
one CPU device); multi-device runs spawn child interpreters because
``--xla_force_host_platform_device_count`` must precede jax
initialization (same pattern as ``tests/test_engine.py``).
"""

import numpy as np
import pytest

from repro.core.vecsim import (WindowOverflowError, execute_windowed,
                               link_add_scenario, sustained_scenario)
from repro.core.vecsim.shard import execute_sharded, pad_rows
from vecsim_cases import build, run_shard_matrix_subprocess


def _assert_matches(win, sh):
    np.testing.assert_array_equal(win.delivered, sh.delivered)
    np.testing.assert_array_equal(win.series, sh.series)
    assert win.stats == sh.stats
    assert win.deliv_count.tolist() == sh.deliv_count.tolist()
    assert win.bcast_done.tolist() == sh.bcast_done.tolist()
    assert win.expired.tolist() == sh.expired.tolist()
    assert win.peak_live == sh.peak_live
    assert (win.lat_sum, win.lat_cnt) == (sh.lat_sum, sh.lat_cnt)
    for key in win.state:
        np.testing.assert_array_equal(win.state[key], sh.state[key],
                                      err_msg=key)


@pytest.mark.parametrize("builder,seed", [
    ("static", 3), ("link_add", 5), ("churn", 7), ("crash", 9),
    ("partition", 11), ("sustained_kreg", 13), ("burst512", 7),
])
def test_sharded_single_device_byte_identical(builder, seed):
    """D=1: the mesh program with no cross-shard traffic still matches
    the windowed reference bit for bit — delivered matrix, series,
    NetStats, aggregates, peak."""
    scn = build(builder, seed, 64)
    win = execute_windowed(scn, scn.m_total, backend="numpy",
                           collect="full", seg_len=16)
    sh = execute_sharded(scn, scn.m_total, n_devices=1, collect="full",
                         seg_len=16)
    assert sh.n_devices == 1
    _assert_matches(win, sh)


def test_sharded_small_window_and_overflow_parity():
    """Retirement actually recycles columns (window below m_total) and
    an impossible window refuses identically on both engines."""
    scn = build("churn", 21, 48)
    w = max(4, scn.m_total // 2)
    try:
        win = execute_windowed(scn, w, backend="numpy", collect="full",
                               seg_len=8)
    except WindowOverflowError:
        with pytest.raises(WindowOverflowError):
            execute_sharded(scn, w, n_devices=1, collect="full", seg_len=8)
        return
    sh = execute_sharded(scn, w, n_devices=1, collect="full", seg_len=8)
    _assert_matches(win, sh)
    with pytest.raises(WindowOverflowError):
        execute_sharded(scn, 2, n_devices=1, collect="full", seg_len=8)


def test_sharded_horizon_expiry_parity():
    """Opt-in horizon force-retirement (including the hung-gate escape
    hatch on a gated scenario) stays byte-identical."""
    scn = link_add_scenario(seed=6, n=40)
    win = execute_windowed(scn, scn.m_total, backend="numpy",
                           collect="full", seg_len=4, horizon=4)
    sh = execute_sharded(scn, scn.m_total, n_devices=1, collect="full",
                         seg_len=4, horizon=4)
    assert win.expired.any()          # the horizon actually bit
    _assert_matches(win, sh)


def test_sharded_aggregate_collect_matches_windowed_aggregates():
    scn = sustained_scenario(seed=4, n=32, k=5, rate=2.0, messages=30,
                             max_delay=2)
    win = execute_windowed(scn, 24, backend="numpy", collect="aggregate",
                           seg_len=8)
    sh = execute_sharded(scn, 24, n_devices=1, collect="aggregate",
                         seg_len=8)
    assert sh.delivered is None
    np.testing.assert_array_equal(win.series, sh.series)
    assert win.stats == sh.stats
    assert win.deliv_count.tolist() == sh.deliv_count.tolist()
    assert win.delivered_frac() == sh.delivered_frac()
    assert win.mean_latency() == sh.mean_latency()


def test_pad_rows():
    assert pad_rows(64, 4) == 64
    assert pad_rows(50, 4) == 52
    assert pad_rows(1, 3) == 3


def test_sharded_runs_via_api_front_door():
    """engine="sharded" through repro.api.run: report fields, extras,
    and exact-engine cross-validation."""
    from repro.api import MetricsSpec, RunSpec, TrafficSpec, WindowSpec, run
    rep = run(RunSpec(protocol="pc", engine="sharded", n=64, seed=11,
                      traffic=TrafficSpec(kind="poisson", rate=2.0,
                                          messages=24),
                      window=WindowSpec(window=24, seg_len=4,
                                        collect="full"),
                      metrics=MetricsSpec(oracle=True, crossval=True)))
    assert rep.engine == "sharded" and rep.backend == "jax"
    assert rep.window == 24
    assert rep.delivered_frac == 1.0
    assert rep.oracle.ok and rep.crossval_ok
    assert rep.extras["devices"] >= 1


def test_sharded_spec_validation():
    from repro.api import RunSpec, ShardSpec, SpecError
    with pytest.raises(SpecError, match="jax device-mesh"):
        RunSpec(engine="sharded", backend="numpy").validate()
    with pytest.raises(SpecError, match="shard.devices"):
        RunSpec(engine="vec", shard=ShardSpec(devices=2)).validate()
    with pytest.raises(SpecError, match="must be an int >= 1"):
        RunSpec(engine="sharded", shard=ShardSpec(devices=0)).validate()
    with pytest.raises(SpecError, match="no windowed engine"):
        RunSpec(protocol="vc", engine="sharded").validate()
    RunSpec(engine="sharded", shard=ShardSpec(devices=1)).validate()


def test_sharded_rejects_more_devices_than_visible():
    import jax
    from repro.core.vecsim.shard import resolve_devices
    avail = jax.device_count()
    with pytest.raises(RuntimeError, match="xla_force_host_platform"):
        resolve_devices(avail + 1)


# --------------------------------------------------------------------- #
# The acceptance matrix: 2 and 4 host devices in child interpreters
# --------------------------------------------------------------------- #
def test_sharded_two_devices_matrix_subprocess():
    run_shard_matrix_subprocess(
        [("churn", 7, 64, 1.0, 8),
         ("crash", 9, 64, 1.0, 16),
         ("link_add", 5, 256, 1.0, 16),    # gating at the larger N
         ("churn", 3, 64, 0.5, 8),         # retirement recycling
         ("burst256", 7, 64, 1.0, 8)],     # compact and full exchange
        shards=2)


_AUTO_SELECT_SNIPPET = """
from repro.api import (RunSpec, TrafficSpec, MetricsSpec, build_scenario,
                       run, select_engine)
spec = RunSpec(n=2000, memory_budget_mb=1,
               traffic=TrafficSpec(kind="poisson", rate=3.0,
                                   messages=500)).validate()
eng, wdw = select_engine(spec, build_scenario(spec))
assert eng == "sharded", eng
assert wdw == 4 * (1 << 20) // (8 * 2000), wdw
rep = run(RunSpec(n=256, memory_budget_mb=1, seed=5,
                  traffic=TrafficSpec(kind="poisson", rate=4.0,
                                      messages=600),
                  metrics=MetricsSpec(crossval=False)))
assert rep.engine == "sharded", rep.engine
assert rep.extras["devices"] == 4
assert rep.delivered_frac == 1.0, rep.delivered_frac
print("AUTO_OK")
"""


def test_sharded_four_devices_matrix_and_auto_selection_subprocess():
    """4 devices: churn/crash at N=64 and N=256 (odd N exercises the
    padding path), plus the per-device-aware auto-selection rule picking
    the sharded engine with the D-scaled window on a real mesh."""
    out = run_shard_matrix_subprocess(
        [("churn", 8, 256, 1.0, 16),
         ("crash", 2, 256, 1.0, 16),
         ("waves", 4, 50, 1.0, 8)],       # 50 % 4 != 0: padding rows
        shards=4, extra=_AUTO_SELECT_SNIPPET)
    assert "AUTO_OK" in out


_DEVICE_FOLD_SNIPPET = """
from repro.core.vecsim import churn_scenario
from repro.core.vecsim.shard import driver
from repro.obs.spans import EngineObs

def folded(on_device):
    driver._folds_on_device = lambda mesh: on_device
    scn = churn_scenario(4, 63)             # 63 rows on 2 shards: padding
    obs = EngineObs(histograms=True)
    st = driver.ShardedStepper(scn, scn.m_total - 4, n_devices=2,
                               seg_len=8, scan="on", obs=obs)
    assert st.fold_on_device is on_device
    while not st.done:
        st.advance()
    return st.finish(), obs.latency_hist

host, host_hist = folded(False)
dev, dev_hist = folded(True)
assert host_hist.sum() > 0
np.testing.assert_array_equal(host_hist, dev_hist)
np.testing.assert_array_equal(host.series, dev.series)
np.testing.assert_array_equal(host.delivered, dev.delivered)
print("FOLD_OK")
"""


def test_device_fold_psums_across_two_shards_subprocess():
    """The histogram folded on a 2-device mesh (psum of each shard's
    counts, padding rows included) equals the host fold."""
    out = run_shard_matrix_subprocess([], shards=2,
                                      extra=_DEVICE_FOLD_SNIPPET)
    assert "FOLD_OK" in out


_PONG_CHUNKS_SNIPPET = """
from repro.core.vecsim.shard import driver, spanner
from repro.obs.spans import EngineObs

scn = build("churn", 7, 64)
# a round in which three rows flush: more than one chunk of either size
st = driver.ShardedStepper(scn, scn.m_total, n_devices=1, seg_len=1,
                           scan="off")
most = 0
while not st.done:
    h = st.host_state()
    most = max(most, int(((h["flush"] == st.t) & h["active"]
                          & ~h["crashed"][:, None]).any(axis=1).sum()))
    st.advance()
assert most > 2, most
# the window's own width, then one the compact exchange runs in
cases = [(w, rows, scan, seg) for w in (scn.m_total, 256) for rows in (1, 2)
         for scan, seg in (("on", 1), ("on", 8), ("off", 8))]
win = {}
for w, rows, scan, seg in cases:
    if w not in win:
        win[w] = execute_windowed(scn, w, backend="numpy", collect="full",
                                  seg_len=8)
    ref = win[w]
    if spanner.PONG_ROWS != rows:
        # rebuild the runners so they trace with the chunk size patched in
        spanner.PONG_ROWS = rows
        spanner.shard_span_runner.cache_clear()
    obs = EngineObs(histograms=False, spans=True)
    sh = execute_sharded(scn, w, n_devices={shards}, collect="full",
                         seg_len=seg, scan=scan, obs=obs)
    tag = (w, rows, scan, seg)
    np.testing.assert_array_equal(ref.delivered, sh.delivered)
    np.testing.assert_array_equal(ref.series, sh.series)
    assert ref.stats == sh.stats, tag
    for key in ref.state:
        np.testing.assert_array_equal(ref.state[key], sh.state[key],
                                      err_msg=str(tag) + key)
    if seg == 1:
        # one round per segment: the counters are per round
        chunks = [e["value"] for e in obs.spans.events()
                  if e["name"] == "pong.chunks"]
        assert len(chunks) == scn.rounds, tag
        assert min(chunks) == 0, tag
        if rows == 1:
            assert max(chunks) >= 2, (tag, max(chunks))
print("PONG_OK")
"""


@pytest.mark.parametrize("shards", [1, 2, 4])
def test_pong_chunks_match_windowed_subprocess(shards):
    """The compacted pong query and flush rows with one or two rows per
    chunk, so that one round runs several chunks (and rounds with no
    candidate run none), at the window's own width and at one where the
    frontier exchange runs compact: the scanned and the stepped sharded
    bodies stay byte-identical to the windowed engine on a churn
    scenario."""
    out = run_shard_matrix_subprocess(
        [], shards=shards,
        extra=_PONG_CHUNKS_SNIPPET.replace("{shards}", str(shards)))
    assert "PONG_OK" in out
