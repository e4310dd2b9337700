"""The scanned-segment differential suite (DESIGN.md §2.7).

``scan="on"`` moves the sharded engine's segment loop device-side — one
``lax.scan`` over rounds inside ``shard_map``, stacked schedules,
donated buffers, a double-buffered frontier exchange, and a bit-packed
int16 fast body for topology-quiescent segments.  Every test here is a
byte-identity proof obligation for that rewrite:

  * scan="on" == scan="off" == windowed numpy reference, at N ∈
    {64, 256} over 1/2/4 (forced host) devices, across churn, crash,
    partition, gating, horizon-expiry and overflow scenarios (the
    multi-device children *also* re-run every case with scan="off" and
    compare the two sharded results directly);
  * segment-tail edges: ragged final segments, zero-traffic tail
    segments, a boundary that retires every live column at once;
  * seg_len is an execution detail, never a semantic one;
  * the donated state tuple really aliases (lowered/compiled HLO +
    ``memory_analysis``) — the peak (N, W) footprint must not double;
  * the spec layer rejects the combinations that cannot work.

Multi-device runs spawn child interpreters because
``--xla_force_host_platform_device_count`` must precede jax
initialization (same pattern as ``tests/test_vecsim_shard.py``).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.vecsim import (WindowOverflowError, execute_windowed,
                               link_add_scenario, static_scenario)
from repro.core.vecsim.shard import execute_sharded
from repro.core.vecsim.shard.spanner import resolve_scan
from vecsim_cases import build, run_shard_matrix_subprocess


def _assert_matches(ref, sh):
    np.testing.assert_array_equal(ref.delivered, sh.delivered)
    np.testing.assert_array_equal(ref.series, sh.series)
    assert ref.stats == sh.stats
    assert ref.deliv_count.tolist() == sh.deliv_count.tolist()
    assert ref.bcast_done.tolist() == sh.bcast_done.tolist()
    assert ref.expired.tolist() == sh.expired.tolist()
    assert ref.peak_live == sh.peak_live
    assert (ref.lat_sum, ref.lat_cnt) == (sh.lat_sum, sh.lat_cnt)
    for key in ref.state:
        np.testing.assert_array_equal(ref.state[key], sh.state[key],
                                      err_msg=key)


def _run_pair(scn, w, seg_len, **kw):
    """The tightest differential: same mesh, same backend, same window —
    only the segment stepping differs."""
    on = execute_sharded(scn, w, n_devices=1, collect="full",
                         seg_len=seg_len, scan="on", **kw)
    off = execute_sharded(scn, w, n_devices=1, collect="full",
                          seg_len=seg_len, scan="off", **kw)
    assert on.scan == "on" and off.scan == "off"
    _assert_matches(off, on)
    return on, off


# --------------------------------------------------------------------- #
# Single-device byte-identity: scan on == scan off == windowed numpy
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("builder,seed", [
    ("static", 3), ("link_add", 5), ("churn", 7), ("crash", 9),
    ("partition", 11), ("sustained_kreg", 13), ("waves", 15),
    ("burst256", 7), ("burst512", 7),
])
def test_scan_single_device_byte_identical(builder, seed):
    scn = build(builder, seed, 64)
    win = execute_windowed(scn, scn.m_total, backend="numpy",
                           collect="full", seg_len=8)
    on, _ = _run_pair(scn, scn.m_total, 8)
    _assert_matches(win, on)


@pytest.mark.parametrize("builder,seed", [("churn", 2), ("crash", 6)])
def test_scan_single_device_byte_identical_n256(builder, seed):
    scn = build(builder, seed, 256)
    win = execute_windowed(scn, scn.m_total, backend="numpy",
                           collect="full", seg_len=16)
    on, _ = _run_pair(scn, scn.m_total, 16)
    _assert_matches(win, on)


def test_scan_retirement_recycling_and_overflow_parity():
    """A window below m_total forces live column recycling through the
    scanned path; an impossible window refuses identically."""
    scn = build("churn", 21, 48)
    w = max(4, scn.m_total // 2)
    try:
        win = execute_windowed(scn, w, backend="numpy", collect="full",
                               seg_len=8)
    except WindowOverflowError:
        win = None
    if win is None:
        with pytest.raises(WindowOverflowError):
            execute_sharded(scn, w, n_devices=1, collect="full",
                            seg_len=8, scan="on")
    else:
        on, _ = _run_pair(scn, w, 8)
        _assert_matches(win, on)
    with pytest.raises(WindowOverflowError):
        execute_sharded(scn, 2, n_devices=1, collect="full", seg_len=8,
                        scan="on")


def test_scan_horizon_expiry_parity():
    """Horizon force-retirement (and its hung-gate escape hatch, on a
    gated scenario) through the scanned segment body."""
    scn = link_add_scenario(seed=6, n=40)
    win = execute_windowed(scn, scn.m_total, backend="numpy",
                           collect="full", seg_len=4, horizon=4)
    on, _ = _run_pair(scn, scn.m_total, 4, horizon=4)
    assert win.expired.any()
    _assert_matches(win, on)


def _flush_rows_most(scn, w):
    """The most rows that flush on some link slot in one round, read
    from the stepped body's state before each round."""
    from repro.core.vecsim.shard.driver import ShardedStepper
    st = ShardedStepper(scn, w, n_devices=1, seg_len=1, scan="off")
    most = 0
    while not st.done:
        h = st.host_state()
        rows = ((h["flush"] == st.t) & h["active"]
                & ~h["crashed"][:, None]).any(axis=1)
        most = max(most, int(rows.sum()))
        st.advance()
    return most


def test_flush_rows_past_one_chunk(monkeypatch):
    """The flush path takes its rows in rank-compacted chunks of
    ``PONG_ROWS``; with the chunk cut to one and two rows, a round
    flushes more rows than one chunk holds, on a window wide enough for
    the compact exchange.  Byte-identical to the windowed engine and to
    scan="off"."""
    from repro.core.vecsim.shard import spanner
    scn = build("burst256", 7, 64)
    w = scn.m_total
    most = _flush_rows_most(scn, w)
    win = execute_windowed(scn, w, backend="numpy", collect="full",
                           seg_len=8)
    try:
        for rows in (1, 2):
            assert most > rows
            # rebuild the runners so they trace with the chunk patched in
            monkeypatch.setattr(spanner, "PONG_ROWS", rows)
            spanner.shard_span_runner.cache_clear()
            on, _ = _run_pair(scn, w, 8)
            _assert_matches(win, on)
    finally:
        monkeypatch.undo()
        spanner.shard_span_runner.cache_clear()


# --------------------------------------------------------------------- #
# Segment-tail edges
# --------------------------------------------------------------------- #
def test_scan_ragged_final_segment():
    """A final segment shorter than seg_len runs with sentinel padding
    rounds; the padding must be inert (results byte-identical to the
    per-round path, which never pads)."""
    scn = build("sustained_kreg", 17, 64)
    seg = next(s for s in (7, 9, 11, 13) if scn.rounds % s)
    assert scn.rounds % seg != 0
    win = execute_windowed(scn, scn.m_total, backend="numpy",
                           collect="full", seg_len=seg)
    on, _ = _run_pair(scn, scn.m_total, seg)
    _assert_matches(win, on)


def test_scan_zero_traffic_tail_and_retire_everything_boundary():
    """A static flood quiesces well before its settle-bound round count:
    at the first segment boundary after quiescence *every* live column
    retires at once, and the remaining segments run zero-traffic on an
    empty window.  Both edges must be inert in the scanned body (the
    fast body's packed frontier is all-zero there) and byte-identical."""
    scn = static_scenario(2, 64)
    win = execute_windowed(scn, scn.m_total, backend="numpy",
                           collect="full", seg_len=4)
    on, _ = _run_pair(scn, scn.m_total, 4)
    _assert_matches(win, on)
    # the settle bound really did overshoot: trailing rounds saw no
    # deliveries, sends, flushes, pongs or gates — all-zero series rows
    # produced by scanned segments over a fully-retired window
    assert (on.series[-4:] == 0).all()
    assert on.delivered_frac() == 1.0


@pytest.mark.parametrize("seg_len", [1, 5, 64])
def test_scan_seg_len_invariance(seg_len):
    """Any seg_len gives the same run as the seg_len=16 base: segment
    boundaries are pure execution structure."""
    scn = build("churn", 31, 64)
    base = execute_sharded(scn, scn.m_total, n_devices=1, collect="full",
                           seg_len=16, scan="on")
    other = execute_sharded(scn, scn.m_total, n_devices=1, collect="full",
                            seg_len=seg_len, scan="on")
    np.testing.assert_array_equal(base.delivered, other.delivered)
    np.testing.assert_array_equal(base.series, other.series)
    assert base.stats == other.stats
    for key in base.state:
        np.testing.assert_array_equal(base.state[key], other.state[key],
                                      err_msg=key)


@pytest.mark.parametrize("seg_len", [1, 3, 16, 64])
def test_scan_seg_len_invariance_narrow_window_horizon(seg_len):
    """seg_len-invariance where it used to break: a narrow window under
    a horizon.  ``activate`` now caps every segment at the earliest
    expiry-due round, so force-expiries (and the columns they free)
    land at the same round for every seg_len — results match the
    windowed numpy reference byte-for-byte."""
    scn = build("crash", 9, 64)
    w, h = 6, 9
    win = execute_windowed(scn, w, backend="numpy", collect="full",
                           horizon=h, seg_len=seg_len)
    sh = execute_sharded(scn, w, n_devices=1, collect="full",
                         seg_len=seg_len, scan="on", horizon=h)
    _assert_matches(win, sh)


def test_scan_on_never_dispatches_standalone_reduce(monkeypatch):
    """The fused segment aggregates fully replace the standalone
    retirement reduce on the scanned path: across a run whose final
    boundary retires every live column at once, zero ``reduce_run``
    dispatches happen — the boundary sweeps consume the fused 8-tuple
    and the drain skips its reduce because nothing is live (the old
    drain ran a full (N, W) reduction just to learn there was nothing
    to record).  scan="off" keeps the standalone reduce as reference."""
    from repro.core.vecsim.shard import driver as drv
    calls = {"reduce": 0}
    orig = drv.shard_retire_kernels

    def counting(d):
        reduce_run, apply_run = orig(d)

        def reduce_counted(*a, **kw):
            calls["reduce"] += 1
            return reduce_run(*a, **kw)
        return reduce_counted, apply_run

    monkeypatch.setattr(drv, "shard_retire_kernels", counting)
    scn = static_scenario(2, 64)
    res = drv.execute_sharded(scn, scn.m_total, n_devices=1,
                              collect="full", seg_len=4, scan="on")
    assert res.delivered_frac() == 1.0
    assert calls["reduce"] == 0
    calls["reduce"] = 0
    drv.execute_sharded(scn, scn.m_total, n_devices=1, collect="full",
                        seg_len=4, scan="off")
    assert calls["reduce"] > 0


def test_overflow_round_invariant_across_seg_len():
    """The S-curve of the old bug: overflow used to fire at whatever
    segment boundary happened to follow the fatal round, so its timing
    depended on seg_len.  It must now raise at the same round — and the
    same :attr:`WindowOverflowError.round` — for every seg_len, with
    and without a horizon, in both engines."""
    scn = build("sustained_kreg", 13, 64)
    for h in (None, 5):
        rounds = set()
        for seg_len in (1, 2, 7, 16, 64):
            for run in (
                lambda: execute_windowed(scn, 2, backend="numpy",
                                         horizon=h, seg_len=seg_len),
                lambda: execute_sharded(scn, 2, n_devices=1, horizon=h,
                                        seg_len=seg_len, scan="on"),
            ):
                with pytest.raises(WindowOverflowError) as ei:
                    run()
                rounds.add(ei.value.round)
        assert len(rounds) == 1, (h, rounds)


# --------------------------------------------------------------------- #
# The acceptance matrix: 2 and 4 forced host devices, children compare
# scan="on" against both the windowed reference and scan="off"
# --------------------------------------------------------------------- #
def test_scan_two_devices_matrix_subprocess():
    run_shard_matrix_subprocess(
        [("churn", 7, 64, 1.0, 8),
         ("link_add", 5, 256, 1.0, 16),   # gating at the larger N
         ("crash", 9, 64, 0.5, 8),        # retirement recycling
         ("burst512", 7, 64, 1.0, 8)],    # every exchange width
        shards=2, scan="on")


def test_scan_four_devices_matrix_subprocess():
    run_shard_matrix_subprocess(
        [("churn", 8, 256, 1.0, 16),
         ("crash", 2, 256, 1.0, 16),
         ("waves", 4, 50, 1.0, 8),        # 50 % 4 != 0: padding rows
         ("static", 3, 64, 1.0, 7),       # ragged final segment
         ("burst256", 7, 64, 1.0, 8)],    # compact and full exchange
        shards=4, scan="on")


def test_scan_pallas_backend_matrix_subprocess():
    """The scanned generic body hosting per-shard Pallas kernel
    launches (deliver sweep, slot frontier, ring scatter)."""
    run_shard_matrix_subprocess(
        [("churn", 7, 64, 1.0, 8),
         ("crash", 9, 64, 1.0, 16)],
        shards=2, backend="pallas", scan="on")


# --------------------------------------------------------------------- #
# Buffer donation: the scanned state tuple must update in place
# --------------------------------------------------------------------- #
def _scan_lowering(n_devices, scn, w, seg_len):
    """Lower the scanned span runner exactly as the driver invokes it."""
    import jax

    from repro.core.vecsim.shard.spanner import (STATE_KEYS,
                                                 shard_span_runner)
    from repro.core.vecsim.sim import init_topo_state
    from repro.core.vecsim.stream import ColumnWindow

    cw = ColumnWindow(scn, w)
    st0 = init_topo_state(scn, w)
    state = tuple(st0[key] for key in STATE_KEYS)
    sst = cw.stacked_schedule(0, min(seg_len, scn.rounds),
                              cw.round_caps(scn.rounds), seg_len)
    ts = np.full(seg_len, -3, np.int32)
    ts[: min(seg_len, scn.rounds)] = np.arange(
        min(seg_len, scn.rounds), dtype=np.int32)
    runner = shard_span_runner(n_devices, scn.k, scn.mode == "pc",
                               scn.always_gate, scn.pong_delay,
                               gating=scn.n_adds > 0, backend="jax",
                               scan=True)
    origins = np.full(w, -1, np.int32)
    with jax.enable_x64(True):
        return runner.jitted.lower(state, sst, ts, origins,
                                   np.int32(scn.rounds)), state


def test_scan_donation_aliases_live_planes():
    """donate_argnums really landed: the lowered and compiled programs
    alias the donated state into the outputs, and the compiler's own
    memory accounting shows at least a full (N, W) plane aliased — the
    regression this guards is a silent donation drop (shape mismatch,
    dtype change) doubling the peak footprint."""
    scn = build("sustained_kreg", 13, 64)
    lowered, state = _scan_lowering(1, scn, scn.m_total, 8)
    txt = lowered.as_text()
    assert "tf.aliasing_output" in txt or "input_output_alias" in txt
    compiled = lowered.compile()
    hlo = compiled.as_text()
    assert "input_output_alias" in hlo
    ma = compiled.memory_analysis()
    if ma is not None:  # backend-dependent; present on CPU
        arr_bytes = state[0].nbytes
        assert ma.alias_size_in_bytes >= arr_bytes, \
            (ma.alias_size_in_bytes, arr_bytes)
        # no hidden full-state temp copy either
        assert ma.temp_size_in_bytes < ma.argument_size_in_bytes + \
            ma.output_size_in_bytes


_DONATION_4DEV_SNIPPET = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, {tests_dir!r})
import numpy as np
from vecsim_cases import build
from test_vecsim_scan import _scan_lowering

scn = build("sustained_kreg", 13, 256)
lowered, state = _scan_lowering(4, scn, scn.m_total, 16)
# multi-device lowerings carry donation as buffer-donor annotations
# (aliasing is resolved at compile time); single-device ones alias
# directly in the stablehlo
txt = lowered.as_text()
assert ("jax.buffer_donor" in txt or "tf.aliasing_output" in txt
        or "input_output_alias" in txt), \\
    "donation dropped from the 4-device lowering"
compiled = lowered.compile()
assert "input_output_alias" in compiled.as_text()
ma = compiled.memory_analysis()
if ma is not None:
    per_dev = state[0].nbytes // 4
    assert ma.alias_size_in_bytes >= per_dev, \\
        (ma.alias_size_in_bytes, per_dev)
print("DONATION_OK")
"""


def test_scan_donation_four_devices_subprocess():
    """Same donation regression on a real 4-device mesh at N=256 (the
    forced-host-device flag must precede jax init, hence the child)."""
    tests_dir = os.path.dirname(os.path.abspath(__file__))
    repo_root = os.path.dirname(tests_dir)
    out = subprocess.run(
        [sys.executable, "-c",
         _DONATION_4DEV_SNIPPET.format(tests_dir=tests_dir)],
        capture_output=True, text=True,
        env=dict(os.environ, PYTHONPATH="src"), cwd=repo_root)
    assert out.returncode == 0 and "DONATION_OK" in out.stdout, \
        out.stdout + out.stderr


# --------------------------------------------------------------------- #
# Knob plumbing and refusal paths
# --------------------------------------------------------------------- #
def test_resolve_scan():
    assert resolve_scan("auto") == "on"
    assert resolve_scan("on") == "on"
    assert resolve_scan("off") == "off"
    with pytest.raises(ValueError, match="unknown scan mode"):
        resolve_scan("fast")


def test_scan_spec_validation():
    from repro.api import RunSpec, ShardSpec, SpecError
    with pytest.raises(SpecError, match="shard.scan"):
        RunSpec(shard=ShardSpec(scan="fast")).validate()
    with pytest.raises(SpecError, match="only applies"):
        RunSpec(engine="windowed", shard=ShardSpec(scan="on")).validate()
    with pytest.raises(SpecError, match="numpy reference engine"):
        RunSpec(backend="numpy", shard=ShardSpec(scan="on")).validate()
    # scan="off" is meaningful wherever the sharded engine could run,
    # numpy backend included (auto-selection may still pick another
    # engine; the knob is then unused, which "off" permits and "on"
    # does not)
    RunSpec(backend="numpy", shard=ShardSpec(scan="off")).validate()
    RunSpec(engine="sharded", shard=ShardSpec(scan="on")).validate()
    # JSON round-trip carries the knob
    spec = RunSpec(engine="sharded", shard=ShardSpec(scan="off")).validate()
    assert RunSpec.from_dict(spec.to_dict()) == spec
    # profile: sharded/auto engines only, and round-trips like scan
    with pytest.raises(SpecError, match="shard.profile"):
        RunSpec(engine="windowed", shard=ShardSpec(profile=True)).validate()
    spec = RunSpec(engine="sharded",
                   shard=ShardSpec(profile=True)).validate()
    assert RunSpec.from_dict(spec.to_dict()) == spec


def test_profile_through_api_front_door():
    """shard.profile=True yields per-segment timings on the raw result
    and scalar totals in extras, without changing any result."""
    from repro.api import RunSpec, ShardSpec, run

    def go(profile):
        return run(RunSpec(engine="sharded", n=64, seed=3,
                           shard=ShardSpec(devices=1, profile=profile)))

    on, off = go(True), go(False)
    prof = on.result.seg_profile
    assert off.result.seg_profile is None
    assert len(prof) == on.extras["profile_segments"] > 0
    assert all(set(p) == {"lo", "hi", "fast", "stage_s", "dispatch_s",
                          "block_s", "retire_s"} for p in prof)
    assert [(p["lo"], p["hi"]) for p in prof] == \
        sorted((p["lo"], p["hi"]) for p in prof)
    assert on.extras["profile_dispatch_s"] == sum(
        p["dispatch_s"] for p in prof)
    assert on.stats == off.stats
    assert on.delivered_frac == off.delivered_frac
    assert on.mean_latency == off.mean_latency


def test_scan_through_api_front_door():
    """extras report the resolved mode, and the two modes agree through
    the whole api stack."""
    from repro.api import RunSpec, ShardSpec, TrafficSpec, WindowSpec, run

    def go(scan):
        return run(RunSpec(protocol="pc", engine="sharded", n=64, seed=11,
                           shard=ShardSpec(scan=scan),
                           traffic=TrafficSpec(kind="poisson", rate=2.0,
                                               messages=24),
                           window=WindowSpec(window=24, seg_len=4,
                                             collect="full")))
    rep_on, rep_off = go("auto"), go("off")
    assert rep_on.extras["scan"] == "on"
    assert rep_off.extras["scan"] == "off"
    assert rep_on.stats == rep_off.stats
    assert rep_on.delivered_frac == rep_off.delivered_frac == 1.0
    np.testing.assert_array_equal(rep_on.result.delivered,
                                  rep_off.result.delivered)
