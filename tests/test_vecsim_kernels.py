"""Pallas delivery-sweep kernels (vecsim.kernels, DESIGN.md §2.6).

Three layers of coverage:

  * kernel-vs-ref — every kernel against its plain-lax ``ref.py`` twin
    on random inputs, including odd window widths with forced ragged
    column tiling, the single-column window, and all-retired (empty)
    segments;
  * backend="pallas" byte-identity — the ISSUE acceptance matrix: the
    monolithic, windowed and sharded engines running the fused kernels
    (interpret mode) produce bit-equal delivered matrices, per-round
    series, NetStats, aggregates and final state against the jax
    backend across every scenario builder at N ∈ {64, 256}, including
    multi-device meshes via the subprocess harness;
  * the api front door — spec validation, select_engine's eager
    SpecError when Pallas cannot initialize, and a full
    ``backend="pallas"`` report equal to the jax report.
"""

import numpy as np
import pytest

jax = pytest.importorskip(
    "jax", reason="the pallas backend needs jax (pip install -r "
    "requirements.txt)")
import jax.numpy as jnp  # noqa: E402

from repro.core.vecsim import WindowOverflowError, execute_windowed  # noqa: E402
from repro.core.vecsim import kernels as kx  # noqa: E402
from repro.core.vecsim.kernels import ref as kref  # noqa: E402
from repro.core.vecsim.shard import execute_sharded  # noqa: E402
from repro.core.vecsim.sim import execute_vec, resolve_backend  # noqa: E402
from vecsim_cases import BUILDERS, run_shard_matrix_subprocess  # noqa: E402

INF = np.int32(2 ** 30)


# --------------------------------------------------------------------- #
# random kernel inputs
# --------------------------------------------------------------------- #
def _inputs(rng, n, w, k):
    return dict(
        t=np.int32(rng.integers(1, 20)),
        arr=np.where(rng.random((n, w)) < 0.4,
                     rng.integers(0, 25, (n, w)), INF).astype(np.int32),
        delivered=np.where(rng.random((n, w)) < 0.4,
                           rng.integers(0, 20, (n, w)), -1).astype(np.int32),
        crashed=rng.random(n) < 0.2,
        is_app=rng.random(w) < 0.7,
        adj=rng.integers(0, n, (n, k)).astype(np.int32),
        delay=rng.integers(1, 4, (n, k)).astype(np.int32),
        gate=np.where(rng.random((n, k)) < 0.3,
                      rng.integers(0, 15, (n, k)), -1).astype(np.int32),
        do=rng.random((n, k)) < 0.3,
        fwd=rng.random((n, k)) < 0.6,
        min_gate=np.where(rng.random(n) < 0.3,
                          rng.integers(0, 15, n), INF).astype(np.int32),
    )


def _eq(got, want):
    got = got if isinstance(got, tuple) else (got,)
    want = want if isinstance(want, tuple) else (want,)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# (n, w, k, block_w): odd window, forced ragged tiling, single column
SHAPES = [(16, 9, 3, None),    # odd window, one tile
          (24, 7, 4, 4),       # odd window, ragged 2-tile grid
          (8, 1, 2, None),     # single-column window
          (12, 11, 3, 3)]      # ragged 4-tile grid


@pytest.mark.parametrize("n,w,k,bw", SHAPES)
def test_kernels_match_refs(n, w, k, bw):
    """Every kernel == its lax ref, bit for bit, across tilings."""
    rng = np.random.default_rng(n * 1000 + w)
    iv = _inputs(rng, n, w, k)
    t = iv["t"]
    _eq(kx.deliver_sweep(iv["arr"], iv["delivered"], iv["crashed"],
                         iv["is_app"], t, block_w=bw),
        kref.deliver_sweep_ref(iv["arr"], iv["delivered"], iv["crashed"],
                               iv["is_app"], t))
    _eq(kx.fused_sweep(iv["arr"], iv["delivered"], iv["crashed"], iv["adj"],
                       iv["delay"], iv["fwd"], iv["is_app"], t, block_w=bw),
        kref.fused_sweep_ref(iv["arr"], iv["delivered"], iv["crashed"],
                             iv["adj"], iv["delay"], iv["fwd"],
                             iv["is_app"], t))
    _eq(kx.frontier_sweep(iv["arr"], iv["delivered"], iv["adj"], iv["delay"],
                          iv["gate"], iv["do"], iv["fwd"], iv["is_app"], t,
                          block_w=bw),
        kref.frontier_sweep_ref(iv["arr"], iv["delivered"], iv["adj"],
                                iv["delay"], iv["gate"], iv["do"],
                                iv["fwd"], iv["is_app"], t))
    _eq(kx.retire_scan(iv["delivered"], iv["crashed"], iv["min_gate"],
                       block_w=bw),
        kref.retire_scan_ref(iv["delivered"], iv["crashed"],
                             iv["min_gate"]))
    rounds = np.int32(22)
    _eq(kx.retire_reduce(iv["arr"], iv["delivered"], iv["crashed"],
                         iv["min_gate"], rounds, block_w=bw),
        kref.retire_reduce_ref(iv["arr"], iv["delivered"], iv["crashed"],
                               iv["min_gate"], rounds))
    # the record-side outputs against plain numpy, not just the lax ref
    _, _, _, arrcnt, sumdel = (np.asarray(x) for x in kx.retire_reduce(
        iv["arr"], iv["delivered"], iv["crashed"], iv["min_gate"], rounds,
        block_w=bw))
    np.testing.assert_array_equal(arrcnt, (iv["arr"] < rounds).sum(axis=0))
    np.testing.assert_array_equal(
        sumdel, np.where(iv["delivered"] >= 0, iv["delivered"], 0)
        .sum(axis=0))
    for gating in (True, False):
        _eq(kx.slot_frontier(iv["delivered"], iv["gate"][:, 0],
                             iv["delay"][:, 0], iv["do"][:, 0],
                             iv["fwd"][:, 0], iv["is_app"], t,
                             gating=gating, block_w=bw),
            kref.slot_frontier_ref(iv["delivered"], iv["gate"][:, 0],
                                   iv["delay"][:, 0], iv["do"][:, 0],
                                   iv["fwd"][:, 0], iv["is_app"], t,
                                   gating=gating))
    vals = np.where(rng.random((n, w)) < 0.4,
                    rng.integers(1, 30, (n, w)), INF).astype(np.int32)
    tgt = rng.integers(0, 2 * n, n).astype(np.int32)
    off = np.int32(n // 2)
    _eq(kx.ring_apply(iv["arr"], vals, tgt, off, block_w=bw),
        kref.ring_apply_ref(jnp.asarray(iv["arr"]), jnp.asarray(vals),
                            jnp.asarray(tgt), off))


def test_kernels_on_all_retired_segment():
    """An all-retired segment (every column recycled: arr=INF,
    delivered=-1) sweeps to a no-op with zero counts."""
    n, w, k = 10, 6, 3
    arr = np.full((n, w), INF, np.int32)
    delivered = np.full((n, w), -1, np.int32)
    crashed = np.zeros(n, bool)
    is_app = np.ones(w, bool)
    adj = np.zeros((n, k), np.int32)
    delay = np.ones((n, k), np.int32)
    fwd = np.ones((n, k), bool)
    t = np.int32(5)
    a2, d2, napp, nping = (np.asarray(x) for x in kx.fused_sweep(
        arr, delivered, crashed, adj, delay, fwd, is_app, t))
    np.testing.assert_array_equal(a2, arr)
    np.testing.assert_array_equal(d2, delivered)
    assert napp.sum() == 0 and nping.sum() == 0
    cnt, alivedel, blocked = (np.asarray(x) for x in kx.retire_scan(
        delivered, crashed, np.full(n, INF, np.int32)))
    assert cnt.sum() == 0 and alivedel.sum() == 0 and blocked.sum() == 0
    red = tuple(np.asarray(x) for x in kx.retire_reduce(
        arr, delivered, crashed, np.full(n, INF, np.int32), np.int32(9)))
    assert all(x.sum() == 0 for x in red)


# --------------------------------------------------------------------- #
# backend="pallas" == backend="jax": the acceptance matrix
# --------------------------------------------------------------------- #
def _assert_windowed_matches(a, b):
    np.testing.assert_array_equal(a.delivered, b.delivered)
    np.testing.assert_array_equal(a.series, b.series)
    assert a.stats == b.stats
    assert a.deliv_count.tolist() == b.deliv_count.tolist()
    assert a.bcast_done.tolist() == b.bcast_done.tolist()
    assert a.expired.tolist() == b.expired.tolist()
    assert a.peak_live == b.peak_live
    assert (a.lat_sum, a.lat_cnt) == (b.lat_sum, b.lat_cnt)
    for key in a.state:
        np.testing.assert_array_equal(a.state[key], b.state[key],
                                      err_msg=key)


@pytest.mark.parametrize("builder", sorted(BUILDERS))
@pytest.mark.parametrize("n", [64, 256])
def test_pallas_monolithic_byte_identical(builder, n):
    """Monolithic engine: the fused-kernel backend reproduces the jax
    backend bit for bit on every scenario builder."""
    scn = BUILDERS[builder](3, n)
    rj = execute_vec(scn, backend="jax")
    rp = execute_vec(scn, backend="pallas")
    assert rp.backend == "pallas"
    np.testing.assert_array_equal(rj.delivered, rp.delivered)
    np.testing.assert_array_equal(rj.series, rp.series)
    assert rj.stats == rp.stats
    for key in rj.state:
        np.testing.assert_array_equal(rj.state[key], rp.state[key],
                                      err_msg=key)


@pytest.mark.parametrize("builder", sorted(BUILDERS))
@pytest.mark.parametrize("n", [64, 256])
def test_pallas_windowed_byte_identical(builder, n):
    """Windowed engine: span kernels + the retirement-scan kernel give
    byte-identical results (delivered, series, NetStats, aggregates,
    peak, state) on every builder, full-width and fractional windows."""
    scn = BUILDERS[builder](5, n)
    for frac, seg in ((1.0, 16), (0.5, 8)):
        w = max(4, int(scn.m_total * frac))
        try:
            rj = execute_windowed(scn, w, backend="jax", collect="full",
                                  seg_len=seg)
        except WindowOverflowError:
            with pytest.raises(WindowOverflowError):
                execute_windowed(scn, w, backend="pallas", collect="full",
                                 seg_len=seg)
            continue
        rp = execute_windowed(scn, w, backend="pallas", collect="full",
                              seg_len=seg)
        _assert_windowed_matches(rj, rp)


def test_pallas_windowed_horizon_and_aggregate_parity():
    """Horizon expiry (the forced-retire escape hatch) and aggregate
    collection go through the same kernel path byte-identically."""
    scn = BUILDERS["churn"](13, 64)
    kw = dict(horizon=24, seg_len=8, collect="full")
    rj = execute_windowed(scn, scn.m_total, backend="jax", **kw)
    rp = execute_windowed(scn, scn.m_total, backend="pallas", **kw)
    _assert_windowed_matches(rj, rp)
    kw = dict(seg_len=8, collect="aggregate")
    aj = execute_windowed(scn, scn.m_total, backend="jax", **kw)
    ap = execute_windowed(scn, scn.m_total, backend="pallas", **kw)
    assert aj.stats == ap.stats
    assert aj.deliv_count.tolist() == ap.deliv_count.tolist()
    np.testing.assert_array_equal(aj.series, ap.series)


@pytest.mark.parametrize("builder", sorted(BUILDERS))
def test_pallas_sharded_single_device_byte_identical(builder):
    """Sharded engine, D=1: per-shard kernel launches inside shard_map
    reproduce the windowed jax reference bit for bit."""
    scn = BUILDERS[builder](7, 64)
    win = execute_windowed(scn, scn.m_total, backend="numpy",
                           collect="full", seg_len=16)
    sh = execute_sharded(scn, scn.m_total, n_devices=1, collect="full",
                         seg_len=16, backend="pallas")
    assert sh.backend == "pallas"
    assert sh.n_devices == 1
    _assert_windowed_matches(win, sh)


@pytest.mark.parametrize("shards,cases", [
    (2, [("churn", 9, 64, 1.0, 8), ("crash", 9, 64, 1.0, 8),
         ("sustained_kreg", 9, 64, 0.5, 8)]),
    (4, [("link_add", 9, 256, 1.0, 16), ("partition", 9, 64, 1.0, 8)]),
])
def test_pallas_sharded_multi_device_matrix(shards, cases):
    """Sharded engine across real multi-device meshes (subprocess: the
    forced host-device flag must precede jax init): the pallas round
    body — per-shard kernels between the ppermute rings — matches the
    windowed numpy reference on gating/churn/crash/partition scenarios
    at N ∈ {64, 256}."""
    run_shard_matrix_subprocess(cases, shards=shards, backend="pallas")


# --------------------------------------------------------------------- #
# api front door + availability surface
# --------------------------------------------------------------------- #
def test_resolve_backend_accepts_pallas():
    assert resolve_backend("pallas") == "pallas"
    assert resolve_backend("auto") == "jax"
    with pytest.raises(ValueError):
        resolve_backend("cuda")


def test_pallas_available_probe_shape():
    ok, note = kx.pallas_available()
    assert isinstance(ok, bool) and isinstance(note, str) and note
    assert ok, "jax is importable here, so the probe must succeed"


def test_pallas_refused_on_tpu(monkeypatch):
    """On a TPU the kernels do not compile: the probe says so, an
    explicit backend="pallas" raises before any run, and auto stays on
    jax — nothing falls back to interpret mode."""
    from repro.api import (RunSpec, SpecError, TrafficSpec, build_scenario,
                           select_engine)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    kx.pallas_available.cache_clear()
    try:
        ok, note = kx.pallas_available()
        assert not ok and "TPU" in note
        assert resolve_backend("auto") == "jax"
        with pytest.raises(kx.PallasUnavailableError):
            resolve_backend("pallas")
        spec = RunSpec(protocol="pc", n=64, backend="pallas",
                       traffic=TrafficSpec(kind="poisson", rate=2.0,
                                           messages=8))
        with pytest.raises(SpecError, match="do not compile"):
            select_engine(spec, build_scenario(spec))
    finally:
        kx.pallas_available.cache_clear()


def test_api_run_pallas_report_matches_jax():
    from repro.api import RunSpec, TrafficSpec, WindowSpec, run
    kw = dict(protocol="pc", engine="windowed", n=64, seed=2,
              traffic=TrafficSpec(kind="poisson", rate=2.0, messages=24),
              window=WindowSpec(window=24, seg_len=4, collect="full"))
    rj = run(RunSpec(backend="jax", **kw))
    rp = run(RunSpec(backend="pallas", **kw))
    assert rp.backend == "pallas"
    assert rp.stats == rj.stats
    assert rp.delivered_frac == rj.delivered_frac
    assert rp.mean_latency == rj.mean_latency


def test_api_spec_validates_pallas_backend():
    from repro.api import RunSpec, SpecError
    RunSpec(backend="pallas").validate()
    with pytest.raises(SpecError, match="backend='cuda'"):
        RunSpec(backend="cuda").validate()
    with pytest.raises(SpecError, match="numpy-only"):
        RunSpec(protocol="vc", backend="pallas").validate()


def test_select_engine_spec_error_when_pallas_unavailable(monkeypatch):
    """An explicit backend='pallas' fails eagerly — with a SpecError
    naming the probe's reason — when Pallas cannot initialize."""
    from repro.api import (BACKENDS, BackendEntry, RunSpec, SpecError,
                           build_scenario, select_engine)
    broken = BackendEntry("pallas", "broken for this test",
                          lambda: (False, "no pallas in this build"))
    monkeypatch.setitem(BACKENDS._items, "pallas", broken)
    spec = RunSpec(backend="pallas").validate()
    with pytest.raises(SpecError, match="no pallas in this build"):
        select_engine(spec, build_scenario(spec))


def test_cli_list_has_backends_section(capsys):
    from repro.api.__main__ import main
    assert main(["--list"]) == 0
    out = capsys.readouterr().out
    assert "backends:" in out
    for key in ("numpy", "jax", "pallas"):
        assert key in out
    assert "available" in out
