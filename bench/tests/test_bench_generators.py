"""The benchmark's copies of the generators draw exactly what the
program's own builders draw for the same settings."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic"))
import gen  # noqa: E402

from repro.api import (DynamicsSpec, LiveSpec, RunSpec,  # noqa: E402
                       TopologySpec, TrafficSpec, build_live_scenario,
                       build_scenario)
from repro.core.vecsim.live.arrivals import build_arrivals  # noqa: E402

FIELDS = ("adj0", "delay0", "bcast_round", "bcast_origin", "add_round",
          "add_p", "add_k", "add_q", "add_delay", "rm_round", "rm_p",
          "rm_k")


def same(mine: dict, scn) -> None:
    assert mine["n"] == scn.n and mine["k"] == scn.k
    assert mine["rounds"] == scn.rounds
    assert mine["pong_delay"] == scn.pong_delay
    for f in FIELDS:
        np.testing.assert_array_equal(mine[f], getattr(scn, f), err_msg=f)


@pytest.mark.parametrize("seed", [1, 11, 2147483648 + 9])
def test_churn_base_matches_build_live_scenario(seed):
    n, k, max_delay = 200, 8, 5
    mine = gen.churn_base(seed, n, k, max_delay, "ring", 25, 25, 300)
    scn = build_live_scenario(RunSpec(
        mode="live", n=n, seed=seed,
        topology=TopologySpec(kind="ring", k=k, max_delay=max_delay),
        dynamics=DynamicsSpec(kind="churn", n_adds=25, n_rms=25,
                              churn_window=300),
        live=LiveSpec(rate=2.0, messages=64)))
    same(mine, scn)


@pytest.mark.parametrize("seed", [3, 2147483648 + 77])
def test_arrivals_match_build_arrivals(seed):
    mine = gen.poisson_arrivals(seed, 1000, 3.5, 5000)
    theirs = build_arrivals("poisson", seed, 1000, 3.5, 5000)
    for a, b in zip(mine, theirs):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("seed", [2, 2147483648 + 3])
def test_kregular_churn_base_matches_build_live_scenario(seed):
    n, k, max_delay = 150, 5, 2
    mine = gen.churn_base(seed, n, k, max_delay, "kregular", 12, 12, 40)
    scn = build_live_scenario(RunSpec(
        mode="live", n=n, seed=seed,
        topology=TopologySpec(kind="kregular", k=k, max_delay=max_delay),
        dynamics=DynamicsSpec(kind="churn", n_adds=12, n_rms=12,
                              churn_window=40),
        live=LiveSpec(rate=2.0, messages=64)))
    same(mine, scn)


CONF = dict(deployment=dict(n=128, topology="ring", k=5, max_delay=2,
                            churn=dict(n_adds=8, n_rms=8, churn_window=40,
                                       round_seed=3)))
MIX = dict(rate=1.5, messages=300, traffic_seed=21)


def test_build_inputs_reads_the_mix():
    out = gen.build_inputs(CONF, MIX, seed=4)
    r, o = build_arrivals("poisson", 21, 128, 1.5, 300)
    np.testing.assert_array_equal(out["arr_round"], r)
    np.testing.assert_array_equal(out["arr_origin"], o)
    assert out["arrival_seed"] == 21
    assert len(out["scenario"]["add_round"]) == 8


@pytest.mark.parametrize("a, b", [(4, 5), (1, 2147483648 + 1)])
def test_seeds_share_the_work_and_differ_in_the_overlay(a, b):
    """Two seeds offer the same submissions and as many link events in
    each round (so one compiled segment program serves both), over
    overlays, delays and churn endpoints of their own."""
    x, y = (gen.build_inputs(CONF, MIX, seed=s) for s in (a, b))
    for key in ("arr_round", "arr_origin"):
        np.testing.assert_array_equal(x[key], y[key])
    sx, sy = x["scenario"], y["scenario"]
    for key in ("add_round", "rm_round", "rounds"):
        np.testing.assert_array_equal(sx[key], sy[key])
    assert not np.array_equal(sx["adj0"], sy["adj0"])
    assert not np.array_equal(sx["add_p"], sy["add_p"])


def test_redrawn_rounds_keep_the_events():
    n, k, max_delay = 200, 8, 5
    base = gen.churn_base(9, n, k, max_delay, "ring", 25, 25, 300)
    lo, hi = gen.churn_span(n, k, max_delay, 300)
    out = gen.redraw_churn_rounds(base, 17, lo, hi)
    for key in ("add_p", "add_k", "add_q", "add_delay", "rm_p", "rm_k",
                "adj0", "delay0"):
        np.testing.assert_array_equal(out[key], base[key])
    for key in ("add_round", "rm_round"):
        assert len(out[key]) == len(base[key])
        assert (np.diff(out[key]) >= 0).all()
        assert lo <= out[key].min() and out[key].max() < hi
        assert base[key].min() >= lo and base[key].max() < hi
