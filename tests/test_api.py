"""The experiment front door: RunSpec validation, registry dispatch,
engine auto-selection, cross-engine agreement through ``repro.api.run``,
the measured vector-clock baseline, legacy-shim warnings, and the CLI."""

import json
import warnings

import numpy as np
import pytest

from repro.api import (DynamicsSpec, MetricsSpec, RunReport, RunSpec,
                       SpecError, TopologySpec, TrafficSpec, WindowSpec,
                       build_scenario, run, select_engine)
from repro.api import ENGINES, PROTOCOLS, SCENARIOS, TOPOLOGIES, TRAFFIC
from repro.core.types import LegacyEntryPointWarning
from repro.core.vecsim import (VecScenario, run_vec, run_vec_windowed,
                               static_scenario)


# --------------------------------------------------------------------- #
# Spec validation
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("bad,match", [
    (dict(protocol="zab"), "protocol='zab'"),
    (dict(engine="vex"), "engine='vex'"),
    (dict(backend="torch"), "backend='torch'"),
    (dict(n=1), "n=1"),
    (dict(topology=TopologySpec(kind="torus")), "topology.kind='torus'"),
    (dict(traffic=TrafficSpec(kind="pareto")), "traffic.kind='pareto'"),
    (dict(dynamics=DynamicsSpec(kind="meteor")), "dynamics.kind='meteor'"),
    (dict(protocol="vc", engine="windowed"), "no windowed engine"),
    (dict(protocol="vc", backend="jax"), "numpy-only"),
    (dict(dynamics=DynamicsSpec(kind="churn"),
          traffic=TrafficSpec(kind="poisson")), "only .* traffic"),
    (dict(dynamics=DynamicsSpec(kind="partition_heal"),
          topology=TopologySpec(kind="smallworld")), "only .* topologies"),
    (dict(window=WindowSpec(window=0)), "window.window"),
    (dict(window=WindowSpec(collect="some")), "window.collect"),
    (dict(metrics=MetricsSpec(snapshot="first_churn")), "last_churn"),
])
def test_spec_validation_rejects_with_informative_errors(bad, match):
    with pytest.raises(SpecError, match=match):
        RunSpec(**bad).validate()


def test_spec_json_round_trip_and_unknown_keys():
    spec = RunSpec(protocol="vc", n=96, seed=7,
                   topology=TopologySpec(kind="kregular", k=6),
                   traffic=TrafficSpec(kind="poisson", rate=2.5,
                                       messages=40))
    assert RunSpec.from_dict(spec.to_dict()) == spec
    assert RunSpec.from_dict(json.loads(json.dumps(spec.to_dict()))) == spec
    with pytest.raises(SpecError, match="unknown RunSpec field"):
        RunSpec.from_dict({"protcol": "pc"})
    with pytest.raises(SpecError, match="unknown topology field"):
        RunSpec.from_dict({"topology": {"kid": "ring"}})


def test_registered_topology_and_traffic_are_actually_buildable():
    """The register-to-extend contract end to end: a topology or traffic
    model registered on the api registries must be dispatched by the
    scenario builders, not just pass key validation."""
    from repro.api import TOPOLOGIES, TRAFFIC, TrafficModel
    from repro.core.vecsim import poisson_traffic, ring_topology

    if "test_star" not in TOPOLOGIES:
        def star(seed, n, k, max_delay, free_slots, beta):
            # a ring on slot 0 plus spokes into process 0 (skipping the
            # last process, whose ring slot already points at 0)
            adj0, delay0 = ring_topology(seed, n, k, max_delay,
                                         free_slots=k - 2)
            adj0[2:n - 1, 1] = 0
            return adj0, delay0
        TOPOLOGIES.register("test_star", star)
    rep = run(RunSpec(engine="vec", backend="numpy", n=24,
                      topology=TopologySpec(kind="test_star", k=3),
                      traffic=TrafficSpec(messages=4)))
    assert rep.delivered_frac == 1.0
    snap = rep.result.state
    assert (snap["adj"][2:23, 1] == 0).all()   # the custom shape ran

    if "test_halfrate" not in TRAFFIC:
        TRAFFIC.register("test_halfrate", TrafficModel(
            build=lambda seed, n, t0, t1, mm, p:
                poisson_traffic(seed, n, p["rate"] / 2, t0, t1, mm),
            mean_rate=lambda p: p["rate"] / 2))
    rep = run(RunSpec(engine="vec", backend="numpy", n=24,
                      traffic=TrafficSpec(kind="test_halfrate", rate=4.0,
                                          messages=10)))
    assert rep.m_app == 10 and rep.delivered_frac == 1.0


def test_registries_expose_expected_keys():
    assert {"pc", "r", "vc"} <= set(PROTOCOLS.keys())
    assert {"exact", "vec", "windowed", "sharded"} == set(ENGINES.keys())
    assert {"ring", "kregular", "smallworld"} <= set(TOPOLOGIES.keys())
    assert {"uniform", "poisson", "bursty"} <= set(TRAFFIC.keys())
    assert {"none", "link_add", "churn", "crash", "partition_heal",
            "churn_wave"} <= set(SCENARIOS.keys())
    with pytest.raises(KeyError, match="registered"):
        PROTOCOLS.get("zab")
    with pytest.raises(KeyError, match="already registered"):
        PROTOCOLS.register("pc", PROTOCOLS.get("pc"))


# --------------------------------------------------------------------- #
# Engine auto-selection (the DESIGN.md §3 budget rule)
# --------------------------------------------------------------------- #
def test_auto_selects_monolithic_when_budget_fits():
    spec = RunSpec(n=64).validate()
    assert select_engine(spec, build_scenario(spec)) == ("vec", None)


def test_auto_selects_windowed_with_budget_sized_window():
    from repro.api import ShardSpec
    # devices pinned to 1 so the assertion holds on multi-device hosts
    # (there the per-device rule would pick the sharded engine instead)
    spec = RunSpec(n=2000, memory_budget_mb=1,
                   shard=ShardSpec(devices=1),
                   traffic=TrafficSpec(kind="poisson", rate=3.0,
                                       messages=500)).validate()
    engine, window = select_engine(spec, build_scenario(spec))
    assert engine == "windowed"
    assert window == (1 << 20) // (8 * 2000)


def test_auto_never_windowed_for_vc():
    spec = RunSpec(protocol="vc", n=2000, memory_budget_mb=1,
                   traffic=TrafficSpec(kind="poisson", rate=3.0,
                                       messages=500)).validate()
    assert select_engine(spec, build_scenario(spec)) == ("vec", None)


def test_explicit_window_selects_windowed():
    spec = RunSpec(n=64, window=WindowSpec(window=128)).validate()
    assert select_engine(spec, build_scenario(spec)) == ("windowed", 128)


def test_auto_selection_exact_budget_boundaries():
    """The thresholds bit for bit: 8·N·M_total == budget stays
    monolithic, one more message tips to a streaming engine, and the
    budget-derived window sits on its 64-column floor there."""
    from repro.api import ShardSpec

    def spec_for(messages, **kw):
        # devices pinned to 1 on auto-engine specs: the boundary under
        # test is the budget rule, not the device count of the host
        # running the suite (validate() rejects the pin on explicit
        # single-host engines)
        if "engine" not in kw:
            kw.setdefault("shard", ShardSpec(devices=1))
        return RunSpec(n=2048, memory_budget_mb=1,
                       traffic=TrafficSpec(kind="poisson", rate=2.0,
                                           messages=messages),
                       **kw).validate()
    # 8 * 2048 * 64 == 1 MiB exactly (no adds, so m_total == messages)
    at = spec_for(64)
    scn = build_scenario(at)
    assert scn.m_total == 64
    assert select_engine(at, scn) == ("vec", None)
    over = spec_for(65)
    engine, window = select_engine(over, build_scenario(over))
    assert engine == "windowed"
    assert window == 64          # clamp floor: budget // (8*2048) == 64
    # the window never exceeds the message axis (explicit engine path)
    from repro.api.run import _auto_window
    tiny = spec_for(65, engine="windowed")
    assert _auto_window(tiny, build_scenario(tiny), devices=64) == 65


def test_auto_selection_is_per_device_aware():
    """shard.devices (or a visible mesh) scales the budget-derived
    window D-fold and routes the run to the sharded engine; a single
    device or a numpy backend keeps the single-host windowed engine; a
    budget the monolithic planes fit is never sharded."""
    from repro.api import ShardSpec
    tr = TrafficSpec(kind="poisson", rate=3.0, messages=500)

    spec4 = RunSpec(n=2000, memory_budget_mb=1, traffic=tr,
                    shard=ShardSpec(devices=4)).validate()
    engine, window = select_engine(spec4, build_scenario(spec4))
    assert engine == "sharded"
    assert window == 4 * (1 << 20) // (8 * 2000)

    spec1 = RunSpec(n=2000, memory_budget_mb=1, traffic=tr,
                    shard=ShardSpec(devices=1)).validate()
    assert select_engine(spec1, build_scenario(spec1)) == (
        "windowed", (1 << 20) // (8 * 2000))

    # the numpy backend can never shard: asking for a mesh with it is a
    # spec error, not a silent single-host fallback
    with pytest.raises(SpecError, match="needs the jax backend"):
        RunSpec(n=2000, memory_budget_mb=1, backend="numpy", traffic=tr,
                shard=ShardSpec(devices=4)).validate()
    # ...and without an explicit mesh, numpy auto-selection stays
    # windowed without ever initializing jax
    numpy1 = RunSpec(n=2000, memory_budget_mb=1, backend="numpy",
                     traffic=tr).validate()
    assert select_engine(numpy1, build_scenario(numpy1))[0] == "windowed"

    fits4 = RunSpec(n=64, shard=ShardSpec(devices=4)).validate()
    assert select_engine(fits4, build_scenario(fits4)) == ("vec", None)

    # an explicit window plus an explicit mesh keeps the mesh
    win4 = RunSpec(n=64, shard=ShardSpec(devices=4),
                   window=WindowSpec(window=128)).validate()
    assert select_engine(win4, build_scenario(win4)) == ("sharded", 128)


def test_spec_shard_section_round_trips():
    from repro.api import ShardSpec
    spec = RunSpec(engine="sharded", shard=ShardSpec(devices=2))
    assert RunSpec.from_dict(spec.to_dict()) == spec
    assert RunSpec.from_dict({"shard": {"devices": 2}}).shard.devices == 2


# --------------------------------------------------------------------- #
# run(): one spec, every engine, agreeing results
# --------------------------------------------------------------------- #
def _base(engine, **kw):
    kw.setdefault("metrics", MetricsSpec(oracle=True))
    return RunSpec(protocol="pc", engine=engine, backend="numpy", n=48,
                   seed=11, traffic=TrafficSpec(messages=8),
                   dynamics=DynamicsSpec(kind="link_add", n_adds=4), **kw)


def test_run_dispatches_every_engine_and_engines_agree():
    reports = {
        eng: run(_base(eng, window=WindowSpec(window=None if eng != "windowed"
                                              else 16, collect="full")))
        for eng in ("exact", "vec", "windowed")}
    for eng, rep in reports.items():
        assert isinstance(rep, RunReport), eng
        assert rep.engine == eng
        assert rep.delivered_frac == 1.0, eng
        assert rep.oracle.ok, (eng, rep.oracle.summary())
    # the two vec engines are byte-identical; exact agrees on volume
    np.testing.assert_array_equal(reports["vec"].result.delivered,
                                  reports["windowed"].result.delivered)
    assert reports["vec"].stats == reports["windowed"].stats
    assert (reports["exact"].stats.deliveries
            == reports["vec"].stats.deliveries)


def test_run_crossval_flag_checks_engine_agreement():
    rep = run(_base("vec", metrics=MetricsSpec(crossval=True)))
    assert rep.crossval_ok is True


def test_run_report_to_dict_is_json_safe():
    rep = run(_base("vec"))
    d = rep.to_dict()
    json.dumps(d)                       # must not raise
    assert d["engine"] == "vec" and d["oracle_ok"] is True
    assert d["stats"]["deliveries"] == rep.stats.deliveries


def test_prebuilt_scenario_escape_hatch():
    scn = static_scenario(seed=3, n=40, m_app=6)
    rep = run(RunSpec(engine="vec", backend="numpy", scenario=scn))
    assert rep.m_app == 6 and rep.delivered_frac == 1.0


def test_protocol_r_runs_ungated():
    rep = run(RunSpec(protocol="r", engine="vec", backend="numpy", n=48,
                      dynamics=DynamicsSpec(kind="link_add", n_adds=4)))
    assert rep.extras["gated_link_rounds"] == 0
    assert rep.stats.oob_messages == 0


# --------------------------------------------------------------------- #
# The measured vector-clock baseline
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("n", [64, 256])
def test_vc_vec_cross_validates_byte_identical(n):
    """The acceptance bar: delivered multisets AND final clock values
    byte-identical between vecsim.vc and core.vector_clock on the exact
    engine."""
    from repro.core.vecsim.crossval import cross_validate
    scn = static_scenario(seed=n + 5, n=n, m_app=16)
    out = cross_validate(scn, protocol="vc")
    assert out["vec_multiset"] == out["exact_multiset"]
    assert out["vec_clocks"] == out["exact_clocks"]
    assert out["vec_report"].ok, out["vec_report"].summary()
    assert out["exact_report"].ok, out["exact_report"].summary()


def test_vc_vec_cross_validates_under_churn_and_crashes():
    from repro.core.vecsim import churn_scenario, crash_scenario
    from repro.core.vecsim.crossval import cross_validate
    for scn in (churn_scenario(seed=31, n=64),
                crash_scenario(seed=7, n=64)):
        out = cross_validate(scn, protocol="vc")
        assert out["vec_multiset"] == out["exact_multiset"]
        assert out["vec_clocks"] == out["exact_clocks"]


def test_vc_overhead_grows_with_broadcasters_pc_does_not():
    """Table 1's separation, measured end to end through the API."""
    def extras(protocol, m_app):
        return run(RunSpec(protocol=protocol, engine="vec",
                           backend="numpy", n=64, seed=2,
                           traffic=TrafficSpec(messages=m_app))).extras
    vc_small = extras("vc", 4)["overhead_bytes_per_msg"]
    vc_large = extras("vc", 32)["overhead_bytes_per_msg"]
    assert vc_large > vc_small >= 24.0   # id + at least one clock entry
    pc_small = extras("pc", 4)["overhead_bytes_per_msg"]
    pc_large = extras("pc", 32)["overhead_bytes_per_msg"]
    assert pc_small == pc_large == 16.0  # the paper's O(1)
    cmp = extras("vc", 32)["comparisons_per_delivery"]
    assert cmp >= 1.0                    # every delivery rescans a clock


def test_vc_comparisons_measure_pending_rescans():
    """A deliberately out-of-order arrival (a fast link added after the
    first message already passed) must park the dependent message in
    pending and charge extra readiness scans — the Fig. 3 situation VC
    resolves by buffering instead of link gating."""
    from repro.core.vecsim.crossval import cross_validate
    from repro.core.vecsim.vc import run_vec_vc
    i32 = lambda *a: np.asarray(a, np.int32)  # noqa: E731
    n, k = 3, 3
    adj0 = np.full((n, k), -1, np.int32)
    delay0 = np.ones((n, k), np.int32)
    adj0[0, 0] = 1                        # 0 -> 1 fast
    adj0[0, 1], delay0[0, 1] = 2, 9       # 0 -> 2 slow: m1 takes 9 rounds
    adj0[1, 0] = 0
    adj0[2, 0] = 0
    scn = VecScenario(
        n=n, k=k, rounds=30, adj0=adj0, delay0=delay0,
        # m2 (causally after m1) is broadcast once the fresh 1 -> 2 link
        # exists, so it overtakes m1 on the way to process 2
        bcast_round=i32(0, 6), bcast_origin=i32(0, 1),
        add_round=i32(5), add_p=i32(1), add_k=i32(2), add_q=i32(2),
        add_delay=i32(1)).validate()
    res = run_vec_vc(scn)
    assert res.delivered_frac() == 1.0
    assert res.max_pending >= 2            # m2 waited for m1 at process 2
    assert res.comparisons > res.stats.deliveries  # rescans happened
    # m2 overtook m1 on the wire (earlier receipt) yet was parked until
    # m1's arrival unblocked it in the same drain fixpoint
    assert res.rcv[2, 1] < res.rcv[2, 0]
    assert res.rcv[2, 1] < res.delivered[2, 1]
    assert res.delivered[2, 0] <= res.delivered[2, 1]
    out = cross_validate(scn, protocol="vc")
    assert out["vec_multiset"] == out["exact_multiset"]
    assert out["vec_clocks"] == out["exact_clocks"]


# --------------------------------------------------------------------- #
# Legacy entry points: same behavior, loud warning
# --------------------------------------------------------------------- #
def test_legacy_run_vec_warns_and_matches_front_door():
    scn = static_scenario(seed=4, n=40, m_app=6)
    with pytest.warns(LegacyEntryPointWarning):
        legacy = run_vec(scn, backend="numpy")
    front = run(RunSpec(engine="vec", backend="numpy", scenario=scn))
    np.testing.assert_array_equal(legacy.delivered, front.result.delivered)
    assert legacy.stats == front.stats


def test_legacy_run_vec_windowed_warns_and_matches_front_door():
    scn = static_scenario(seed=4, n=40, m_app=6)
    with pytest.warns(LegacyEntryPointWarning):
        legacy = run_vec_windowed(scn, scn.m_total, backend="numpy",
                                  collect="full")
    front = run(RunSpec(engine="windowed", backend="numpy", scenario=scn,
                        window=WindowSpec(window=scn.m_total,
                                          collect="full")))
    np.testing.assert_array_equal(legacy.delivered, front.result.delivered)
    assert legacy.stats == front.stats


def test_front_door_emits_no_legacy_warnings():
    with warnings.catch_warnings():
        warnings.simplefilter("error", LegacyEntryPointWarning)
        run(_base("vec"))
        run(_base("windowed",
                  window=WindowSpec(window=16, collect="full"),
                  metrics=MetricsSpec(oracle=True, crossval=True)))


# --------------------------------------------------------------------- #
# CLI
# --------------------------------------------------------------------- #
def test_cli_runs_a_tiny_spec(capsys):
    from repro.api.__main__ import main
    rc = main(["--protocol", "pc", "--engine", "vec", "--backend", "numpy",
               "--n", "32", "--messages", "4", "--oracle"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["engine"] == "vec" and out["oracle_ok"] is True
    assert out["delivered_frac"] == 1.0


def test_cli_spec_json_and_dump(tmp_path, capsys):
    spec_file = tmp_path / "spec.json"
    spec_file.write_text(json.dumps(
        {"protocol": "vc", "engine": "vec", "n": 32,
         "traffic": {"messages": 4}}))
    from repro.api.__main__ import main
    assert main(["--spec", str(spec_file), "--dump-spec"]) == 0
    dumped = json.loads(capsys.readouterr().out)
    assert dumped["protocol"] == "vc" and dumped["n"] == 32
    assert main(["--spec", str(spec_file)]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["extras"]["comparisons_per_delivery"] > 0.0


def test_cli_rejects_bad_spec(capsys):
    from repro.api.__main__ import main
    assert main(["--protocol", "pc", "--n", "1"]) == 2
    assert "n=1" in capsys.readouterr().err


def test_cli_list_is_a_discovery_surface(capsys):
    """--list names every registered key on every axis WITH its
    description, so a new user can discover the experiment space
    without reading source."""
    from repro.api.__main__ import main
    assert main(["--list"]) == 0
    out = capsys.readouterr().out
    for key in ("pc", "r", "vc",                          # protocols
                "exact", "vec", "windowed", "sharded",    # engines
                "ring", "kregular", "smallworld",         # topologies
                "uniform", "poisson", "bursty",           # traffic
                "churn", "crash", "link_add", "none",
                "partition_heal", "churn_wave",           # scenarios
                "hash", "all",                            # samplers
                "log", "fail",                            # audit modes
                "prometheus", "jsonl"):                   # ops sinks
        assert key in out, key
    # descriptions, not bare keys
    assert "shard_map frontier exchange" in out
    assert "Algorithm 2" in out
    assert "Watts-Strogatz" in out
    # flight-recorder axes (S10) are discoverable with descriptions
    assert "samplers" in out and "audit" in out and "ops sinks" in out
    assert "splitmix64" in out
    assert "CausalityViolationError" in out
    assert "append-only JSONL stream" in out


@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_dir_is_fixed_or_from_env(tmp_path, from_env):
    """The entry points' compile cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, otherwise ``.jax_cache`` in the checkout — checked in a
    child so this process never turns the cache on."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.path.join(root, "src")
    want = os.path.join(root, ".jax_cache")
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = want = str(tmp_path)
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; from repro.compile_cache import enable_compile_cache;"
         " print(enable_compile_cache());"
         " print(jax.config.jax_compilation_cache_dir)"],
        capture_output=True, text=True, env=env, check=True)
    assert out.stdout.split() == [want, want], out.stdout + out.stderr
