"""``repro.api.run`` — one front door for every protocol experiment.

``run(spec)`` resolves every axis of a validated :class:`RunSpec`
through the registries, builds (or accepts) the scenario, picks the
engine, executes, and returns a uniform :class:`RunReport` whatever ran
underneath — the exact event simulator, the monolithic vec engine, the
streaming windowed engine, or the vectorized vector-clock baseline.

Engine auto-selection (DESIGN.md §3): with ``engine="auto"``,

  1. an explicit ``window.window`` selects the streaming engine;
  2. otherwise the monolithic vec engine runs iff its two dense
     ``(N, M_total)`` int32 planes fit the spec's memory budget
     (``8·N·M_total <= memory_budget_mb``);
  3. otherwise a streaming engine runs with the budget-derived window —
     **per-device-aware**: when more than one device is visible (or
     ``shard.devices`` asks for several), the device-sharded engine
     (``vecsim.shard``) takes the run with
     ``window = clamp(D·budget // (8·N), 64, M_total)`` — the budget is
     per device, so a mesh widens the window D-fold; on a single device
     the single-host windowed engine runs with
     ``window = clamp(budget // (8·N), 64, M_total)``.

The exact event engine is never auto-selected — it is the O(objects)
reference implementation and must be asked for by name.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field, replace
from typing import Any, Dict, Optional, Tuple

import numpy as np

from ..core.oracle import OracleReport, check_trace
from ..core.types import NetStats
from ..core.vecsim import crossval as _crossval
from ..core.vecsim import stream as _stream
from ..core.vecsim.live import LiveLoop, LiveReport
from ..core.vecsim.metrics import build_trace
from ..core.vecsim.scenario import VecScenario
from ..core.vecsim.sim import execute_vec, resolve_backend
from ..core.vecsim.vc import run_vec_vc
from ..obs.audit import CausalAuditor
from ..obs.flight import FlightRecorder, provenance_trace_events
from ..obs.graphs import overhead_per_message
from ..obs.hist import percentiles_from_hist
from ..obs.ops import OpsPlane
from ..obs.sinks import write_chrome_trace
from ..obs.spans import EngineObs
from .registry import ENGINES, PROTOCOLS, SCENARIOS, SINKS, EngineEntry
from .spec import RunSpec, SpecError

__all__ = ["RunReport", "run", "build_scenario", "select_engine",
           "build_live_scenario"]


@dataclass
class RunReport:
    """Uniform result of :func:`run`, whatever engine executed."""

    spec: RunSpec
    engine: str                # engine that actually ran
    backend: str               # resolved backend ("object" for exact)
    window: Optional[int]      # live columns (windowed engine only)
    wall_seconds: float
    n: int
    m_app: int
    rounds: int                # scenario rounds (0 for the exact engine)
    stats: NetStats
    delivered_frac: float
    mean_latency: float        # rounds (vec) / sim-time units (exact)
    extras: Dict[str, float] = field(default_factory=dict)
    oracle: Optional[OracleReport] = None
    crossval_ok: Optional[bool] = None
    result: Any = None         # the raw engine result object
    scenario: Any = None       # the VecScenario that ran
    live: Optional[LiveReport] = None   # serving report (mode="live")
    obs: Any = None            # EngineObs telemetry accumulator (or None)

    def to_dict(self) -> Dict[str, Any]:
        """JSON-safe summary (drops the raw result and scenario)."""
        try:
            spec_d = self.spec.to_dict()
        except SpecError:
            spec_d = {"scenario": "prebuilt"}
        return dict(
            spec=spec_d, engine=self.engine, backend=self.backend,
            window=self.window, wall_seconds=round(self.wall_seconds, 4),
            n=self.n, m_app=self.m_app, rounds=self.rounds,
            stats=vars(self.stats).copy(),
            delivered_frac=self.delivered_frac,
            mean_latency=self.mean_latency,
            extras={k: (v if isinstance(v, (int, str)) else float(v))
                    for k, v in self.extras.items()},
            oracle_ok=None if self.oracle is None else self.oracle.ok,
            crossval_ok=self.crossval_ok,
            live=None if self.live is None else self.live.to_dict(),
        )


# --------------------------------------------------------------------- #
# Scenario construction and engine selection
# --------------------------------------------------------------------- #
def build_scenario(spec: RunSpec) -> VecScenario:
    """Resolve the spec's topology/traffic/dynamics sections into a
    :class:`VecScenario` (or pass through a prebuilt one)."""
    if spec.scenario is not None:
        scn = spec.scenario
    else:
        scn = SCENARIOS.get(spec.dynamics.kind).build(spec)
    proto = PROTOCOLS.get(spec.protocol)
    want_mode = proto.mode if proto.mode is not None else scn.mode
    if scn.mode != want_mode or scn.always_gate != spec.always_gate:
        scn = replace(scn, mode=want_mode,
                      always_gate=spec.always_gate).validate()
    return scn


def _auto_window(spec: RunSpec, scn: VecScenario, devices: int = 1) -> int:
    """The budget-derived window (DESIGN.md §3.3 rule 3):
    ``clamp(D·budget // (8·N), 64, M_total)`` live columns — the memory
    budget reads per device, so a mesh scales the window with it."""
    budget = devices * spec.memory_budget_mb * 2 ** 20
    return int(min(max(64, budget // (8 * scn.n)), scn.m_total))


def _device_count(spec: RunSpec) -> int:
    """Devices the sharded engine would run on: the explicit
    ``shard.devices`` if set, else whatever jax can see (1 when jax is
    absent — auto-selection then never proposes the sharded engine)."""
    if spec.shard.devices is not None:
        return spec.shard.devices
    try:
        import jax
        return jax.device_count()
    except ImportError:
        return 1


def select_engine(spec: RunSpec, scn: VecScenario
                  ) -> Tuple[str, Optional[int]]:
    """Apply the DESIGN.md §3 auto-selection rule; explicit engines pass
    through unchanged (with the spec's window, if any — validate()
    rejects a window on the monolithic/exact engines).

    An explicit ``backend="pallas"`` fails here — eagerly, with a
    :class:`SpecError` naming the probe's reason — when the kernels
    cannot run here (on a TPU they do not compile); ``backend="auto"``
    is always the jax backend."""
    if spec.backend == "pallas":
        from .registry import BACKENDS
        ok, note = BACKENDS.get("pallas").probe()
        if not ok:
            raise SpecError(
                f"backend='pallas' requested but Pallas cannot "
                f"initialize ({note}); use backend='jax' or 'auto'")
    if spec.engine != "auto":
        return spec.engine, spec.window.window
    if spec.window.window is not None:
        # an explicit window is a streaming request; an explicit mesh
        # request must not be dropped on the floor with it (validate()
        # rejects devices>1 on the numpy backend)
        if (spec.shard.devices or 1) > 1:
            return "sharded", spec.window.window
        return "windowed", spec.window.window
    proto = PROTOCOLS.get(spec.protocol)
    budget = spec.memory_budget_mb * 2 ** 20
    mono_bytes = 8 * scn.n * max(scn.m_total, 1)
    if mono_bytes <= budget or not proto.windowed:
        return "vec", None
    if spec.backend == "numpy":
        # numpy can never shard — skip device detection (and its jax
        # runtime initialization) entirely
        return "windowed", _auto_window(spec, scn)
    devices = _device_count(spec)
    if devices > 1:
        return "sharded", _auto_window(spec, scn, devices=devices)
    return "windowed", _auto_window(spec, scn)


def _snapshot_round(spec: RunSpec, scn: VecScenario) -> Optional[int]:
    snap = spec.metrics.snapshot
    if snap == "last_churn":
        return int(scn.add_round[-1]) if scn.n_adds else None
    return snap


# --------------------------------------------------------------------- #
# Engine adapters (registered under repro.api.ENGINES)
# --------------------------------------------------------------------- #
def _latency_from_trace(trace) -> float:
    t_bcast: Dict[Tuple[int, int], float] = {}
    lat_sum, lat_cnt = 0.0, 0
    for t, kind, pid, m in trace:
        if kind not in ("broadcast", "deliver"):
            continue                      # open/close/crash carry no AppMsg
        key = (m.origin, m.counter)
        if kind == "broadcast":
            t_bcast[key] = t
        elif key in t_bcast:
            lat_sum += t - t_bcast[key]
            lat_cnt += 1
    return lat_sum / lat_cnt if lat_cnt else float("nan")


def _run_exact(spec: RunSpec, scn: VecScenario, window: Optional[int],
               snapshot_round: Optional[int], obs=None):
    net = _crossval.run_exact(scn, seed=spec.seed, protocol=spec.protocol,
                              snapshot_round=snapshot_round)
    n_bcast = sum(1 for _, kind, _, _ in net.trace if kind == "broadcast")
    alive = sum(1 for p in net.procs.values() if not p.crashed)
    frac = (net.stats.deliveries / (alive * n_bcast)
            if alive * n_bcast else 1.0)
    extras: Dict[str, float] = {
        "overhead_bytes_per_msg": overhead_per_message(net)}
    if spec.protocol == "vc":
        comparisons = sum(p.comparisons for p in net.procs.values())
        extras["comparisons_per_delivery"] = (
            comparisons / max(net.stats.deliveries, 1))
        extras["max_pending"] = max(p.max_pending
                                    for p in net.procs.values())
        extras["space_entries_max"] = max(p.local_space_entries()
                                          for p in net.procs.values())
    return net, net.stats, frac, _latency_from_trace(net.trace), extras


def _vec_extras(spec: RunSpec, res) -> Dict[str, float]:
    if spec.protocol == "vc":
        return {
            "overhead_bytes_per_msg": res.overhead_bytes_per_message(),
            "comparisons_per_delivery": res.comparisons_per_delivery(),
            "max_pending": res.max_pending,
            "space_entries_max": int((res.vc > 0).sum(axis=1).max()),
        }
    return {
        "overhead_bytes_per_msg": res.stats.control_bytes
        / max(res.stats.sent_messages, 1),
        "gated_link_rounds": int(res.series[:, 5].sum()),
        "pongs": int(res.series[:, 4].sum()),
    }


def _run_vec(spec: RunSpec, scn: VecScenario, window: Optional[int],
             snapshot_round: Optional[int], obs=None):
    if spec.protocol == "vc":
        if snapshot_round is not None:
            raise SpecError("metrics.snapshot is not supported for the "
                            "'vc' protocol (it has no gating state to "
                            "snapshot)")
        res = run_vec_vc(scn)
    else:
        res = execute_vec(scn, backend=spec.backend,
                          snapshot_round=snapshot_round)
    return (res, res.stats, res.delivered_frac(), res.mean_latency(),
            _vec_extras(spec, res))


def _run_windowed(spec: RunSpec, scn: VecScenario, window: Optional[int],
                  snapshot_round: Optional[int], obs=None):
    if window is None:
        # explicit engine="windowed" without a window: apply the budget rule
        window = _auto_window(spec, scn)
    res = _stream.execute_windowed(
        scn, window, backend=spec.backend, horizon=spec.window.horizon,
        seg_len=spec.window.seg_len, snapshot_round=snapshot_round,
        collect=spec.window.collect, obs=obs)
    extras = _vec_extras(spec, res)
    extras["peak_live"] = res.peak_live
    extras["expired_columns"] = int(res.expired.sum())
    return (res, res.stats, res.delivered_frac(), res.mean_latency(),
            extras)


def _run_sharded(spec: RunSpec, scn: VecScenario, window: Optional[int],
                 snapshot_round: Optional[int], obs=None):
    if spec.protocol == "vc":
        raise SpecError("protocol 'vc' has no sharded engine (its "
                        "delivery drain is a data-dependent host loop); "
                        "use engine='vec'")
    from ..core.vecsim.shard import execute_sharded
    devices = spec.shard.devices
    if window is None:
        # explicit engine="sharded" without a window: the per-device
        # budget rule over the devices the run will actually use
        window = _auto_window(spec, scn, devices=_device_count(spec))
    res = execute_sharded(
        scn, window, n_devices=devices, horizon=spec.window.horizon,
        seg_len=spec.window.seg_len, snapshot_round=snapshot_round,
        collect=spec.window.collect, backend=spec.backend,
        scan=spec.shard.scan, profile=spec.shard.profile, obs=obs)
    extras = _vec_extras(spec, res)
    extras["peak_live"] = res.peak_live
    extras["expired_columns"] = int(res.expired.sum())
    extras["devices"] = res.n_devices
    extras["scan"] = res.scan
    if res.seg_profile is not None:
        # scalar totals only; the per-segment list stays on the raw
        # result (report.result.seg_profile) — extras are float-coerced
        for key in ("stage_s", "dispatch_s", "block_s", "retire_s"):
            extras["profile_" + key] = float(
                sum(p[key] for p in res.seg_profile))
        extras["profile_segments"] = len(res.seg_profile)
        extras["profile_fast_segments"] = sum(
            1 for p in res.seg_profile if p["fast"])
    return (res, res.stats, res.delivered_frac(), res.mean_latency(),
            extras)


ENGINES.register("exact", EngineEntry(
    "exact", "O(objects) discrete-event reference simulator (never "
    "auto-selected; paper-faithful sub-round timing)", _run_exact))
ENGINES.register("vec", EngineEntry(
    "vec", "monolithic vectorized lockstep engine: dense (N, M_total) "
    "planes, numpy or jax backend", _run_vec))
ENGINES.register("windowed", EngineEntry(
    "windowed", "streaming windowed engine: O(N*window) live-column "
    "buffer for sustained traffic on one host", _run_windowed))
ENGINES.register("sharded", EngineEntry(
    "sharded", "device-sharded windowed engine: process axis partitioned "
    "over a jax mesh (shard_map frontier exchange), N to 10^6+; "
    "shard.scan=auto|on|off picks whole-segment lax.scan vs per-round "
    "stepping, shard.profile=True records per-segment timings",
    _run_sharded))


# --------------------------------------------------------------------- #
# Telemetry plumbing (repro.obs; DESIGN.md §2.10)
# --------------------------------------------------------------------- #
def _build_obs(spec: RunSpec, engine_name: str,
               live: bool = False) -> Optional[EngineObs]:
    """The :class:`EngineObs` accumulator a run threads through its
    engine, or None when every telemetry pillar is off — the engines
    then trace exactly the pre-telemetry program (the overhead gate in
    CI holds them to it)."""
    ob = spec.obs
    hist = ob.histograms
    if hist is None:
        # auto: on wherever an engine can feed it (the streaming
        # engines' retire reductions, and every live run)
        hist = live or engine_name in ("windowed", "sharded")
    spans = bool(ob.spans or ob.trace_out is not None)
    flight = None
    if ob.provenance is not None:
        if not live and engine_name not in ("windowed", "sharded"):
            raise SpecError(
                f"obs.provenance needs a streaming engine (the hooks "
                f"ride column retirement), but this run resolved to "
                f"engine={engine_name!r}; set an explicit window or "
                "engine='windowed'/'sharded'")
        auditor = (CausalAuditor(ob.audit) if ob.audit != "off"
                   else None)
        flight = FlightRecorder(rate=ob.provenance, seed=spec.seed,
                                sampler=ob.sampler, auditor=auditor,
                                live=live)
    if not live and not hist and not spans and ob.metrics_out is None \
            and flight is None:
        return None
    obs = EngineObs(histograms=hist, spans=spans,
                    span_capacity=ob.span_capacity)
    obs.flight = flight
    return obs


def _obs_extras(obs: Optional[EngineObs], extras: Dict[str, float]) -> None:
    """Histogram-derived latency percentiles and telemetry counters into
    the report extras."""
    if obs is None:
        return
    total = int(obs.latency_hist.sum())
    if obs.histograms and total > 0:
        p50, p99, p999 = percentiles_from_hist(
            obs.latency_hist, (50.0, 99.0, 99.9))
        extras["latency_p50"] = p50
        extras["latency_p99"] = p99
        extras["latency_p999"] = p999
        extras["latency_hist_total"] = total
    fl = obs.flight
    if fl is not None:
        extras["provenance_sampled"] = fl.sampled
        if fl.auditor is not None:
            extras["audit_pairs_checked"] = fl.auditor.pairs_checked
            extras["audit_violations"] = len(fl.auditor.violations)
    for name, value in obs.counters.items():
        extras[name] = value


def _metrics_doc(spec: RunSpec, report: "RunReport",
                 obs: EngineObs) -> dict:
    """The sink-agnostic telemetry doc a metrics sink serializes."""
    fl = obs.flight
    run = dict(engine=report.engine, backend=report.backend,
               mode=spec.mode, protocol=spec.protocol, n=report.n,
               m_app=report.m_app, rounds=report.rounds,
               seed=spec.seed)
    if "devices" in report.extras:
        run["devices"] = int(report.extras["devices"])
    return dict(
        run=run,
        summary=dict(
            wall_seconds=report.wall_seconds,
            delivered_frac=report.delivered_frac,
            mean_latency=report.mean_latency,
            **{k: v for k, v in report.extras.items()
               if isinstance(v, (int, float))}),
        gauges={k: list(v) for k, v in obs.gauges.items()},
        counters=dict(obs.counters),
        latency_hist=(obs.latency_hist
                      if obs.histograms and obs.latency_hist.sum() > 0
                      else None),
        provenance=(fl.export() if fl is not None else None))


def _write_obs_outputs(spec: RunSpec, report: "RunReport") -> None:
    ob, obs = spec.obs, report.obs
    if obs is None:
        return
    if ob.metrics_out is not None:
        SINKS.get(ob.sink).write(ob.metrics_out,
                                 _metrics_doc(spec, report, obs))
    if ob.trace_out is not None:
        try:
            run_args = spec.to_dict()
        except SpecError:
            run_args = {"scenario": "prebuilt"}
        extra = None
        fl = obs.flight
        if fl is not None and fl.completed:
            extra = provenance_trace_events(
                fl.export(),
                n_devices=int(report.extras.get("devices", 1)))
        write_chrome_trace(ob.trace_out, obs.spans, run_args=run_args,
                           extra_events=extra)


# --------------------------------------------------------------------- #
# Live serving mode
# --------------------------------------------------------------------- #
def build_live_scenario(spec: RunSpec) -> VecScenario:
    """The broadcast-free base a live run serves over: the spec's
    topology and dynamics with every pre-scripted broadcast stripped
    (live traffic arrives through the ingest queue instead)."""
    scn = build_scenario(spec)
    if scn.m_app:
        scn = replace(scn, bcast_round=np.empty(0, np.int32),
                      bcast_origin=np.empty(0, np.int32)).validate()
    return scn


def _select_live_engine(spec: RunSpec, scn: VecScenario
                        ) -> Tuple[str, int]:
    """Streaming-engine selection for live mode: the explicit engine if
    named, else sharded on a multi-device mesh, windowed otherwise; the
    window follows the batch budget rule with ``M_total`` read from the
    serving capacity (``live.messages`` + pre-scripted adds)."""
    if spec.engine in ("windowed", "sharded"):
        name = spec.engine
    elif spec.backend == "numpy":
        name = "windowed"
    else:
        name = "sharded" if _device_count(spec) > 1 else "windowed"
    window = spec.window.window
    if window is None:
        devices = _device_count(spec) if name == "sharded" else 1
        budget = devices * spec.memory_budget_mb * 2 ** 20
        m_total = spec.live.messages + scn.n_adds
        window = int(min(max(64, budget // (8 * scn.n)), max(m_total, 1)))
    return name, window


def _run_live(spec: RunSpec, on_tick=None) -> RunReport:
    scn = build_live_scenario(spec)
    engine_name, window = _select_live_engine(spec, scn)
    obs = _build_obs(spec, engine_name, live=True)
    lv = spec.live
    arrival_params = dict(rate_lo=lv.rate_lo, period=lv.period,
                          duty=lv.duty)
    ob = spec.obs
    ops = None
    if ob.ops_out is not None or ob.watch:
        ops = OpsPlane(out=ob.ops_out, sink=ob.ops_sink,
                       every=ob.ops_every, slo_p99=lv.slo_p99,
                       watch=True if ob.watch else None)
    loop = LiveLoop(
        scn, window, engine=engine_name, backend=spec.backend,
        devices=spec.shard.devices, scan=spec.shard.scan,
        seg_len=spec.window.seg_len, horizon=spec.window.horizon,
        collect=spec.window.collect, arrivals=lv.arrivals,
        admission=lv.admission, rate=lv.rate, messages=lv.messages,
        queue_cap=lv.queue_cap, per_round_cap=lv.per_round_cap,
        slo_p99=lv.slo_p99, seed=spec.seed,
        arrival_params=arrival_params, profile=spec.shard.profile,
        obs=obs, on_tick=on_tick, ops=ops)
    lr = loop.run()
    res = lr.result

    extras = _vec_extras(spec, res)
    extras["peak_live"] = lr.peak_live
    for key in ("offered", "admitted", "shed_queue", "shed_policy",
                "unserved", "queue_peak", "backpressure_ticks",
                "overflow_catches", "requests_per_sec", "p50", "p99",
                "p999", "mean_latency_rounds"):
        v = getattr(lr, key)
        if isinstance(v, float) and not np.isfinite(v):
            continue
        extras["serve_" + key] = v
    if lr.slo_ok is not None:
        extras["serve_slo_ok"] = int(lr.slo_ok)
    _obs_extras(obs, extras)

    report = RunReport(
        spec=spec, engine=engine_name,
        backend=getattr(res, "backend", resolve_backend(spec.backend)),
        window=getattr(res, "window", window),
        wall_seconds=lr.wall_seconds, n=scn.n,
        m_app=lr.scenario.m_app, rounds=lr.scenario.rounds,
        stats=res.stats, delivered_frac=lr.delivered_frac,
        mean_latency=res.mean_latency(), extras=extras, result=res,
        scenario=lr.scenario, live=lr, obs=obs)
    # the live result is re-indexed to the admitted scenario, so the
    # batch-mode checkers run on it unchanged
    if spec.metrics.oracle:
        report.oracle = _check_oracle(spec, lr.scenario, engine_name, res)
    if spec.metrics.crossval:
        report.crossval_ok = _check_crossval(spec, lr.scenario,
                                             report.window, engine_name,
                                             res)
    _write_obs_outputs(spec, report)
    return report


# --------------------------------------------------------------------- #
# The front door
# --------------------------------------------------------------------- #
def run(spec: RunSpec, on_tick=None) -> RunReport:
    """Validate ``spec``, build the scenario, pick the engine, execute,
    and measure — the one entry point every benchmark and example uses.
    ``on_tick`` (live mode only) is called with a small progress dict
    after every serving tick."""
    spec.validate()
    if spec.mode == "live":
        return _run_live(spec, on_tick=on_tick)
    scn = build_scenario(spec)
    engine_name, window = select_engine(spec, scn)
    snapshot_round = _snapshot_round(spec, scn)
    runner = ENGINES.get(engine_name)
    obs = _build_obs(spec, engine_name)

    t0 = time.perf_counter()
    result, stats, frac, latency, extras = runner(spec, scn, window,
                                                  snapshot_round, obs=obs)
    wall = time.perf_counter() - t0
    _obs_extras(obs, extras)

    if engine_name == "exact":
        backend = "object"
    elif spec.protocol == "vc":
        backend = "numpy"
    else:
        backend = getattr(result, "backend", resolve_backend(spec.backend))

    report = RunReport(
        spec=spec, engine=engine_name, backend=backend,
        # the result records the window actually used (covers explicit
        # engine="windowed"/"sharded" with the budget-derived default)
        window=(getattr(result, "window", window)
                if engine_name in ("windowed", "sharded") else None),
        wall_seconds=wall, n=scn.n, m_app=scn.m_app, rounds=scn.rounds,
        stats=stats, delivered_frac=frac, mean_latency=latency,
        extras=extras, result=result, scenario=scn, obs=obs)

    if spec.metrics.oracle:
        report.oracle = _check_oracle(spec, scn, engine_name, result)
    if spec.metrics.crossval:
        report.crossval_ok = _check_crossval(spec, scn, report.window,
                                             engine_name, result)
    _write_obs_outputs(spec, report)
    return report


def _check_oracle(spec: RunSpec, scn: VecScenario, engine: str, result):
    if engine == "exact":
        crashed = {pid for pid, p in result.procs.items() if p.crashed}
        return check_trace(result.trace, crashed=crashed,
                           all_pids=set(range(scn.n)))
    if getattr(result, "delivered", None) is None:
        raise SpecError(
            "metrics.oracle needs the full delivered matrix; set "
            "window.collect='full' (aggregate-mode windowed runs keep "
            "only per-message counters)")
    crashed = set(np.nonzero(result.state["crashed"])[0].tolist())
    return check_trace(build_trace(result), crashed=crashed,
                       all_pids=set(range(scn.n)))


def _check_crossval(spec: RunSpec, scn: VecScenario,
                    window: Optional[int], engine: str, result) -> bool:
    # reuse the run we just executed when it carries the full delivered
    # matrix; the exact engine's own run can't serve as the vec side
    reuse = (result if engine != "exact"
             and getattr(result, "delivered", None) is not None else None)
    out = _crossval.cross_validate(
        scn, seed=spec.seed, backend=resolve_backend(spec.backend)
        if spec.protocol != "vc" else "numpy",
        window=window, protocol=spec.protocol, vec_result=reuse)
    ok = out["vec_multiset"] == out["exact_multiset"]
    if spec.protocol == "vc":
        ok = ok and out["vec_clocks"] == out["exact_clocks"]
    return bool(ok)
