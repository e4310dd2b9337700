"""String-keyed registries: the pluggable axes of the experiment API.

Four axes are extensible by registration rather than by editing call
sites — protocols, engines, topologies and traffic patterns — plus the
scenario-family registry that maps a ``DynamicsSpec.kind`` to the
concrete :class:`~repro.core.vecsim.scenario.VecScenario` builder.  Each
registry is a plain :class:`Registry` of entry objects; ``repro.api.run``
resolves every axis of a :class:`~repro.api.spec.RunSpec` through these
tables, so registering a new entry makes it reachable from specs, the
CLI and every rebased benchmark at once.

    from repro.api import SCENARIOS, ScenarioEntry

    @SCENARIOS.register("my_workload")
    def _build(spec): ...

Engine entries are registered by ``repro.api.run`` at import time (they
close over the dispatch logic); everything else registers here.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterable, Optional

from ..core.vecsim import scenario as _scn
from ..core.vecsim.live import _ADMISSION, _ARRIVALS
from ..obs.audit import AUDIT_MODES as _AUDIT_MODES
from ..obs.flight import SAMPLERS as _SAMPLERS
from ..obs.ops import OPS_SINKS as _OPS_SINKS
from ..obs.sinks import SINKS as _SINKS
from .spec import RunSpec, SpecError

__all__ = ["Registry", "ProtocolEntry", "EngineEntry", "BackendEntry",
           "ScenarioEntry", "PROTOCOLS", "ENGINES", "BACKENDS",
           "TOPOLOGIES", "TRAFFIC", "SCENARIOS", "ARRIVALS", "ADMISSION",
           "SINKS", "SAMPLERS", "AUDIT", "OPS_SINKS", "describe_entry"]


class Registry:
    """A small string-keyed table with informative lookup failures.

    ``items`` may be an existing dict to wrap *live* (no copy): the
    topology and traffic registries share the dispatch tables inside
    ``vecsim.scenario``, so registering here makes the key immediately
    buildable by every scenario builder."""

    def __init__(self, name: str, items: Optional[Dict[str, Any]] = None):
        self.name = name
        self._items: Dict[str, Any] = {} if items is None else items

    def register(self, key: str, value: Any = None):
        """Register directly (``register(key, value)``) or as a
        decorator (``@register(key)``)."""
        if value is not None:
            self._add(key, value)
            return value

        def deco(fn):
            self._add(key, fn)
            return fn
        return deco

    def _add(self, key: str, value: Any) -> None:
        if key in self._items:
            raise KeyError(f"{self.name} key {key!r} already registered")
        self._items[key] = value

    def get(self, key: str) -> Any:
        try:
            return self._items[key]
        except KeyError:
            raise KeyError(f"unknown {self.name} key {key!r}; registered: "
                           f"{sorted(self._items)}") from None

    def keys(self) -> Iterable[str]:
        return self._items.keys()

    def __contains__(self, key: str) -> bool:
        return key in self._items

    def __iter__(self):
        return iter(self._items)


PROTOCOLS = Registry("protocol")
ENGINES = Registry("engine")        # populated by repro.api.run on import
BACKENDS = Registry("backend")
# Live views of the vecsim dispatch tables: a topology registered here is
# buildable by every scenario builder (uniform signature
# (seed, n, k, max_delay, free_slots, beta) -> (adj0, delay0)); a
# TrafficModel registered here is usable by sustained_scenario.
TOPOLOGIES = Registry("topology", items=_scn._TOPOLOGIES)
TRAFFIC = Registry("traffic", items=_scn._TRAFFIC)
SCENARIOS = Registry("scenario")
# Live serving axes (mode="live"): open-loop arrival processes and
# admission policies, shared live with vecsim.live so an ArrivalProcess
# or AdmissionPolicy registered here is immediately buildable by
# LiveLoop (and vice versa).
ARRIVALS = Registry("arrivals", items=_ARRIVALS)
ADMISSION = Registry("admission", items=_ADMISSION)
# Telemetry export sinks (ObsSpec.sink), shared live with repro.obs so a
# MetricsSink registered here is immediately usable by --metrics-out.
SINKS = Registry("sink", items=_SINKS)
# Flight-recorder surface (DESIGN §2.11), wrapped live from repro.obs:
# provenance samplers (ObsSpec.sampler), causality-audit modes
# (ObsSpec.audit) and live ops-plane sinks (ObsSpec.ops_sink).
SAMPLERS = Registry("sampler", items=_SAMPLERS)
AUDIT = Registry("audit mode", items=_AUDIT_MODES)
OPS_SINKS = Registry("ops sink", items=_OPS_SINKS)


# --------------------------------------------------------------------- #
# Protocols
# --------------------------------------------------------------------- #
@dataclass(frozen=True)
class ProtocolEntry:
    """One causal-broadcast protocol, runnable on every engine that
    supports it.  ``mode`` is the :class:`VecScenario` mode the pc/r vec
    engine executes (None = the protocol has its own vec runner);
    ``windowed`` marks streaming-window support."""

    name: str
    description: str
    mode: Optional[str]        # VecScenario.mode for the shared vec engine
    windowed: bool


@dataclass(frozen=True)
class BackendEntry:
    """One compute backend of the vec engines, with a lazy availability
    probe: ``probe() -> (ok, note)`` tells the discovery surface (and
    ``select_engine``) whether the backend can run here and why/how.
    The probe never raises and never imports at registration time."""

    name: str
    description: str
    probe: Callable[[], tuple]

    @property
    def available(self) -> bool:
        return bool(self.probe()[0])

    def availability_note(self) -> str:
        return str(self.probe()[1])


@dataclass(frozen=True)
class EngineEntry:
    """One execution engine: a runner callable plus the one-line
    description the CLI discovery surface (``python -m repro.api
    --list``) prints.  Calling the entry calls the runner, so
    ``repro.api.run`` dispatches through it unchanged."""

    name: str
    description: str
    run: Callable

    def __call__(self, *args, **kwargs):
        return self.run(*args, **kwargs)


def describe_entry(value: Any) -> str:
    """Best-effort one-line description of a registry value: an explicit
    ``description`` attribute, the value itself when it *is* the
    description (the batch-traffic marker), or the first docstring line
    of a registered callable."""
    desc = getattr(value, "description", None)
    if isinstance(desc, str) and desc:
        return desc
    if isinstance(value, str):
        return value
    import inspect
    doc = inspect.getdoc(value)
    return doc.splitlines()[0].strip() if doc else ""


PROTOCOLS.register("pc", ProtocolEntry(
    "pc", "PC-broadcast: O(1) control info, link-safety ping gating "
    "(the paper's Algorithm 2)", mode="pc", windowed=True))
PROTOCOLS.register("r", ProtocolEntry(
    "r", "R-broadcast: flooding without link gating (causally unsafe "
    "on dynamic overlays — the Fig. 3 foil)", mode="r", windowed=True))
PROTOCOLS.register("vc", ProtocolEntry(
    "vc", "vector-clock causal broadcast: O(N) piggybacked clocks, "
    "O(W·N) delivery drain (Table 1 baseline, measured)", mode=None,
    windowed=False))


# --------------------------------------------------------------------- #
# Backends: how the vec engines execute a round body
# --------------------------------------------------------------------- #
def _probe_numpy():
    return True, "always available"


def _probe_jax():
    try:
        import jax
    except Exception as exc:  # pragma: no cover - environment-dependent
        return False, f"jax not importable: {exc}"
    return True, f"jax {jax.__version__} on {jax.default_backend()}"


def _probe_pallas():
    from ..core.vecsim.kernels import pallas_available
    return pallas_available()


BACKENDS.register("numpy", BackendEntry(
    "numpy", "mutating numpy reference: readable, host-speed, the "
    "semantics every other backend must match byte-for-byte",
    _probe_numpy))
BACKENDS.register("jax", BackendEntry(
    "jax", "jitted lax.scan round body (vec/windowed) and the shard_map "
    "mesh program (sharded; shard.scan='on' runs whole segments as one "
    "device-side lax.scan, DESIGN.md §2.7)", _probe_jax))
BACKENDS.register("pallas", BackendEntry(
    "pallas", "fused Pallas delivery-sweep kernels in the round body "
    "(vecsim.kernels, DESIGN.md §2.6): interpret mode on CPU, a "
    "byte-parity check; they do not compile for the TPU and are never "
    "auto-selected",
    _probe_pallas))


# --------------------------------------------------------------------- #
# Traffic: the batch ("uniform") marker rides alongside the shared
# sustained TrafficModel table
# --------------------------------------------------------------------- #
TRAFFIC.register("uniform", "unique (origin, round) broadcasts spread "
                 "uniformly over the schedule window (batch scheduling; "
                 "not a sustained TrafficModel)")
# "poisson" and "bursty" arrive through the shared _TRAFFIC table as
# TrafficModel entries; register new sustained models the same way:
#   TRAFFIC.register("flashcrowd", TrafficModel(build=..., mean_rate=...))


# --------------------------------------------------------------------- #
# Scenario families: DynamicsSpec.kind -> VecScenario builder
# --------------------------------------------------------------------- #
@dataclass(frozen=True)
class ScenarioEntry:
    """Adapter from a validated :class:`RunSpec` to a scenario."""

    name: str
    build: Callable[[RunSpec], Any]
    topologies: Optional[frozenset] = None   # None = any registered
    traffic: Optional[frozenset] = frozenset({"uniform"})  # None = any
    description: str = ""                    # one line for --list

    def check(self, spec: RunSpec) -> None:
        if self.topologies is not None \
                and spec.topology.kind not in self.topologies:
            raise SpecError(
                f"dynamics kind {self.name!r} supports only "
                f"{sorted(self.topologies)} topologies (got "
                f"{spec.topology.kind!r})")
        if self.traffic is not None and spec.traffic.kind not in self.traffic:
            raise SpecError(
                f"dynamics kind {self.name!r} supports only "
                f"{sorted(self.traffic)} traffic (got "
                f"{spec.traffic.kind!r})")


def _mode(spec: RunSpec) -> str:
    entry = PROTOCOLS.get(spec.protocol)
    return entry.mode if entry.mode is not None else "pc"


def _build_none(spec: RunSpec):
    t, tr = spec.topology, spec.traffic
    if tr.kind == "uniform":
        return _scn.static_scenario(
            seed=spec.seed, n=spec.n, k=t.k, m_app=tr.messages,
            max_delay=t.max_delay, mode=_mode(spec),
            pong_delay=spec.pong_delay, topology=t.kind, beta=t.beta)
    return _scn.sustained_scenario(
        seed=spec.seed, n=spec.n, k=t.k, rate=tr.rate,
        messages=tr.messages, topology=t.kind, traffic=tr.kind,
        beta=t.beta, burst_period=tr.period, burst_duty=tr.duty,
        rate_lo=tr.rate_lo, max_delay=t.max_delay, mode=_mode(spec),
        pong_delay=spec.pong_delay)


def _build_link_add(spec: RunSpec):
    t, d = spec.topology, spec.dynamics
    return _scn.link_add_scenario(
        seed=spec.seed, n=spec.n, k=t.k, m_app=spec.traffic.messages,
        n_adds=d.n_adds, max_delay=t.max_delay,
        pong_delay=spec.pong_delay, topology=t.kind, beta=t.beta)


def _build_churn(spec: RunSpec):
    t, d = spec.topology, spec.dynamics
    return _scn.churn_scenario(
        seed=spec.seed, n=spec.n, k=t.k, m_app=spec.traffic.messages,
        n_adds=d.n_adds, n_rms=d.n_rms, max_delay=t.max_delay,
        pong_delay=spec.pong_delay, churn_window=d.churn_window,
        topology=t.kind, beta=t.beta)


def _build_crash(spec: RunSpec):
    t, d = spec.topology, spec.dynamics
    return _scn.crash_scenario(
        seed=spec.seed, n=spec.n, k=t.k, m_app=spec.traffic.messages,
        n_crashes=d.n_crashes, max_delay=t.max_delay,
        pong_delay=spec.pong_delay, topology=t.kind, beta=t.beta)


def _build_partition_heal(spec: RunSpec):
    t, d = spec.topology, spec.dynamics
    return _scn.partition_heal_scenario(
        seed=spec.seed, n=spec.n, k=t.k, m_app=spec.traffic.messages,
        n_bridge=d.n_bridge, max_delay=t.max_delay,
        pong_delay=spec.pong_delay,
        traffic_during_partition=d.traffic_during_partition)


def _build_churn_wave(spec: RunSpec):
    t, d = spec.topology, spec.dynamics
    return _scn.churn_wave_scenario(
        seed=spec.seed, n=spec.n, k=t.k, m_app=spec.traffic.messages,
        waves=d.waves, adds_per_wave=d.n_adds, rms_per_wave=d.n_rms,
        max_delay=t.max_delay, pong_delay=spec.pong_delay,
        topology=t.kind, beta=t.beta)


SCENARIOS.register("none", ScenarioEntry(
    "none", _build_none, traffic=None,   # any registered traffic model
    description="static overlay; batch or sustained traffic only"))
SCENARIOS.register("link_add", ScenarioEntry(
    "link_add", _build_link_add,
    description="batched link additions racing later broadcasts (the "
    "Fig. 3 shortcut that ping gating makes safe)"))
SCENARIOS.register("churn", ScenarioEntry(
    "churn", _build_churn,
    description="interleaved link additions and removals under traffic"))
SCENARIOS.register("crash", ScenarioEntry(
    "crash", _build_crash,
    description="silent mid-broadcast crashes (Fig. 5b)"))
SCENARIOS.register("partition_heal", ScenarioEntry(
    "partition_heal", _build_partition_heal,
    topologies=frozenset({"ring"}),
    description="brownout partition over a thin bridge, then healed "
    "cross links re-gating"))
SCENARIOS.register("churn_wave", ScenarioEntry(
    "churn_wave", _build_churn_wave,
    description="periodic waves of adds+removals with traffic "
    "throughout (diurnal / flash-crowd membership)"))
