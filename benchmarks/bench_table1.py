"""Table 1 reproduction: message overhead, delivery execution time, and
local space for vector-clock causal broadcast vs. PC-broadcast — both
protocols **measured**, on either engine, through the one front door
(``repro.api.run``).

Two engines (``--engine``):

  * ``exact`` — both protocols run as Python processes on the event
    simulator at N in {50, 100, 200}, oracle-checked;
  * ``vec``   — both protocols run on the vectorized lockstep substrate
    at N in {1000, 10000, 50000}: PC-broadcast on the shared vec engine,
    the vector-clock baseline on its dense-clock vec twin
    (``vecsim.vc``), on the *same scenario* (same seed, topology and
    broadcast schedule), so the O(1)-vs-O(N) separation is measured —
    per-hop piggyback bytes and readiness-scan comparison counts — at
    population sizes the object simulator cannot reach.  The analytic
    model the measured columns replace is kept as ``vc_model`` rows for
    contrast.

Emits CSV rows  name,us_per_call,derived  where ``derived`` is the
table's complexity metric (bytes/message, comparisons/delivery, entries).
"""

from __future__ import annotations

import argparse

from repro.api import (MetricsSpec, RunSpec, TopologySpec, TrafficSpec,
                       WindowSpec, run)
from repro.core.vecsim import vc_overhead_model


def _spec(protocol: str, engine: str, n: int, m_app: int, k: int,
          backend: str = "numpy", window: int | None = None,
          oracle: bool = False) -> RunSpec:
    """One Table 1 cell: a static overlay with ``m_app`` broadcasts.
    The scenario depends only on (seed, n, k, m_app), so the pc and vc
    runs of a size execute the identical causal workload."""
    return RunSpec(
        protocol=protocol, engine=engine, backend=backend, n=n, seed=n,
        topology=TopologySpec(kind="ring", k=k),
        traffic=TrafficSpec(kind="uniform", messages=m_app),
        window=WindowSpec(window=window),
        metrics=MetricsSpec(oracle=oracle))


def rows_exact(sizes=(50, 100, 200)):
    out = []
    for n in sizes:
        # broadcasters scale with N so the vector-clock entry count (one
        # per process that EVER broadcast — the paper's N) grows too
        m_app = n // 2
        k = max(3, n // 32)
        # --- PC-broadcast -------------------------------------------- #
        rep = run(_spec("pc", "exact", n, m_app, k, oracle=True))
        assert rep.oracle.ok, rep.oracle.summary()
        us = rep.wall_seconds / max(rep.stats.deliveries, 1) * 1e6
        out.append((f"table1/pc/overhead_bytes/N={n}", us,
                    rep.extras["overhead_bytes_per_msg"]))
        space = max(len(p.received) for p in rep.result.procs.values())
        out.append((f"table1/pc/space_entries/N={n}", us, space))

        # --- vector clocks -------------------------------------------- #
        rep = run(_spec("vc", "exact", n, m_app, k, oracle=True))
        assert rep.oracle.ok, rep.oracle.summary()
        us = rep.wall_seconds / max(rep.stats.deliveries, 1) * 1e6
        out.append((f"table1/vc/overhead_bytes/N={n}", us,
                    rep.extras["overhead_bytes_per_msg"]))
        out.append((f"table1/vc/comparisons_per_delivery/N={n}", us,
                    rep.extras["comparisons_per_delivery"]))
        out.append((f"table1/vc/space_entries/N={n}", us,
                    rep.extras["space_entries_max"]))
    return out


def rows_vec(sizes=(1000, 10_000, 50_000), backend: str = "numpy",
             window: int | None = None):
    out = []
    for n in sizes:
        m_app = 32
        # --- PC-broadcast on the shared vec engine --------------------- #
        rep = run(_spec("pc", "windowed" if window else "vec", n, m_app,
                        k=6, backend=backend, window=window))
        assert rep.delivered_frac == 1.0
        us = rep.wall_seconds / max(rep.stats.deliveries, 1) * 1e6
        out.append((f"table1/pc/overhead_bytes/N={n}", us,
                    rep.extras["overhead_bytes_per_msg"]))
        # received-set entries: every process ends up knowing every id
        out.append((f"table1/pc/space_entries/N={n}", us, m_app))
        # the replaced analytic model, kept for contrast with measurement
        if rep.result.delivered is not None:
            mb, mc = vc_overhead_model(rep.result)
            out.append((f"table1/vc_model/overhead_bytes/N={n}", us, mb))
            out.append((f"table1/vc_model/comparisons_per_delivery/N={n}",
                        us, mc))

        # --- vector clocks, measured on the same scenario -------------- #
        rep = run(_spec("vc", "vec", n, m_app, k=6))
        assert rep.delivered_frac == 1.0
        us = rep.wall_seconds / max(rep.stats.deliveries, 1) * 1e6
        out.append((f"table1/vc/overhead_bytes/N={n}", us,
                    rep.extras["overhead_bytes_per_msg"]))
        out.append((f"table1/vc/comparisons_per_delivery/N={n}", us,
                    rep.extras["comparisons_per_delivery"]))
        out.append((f"table1/vc/space_entries/N={n}", us,
                    rep.extras["space_entries_max"]))
    return out


def rows(engine: str = "exact", n: int | None = None,
         backend: str = "numpy", window: int | None = None):
    if engine == "vec":
        return rows_vec((n,) if n is not None else (1000, 10_000, 50_000),
                        backend=backend, window=window)
    return rows_exact((n,) if n is not None else (50, 100, 200))


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--engine", choices=("exact", "vec"), default="exact")
    ap.add_argument("--n", type=int, default=None,
                    help="single population size (default: engine sweep)")
    ap.add_argument("--backend", choices=("numpy", "jax", "pallas", "auto"),
                    default="numpy")
    ap.add_argument("--window", type=int, default=None,
                    help="route the pc vec runs through the streaming "
                         "windowed engine with this many live columns")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    for name, us, derived in rows(args.engine, args.n, args.backend,
                                  args.window):
        print(f"{name},{us:.2f},{derived:.2f}")


if __name__ == "__main__":
    main()
