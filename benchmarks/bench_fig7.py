"""Fig. 7 reproduction: transmission-delay sweep on a dynamic overlay —
mean shortest path over safe links (PC) vs all links, and unsafe links /
buffered messages per process — through the one front door
(``repro.api.run``) on either engine.

Both engines now run the *same* churn scenario family (batched link
add/remove schedules racing app traffic), so the rows are directly
comparable:

  * ``exact`` — the discrete-event simulator at N=300 (default): every
    open/close flows through the real ``PCBroadcast`` processes, the run
    is oracle-checked, and the graph metrics come from a mid-run
    snapshot captured at the last churn round;
  * ``vec``   — the vectorized lockstep engine at N=50,000 (default):
    the same sweep at the population sizes the paper's scalability claim
    is about.  ``--window`` routes execution through the streaming
    windowed engine (O(N·window) memory).

Transmission delay maps to link delay in rounds; metrics are taken from
a state snapshot at the end of the churn window, where gating is
busiest.

CSV:  fig7/<metric>/delay=<d>,us_per_call,derived
"""

from __future__ import annotations

import argparse

from repro.api import (DynamicsSpec, MetricsSpec, RunSpec, TopologySpec,
                       TrafficSpec, WindowSpec, run)
from repro.obs import mean_shortest_path
from repro.core.vecsim import (full_out_mask, mean_shortest_path_vec,
                               safe_out_mask, unsafe_link_stats_vec)


def _spec(engine: str, n: int, k: int, delay: int, m_app: int, churn: int,
          backend: str = "numpy", window: int | None = None,
          oracle: bool = False) -> RunSpec:
    return RunSpec(
        protocol="pc", engine=engine, backend=backend, n=n,
        seed=3 + delay,
        topology=TopologySpec(kind="ring", k=k, max_delay=delay),
        traffic=TrafficSpec(kind="uniform", messages=m_app),
        dynamics=DynamicsSpec(kind="churn", n_adds=churn, n_rms=churn,
                              churn_window=16),
        window=WindowSpec(window=window),
        metrics=MetricsSpec(snapshot="last_churn", oracle=oracle))


def rows_exact(n: int = 300, m_app: int = 12, churn: int = 24):
    """The churn sweep on the event simulator: real processes, every
    open/close through Algorithm 2's ping phase, oracle-checked."""
    out = []
    for delay in (1, 2, 3, 4, 5):
        rep = run(_spec("exact", n, k=16, delay=delay, m_app=m_app,
                        churn=churn, oracle=True))
        assert rep.oracle.causal_ok and not rep.oracle.double_deliveries, \
            rep.oracle.summary()
        graphs = rep.result.snapshot_graphs
        srcs = list(range(0, n, max(1, n // 10)))
        sp_safe = mean_shortest_path(graphs["safe"], srcs,
                                     unreachable_penalty=float(n))
        sp_all = mean_shortest_path(graphs["full"], srcs,
                                    unreachable_penalty=float(n))
        unsafe, buffered, _ = graphs["unsafe"]
        wall = rep.wall_seconds * 1e6
        out.append((f"fig7/sp_safe/delay={delay}", wall, sp_safe))
        out.append((f"fig7/sp_all/delay={delay}", wall, sp_all))
        out.append((f"fig7/unsafe_links/delay={delay}", wall, unsafe))
        out.append((f"fig7/buffered_msgs/delay={delay}", wall, buffered))
    return out


def rows_vec(n: int = 50_000, backend: str = "numpy", m_app: int = 12,
             churn: int = 128, window: int | None = None):
    """The same sweep on the vectorized engine at large N.  ``window``
    routes execution through the streaming windowed engine; the snapshot
    then carries the live buffer and its ``is_app`` mask, which the
    metrics consume transparently."""
    out = []
    k = 17                    # ~ the paper's Fig. 7 links/process
    for delay in (1, 2, 3, 4, 5):
        rep = run(_spec("windowed" if window else "vec", n, k=k,
                        delay=delay, m_app=m_app, churn=churn,
                        backend=backend, window=window))
        assert rep.delivered_frac == 1.0, "vec run did not quiesce"
        snap = rep.result.snapshot
        snap_t = int(rep.scenario.add_round[-1])
        wall = rep.wall_seconds * 1e6
        srcs = list(range(0, n, max(1, n // 10)))
        sp_safe = mean_shortest_path_vec(
            snap["adj"], safe_out_mask(snap), srcs,
            unreachable_penalty=float(n))
        sp_all = mean_shortest_path_vec(
            snap["adj"], full_out_mask(snap), srcs,
            unreachable_penalty=float(n))
        unsafe, buffered, _ = unsafe_link_stats_vec(snap, snap_t,
                                                    rep.m_app)
        out.append((f"fig7/sp_safe/delay={delay}", wall, sp_safe))
        out.append((f"fig7/sp_all/delay={delay}", wall, sp_all))
        out.append((f"fig7/unsafe_links/delay={delay}", wall, unsafe))
        out.append((f"fig7/buffered_msgs/delay={delay}", wall, buffered))
    return out


def rows(engine: str = "exact", n: int | None = None,
         backend: str = "numpy", window: int | None = None):
    if engine == "vec":
        return rows_vec(n if n is not None else 50_000, backend=backend,
                        window=window)
    return rows_exact(n if n is not None else 300)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--engine", choices=("exact", "vec"), default="exact")
    ap.add_argument("--n", type=int, default=None,
                    help="processes (default: 300 exact / 50000 vec)")
    ap.add_argument("--backend", choices=("numpy", "jax", "pallas", "auto"),
                    default="numpy",
                    help="vec-engine backend (numpy is fastest on CPU; "
                         "jax is the accelerator/sharding path)")
    ap.add_argument("--window", type=int, default=None,
                    help="run the vec sweep through the streaming "
                         "windowed engine with this many live columns")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    for name, us, derived in rows(args.engine, args.n, args.backend,
                                  args.window):
        print(f"{name},{us:.0f},{derived:.3f}")


if __name__ == "__main__":
    main()
