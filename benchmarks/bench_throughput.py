"""Sustained-throughput benchmark for the streaming windowed engine.

Drives the windowed engine through ``repro.api.run`` (a sustained
Poisson/bursty ``RunSpec`` with ``engine="windowed"``) and measures how
much causal broadcast one host can push through a fixed O(N·window) memory
budget — the throughput-scalability axis the monolithic (N, M_total)
engine cannot reach (1M broadcasts at N=10k would need an 80 GB dense
matrix; the window holds it in a few hundred MB).

Reports simulated broadcasts/sec and message-copies (sends)/sec of wall
clock, delivered fraction, mean delivery latency in rounds, the live-
column high-water mark, and the exact buffer bytes the window pinned.
Writes everything to ``BENCH_throughput.json`` (``--out``) and prints
the usual ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python benchmarks/bench_throughput.py \
        --n 10000 --messages 1000000 --rate 1000 --window 16384 \
        --backend jax --out BENCH_throughput.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))


def run_point(n: int, messages: int, rate: float, window: int, k: int,
              backend: str, topology: str, traffic: str, seg_len: int,
              horizon: int | None, max_delay: int, seed: int) -> dict:
    from dataclasses import replace

    from repro.api import (RunSpec, TopologySpec, TrafficSpec, WindowSpec,
                           build_scenario, run)

    spec = RunSpec(
        protocol="pc", engine="windowed", backend=backend, n=n, seed=seed,
        topology=TopologySpec(kind=topology, k=k, max_delay=max_delay),
        traffic=TrafficSpec(kind=traffic, rate=rate, messages=messages),
        window=WindowSpec(window=window, seg_len=seg_len, horizon=horizon,
                          collect="aggregate"))
    t0 = time.perf_counter()
    scn = build_scenario(spec.validate())
    build_s = time.perf_counter() - t0
    # hand the prebuilt scenario back so the report's wall clock is pure
    # engine time, with the build cost reported separately
    rep = run(replace(spec, scenario=scn))
    res, run_s = rep.result, rep.wall_seconds
    if horizon is None:
        # without a horizon the windowed engine is exact: anything less
        # than full delivery is a correctness regression, not a number
        assert not res.expired.any(), "columns expired without a horizon"
        assert rep.delivered_frac == 1.0, \
            f"windowed run did not quiesce ({rep.delivered_frac:.6f})"
    buffer_bytes = 2 * n * window * 4          # arr + delivered, int32
    return dict(
        n=n, k=k, messages=messages, rate=rate, window=window,
        backend=rep.backend, topology=topology, traffic=traffic,
        seg_len=seg_len, horizon=horizon, rounds=scn.rounds,
        build_seconds=round(build_s, 3),
        run_seconds=round(run_s, 3),
        msgs_per_sec=round(messages / run_s, 1),
        sends=res.stats.sent_messages,
        sends_per_sec=round(res.stats.sent_messages / run_s, 1),
        deliveries=res.stats.deliveries,
        delivered_frac=round(rep.delivered_frac, 6),
        mean_latency_rounds=round(rep.mean_latency, 3),
        peak_live=res.peak_live,
        expired=int(res.expired.sum()),
        window_buffer_bytes=buffer_bytes,
    )


def rows(n: int = 5000, messages: int = 100_000, rate: float = 500.0,
         window: int = 8192, k: int = 8, backend: str = "auto",
         topology: str = "kregular", traffic: str = "poisson",
         seg_len: int = 8, horizon: int | None = None, max_delay: int = 1,
         seed: int = 0, out: str | None = None):
    point = run_point(n, messages, rate, window, k, backend, topology,
                      traffic, seg_len, horizon, max_delay, seed)
    if out:
        from repro.obs.report import write_bench_report
        write_bench_report(out, "throughput", point)
    us = point["run_seconds"] * 1e6
    tag = f"n={n},m={messages}"
    return [
        (f"throughput/msgs_per_sec/{tag}", us, point["msgs_per_sec"]),
        (f"throughput/sends_per_sec/{tag}", us, point["sends_per_sec"]),
        (f"throughput/delivered_frac/{tag}", us, point["delivered_frac"]),
        (f"throughput/latency_rounds/{tag}", us, point["mean_latency_rounds"]),
        (f"throughput/peak_live/{tag}", us, float(point["peak_live"])),
        (f"throughput/buffer_mb/{tag}", us,
         point["window_buffer_bytes"] / 2 ** 20),
    ]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=5000)
    ap.add_argument("--messages", type=int, default=100_000)
    ap.add_argument("--rate", type=float, default=500.0,
                    help="mean broadcasts per lockstep round")
    ap.add_argument("--window", type=int, default=8192,
                    help="live message columns (memory = 8·N·window bytes)")
    ap.add_argument("--k", type=int, default=8, help="out-links per process")
    ap.add_argument("--backend", choices=("numpy", "jax", "pallas", "auto"),
                    default="auto",
                    help="jax is the fast path for sustained runs: the "
                    "jitted segment scan fuses the per-round masks that "
                    "dominate at large N·window")
    ap.add_argument("--topology", choices=("kregular", "ring", "smallworld"),
                    default="kregular")
    ap.add_argument("--traffic", choices=("poisson", "bursty"),
                    default="poisson")
    ap.add_argument("--seg-len", type=int, default=8,
                    help="rounds per jitted segment between retirements")
    ap.add_argument("--horizon", type=int, default=None,
                    help="force-retire columns older than this many rounds")
    ap.add_argument("--max-delay", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_throughput.json")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    for name, us, derived in rows(args.n, args.messages, args.rate,
                                  args.window, args.k, args.backend,
                                  args.topology, args.traffic, args.seg_len,
                                  args.horizon, args.max_delay, args.seed,
                                  args.out):
        print(f"{name},{us:.0f},{derived:.3f}")


if __name__ == "__main__":
    main()
