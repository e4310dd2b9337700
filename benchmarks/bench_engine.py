"""Scalability beyond the paper: Python event core vs tensorized JAX
engine (per-round cell-update throughput), N up to 10k on one CPU core.

CSV:  engine/<impl>/N=<n>,us_per_call(run),derived(M cell-rounds/s)
"""

from __future__ import annotations

import time

import numpy as np

from repro.core import BoundedPCBroadcast, Network, ring_plus_random
from repro.core.engine import analyze, random_instance, run_engine


def python_core(n: int, n_bcast: int = 16):
    net = Network(seed=1, default_delay=1.0, oob_delay=0.5)
    for pid in range(n):
        net.add_process(BoundedPCBroadcast(pid, ping_mode="route"))
    ring_plus_random(net, range(n), k=8)
    t0 = time.perf_counter()
    for i in range(n_bcast):
        net.procs[(i * 7) % n].broadcast(("m", i))
        net.run(until=net.time + 1.0)
    net.run()
    dt = time.perf_counter() - t0
    # normalize to the same unit as the engine: process x msg x round
    rounds = max(1, int(net.time))
    cell_rounds = n * n_bcast * rounds
    return dt, cell_rounds / dt / 1e6


def jax_engine(n: int, m: int = 64, rounds: int = 64):
    cfg, sched, adj0, delay0 = random_instance(
        5, n=n, k=8, m_app=m, n_adds=24, n_rms=24, rounds=rounds,
        mode="pc")
    run_engine(cfg, sched, adj0, delay0)          # compile
    t0 = time.perf_counter()
    d = run_engine(cfg, sched, adj0, delay0)
    dt = time.perf_counter() - t0
    rep = analyze(d, sched)
    assert rep["violations"] == 0
    cell_rounds = n * sched.m_total * rounds
    return dt, cell_rounds / dt / 1e6


def rows():
    out = []
    for n in (500, 2000):
        dt, thr = python_core(n)
        out.append((f"engine/python/N={n}", dt * 1e6, thr))
    for n in (2000, 10_000):
        dt, thr = jax_engine(n)
        out.append((f"engine/jax/N={n}", dt * 1e6, thr))
    return out


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    for name, us, derived in rows():
        print(f"{name},{us:.0f},{derived:.2f}")


if __name__ == "__main__":
    main()
