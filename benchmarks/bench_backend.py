"""jax-vs-pallas backend comparison on the vecsim hot path.

Runs the identical windowed sustained ``RunSpec`` once per backend
(jax, then pallas) through ``repro.api.run``, checks the two runs agree
on the protocol numbers (sends, deliveries, delivered fraction — the
byte-identity the test suite asserts in full), and records rounds/sec
and messages/sec side by side in ``BENCH_backend.json``.

Pallas executes in interpret mode — byte-identical but paying the
interpreter's lowering overhead — so the comparison documents the cost
of the testing path, not a speedup.  The kernels do not compile for the
TPU (DESIGN.md §2.6), where the pallas backend is refused.
``pallas_mode`` in the JSON is the availability probe's note.

    PYTHONPATH=src python benchmarks/bench_backend.py \
        --n 2048 --messages 4096 --rate 64 --window 512 \
        --out BENCH_backend.json

``--smoke`` shrinks the point for CI (the kernel-smoke job runs it on
every push).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))

BACKENDS = ("jax", "pallas")


def run_point(backend: str, scn, spec) -> dict:
    from dataclasses import replace

    from repro.api import run

    rep = run(replace(spec, backend=backend, scenario=scn))
    res, wall = rep.result, rep.wall_seconds
    return dict(
        backend=rep.backend, rounds=scn.rounds,
        run_seconds=round(wall, 3),
        rounds_per_sec=round(scn.rounds / wall, 1),
        msgs_per_sec=round(scn.m_app / wall, 1),
        sends=res.stats.sent_messages,
        deliveries=res.stats.deliveries,
        delivered_frac=round(rep.delivered_frac, 6),
        peak_live=res.peak_live,
    )


def rows(n: int = 2048, messages: int = 4096, rate: float = 64.0,
         window: int = 512, k: int = 6, seg_len: int = 8,
         max_delay: int = 1, seed: int = 0, out: str | None = None):
    from repro.api import (BACKENDS as BACKEND_REGISTRY, RunSpec,
                           TopologySpec, TrafficSpec, WindowSpec,
                           build_scenario)

    spec = RunSpec(
        protocol="pc", engine="windowed", n=n, seed=seed,
        topology=TopologySpec(kind="kregular", k=k, max_delay=max_delay),
        traffic=TrafficSpec(kind="poisson", rate=rate, messages=messages),
        window=WindowSpec(window=window, seg_len=seg_len,
                          collect="aggregate"))
    t0 = time.perf_counter()
    scn = build_scenario(spec.validate())
    build_s = time.perf_counter() - t0
    points = [run_point(backend, scn, spec) for backend in BACKENDS]
    jaxp, palp = points
    # the backends must tell the same protocol story before their wall
    # clocks are worth comparing
    for key in ("sends", "deliveries", "delivered_frac", "peak_live"):
        assert jaxp[key] == palp[key], (key, jaxp[key], palp[key])
    ok, note = BACKEND_REGISTRY.get("pallas").probe()
    doc = dict(
        n=n, k=k, messages=messages, rate=rate, window=window,
        seg_len=seg_len, rounds=scn.rounds,
        build_seconds=round(build_s, 3),
        pallas_available=ok, pallas_mode=note,
        points=points,
        pallas_vs_jax_speedup=round(
            jaxp["run_seconds"] / palp["run_seconds"], 3),
    )
    if out:
        from repro.obs.report import write_bench_report
        write_bench_report(out, "backend", doc)
    tag = f"n={n},m={messages},w={window}"
    out_rows = []
    for point in points:
        us = point["run_seconds"] * 1e6
        out_rows += [
            (f"backend/{point['backend']}/rounds_per_sec/{tag}", us,
             point["rounds_per_sec"]),
            (f"backend/{point['backend']}/msgs_per_sec/{tag}", us,
             point["msgs_per_sec"]),
        ]
    out_rows.append((f"backend/pallas_vs_jax_speedup/{tag}",
                     palp["run_seconds"] * 1e6,
                     doc["pallas_vs_jax_speedup"]))
    return out_rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=2048)
    ap.add_argument("--messages", type=int, default=4096)
    ap.add_argument("--rate", type=float, default=64.0,
                    help="mean broadcasts per lockstep round")
    ap.add_argument("--window", type=int, default=512,
                    help="live message columns (memory = 8·N·window bytes)")
    ap.add_argument("--k", type=int, default=6, help="out-links per process")
    ap.add_argument("--seg-len", type=int, default=8,
                    help="rounds per jitted segment between retirements")
    ap.add_argument("--max-delay", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized point (seconds, not minutes)")
    ap.add_argument("--out", default="BENCH_backend.json")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.smoke:
        args.n, args.messages, args.rate = 256, 512, 16.0
        args.window = 128
    for name, us, derived in rows(args.n, args.messages, args.rate,
                                  args.window, args.k, args.seg_len,
                                  args.max_delay, args.seed, args.out):
        print(f"{name},{us:.0f},{derived:.3f}")


if __name__ == "__main__":
    main()
