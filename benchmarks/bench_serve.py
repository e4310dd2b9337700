"""Serving-capacity benchmark: the offered-rate vs p99-SLO knee.

Sweeps an open-loop offered rate (submissions per simulated round)
through ``repro.api`` live mode and reports, per rate point, the
measured rounds-to-delivery percentiles (queueing delay included), the
sustained wall-clock requests/s, and whether the p99 met the SLO.  The
*knee* — the highest offered rate whose p99 still meets the SLO — is
the headline: ``capacity_rate`` (simulated load the service can absorb)
and ``capacity_req_per_s`` (the wall-clock ingest rate it sustained
there).  On a multi-device mesh the process axis shards exactly as in
``bench_scale``; forced host devices are set up here when needed::

    PYTHONPATH=src python benchmarks/bench_serve.py \
        --n 65536 --devices 4 --messages 20000 --rates 4,8,16,32

Writes ``BENCH_serve.json`` (``--out``) and prints the usual
``name,us_per_call,derived`` CSV rows.  CI regression floor:
``--assert-floor 0.5 --floor-ref BENCH_serve.json`` fails the run when
the knee's sustained requests/s drops more than 50% below the committed
reference on the same host class.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))


def run_point(n: int, devices: int | None, engine: str, scan: str,
              arrivals: str, admission: str, rate: float, messages: int,
              window: int | None, queue_cap: int, seg_len: int,
              slo_p99: float, k: int, topology: str, max_delay: int,
              seed: int, period: int, duty: float,
              rate_lo: float | None = None) -> dict:
    from repro.api import (LiveSpec, RunSpec, ShardSpec, TopologySpec,
                           WindowSpec, run)

    spec = RunSpec(
        protocol="pc", mode="live", engine=engine, n=n, seed=seed,
        shard=ShardSpec(devices=devices, scan=scan),
        topology=TopologySpec(kind=topology, k=k, max_delay=max_delay),
        window=WindowSpec(window=window, seg_len=seg_len,
                          collect="aggregate"),
        live=LiveSpec(arrivals=arrivals, admission=admission, rate=rate,
                      messages=messages, queue_cap=queue_cap,
                      slo_p99=slo_p99, period=period, duty=duty,
                      rate_lo=rate_lo))
    rep = run(spec)
    lr = rep.live
    assert lr.admitted + lr.shed_queue + lr.shed_policy \
        + lr.unserved == lr.offered, "serve accounting leak"
    return dict(
        rate=rate, offered=lr.offered, admitted=lr.admitted,
        shed=lr.shed_queue + lr.shed_policy, unserved=lr.unserved,
        rounds=lr.rounds, ticks=lr.ticks_run,
        engine=rep.engine, window=rep.window,
        wall_seconds=round(lr.wall_seconds, 3),
        req_per_s=round(lr.requests_per_sec, 1),
        p50=round(lr.p50, 2), p99=round(lr.p99, 2),
        p999=round(lr.p999, 2),
        mean_latency_rounds=round(lr.mean_latency_rounds, 2),
        queue_peak=lr.queue_peak,
        backpressure_ticks=lr.backpressure_ticks,
        overflow_catches=lr.overflow_catches,
        delivered_frac=round(lr.delivered_frac, 6),
        slo_ok=bool(lr.slo_ok),
    )


def capacity(doc: dict) -> float:
    """The comparable headline of a bench snapshot: sustained wall-clock
    requests/s at the knee (0.0 when no rate point met the SLO)."""
    return float(doc.get("capacity_req_per_s") or 0.0)


def rows(n: int = 1 << 16, devices: int | None = None,
         engine: str = "auto", scan: str = "auto",
         arrivals: str = "poisson", admission: str = "defer",
         rates: tuple = (4.0, 8.0, 16.0, 32.0), messages: int = 20000,
         window: int | None = None, queue_cap: int = 1 << 16,
         seg_len: int = 32, slo_p99: float = 256.0, k: int = 4,
         topology: str = "kregular", max_delay: int = 1, seed: int = 0,
         period: int = 256, duty: float = 0.25,
         rate_lo: float | None = None, out: str | None = None):
    points = []
    for rate in rates:
        t0 = time.perf_counter()
        p = run_point(n, devices, engine, scan, arrivals, admission,
                      rate, messages, window, queue_cap, seg_len,
                      slo_p99, k, topology, max_delay, seed, period,
                      duty, rate_lo)
        p["point_seconds"] = round(time.perf_counter() - t0, 3)
        points.append(p)
    ok = [p for p in points if p["slo_ok"]]
    knee = max(ok, key=lambda p: p["rate"]) if ok else None
    eng = points[0]["engine"]
    doc = dict(
        n=n,
        devices=(devices if devices is not None
                 else ("all" if eng == "sharded" else 1)),
        engine=eng, arrivals=arrivals,
        admission=admission, messages=messages, slo_p99=slo_p99,
        period=period, duty=duty, rate_lo=rate_lo,
        seg_len=seg_len, window=points[0]["window"],
        capacity_rate=knee["rate"] if knee else None,
        capacity_req_per_s=knee["req_per_s"] if knee else None,
        capacity_p99=knee["p99"] if knee else None,
        points=points)
    if out:
        from repro.obs.report import write_bench_report
        write_bench_report(out, "serve", doc)
    csv = []
    for p in points:
        tag = f"n={n},rate={p['rate']:g}"
        us = p["wall_seconds"] * 1e6
        csv += [(f"serve/p99_rounds/{tag}", us, p["p99"]),
                (f"serve/req_per_s/{tag}", us, p["req_per_s"]),
                (f"serve/slo_ok/{tag}", us, float(p["slo_ok"]))]
    csv.append((f"serve/capacity_req_per_s/n={n}",
                sum(p["wall_seconds"] for p in points) * 1e6,
                capacity(doc)))
    return doc, csv


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=1 << 16,
                    help="processes (default 65,536)")
    ap.add_argument("--devices", type=int, default=None,
                    help="device-mesh size (engine 'sharded'); default: "
                         "single host, engine auto-selected")
    ap.add_argument("--no-force-host", action="store_true",
                    help="do not force host platform devices (use this "
                         "on a real accelerator mesh)")
    ap.add_argument("--engine", choices=("auto", "windowed", "sharded"),
                    default="auto")
    ap.add_argument("--scan", choices=("auto", "on", "off"),
                    default="auto")
    ap.add_argument("--arrivals", default="poisson",
                    help="arrival process (poisson | bursty | diurnal)")
    ap.add_argument("--admission", default="defer",
                    help="admission policy (defer | shed | admit)")
    ap.add_argument("--rates", default="4,8,16,32",
                    help="comma-separated offered rates (msgs per "
                         "simulated round) to sweep")
    ap.add_argument("--messages", type=int, default=20000,
                    help="submissions offered per rate point")
    ap.add_argument("--window", type=int, default=None,
                    help="live columns; default: memory-budget rule")
    ap.add_argument("--queue-cap", type=int, default=1 << 16)
    ap.add_argument("--seg-len", type=int, default=32)
    ap.add_argument("--slo-p99", type=float, default=256.0,
                    help="p99 rounds-to-delivery SLO defining the knee")
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--topology",
                    choices=("kregular", "ring", "smallworld"),
                    default="kregular")
    ap.add_argument("--max-delay", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--period", type=int, default=256,
                    help="bursty/diurnal period in rounds")
    ap.add_argument("--duty", type=float, default=0.25,
                    help="bursty high-rate fraction of each period")
    ap.add_argument("--rate-lo", type=float, default=None,
                    help="bursty baseline rate (default: rate / 8)")
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--assert-floor", type=float, default=None,
                    metavar="FRAC",
                    help="fail if the knee's requests/s drops more than "
                         "FRAC below the --floor-ref snapshot")
    ap.add_argument("--floor-ref", default="BENCH_serve.json",
                    help="committed reference snapshot for --assert-floor")
    args = ap.parse_args()
    # forced host devices must land before jax initializes
    if not args.no_force_host and (args.devices or 1) > 1:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.devices}").strip()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ref = None
    if args.assert_floor is not None:
        # read the reference before --out can overwrite the same file
        from repro.obs.report import load_bench_report
        ref = load_bench_report(args.floor_ref, kind="serve")
    rates = tuple(float(r) for r in args.rates.split(","))
    doc, csv = rows(args.n, args.devices, args.engine, args.scan,
                    args.arrivals, args.admission, rates, args.messages,
                    args.window, args.queue_cap, args.seg_len,
                    args.slo_p99, args.k, args.topology, args.max_delay,
                    args.seed, args.period, args.duty, args.rate_lo,
                    args.out)
    for name, us, derived in csv:
        print(f"{name},{us:.0f},{derived:.3f}")
    if doc["capacity_rate"] is None:
        print("warning: no rate point met the SLO", file=sys.stderr)
    if ref is not None:
        floor = (1.0 - args.assert_floor) * capacity(ref)
        got = capacity(doc)
        if got < floor:
            print(f"FLOOR VIOLATION: capacity req/s {got:.0f} < "
                  f"{floor:.0f} ({(1 - args.assert_floor) * 100:.0f}% of "
                  f"reference {capacity(ref):.0f})", file=sys.stderr)
            sys.exit(1)
        print(f"floor ok: capacity req/s {got:.0f} >= {floor:.0f}")


if __name__ == "__main__":
    main()
