"""Million-process scale benchmark for the device-sharded engine.

Drives a sustained-traffic run at N ≥ 1M processes through
``repro.api.run`` with ``engine="sharded"`` — the population regime the
paper's constant-size control information exists for, and two orders of
magnitude past the single-host engines (the monolithic engine caps near
N ≈ 100k; the windowed engine holds the traffic axis but still keeps
every (N, W) plane on one device).  The process axis is partitioned
over a ``shard_map`` device mesh; on CPU the mesh comes from forced
host platform devices, which this script sets up itself (the flag must
precede jax initialization)::

    PYTHONPATH=src python benchmarks/bench_scale.py \
        --n 1048576 --devices 4 --messages 512 --rate 4 --window 128

Measurement: the run always profiles per segment (``shard.profile``).
The first segment of each distinct segment program (the bit-packed fast
body and the generic scanned body compile separately) is the *warmup*
segment — its wall time includes jit tracing and XLA compilation — so
the headline throughput (``sends_per_sec_steady``) is recomputed from
the steady-state segments only, with the compile cost reported
separately as ``compile_s``.  The naive whole-run rate stays in the
JSON as ``sends_per_sec`` for comparability with older snapshots.

Reports simulated broadcasts/s and message-copies (sends)/s of wall
clock, delivered fraction, mean delivery latency, the live-column
high-water mark, and the per-device buffer bytes the window pinned.
Writes everything to ``BENCH_scale.json`` (``--out``), optionally a
per-segment host/device timing artifact (``--segments-out``), and
prints the usual ``name,us_per_call,derived`` CSV rows.  CI regression
floor: ``--assert-floor 0.3 --floor-ref BENCH_scale.json`` fails the
run when steady throughput drops more than 30% below the committed
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


def _steady_state(seg_profile, series):
    """Split the profiled segments into warmup and steady state.

    Returns ``(compile_s, steady_s, steady_sends, segments)`` where
    ``segments`` is the JSON-ready per-segment breakdown: round bounds,
    which body ran, the four wall components, and the send count the
    segment's rounds produced (from the per-round series, so the split
    never changes the totals)."""
    segments = []
    for p in seg_profile:
        sent = int(series[p["lo"]:p["hi"], 1:4].sum())
        wall = (p["stage_s"] + p["dispatch_s"] + p["block_s"]
                + p["retire_s"])
        segments.append(dict(p, sends=sent, wall_s=wall))
    warm_idx = {}
    for i, s in enumerate(segments):
        warm_idx.setdefault(s["fast"], i)
    warm = set(warm_idx.values())
    steady = [s for i, s in enumerate(segments) if i not in warm]
    steady_s = sum(s["wall_s"] for s in steady)
    steady_sends = sum(s["sends"] for s in steady)
    # compile estimate: how much longer each kind's first segment took
    # than that kind's median steady segment
    compile_s = 0.0
    for kind, i in warm_idx.items():
        peers = sorted(s["wall_s"] for s in steady if s["fast"] == kind)
        if peers:
            compile_s += max(0.0, segments[i]["wall_s"]
                             - peers[len(peers) // 2])
        else:
            compile_s += segments[i]["wall_s"]
    for i, s in enumerate(segments):
        s["warmup"] = i in warm
    return compile_s, steady_s, steady_sends, segments


def run_point(n: int, devices: int, messages: int, rate: float,
              window: int, k: int, topology: str, traffic: str,
              seg_len: int, horizon: int | None, max_delay: int,
              seed: int, scan: str = "auto", obs=None) -> dict:
    from dataclasses import replace

    from repro.api import (ObsSpec, RunSpec, ShardSpec, TopologySpec,
                           TrafficSpec, WindowSpec, build_scenario, run)
    from repro.core.vecsim.shard import pad_rows

    spec = RunSpec(
        protocol="pc", engine="sharded", n=n, seed=seed,
        shard=ShardSpec(devices=devices, scan=scan, profile=True),
        topology=TopologySpec(kind=topology, k=k, max_delay=max_delay),
        traffic=TrafficSpec(kind=traffic, rate=rate, messages=messages),
        window=WindowSpec(window=window, seg_len=seg_len, horizon=horizon,
                          collect="aggregate"),
        # throughput microbench: telemetry off by default so the
        # committed floor keeps measuring the bare engine (the
        # obs-overhead bench measures both sides explicitly)
        obs=obs if obs is not None else ObsSpec(histograms=False))
    t0 = time.perf_counter()
    scn = build_scenario(spec.validate())
    build_s = time.perf_counter() - t0
    # hand the prebuilt scenario back so the report's wall clock is pure
    # engine time, with the build cost reported separately
    rep = run(replace(spec, scenario=scn))
    res, run_s = rep.result, rep.wall_seconds
    if horizon is None:
        # without a horizon the engine is exact: anything less than full
        # delivery is a correctness regression, not a number
        assert not res.expired.any(), "columns expired without a horizon"
        assert rep.delivered_frac == 1.0, \
            f"sharded run did not quiesce ({rep.delivered_frac:.6f})"
    compile_s, steady_s, steady_sends, segments = _steady_state(
        res.seg_profile, res.series)
    n_pad = pad_rows(n, res.n_devices)
    buffer_bytes = 2 * n_pad * window * 4          # arr + delivered, int32
    point = dict(
        n=n, devices=res.n_devices, k=k, messages=messages, rate=rate,
        window=window, topology=topology, traffic=traffic,
        seg_len=seg_len, horizon=horizon, scan=rep.extras["scan"],
        rounds=scn.rounds,
        build_seconds=round(build_s, 3),
        run_seconds=round(run_s, 3),
        compile_s=round(compile_s, 3),
        steady_run_seconds=round(steady_s, 3),
        msgs_per_sec=round(messages / run_s, 1),
        sends=res.stats.sent_messages,
        sends_per_sec=round(res.stats.sent_messages / run_s, 1),
        steady_sends=steady_sends,
        sends_per_sec_steady=round(steady_sends / steady_s, 1)
        if steady_s > 0 else None,
        deliveries=res.stats.deliveries,
        delivered_frac=round(rep.delivered_frac, 6),
        mean_latency_rounds=round(rep.mean_latency, 3),
        peak_live=res.peak_live,
        expired=int(res.expired.sum()),
        window_buffer_bytes=buffer_bytes,
        buffer_bytes_per_device=buffer_bytes // res.n_devices,
    )
    return point, segments


def steady_rate(point: dict) -> float:
    """The comparable throughput of a bench point: steady-state when
    recorded, the whole-run rate for pre-S2 snapshots."""
    rate = point.get("sends_per_sec_steady")
    return float(rate if rate else point["sends_per_sec"])


def rows(n: int = 1 << 20, devices: int = 4, messages: int = 512,
         rate: float = 4.0, window: int = 128, k: int = 4,
         topology: str = "kregular", traffic: str = "poisson",
         seg_len: int = 32, horizon: int | None = None,
         max_delay: int = 1, seed: int = 0, out: str | None = None,
         scan: str = "auto", segments_out: str | None = None):
    point, segments = run_point(n, devices, messages, rate, window, k,
                                topology, traffic, seg_len, horizon,
                                max_delay, seed, scan)
    if out:
        from repro.obs.report import write_bench_report
        write_bench_report(out, "scale", point)
    if segments_out:
        with open(segments_out, "w") as fh:
            json.dump(dict(n=n, devices=point["devices"],
                           seg_len=seg_len, scan=point["scan"],
                           segments=segments), fh, indent=2)
    us = point["run_seconds"] * 1e6
    tag = f"n={n},d={point['devices']}"
    return point, [
        (f"scale/msgs_per_sec/{tag}", us, point["msgs_per_sec"]),
        (f"scale/sends_per_sec/{tag}", us, point["sends_per_sec"]),
        (f"scale/sends_per_sec_steady/{tag}", us, steady_rate(point)),
        (f"scale/compile_s/{tag}", us, point["compile_s"]),
        (f"scale/delivered_frac/{tag}", us, point["delivered_frac"]),
        (f"scale/latency_rounds/{tag}", us, point["mean_latency_rounds"]),
        (f"scale/peak_live/{tag}", us, float(point["peak_live"])),
        (f"scale/buffer_mb_per_device/{tag}", us,
         point["buffer_bytes_per_device"] / 2 ** 20),
    ]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=1 << 20,
                    help="processes (default 1,048,576)")
    ap.add_argument("--devices", type=int, default=4,
                    help="device-mesh size the process axis shards over")
    ap.add_argument("--no-force-host", action="store_true",
                    help="do not force host platform devices (use this "
                         "on a real accelerator mesh)")
    ap.add_argument("--messages", type=int, default=512)
    ap.add_argument("--rate", type=float, default=4.0,
                    help="mean broadcasts per lockstep round")
    ap.add_argument("--window", type=int, default=128,
                    help="live message columns "
                         "(memory = 8·N·window bytes across the mesh)")
    ap.add_argument("--k", type=int, default=4, help="out-links per process")
    ap.add_argument("--topology", choices=("kregular", "ring", "smallworld"),
                    default="kregular")
    ap.add_argument("--traffic", choices=("poisson", "bursty"),
                    default="poisson")
    ap.add_argument("--seg-len", type=int, default=32,
                    help="rounds per jitted segment between retirements")
    ap.add_argument("--horizon", type=int, default=None,
                    help="force-retire columns older than this many rounds")
    ap.add_argument("--max-delay", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scan", choices=("auto", "on", "off"), default="auto",
                    help="segment stepping: one lax.scan per segment (on, "
                         "the auto default) vs per-round dispatch (off)")
    ap.add_argument("--out", default="BENCH_scale.json")
    ap.add_argument("--segments-out", default=None,
                    help="also write the per-segment host/device timing "
                         "breakdown (CI artifact)")
    ap.add_argument("--assert-floor", type=float, default=None,
                    metavar="FRAC",
                    help="fail if steady sends/s drops more than FRAC "
                         "below the --floor-ref snapshot (e.g. 0.3)")
    ap.add_argument("--floor-ref", default="BENCH_scale.json",
                    help="committed reference snapshot for --assert-floor")
    args = ap.parse_args()
    # the forced-host-device flag must land before jax initializes, so
    # it happens here, ahead of any repro.api import
    if not args.no_force_host and args.devices > 1:
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
        ref = load_bench_report(args.floor_ref, kind="scale")
    point, csv = rows(args.n, args.devices, args.messages, args.rate,
                      args.window, args.k, args.topology, args.traffic,
                      args.seg_len, args.horizon, args.max_delay,
                      args.seed, args.out, args.scan, args.segments_out)
    for name, us, derived in csv:
        print(f"{name},{us:.0f},{derived:.3f}")
    if ref is not None:
        # sends/s is work-per-wall-second, so it compares across N; the
        # slack fraction absorbs host noise and working-set effects
        floor = (1.0 - args.assert_floor) * steady_rate(ref)
        got = steady_rate(point)
        if got < floor:
            print(f"FLOOR VIOLATION: steady sends/s {got:.0f} < "
                  f"{floor:.0f} ({(1 - args.assert_floor) * 100:.0f}% of "
                  f"reference {steady_rate(ref):.0f})", file=sys.stderr)
            sys.exit(1)
        print(f"floor ok: steady sends/s {got:.0f} >= {floor:.0f}")


if __name__ == "__main__":
    main()
