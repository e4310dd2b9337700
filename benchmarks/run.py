"""Benchmark harness — one module per paper table/figure plus the
engine bench.  Prints ``name,us_per_call,derived`` CSV.

``--engine exact`` (default) runs the paper-scale reproductions on the
discrete-event simulator; ``--engine vec`` runs the Table 1 / Fig. 7
sweeps on the vectorized lockstep engine at large N (``--n`` overrides
the population) plus the sustained-throughput bench of the streaming
windowed engine; ``--engine both`` runs the two back to back.
``--window`` routes every vec-engine sweep through the streaming
windowed engine with that many live columns.  ``--scale-devices D``
additionally runs a harness-sized point of the device-sharded scale
bench (``bench_scale``) on a D-device mesh.  The jitted-engine bench
(``bench_engine``) is independent of ``--engine`` and always runs.  All
protocol benches dispatch through ``repro.api.run`` (one spec, one front
door).
"""

from __future__ import annotations

import argparse
import os
import sys
import traceback

# allow `python benchmarks/run.py` from the repo root
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--engine", choices=("exact", "vec", "both"),
                    default="exact")
    ap.add_argument("--n", type=int, default=None,
                    help="population override for the protocol benches")
    ap.add_argument("--backend", choices=("numpy", "jax", "pallas", "auto"),
                    default="numpy", help="vec-engine backend")
    ap.add_argument("--compare-backends", action="store_true",
                    help="also run a harness-sized jax-vs-pallas point "
                         "(full run: benchmarks/bench_backend.py)")
    ap.add_argument("--window", type=int, default=None,
                    help="route the vec sweeps (and the throughput "
                         "bench) through the streaming windowed engine "
                         "with this many live columns")
    ap.add_argument("--scale-devices", type=int, default=None,
                    help="also run a harness-sized sharded scale point "
                         "on this many devices (forces host platform "
                         "devices; full run: benchmarks/bench_scale.py)")
    ap.add_argument("--serve", action="store_true",
                    help="also run a harness-sized live-serving capacity "
                         "sweep (open-loop ingest; honors --scale-devices "
                         "and --scan; full run: benchmarks/bench_serve.py)")
    ap.add_argument("--scan", choices=("auto", "on", "off"), default="auto",
                    help="sharded segment stepping for --serve/"
                         "--scale-devices points")
    args = ap.parse_args()
    if args.scale_devices and args.engine == "exact":
        print("warning: --scale-devices runs with the vec benches only; "
              "pass --engine vec or --engine both", file=sys.stderr)
    if args.scale_devices and args.scale_devices > 1:
        # must precede jax initialization (the bench modules import jax
        # lazily, so setting it here is early enough from the CLI)
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.scale_devices}").strip()
    # imported after the device-count env var so it precedes jax init
    from benchmarks import bench_backend, bench_engine, bench_fig7, \
        bench_scale, bench_serve, bench_table1, bench_throughput
    engines = ("exact", "vec") if args.engine == "both" else (args.engine,)

    print("name,us_per_call,derived")
    failed = 0
    for eng in engines:
        # keep the historical row names in single-engine runs; disambiguate
        # with an engine prefix only when both engines emit the same rows
        prefix = f"{eng}/" if len(engines) > 1 else ""
        # in "both" mode a large --n meant for the vec engine would drive
        # the event simulator far past its ~2k ceiling — vec only there
        n = args.n if (eng == "vec" or len(engines) == 1) else None
        for mod in (bench_table1, bench_fig7):
            try:
                for name, us, derived in mod.rows(engine=eng, n=n,
                                                  backend=args.backend,
                                                  window=args.window):
                    print(f"{prefix}{name},{us:.2f},{derived:.3f}",
                          flush=True)
            except Exception:                  # noqa: BLE001
                failed += 1
                traceback.print_exc()
        if eng == "vec":
            # sustained throughput is windowed-engine-specific: a
            # harness-sized point (the nightly CI smoke runs the big one)
            try:
                for name, us, derived in bench_throughput.rows(
                        n=args.n if args.n is not None else 2000,
                        messages=20_000, rate=200.0,
                        window=args.window if args.window else 4096,
                        backend=args.backend, seg_len=8, out=None):
                    print(f"{prefix}{name},{us:.2f},{derived:.3f}",
                          flush=True)
            except Exception:                  # noqa: BLE001
                failed += 1
                traceback.print_exc()
        if eng == "vec" and args.compare_backends:
            try:
                for name, us, derived in bench_backend.rows(
                        n=256, messages=512, rate=16.0, window=128,
                        seg_len=8, out=None):
                    print(f"{prefix}{name},{us:.2f},{derived:.3f}",
                          flush=True)
            except Exception:                  # noqa: BLE001
                failed += 1
                traceback.print_exc()
        if eng == "vec" and args.scale_devices:
            try:
                _, csv = bench_scale.rows(
                    n=args.n if args.n is not None else 65536,
                    devices=args.scale_devices, messages=128,
                    rate=4.0, window=64, seg_len=8, out=None,
                    scan=args.scan)
                for name, us, derived in csv:
                    print(f"{prefix}{name},{us:.2f},{derived:.3f}",
                          flush=True)
            except Exception:                  # noqa: BLE001
                failed += 1
                traceback.print_exc()
        if eng == "vec" and args.serve:
            # live serving capacity: a harness-sized two-rate sweep (the
            # nightly CI smoke runs the full bench_serve sweep)
            try:
                _, csv = bench_serve.rows(
                    n=args.n if args.n is not None else 4096,
                    devices=args.scale_devices,
                    engine="sharded" if args.scale_devices else "auto",
                    scan=args.scan, rates=(4.0, 16.0), messages=2000,
                    window=args.window, seg_len=8, out=None)
                for name, us, derived in csv:
                    print(f"{prefix}{name},{us:.2f},{derived:.3f}",
                          flush=True)
            except Exception:                  # noqa: BLE001
                failed += 1
                traceback.print_exc()
    try:
        for name, us, derived in bench_engine.rows():
            print(f"{name},{us:.2f},{derived:.3f}", flush=True)
    except Exception:                          # noqa: BLE001
        failed += 1
        traceback.print_exc()
    if failed:
        sys.exit(1)


if __name__ == '__main__':
    main()
