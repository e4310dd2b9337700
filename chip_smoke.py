#!/usr/bin/env python3
"""Smoke check: the PC-broadcast engine runs on the TPU through its
front door, ``repro.api.run``, and delivers what it should.

    python chip_smoke.py                 # one chip: crossval, batch, serve
    python chip_smoke.py --four-chips    # 4-chip host: sharded D=4 vs D=1
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny            # CPU rehearsal
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        JAX_PLATFORMS=cpu python chip_smoke.py --tiny --four-chips

Phases (one process, so one process holds the chip):

  crossval  N=256 PC-broadcast under link churn on the windowed engine
            and on the sharded engine with one device; the happens-before
            oracle and the exact-simulator cross-validation must pass.
  batch     the README's headline deployment on one chip: N=1,048,576,
            k-regular k=4, delay 1, 512 Poisson broadcasts at 4/round,
            engine and window chosen by ``auto``; full delivery required.
  serve     live mode at N=262,144 on the sharded engine with one
            device: 4,096 Poisson submissions at 8/round, ``defer``
            admission, p99 SLO 256 rounds; the
            admission accounting must balance, every admitted message
            must be delivered, and p99 must be finite.

``--four-chips`` runs only the batch and serve deployments on the
sharded engine over four devices, each against the same spec on one
device, and requires equal results (wall-clock rates excepted).

Each phase prints one line: what ran, its wall seconds (compilation
included) and what it checked.  These are smoke timings, not benchmark
numbers.  The last line of a passing run is one JSON object naming the
device; any failed check, or a platform other than ``tpu`` without
``--tiny``, exits non-zero without it.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

# full sizes; --tiny divides N by TINY_DIV and keeps every window
N_BATCH, N_SERVE, N_CROSSVAL = 1 << 20, 1 << 18, 256
TINY_DIV = 256
W_BATCH, W_SERVE = 128, 512   # the budget-derived windows at full size


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> str:
    if not ok:
        raise CheckFailed(what)
    return what


class CompileStats:
    """Compilations and persistent-cache hits, from JAX's monitoring
    events, so a phase line can say how much of its time was compiling."""

    def __init__(self):
        from jax import monitoring
        self.compile_s = 0.0
        self.hits = self.misses = 0
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def snapshot(self):
        return self.compile_s, self.hits, self.misses


def phase(name: str, stats: CompileStats, fn, failures: list) -> None:
    c0, h0, m0 = stats.snapshot()
    t0 = time.perf_counter()
    try:
        ran, checked = fn()
    except Exception as exc:  # report every phase, then fail the run
        traceback.print_exc()
        failures.append(name)
        print(f"smoke {name}: FAILED after "
              f"{time.perf_counter() - t0!r} s: {exc!r}", flush=True)
        return
    wall = time.perf_counter() - t0
    c1, h1, m1 = stats.snapshot()
    print(f"smoke {name}: {ran} | wall {wall!r} s (smoke timing, "
          f"compile {c1 - c0!r} s, cache hits {h1 - h0}, misses "
          f"{m1 - m0}) | passed: {checked}", flush=True)


def crossval_phase():
    from repro.api import (DynamicsSpec, MetricsSpec, RunSpec, ShardSpec,
                           TopologySpec, TrafficSpec, WindowSpec, run)
    checked = []
    for engine, shard in (("windowed", ShardSpec()),
                          ("sharded", ShardSpec(devices=1))):
        rep = run(RunSpec(
            protocol="pc", engine=engine, backend="auto", n=N_CROSSVAL,
            seed=7, topology=TopologySpec(kind="kregular", k=4),
            traffic=TrafficSpec(kind="uniform", messages=24),
            dynamics=DynamicsSpec(kind="churn"),
            window=WindowSpec(window=32, seg_len=8, collect="full"),
            shard=shard, metrics=MetricsSpec(oracle=True, crossval=True)))
        checked.append(check(
            rep.oracle.ok and rep.crossval_ok is True,
            f"{engine}/{rep.backend} oracle={rep.oracle.ok} "
            f"crossval={rep.crossval_ok}"))
    return f"N={N_CROSSVAL} pc churn", "; ".join(checked)


def batch_spec(n: int, **kw):
    from repro.api import RunSpec, TopologySpec, TrafficSpec, WindowSpec
    window = kw.pop("window", None)
    # the budget that derives W_BATCH at this N (1024 MiB at full size)
    budget = max(1, 8 * n * W_BATCH >> 20)
    return RunSpec(
        protocol="pc", n=n, seed=0, memory_budget_mb=budget,
        topology=TopologySpec(kind="kregular", k=4, max_delay=1),
        traffic=TrafficSpec(kind="poisson", rate=4.0, messages=512),
        window=WindowSpec(window=window, seg_len=32), **kw)


def serve_spec(n: int, messages: int, **kw):
    from repro.api import LiveSpec, RunSpec, TopologySpec, WindowSpec
    window = kw.pop("window", None)
    budget = max(1, 8 * n * W_SERVE >> 20)
    return RunSpec(
        protocol="pc", mode="live", n=n, seed=0, memory_budget_mb=budget,
        topology=TopologySpec(kind="kregular", k=4, max_delay=1),
        window=WindowSpec(window=window, seg_len=32),
        live=LiveSpec(arrivals="poisson", admission="defer", rate=8.0,
                      messages=messages, slo_p99=256.0), **kw)


def check_batch(rep) -> str:
    return check(rep.delivered_frac == 1.0,
                 f"delivered_frac={rep.delivered_frac}")


def check_serve(rep) -> str:
    lr = rep.live
    balance = lr.admitted + lr.shed_queue + lr.shed_policy + lr.unserved
    return "; ".join([
        check(balance == lr.offered,
              f"admitted+shed+unserved={balance} == offered={lr.offered}"),
        check(lr.delivered_messages == lr.admitted
              and lr.delivered_frac == 1.0,
              f"delivered {lr.delivered_messages}/{lr.admitted} admitted, "
              f"delivered_frac={lr.delivered_frac}"),
        check(math.isfinite(lr.p99), f"p99={lr.p99} rounds"),
    ])


def batch_phase(n: int):
    from repro.api import run
    rep = run(batch_spec(n))
    return (f"N={n} engine={rep.engine} (auto) window={rep.window} "
            f"rounds={rep.rounds} sends={rep.stats.sent_messages}",
            check_batch(rep))


def serve_phase(n: int, messages: int):
    from repro.api import ShardSpec, run
    # the sharded engine on one device keeps the live state on the chip;
    # the windowed engine (auto's pick here) copies it host<->device
    # every tick: 469 s for this phase on one v5e against 95 s sharded
    rep = run(serve_spec(n, messages, engine="sharded",
                         shard=ShardSpec(devices=1)))
    lr = rep.live
    return (f"N={n} engine={rep.engine} window={rep.window} "
            f"ticks={lr.ticks_run} rounds={lr.rounds} "
            f"requests/s={lr.requests_per_sec!r} p50={lr.p50} "
            f"p99={lr.p99}", check_serve(rep))


def comparable(rep) -> dict:
    """Every result field that must not depend on the device count."""
    out = dict(stats=vars(rep.stats), delivered_frac=rep.delivered_frac,
               mean_latency=rep.mean_latency,
               peak_live=rep.extras["peak_live"])
    for key, v in rep.extras.items():
        if key.startswith(("latency_p", "serve_")) \
                and key != "serve_requests_per_sec":
            out[key] = v
    return out


def check_placement(spec, devices: int) -> str:
    """The sharded state really spans ``devices`` devices, one row block
    each (not everything on device 0)."""
    from repro.api import build_scenario
    from repro.core.vecsim.shard.driver import ShardedStepper
    st = ShardedStepper(build_scenario(spec), spec.window.window,
                        n_devices=devices)
    arr = st.state[0]
    rows = {s.device.id: s.data.shape[0] for s in arr.addressable_shards}
    return check(len(arr.sharding.device_set) == devices
                 and len(rows) == devices
                 and set(rows.values()) == {arr.shape[0] // devices},
                 f"arr rows per device {rows}")


def four_chip_phase(spec_fn, checker, placement: bool):
    from dataclasses import replace

    from repro.api import ShardSpec, run
    reps = {d: run(replace(spec_fn(), engine="sharded",
                           shard=ShardSpec(devices=d)))
            for d in (4, 1)}
    a, b = comparable(reps[4]), comparable(reps[1])
    diff = sorted(k for k in set(a) | set(b) if a.get(k) != b.get(k))
    checked = [checker(reps[4]),
               check(not diff, f"D=4 equals D=1 on {len(a)} fields"
                     + (f" (differ: {diff})" if diff else ""))]
    if placement:
        checked.append(check_placement(spec_fn(), 4))
    walls = ", ".join(f"D={d} {r.wall_seconds!r} s"
                      for d, r in reps.items())
    return (f"N={reps[4].n} sharded window={reps[4].window} "
            f"rounds={reps[4].rounds} ({walls})", "; ".join(checked))


def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal: N divided by %d, any platform"
                    % TINY_DIV)
    ap.add_argument("--four-chips", action="store_true",
                    help="only the sharded D=4 vs D=1 comparison")
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"error: no repro package under {SRC}; run chip_smoke.py "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    want = 4 if args.four_chips else 1
    if platform != "tpu" and not args.tiny:
        print(f"error: JAX found no TPU (platform {platform!r}); "
              "chip_smoke.py measures nothing on another platform "
              "(--tiny rehearses on CPU)", file=sys.stderr)
        return 1
    if len(devs) < want:
        print(f"error: --four-chips needs 4 devices, JAX sees {len(devs)}",
              file=sys.stderr)
        return 1
    order = [(d.id, getattr(d, "coords", None)) for d in devs]
    print(f"smoke device: {platform} {kind} x{len(devs)} (id, coords in "
          f"jax.devices() order: {order}); compile cache {cache_dir}",
          flush=True)

    div = TINY_DIV if args.tiny else 1
    n_batch, n_serve = N_BATCH // div, N_SERVE // div
    messages = 4096 // (4 if args.tiny else 1)
    stats = CompileStats()
    failures: list = []
    if args.four_chips:
        phase("batch x4", stats, lambda: four_chip_phase(
            lambda: batch_spec(n_batch, window=W_BATCH), check_batch, True),
            failures)
        phase("serve x4", stats, lambda: four_chip_phase(
            lambda: serve_spec(n_serve, messages, window=W_SERVE),
            check_serve, False), failures)
    else:
        phase("crossval", stats, crossval_phase, failures)
        phase("batch", stats, lambda: batch_phase(n_batch), failures)
        phase("serve", stats, lambda: serve_phase(n_serve, messages),
              failures)
    compile_s, hits, misses = stats.snapshot()
    print(f"smoke compile total: {compile_s!r} s, persistent cache hits "
          f"{hits}, misses {misses}", flush=True)
    if failures:
        print(f"smoke FAILED: {', '.join(failures)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
