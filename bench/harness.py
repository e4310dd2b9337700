"""One benchmark cell, end to end.

``main`` loads the cell named by ``--workload`` from ``BENCHMARK.json``
(its deployment file under ``bench/configs``, its traffic mix under
``bench/traffic``), draws the inputs from ``--seed``, builds the program
under test on the chip, warms it up, measures for ``--seconds``, checks
what the timed path produced against ``bench/reference.py`` and prints
the result line.  Each metric is computed by the reader of the same
name under ``bench/metrics``; a reader that finds nothing to read
returns ``None`` and the metric is left out.

A cell drives ``LiveLoop`` over the sharded engine (``ShardedStepper``,
one device per chip, scanned segments, latency histograms on) with
open-loop Poisson arrivals, one tick per segment.  The window closes at
the first tick boundary at or after ``--seconds``; the loop then keeps
ticking until every message submitted inside the window has retired
(those ticks count for latency only).

``--trace 1`` turns the program's span recorder on and records a JAX
profiler trace of the window's first ``TRACE_SECONDS``; it reports the
per-layer metrics instead of the end-to-end ones.
"""

from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from collections import Counter
from typing import Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

TRACE_SECONDS = 3.0     # length of the profiled stretch of a traced run
SPAN_CAPACITY = 1 << 20
DRAIN_SECONDS = 90.0    # a live run waits this long past the window
WARM_EXTRA = 64         # warm-up steps allowed past the mix's least while
                        # waiting for a first retirement
LIMITS = {              # every compared number is an exact count
    "series_rounds_wrong": 0, "msgs_wrong": 0, "hist_l1": 0,
    "early_retired": 0, "admission_violations": 0, "unretired_due": 0}


class BenchError(Exception):
    """A cell that cannot run as described (exit code 2)."""


class NoChip(Exception):
    """No accelerator, or fewer chips than the cell asks for (exit 3)."""


class Hooks:
    """What a test or a control run changes underneath the timed path.
    The benchmark's own runs use these defaults."""

    program_mode = "pc"      # the protocol the program runs

    def on_stepper(self, stepper) -> None:
        """Called once the program's stepper exists."""


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise BenchError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


gen = _load(os.path.join(BENCH, "traffic", "gen.py"), "bench_traffic_gen")
reference = _load(os.path.join(BENCH, "reference.py"), "bench_reference")
peaks = _load(os.path.join(BENCH, "peaks.py"), "bench_peaks")
trace_reduce = _load(os.path.join(BENCH, "trace_reduce.py"),
                     "bench_trace_reduce")


# ------------------------------------------------------------------ cell
class Cell:
    """A workload of ``BENCHMARK.json`` with its deployment, traffic mix
    and metric entries, all found by name."""

    def __init__(self, root: str, name: str):
        path = os.path.join(root, "BENCHMARK.json")
        try:
            with open(path) as f:
                self.bench = json.load(f)
        except (OSError, ValueError) as exc:
            raise BenchError(f"cannot read {path}: {exc}") from None
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise BenchError(f"no workload {name!r} in BENCHMARK.json "
                             f"(known: {sorted(cells)})")
        self.root = root
        self.name = name
        self.spec = cells[name]
        self.chips = int(self.spec["chips"])
        confs = {c["name"]: c for c in self.bench["configs"]}
        self.config = self._json(confs[self.spec["config"]]["file"])
        self.mix = self._json(os.path.join(
            "bench", "traffic", self.spec["traffic"] + ".json"))

    def _json(self, rel: str) -> dict:
        try:
            with open(os.path.join(self.root, rel)) as f:
                return json.load(f)
        except (OSError, ValueError) as exc:
            raise BenchError(f"cannot read {rel}: {exc}") from None

    def metrics(self, trace: bool) -> List[dict]:
        """The metric entries this cell reports in this kind of run."""
        group = self.bench["per_layer" if trace else "end_to_end"]
        return [m for m in group
                if "workloads" not in m or self.name in m["workloads"]]

    def reader(self, metric: str):
        path = os.path.join(self.root, "bench", "metrics", metric + ".py")
        if not os.path.exists(path):
            raise BenchError(f"no reader {path} for metric {metric!r}")
        return _load(path, "bench_metric_" + metric.replace(".", "_"))


# --------------------------------------------------------------- devices
def require_chips(chips: int):
    """The first ``chips`` accelerator devices; :class:`NoChip` when JAX
    finds no TPU or too few of them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


class CompileStats:
    """Backend compilations, counted from JAX's monitoring events."""

    def __init__(self):
        from jax import monitoring
        self.compiles = 0
        monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


def _peak_bytes(devices) -> int:
    out = 0
    for d in devices:
        ms = d.memory_stats() or {}
        out = max(out, int(ms.get("peak_bytes_in_use", 0)))
    return out


def _vec_scenario(sc: dict, mode: str):
    from repro.core.vecsim.scenario import VecScenario
    keys = ("n", "k", "rounds", "adj0", "delay0", "bcast_round",
            "bcast_origin", "add_round", "add_p", "add_k", "add_q",
            "add_delay", "rm_round", "rm_p", "rm_k", "pong_delay")
    return VecScenario(mode=mode, **{k: sc[k] for k in keys})


def _warm_gather_widths(stepper) -> None:
    """Compile every width the retiring-column histogram gather can
    take (powers of two from 8 up to the window)."""
    if not stepper.hist:
        return
    r = 8
    while True:
        w = min(r, max(stepper.w, 8))
        stepper._take(stepper.state[1], np.zeros(w, np.int32),
                      np.full(w, stepper.rounds + 1, np.int32)
                      ).block_until_ready()
        if w >= max(stepper.w, 8):
            return
        r *= 2


class _Profile:
    """A JAX profiler trace of one stretch, bracketed by the host span
    ``bench.traced`` so the reduction can find the stretch."""

    def __init__(self):
        import jax
        self.jax = jax
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.ann = None
        self.mono0 = None

    def start(self):
        # host spans come from TraceMe annotations and the program's own
        # recorder; Python function tracing would bury them
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        self.jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.ann = self.jax.profiler.TraceAnnotation("bench.traced")
        self.ann.__enter__()
        self.mono0 = time.monotonic_ns()

    def stop(self):
        self.ann.__exit__(None, None, None)
        self.jax.profiler.stop_trace()

    def reduce(self, program_spans) -> dict:
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise BenchError("the profiler wrote no trace")
        dev, host = trace_reduce.read_events(files[0])
        lo, hi = trace_reduce.find_span(host, "bench.traced")
        # the program's spans are on the monotonic clock: move them onto
        # the trace's by the offset of the bracketing span
        off = self.mono0 - lo
        extra = [(n, s - off, s - off + d) for n, s, d in program_spans]
        out = trace_reduce.reduce_trace(dev, host, lo, hi, extra)
        shutil.rmtree(self.dir, ignore_errors=True)
        return out


def _spans(obs, lo_ns: int, hi_ns: int):
    """The program's recorded spans that start inside ``[lo, hi)``, as
    ``(name, t0_ns, dur_ns)``."""
    rec = obs.spans
    n = rec.n
    names = rec._names
    out = []
    for i in np.nonzero((rec.kind[:n] == 0) & (rec.t0_ns[:n] >= lo_ns)
                        & (rec.t0_ns[:n] < hi_ns))[0]:
        out.append((names[rec.name_id[i]], int(rec.t0_ns[i]),
                    int(rec.dur_ns[i])))
    return out


# ----------------------------------------------------------- comparisons
def _compare_series(prog: np.ndarray, ref: np.ndarray, t_end: int) -> int:
    return int((prog[:t_end] != ref[:t_end]).any(axis=1).sum())


def _compare_msgs(ids, prog_cnt, prog_sum, ref_cnt, ref_sum) -> int:
    return int(((prog_cnt[ids] != ref_cnt[ids])
                | (prog_sum[ids] != ref_sum[ids])).sum())


def _inflight(ref, app_start, add_round, t_end: int) -> np.ndarray:
    """Messages in flight in each round ``< t_end``: from a message's
    start to its last delivery, app messages and pings alike."""
    diff = np.zeros(t_end + 2, np.int64)
    m = len(ref.app_count)
    for s, last in ((np.asarray(app_start, np.int64)[:m], ref.app_last),
                    (np.asarray(add_round, np.int64), ref.ping_last)):
        ok = last < reference.INF
        np.add.at(diff, np.minimum(s[ok], t_end + 1), 1)
        np.add.at(diff, np.minimum(last[ok] + 1, t_end + 1), -1)
    return np.cumsum(diff)[:t_end]


def _least_bytes(sc: dict, inflight: np.ndarray, rounds) -> float:
    """``peaks.least_bytes_per_round`` summed over ``rounds = (a, b)``
    of one scenario."""
    links = int((np.asarray(sc["adj0"]) >= 0).sum())
    a, b = rounds
    return sum(peaks.least_bytes_per_round(sc["n"], int(inflight[r]), links)
               for r in range(a, min(b, len(inflight))))


# ------------------------------------------------------------------- run
class _Stop(Exception):
    pass


def run_live(cell: Cell, args, devices, hooks: Hooks, clock: dict) -> dict:
    from repro.core.vecsim.live import LiveLoop
    from repro.obs.spans import EngineObs

    dep, mix = cell.config["deployment"], cell.mix
    inputs = gen.build_inputs(cell.config, mix, args.seed)
    sc = inputs["scenario"]
    obs = EngineObs(histograms=True, spans=bool(args.trace),
                    span_capacity=SPAN_CAPACITY)
    messages = int(mix["messages"])
    ticks: List[dict] = []
    retire_tick = np.full(messages, -1, np.int64)
    prof = _Profile() if args.trace else None
    s = dict(phase="warm", warm=0, prev_t=0, traced=None)

    def on_tick(info):
        now = time.perf_counter()
        st = loop.stepper
        ticks.append(dict(lo=s["prev_t"], hi=int(st.t), end=now))
        s["prev_t"] = int(st.t)
        m_bc = loop.cw.m_bc
        new = (st.deliv_count[:m_bc] > 0) & (retire_tick[:m_bc] < 0)
        retire_tick[:m_bc][new] = len(ticks) - 1
        if s["phase"] == "warm":
            s["warm"] += 1
            if s["warm"] == 1:
                _warm_gather_widths(st)
            least = int(mix["warm_ticks"])
            if s["warm"] >= least + WARM_EXTRA or (
                    s["warm"] >= least and st.deliv_count.any()):
                s.update(phase="window", r0=int(st.t), tick0=len(ticks),
                         comp0=clock["compiles"].compiles)
                if prof:
                    prof.start()
                s["mono_lo"] = time.monotonic_ns()
                s["w0"] = time.perf_counter()
                clock["setup_s"] = s["w0"] - clock["t0"]
            return
        if s["phase"] == "window":
            if prof and s["traced"] is None and now - s["w0"] >= TRACE_SECONDS:
                prof.stop()
                s["traced"] = (s["r0"], int(st.t))
            if now - s["w0"] >= args.seconds:
                s.update(phase="drain", w1=now, r1=int(st.t),
                         tick1=len(ticks), mono_hi=time.monotonic_ns(),
                         comp1=clock["compiles"].compiles)
                if prof and s["traced"] is None:
                    prof.stop()
                    s["traced"] = (s["r0"], int(st.t))
                lo, hi = np.searchsorted(arr_round, [s["r0"], s["r1"]])
                s["n_due"] = int(hi - lo)
            else:
                return
        # drain: until every message submitted in the window retired
        sub = loop.submit_round[:m_bc]
        due = (sub >= s["r0"]) & (sub < s["r1"])
        done = int((due & (retire_tick[:m_bc] >= 0)).sum())
        if done >= s["n_due"] or now - s["w1"] > DRAIN_SECONDS:
            s["end"] = now
            raise _Stop

    loop = LiveLoop(
        _vec_scenario(sc, hooks.program_mode),
        dep["window"], engine="sharded", backend="jax", devices=cell.chips,
        scan="on", seg_len=dep["seg_len"], collect="aggregate", arrivals="poisson",
        admission=mix["admission"], rate=float(mix["rate"]),
        messages=messages, queue_cap=int(mix["queue_cap"]),
        per_round_cap=mix.get("per_round_cap"),
        seed=inputs["arrival_seed"], obs=obs, on_tick=on_tick)
    arr_round, arr_origin = inputs["arr_round"], inputs["arr_origin"]
    if not (np.array_equal(loop.arr_round, arr_round)
            and np.array_equal(loop.arr_origin, arr_origin)):
        raise BenchError("the live loop draws other arrivals than the "
                         "benchmark's own generator")
    hooks.on_stepper(loop.stepper)
    try:
        loop.run()
    except _Stop:
        pass
    if "w1" not in s:
        raise BenchError("the live loop stopped before the window closed")
    s.setdefault("end", time.perf_counter())
    peak = _peak_bytes(devices)

    st, cw = loop.stepper, loop.cw
    m_bc, cap = cw.m_bc, cw.m_app_cap
    t_end = int(st.t)
    series = st.series.copy()
    count, rsum = st.deliv_count.copy(), st.deliv_round_sum.copy()
    hist = obs.latency_hist.copy()
    bc_round = cw.bc_round[:m_bc].astype(np.int64)
    bc_origin = cw.bc_origin[:m_bc].astype(np.int64)
    submit = loop.submit_round[:m_bc].copy()
    queued = list(loop.queue)
    ingested, shed = loop.arr_ptr, loop.shed_queue + loop.shed_policy
    prc = loop.prc
    spans = (_spans(obs, s["mono_lo"], s["mono_hi"]) if args.trace else [])
    st.state = None
    del loop, st, cw

    r0 = time.perf_counter()
    ref = reference.solve(sc, bc_round, bc_origin, t_end, app_base=submit)
    ref_s = time.perf_counter() - r0
    m = len(ref.app_count)
    ids = np.nonzero(count[:m_bc] > 0)[0]
    checks = dict(series_rounds_wrong=_compare_series(series, ref.series,
                                                      t_end))
    wrong = _compare_msgs(ids[ids < m], count, rsum, ref.app_count,
                          ref.app_sum) + int((ids >= m).sum())
    pid = np.nonzero(count[cap:] > 0)[0]
    wrong += _compare_msgs(pid, count[cap:], rsum[cap:], ref.ping_count,
                           ref.ping_sum)
    checks["msgs_wrong"] = wrong
    checks["hist_l1"] = int(np.abs(
        hist - ref.app_hist[ids[ids < m]].sum(axis=0)).sum())
    his = np.array([t["hi"] for t in ticks], np.int64)
    ok_ids = ids[ids < m]
    checks["early_retired"] = int(
        (ref.app_last[ok_ids] >= his[retire_tick[ok_ids]]).sum())
    checks["admission_violations"] = _admission_violations(
        bc_round, bc_origin, submit, queued, arr_round[:ingested],
        arr_origin[:ingested], shed, prc)
    due = (submit >= s["r0"]) & (submit < s["r1"])
    got = due & (retire_tick[:m_bc] >= 0)
    checks["unretired_due"] = s["n_due"] - int(got.sum())

    # wall-clock latency: from the end of the tick that simulated the
    # submission round to the end of the tick that retired the message
    ends = np.array([t["end"] for t in ticks])
    sub_tick = np.searchsorted(his, submit[got], side="right")
    lat_ms = (ends[retire_tick[:m_bc][got]] - ends[sub_tick]) * 1e3
    tick_rows = ticks[s["tick0"]:s["tick1"]]
    inflight = _inflight(ref, bc_round, sc["add_round"], t_end)
    traced = s["traced"]
    work = None if traced is None else _least_bytes(sc, inflight, traced)
    return dict(
        reference_s=ref_s, drain_s=s["end"] - s["w1"],
        window_s=s["w1"] - s["w0"], ticks=len(tick_rows),
        rounds=s["r1"] - s["r0"],
        deliveries=int(series[s["r0"]:s["r1"], 0].sum()),
        latencies_ms=lat_ms, peak_bytes=peak, spans=spans,
        compiles_in_window=s["comp1"] - s["comp0"], checks=checks,
        attempted=int(got.sum()), failed=checks["msgs_wrong"]
        + checks["early_retired"] + checks["unretired_due"],
        profile=prof, round_work=work)


def _admission_violations(bc_round, bc_origin, submit, queued, arr_round,
                          arr_origin, shed, prc) -> int:
    """Admissions that break the serving contract: not after their
    submission, over the per-round cap, a repeated (origin, round), or
    not matching an offered submission; plus submissions lost."""
    bad = int((bc_round <= submit).sum())
    if len(bc_round):
        per_round = np.bincount(bc_round)
        bad += int(np.maximum(per_round - prc, 0).sum())
        pairs = bc_round * (1 << 32) + bc_origin
        bad += len(pairs) - len(np.unique(pairs))
    offered = Counter(zip(arr_round.tolist(), arr_origin.tolist()))
    taken = Counter(zip(submit.tolist(), bc_origin.tolist()))
    taken.update(queued)
    bad += sum((taken - offered).values())
    bad += abs(len(arr_round) - (len(bc_round) + len(queued) + shed))
    return bad


# ------------------------------------------------------------------ main
def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run_cell(cell: Cell, args, devices, hooks: Hooks, t0: float) -> dict:
    """Run the cell and return the result line's fields."""
    clock = dict(t0=t0, compiles=CompileStats())
    ctx = run_live(cell, args, devices, hooks, clock)
    d0 = devices[0]
    ctx.update(setup_s=clock["setup_s"], n=cell.config["deployment"]["n"],
               device_kind=d0.device_kind, peaks=peaks)
    device = dict(platform=d0.platform, kind=d0.device_kind,
                  count=len(devices), memory_peak_bytes=ctx["peak_bytes"])
    out = {}
    if args.trace:
        tr = ctx["profile"].reduce(ctx["spans"])
        ctx["trace"] = tr
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        out["breakdown"] = dict(device_ops=tr["device_ops"],
                                idle_gaps=tr["idle_gaps"])
    metrics = {}
    for entry in cell.metrics(bool(args.trace)):
        value = cell.reader(entry["name"]).read(ctx)
        if value is not None:
            metrics[entry["name"]] = dict(value=float(value),
                                          unit=entry["unit"])
    checks = {k: dict(value=int(v), limit=LIMITS[k])
              for k, v in ctx["checks"].items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result = dict(correct=correct, attempted=int(ctx["attempted"]),
                  failed=int(ctx["failed"]), metrics=metrics,
                  device=device, **out)
    result["checks"] = checks
    return dict(result=result, ctx=ctx)


def main(argv=None, t0: Optional[float] = None,
         hooks: Optional[Hooks] = None) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    args = _parse(argv)
    hooks = hooks or Hooks()
    try:
        cell = Cell(ROOT, args.workload)
    except BenchError as exc:
        print(f"error: {exc}", file=sys.stderr)
        return 2
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"error: the program under test is not here ({src}/repro "
              "missing); run from a checkout of the repository",
              file=sys.stderr)
        return 2
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        devices = require_chips(cell.chips)
    except NoChip as exc:
        print(f"error: {exc}; the benchmark measures nothing elsewhere",
              file=sys.stderr)
        return 3
    d0 = devices[0]
    tag = f"[{d0.platform} {d0.device_kind} x{len(devices)}]"

    def say(msg):
        print(f"bench {tag} {msg}", file=sys.stderr, flush=True)

    say(f"cell {cell.name} seed {args.seed} seconds {args.seconds} "
        f"trace {args.trace}; compile cache {cache_dir}")
    try:
        out = run_cell(cell, args, devices, hooks, t0)
    except BenchError as exc:
        say(f"error: {exc}")
        return 2
    ctx, result = out["ctx"], out["result"]
    say(f"window {ctx['window_s']!r} s, {ctx['rounds']} rounds, "
        f"{ctx['deliveries']} deliveries; compiles in window "
        f"{ctx['compiles_in_window']}; reference {ctx['reference_s']!r} s; "
        f"drain {ctx['drain_s']!r} s; run {time.perf_counter() - t0!r} s")
    if ctx["spans"] and ctx["rounds"]:
        tot: Dict[str, int] = {}
        for name, _, dur in ctx["spans"]:
            tot[name] = tot.get(name, 0) + dur
        say("program spans, ms per simulated round: " + ", ".join(
            f"{n} {v / ctx['rounds'] / 1e6:.3f}"
            for n, v in sorted(tot.items(), key=lambda kv: -kv[1])))
    for name, m in result["metrics"].items():
        say(f"metric {name} = {m['value']!r} {m['unit']}")
    for name, c in result["checks"].items():
        say(f"check {name} = {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0
