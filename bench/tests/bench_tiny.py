"""A small copy of the benchmark for tests on the CPU.

``make_root`` copies ``bench/`` into a temporary directory beside a link
to the program's ``src``, adds small deployments and traffic mixes as
new files, and writes a ``BENCHMARK.json`` whose cells use them.
``run`` drives one cell through the harness's own ``main`` in this
process, with the chip check and the peaks table steered here, and
returns the result line.
"""

from __future__ import annotations

import contextlib
import importlib
import io
import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)

CONFIGS = {
    "churn-tiny": dict(name="churn-tiny", deployment=dict(
        n=256, topology="ring", k=6, max_delay=3, window=64, seg_len=8,
        churn=dict(n_adds=32, n_rms=32, churn_window=64, round_seed=5))),
}
MIXES = {
    "serve-tiny": dict(rate=1.0, messages=100000, admission="defer",
                       queue_cap=256, warm_ticks=2, traffic_seed=6),
    # above the knee: the queue grows through the run (it never sheds)
    "over-tiny": dict(rate=6.0, messages=200000, admission="defer",
                      queue_cap=200000, warm_ticks=2, traffic_seed=7),
}
CELLS = [("churn.serve", "churn-tiny", "serve-tiny"),
         ("churn.over", "churn-tiny", "over-tiny")]


def make_root(dst: str, cells=CELLS, configs=CONFIGS, mixes=MIXES) -> str:
    shutil.copytree(BENCH, os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(dst, "src"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for name, conf in configs.items():
        with open(os.path.join(dst, "bench", "configs", name + ".json"),
                  "w") as f:
            json.dump(conf, f)
    for name, mix in mixes.items():
        with open(os.path.join(dst, "bench", "traffic", name + ".json"),
                  "w") as f:
            json.dump(mix, f)
    bench["configs"] = [dict(name=n, source="test", reduced=[], why="test",
                             file=f"bench/configs/{n}.json")
                        for n in configs]
    bench["workloads"] = [dict(name=c, config=cf, traffic=t, chips=1,
                               why="test") for c, cf, t in cells]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [c for c, _, _ in cells]
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return dst


def harness_of(root: str):
    """The harness module of the copy at ``root``, with the chip check
    and the peaks table steered for the CPU."""
    sys.path.insert(0, os.path.join(root, "bench"))
    try:
        sys.modules.pop("harness", None)
        h = importlib.import_module("harness")
    finally:
        sys.path.pop(0)
    import jax
    h.require_chips = lambda chips: jax.devices()[:chips]
    h.peaks.PEAKS.setdefault("cpu", dict(hbm_bytes_per_s=1e11))
    h.TRACE_SECONDS = 0.3
    return h


def run(root: str, cell: str, seed: int = 3, seconds: float = 1.5,
        trace: int = 0, hooks=None):
    """Run ``cell`` through the copy's harness; returns ``(rc, result
    or None, stderr text)``."""
    h = harness_of(root)
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = h.main(["--workload", cell, "--seed", str(seed), "--seconds",
                     str(seconds), "--trace", str(trace)], hooks=hooks)
    lines = out.getvalue().strip().splitlines()
    result = json.loads(lines[-1]) if rc == 0 and lines else None
    return rc, result, err.getvalue()
