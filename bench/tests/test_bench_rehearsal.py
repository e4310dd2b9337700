"""Both traffic mixes through the harness on the CPU at a tiny size,
by the same code path as on the chip; a cell added by new files only;
and the refusals: no TPU, no program beside the benchmark."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import bench_tiny  # noqa: E402

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("cell, trace", [(cell, trace)
                                         for cell, _, _ in bench_tiny.CELLS
                                         for trace in (0, 1)])
def test_rehearsal_reports_a_correct_result(root, cell, trace):
    rc, res, err = bench_tiny.run(root, cell, seed=2147483648 + 17,
                                  trace=trace)
    assert rc == 0, err
    assert KEYS <= set(res) and list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["count"] == 1
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    group = bench["per_layer" if trace else "end_to_end"]
    want = {m["name"] for m in group
            if "workloads" not in m or cell in m["workloads"]}
    # the CPU has no memory statistics, so no peak to report there
    want.discard("peak_hbm_gib")
    assert want <= set(res["metrics"]), (want, res["metrics"])
    if trace:
        assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
        assert res["breakdown"]["device_ops"]
        assert res["metrics"]["round_roofline"]["value"] < 100
    for line in err.splitlines():
        if line.startswith("bench "):
            assert line.startswith("bench [cpu cpu x1] "), line


def test_a_cell_added_by_new_files_only(tmp_path):
    """A later change adds a deployment, a traffic mix and a per-layer
    metric as new files and a BENCHMARK.json entry; the harness runs
    them by name with no existing file edited."""
    root = bench_tiny.make_root(str(tmp_path))
    before = {}
    for dirpath, _, files in os.walk(os.path.join(root, "bench")):
        for name in files:
            p = os.path.join(dirpath, name)
            with open(p, "rb") as f:
                before[p] = f.read()
    conf = dict(name="kreg-other", deployment=dict(
        n=384, topology="kregular", k=5, max_delay=2, window=48, seg_len=8,
        churn=dict(n_adds=0, n_rms=0, churn_window=16, round_seed=1)))
    with open(os.path.join(root, "bench/configs/kreg-other.json"), "w") as f:
        json.dump(conf, f)
    with open(os.path.join(root, "bench/traffic/serve-slow.json"), "w") as f:
        json.dump(dict(rate=0.5, messages=4000, admission="defer",
                       queue_cap=64, warm_ticks=1, traffic_seed=2), f)
    with open(os.path.join(root, "bench/metrics/rounds_per_s.py"), "w") as f:
        f.write("def read(ctx):\n    return ctx['rounds'] / ctx['window_s']\n")
    bench_path = os.path.join(root, "BENCHMARK.json")
    with open(bench_path) as f:
        bench = json.load(f)
    bench["configs"].append(dict(name="kreg-other", source="test",
                                 file="bench/configs/kreg-other.json",
                                 reduced=[], why="test"))
    bench["workloads"].append(dict(name="other.slow", config="kreg-other",
                                   traffic="serve-slow", chips=1,
                                   why="test"))
    bench["end_to_end"].append(dict(name="rounds_per_s", unit="rounds/s",
                                    better="higher", bound=0.05,
                                    source="host_clock",
                                    workloads=["other.slow"]))
    with open(bench_path, "w") as f:
        json.dump(bench, f)
    rc, res, err = bench_tiny.run(root, "other.slow", seed=5)
    assert rc == 0, err
    assert res["correct"] is True
    assert res["metrics"]["rounds_per_s"]["value"] > 0
    for p, data in before.items():
        with open(p, "rb") as f:
            assert f.read() == data, p


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "churn17-256k.serve",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def test_no_tpu_means_no_result():
    out = _run_cli(bench_tiny.REPO)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert "{" not in out.stdout


def test_benchmark_alone_without_the_program_fails(tmp_path):
    import shutil
    shutil.copytree(bench_tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(bench_tiny.REPO, "BENCHMARK.json"), tmp_path)
    out = _run_cli(str(tmp_path))
    assert out.returncode != 0
    assert "{" not in out.stdout
