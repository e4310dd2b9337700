"""Shared scenario case builders for the vecsim differential suites.

One ``(name, seed, n) -> VecScenario`` dispatch used by the hypothesis
fuzz suite (``test_vecsim_fuzz.py``), the sharded-engine matrix tests
(``test_vecsim_shard.py``) and — crucially — the *subprocess* snippets
those tests spawn to get multi-device meshes
(``XLA_FLAGS=--xla_force_host_platform_device_count`` must precede jax
initialization, so multi-shard runs happen in child interpreters that
rebuild the identical scenario from ``(name, seed, n)``).  Keeping the
builders here, hypothesis-free, is what lets a child import them
without the fuzz suite's optional dependency.
"""

from repro.core.vecsim import (churn_scenario, churn_wave_scenario,
                               crash_scenario, link_add_scenario,
                               partition_heal_scenario, static_scenario,
                               sustained_scenario)


def _burst(seed: int, n: int, w: int):
    """Dense broadcast waves under link churn, ``m_total == w``: at
    N=64 some rounds hold more than 128 (and at w=512 more than 256)
    columns with a new delivery, so the gated body's frontier exchange
    runs at each of its widths."""
    n_adds = max(2, n // 8)
    return churn_scenario(seed, n, m_app=w - n_adds, n_adds=n_adds,
                          churn_window=6)


BUILDERS = {
    "static": lambda seed, n: static_scenario(seed, n),
    "link_add": lambda seed, n: link_add_scenario(seed, n),
    "churn": lambda seed, n: churn_scenario(seed, n),
    "crash": lambda seed, n: crash_scenario(seed, n),
    "waves": lambda seed, n: churn_wave_scenario(seed, n, waves=2),
    "partition": lambda seed, n: partition_heal_scenario(
        seed, max(n, 12), traffic_during_partition=bool(seed % 2)),
    "sustained_kreg": lambda seed, n: sustained_scenario(
        seed, n, k=5, rate=1.0 + (seed % 3), messages=24,
        topology="kregular", max_delay=2),
    "sustained_sw": lambda seed, n: sustained_scenario(
        seed, n, k=5, rate=2.0, messages=24, topology="smallworld",
        traffic="bursty", max_delay=2),
}

# Cases for the exactness suites only: too wide for the fuzz suite and
# the interpret-mode kernel sweeps that run every builder
WIDE = {
    "burst256": lambda seed, n: _burst(seed, n, 256),
    "burst512": lambda seed, n: _burst(seed, n, 512),
}


def build(name: str, seed: int, n: int):
    return (WIDE[name] if name in WIDE else BUILDERS[name])(seed, n)


# --------------------------------------------------------------------- #
# Multi-device subprocess harness
# --------------------------------------------------------------------- #
_SNIPPET = """
import os, sys
os.environ["XLA_FLAGS"] = \
    "--xla_force_host_platform_device_count={shards}"
sys.path.insert(0, {tests_dir!r})
import numpy as np
from vecsim_cases import build
from repro.core.vecsim import WindowOverflowError, execute_windowed
from repro.core.vecsim.shard import execute_sharded

for name, seed, n, frac, seg in {cases!r}:
    scn = build(name, seed, n)
    w = max(4, int(scn.m_total * frac))
    try:
        win = execute_windowed(scn, w, backend="numpy", collect="full",
                               seg_len=seg)
    except WindowOverflowError:
        win = None
    try:
        sh = execute_sharded(scn, w, n_devices={shards}, collect="full",
                             seg_len=seg, backend={backend!r},
                             scan={scan!r})
    except WindowOverflowError:
        sh = None
    assert (win is None) == (sh is None), (name, "overflow parity")
    if win is not None:
        np.testing.assert_array_equal(win.delivered, sh.delivered)
        np.testing.assert_array_equal(win.series, sh.series)
        assert win.stats == sh.stats, name
        assert win.deliv_count.tolist() == sh.deliv_count.tolist()
        assert win.bcast_done.tolist() == sh.bcast_done.tolist()
        assert win.peak_live == sh.peak_live
        assert (win.lat_sum, win.lat_cnt) == (sh.lat_sum, sh.lat_cnt)
        for key in win.state:
            np.testing.assert_array_equal(win.state[key], sh.state[key],
                                          err_msg=name + "/" + key)
    if sh is not None and {scan!r} == "on":
        # the scanned segment body must be byte-identical to the
        # per-round sharded path it replaced, not just to the windowed
        # reference — compare against scan="off" in the same mesh
        off = execute_sharded(scn, w, n_devices={shards}, collect="full",
                              seg_len=seg, backend={backend!r},
                              scan="off")
        assert sh.scan == "on" and off.scan == "off"
        np.testing.assert_array_equal(off.delivered, sh.delivered)
        np.testing.assert_array_equal(off.series, sh.series)
        assert off.stats == sh.stats, (name, "scan on vs off")
        for key in off.state:
            np.testing.assert_array_equal(off.state[key], sh.state[key],
                                          err_msg=name + "/scan/" + key)
    print("CASE_OK", name, n)
{extra}
print("ALL_OK")
"""


def run_shard_matrix_subprocess(cases, shards, extra: str = "",
                                backend: str = "jax", scan: str = "auto"):
    """Run ``cases`` — ``(builder, seed, n, window_frac, seg_len)``
    tuples — in a child interpreter with ``shards`` forced host devices,
    asserting the sharded engine is byte-identical to the windowed
    reference on each (or that both overflow).  ``extra`` appends
    arbitrary assertion code to the child (used for the auto-selection
    check, which also needs the multi-device mesh).  ``backend`` picks
    the sharded round body — ``"jax"`` or ``"pallas"`` (interpret-mode
    kernel launches inside the child's shard_map).  ``scan`` picks the
    segment stepping; with the scanned path in play the child *also*
    re-runs each case with ``scan="off"`` and asserts the two sharded
    results match byte for byte (the tightest differential: same mesh,
    same backend, only the stepping strategy differs)."""
    import os
    import subprocess
    import sys

    tests_dir = os.path.dirname(os.path.abspath(__file__))
    repo_root = os.path.dirname(tests_dir)
    snippet = _SNIPPET.format(shards=shards, tests_dir=tests_dir,
                              cases=list(cases), extra=extra,
                              backend=backend, scan=scan)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", snippet],
                         capture_output=True, text=True, env=env,
                         cwd=repo_root)
    assert out.returncode == 0 and "ALL_OK" in out.stdout, \
        out.stdout + out.stderr
    return out.stdout
