"""The main path's device programs compile for a TPU v5e.

Nothing here runs on a chip: each test lowers a segment body with
shapes placed on a *described* ``v5e:2x2`` topology and asks the TPU
compiler for the executable, which refuses what the chip would refuse
(unsupported ops, layouts, programs that outgrow device memory).  The
topology is described inside a fixture, never while a module is
imported, so the test workers collect the same tests and only the
worker given this file loads the TPU library.
"""

import os
import re

import jax
import numpy as np
import pytest
from jax.sharding import (NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.api import (DynamicsSpec, RunSpec, TopologySpec, TrafficSpec,
                       build_scenario)
from repro.core.vecsim.sim import (_STATE_KEYS, init_topo_state,
                                   jax_span_runner)
from repro.core.vecsim.stream import ColumnWindow

HBM_BYTES = 16 << 30   # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an entry compiled for a described chip cannot be read back without
    # one: keep these compiles out of any persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _scenario(n, traffic="poisson", dynamics="none"):
    return build_scenario(RunSpec(
        protocol="pc", n=n, seed=0,
        topology=TopologySpec(kind="kregular", k=4, max_delay=1),
        traffic=TrafficSpec(kind=traffic, rate=4.0, messages=256),
        dynamics=DynamicsSpec(kind=dynamics)).validate())


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                       sharding=sharding), tree)


def _assert_fits(compiled):
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert 0 < total <= HBM_BYTES, total


def test_windowed_span_runner_compiles_for_v5e(topo):
    """The windowed engine's jitted segment (``jax_span_runner``) at
    N=65,536 and the README's W=128."""
    n, w, seg_len = 1 << 16, 128, 8
    scn = _scenario(n)
    cw = ColumnWindow(scn, w)
    st = init_topo_state(scn, w)
    sched = cw.padded_schedule(0, seg_len,
                               cw.segment_caps(scn.rounds, seg_len))
    one = SingleDeviceSharding(topo.devices[0])
    run = jax_span_runner(scn.k, True, scn.always_gate, scn.pong_delay,
                          gating=scn.n_adds > 0)
    args = (tuple(st[key] for key in _STATE_KEYS),
            {f: getattr(sched, f) for f in sched.__dataclass_fields__},
            np.zeros(seg_len, np.int32))
    with jax.enable_x64(True):
        compiled = run.jitted.lower(*_shapes(args, one)).compile()
    _assert_fits(compiled)


def _gather_shapes(hlo: str):
    """Shape of every ``gather`` result in compiled HLO text."""
    return [tuple(int(x) for x in dims.split(",") if x)
            for dims in re.findall(r"= \w+\[([\d,]*)\]\S* gather\(", hlo)]


def _scatter_widths(hlo: str, rows: int):
    """Column counts of every ``scatter`` result of ``rows`` rows in
    compiled HLO text."""
    return {int(c) for c in re.findall(
        r"= \w+\[%d,(\d+)\]\S* scatter\(" % rows, hlo)}


def test_sharded_scan_generic_body_compiles_for_v5e(topo, monkeypatch):
    """The sharded engine's scanned generic body (gating live: a churn
    scenario) at the benchmark's W=512 on one described device; the
    runner's mesh is steered onto the described device here, in the
    test.  Its pong query reads only the candidate rows' slots, in
    chunks: no gather spans every (row, slot) of the (N, K) tables.  Its
    frontier exchange scatters at the compact widths and, as the
    fallback, at full width."""
    from repro.core.vecsim.shard import spanner
    n, w, seg_len = 1 << 14, 512, 8
    scn = _scenario(n, traffic="uniform", dynamics="churn")
    assert scn.n_adds > 0
    mesh = jax.sharding.Mesh(np.array(topo.devices[:1]), ("shard",))
    monkeypatch.setattr(spanner, "shard_mesh", lambda d: mesh)
    # __wrapped__: keep a described-device runner out of the lru cache
    run = spanner.shard_span_runner.__wrapped__(
        1, scn.k, True, scn.always_gate, scn.pong_delay, gating=True,
        backend="jax", scan=True)
    cw = ColumnWindow(scn, w)
    st = init_topo_state(scn, w)
    sst = cw.stacked_schedule(0, seg_len, cw.round_caps(scn.rounds),
                              seg_len)
    row, rep = NamedSharding(mesh, P("shard")), NamedSharding(mesh, P())
    state = _shapes(tuple(st[key] for key in spanner.STATE_KEYS), row)
    rest = _shapes((sst, np.zeros(seg_len, np.int32),
                    np.full(w, -1, np.int32), np.int32(scn.rounds)), rep)
    with jax.enable_x64(True):
        compiled = run.jitted.lower(state, *rest).compile()
    _assert_fits(compiled)
    hlo = compiled.as_text()
    assert "input_output_alias" in hlo
    shapes = _gather_shapes(hlo)
    assert shapes and (n, scn.k) not in shapes, shapes
    assert (n * scn.k,) not in shapes, shapes
    assert (spanner.PONG_ROWS, scn.k) in shapes, shapes
    assert _scatter_widths(hlo, n) >= set(spanner.FRONTIER_WIDTHS) | {w}


def test_sharded_hist_fold_compiles_for_v5e(topo, monkeypatch):
    """The on-device latency-histogram fold at the benchmark's N=2^18,
    W=512 on one described device: it reads the delivered plane in
    place, so its temporaries stay far below one (N, W) plane and the
    retire step adds nothing to the segment's peak."""
    from repro.core.vecsim.shard import spanner
    n, w = 1 << 18, 512
    mesh = jax.sharding.Mesh(np.array(topo.devices[:1]), ("shard",))
    monkeypatch.setattr(spanner, "shard_mesh", lambda d: mesh)
    run = spanner.shard_hist_runner.__wrapped__(1)
    plane = jax.ShapeDtypeStruct((n, w), np.int32,
                                 sharding=NamedSharding(mesh, P("shard")))
    base = jax.ShapeDtypeStruct((w,), np.int32,
                                sharding=NamedSharding(mesh, P()))
    with jax.enable_x64(True):
        compiled = run.jitted.lower(plane, base).compile()
    _assert_fits(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < n * w * 4 // 64
