"""Multi-device tensorized engine: process axis sharded over a mesh.

The per-round body from ``step.py`` runs unmodified under ``jax.jit`` with
the ``(N, M)`` / ``(N, K)`` state sharded on the process axis; XLA inserts
the cross-shard collectives for scatters whose target row lives on another
device.  On a TPU pod this is how a 10^6-process fleet simulation runs; on
this box it is exercised with ``--xla_force_host_platform_device_count``
(tests spawn a subprocess so the flag precedes jax initialization).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .state import EngineConfig, Schedule, build_state
from .step import make_step

__all__ = ["run_engine_sharded", "pad_instance"]


def pad_instance(cfg: EngineConfig, adj0: np.ndarray, delay0: np.ndarray,
                 n_devices: int):
    """Pad the process axis to a multiple of the device count with inert,
    link-less processes (they never send or receive)."""
    n = cfg.n
    n_pad = (-n) % n_devices
    if n_pad == 0:
        return cfg, adj0, delay0
    adj0 = np.concatenate([adj0, np.full((n_pad, cfg.k), -1, adj0.dtype)])
    delay0 = np.concatenate(
        [delay0, np.ones((n_pad, cfg.k), delay0.dtype)])
    cfg = EngineConfig(n=n + n_pad, k=cfg.k, rounds=cfg.rounds, mode=cfg.mode,
                       pong_delay=cfg.pong_delay, always_gate=cfg.always_gate)
    return cfg, adj0, delay0


def run_engine_sharded(cfg: EngineConfig, sched: Schedule, adj0, delay0,
                       mesh=None):
    """Same contract as ``run_engine`` but state sharded over 'procs'."""
    if mesh is None:
        # Auto axes: the scatters in step.py leave their output sharding
        # to the partitioner (make_mesh defaults to Explicit axes)
        mesh = jax.make_mesh((jax.device_count(),), ("procs",),
                             axis_types=(jax.sharding.AxisType.Auto,))
    n_dev = int(np.prod(mesh.devices.shape))
    cfg, adj0, delay0 = pad_instance(cfg, adj0, delay0, n_dev)

    row = NamedSharding(mesh, P("procs"))
    st = build_state(cfg, sched, adj0, delay0)
    order = ("arr", "delivered", "adj", "delay", "active", "gate", "flush",
             "ping")
    state = tuple(jax.device_put(st[k], row) for k in order)

    step = make_step(cfg, sched)

    def run(state):
        rounds = jnp.arange(cfg.rounds, dtype=jnp.int32)
        final, _ = jax.lax.scan(step, state, rounds)
        return final

    shardings = tuple(row for _ in order)
    run_c = jax.jit(run, in_shardings=(shardings,),
                    out_shardings=shardings)
    final = run_c(state)
    return np.asarray(final[1])
