"""Pipeline parallelism (GPipe schedule) as a composable JAX transform.

``pipeline(stage_fn)`` runs a stack of S stages (params stacked on the
leading axis, sharded one-per-device over a ``stage`` mesh axis) over M
microbatches with the classic skewed clock: tick t feeds stage s the
microbatch (t - s), activations hop stage->stage via ``ppermute``.  The
whole schedule is a ``lax.scan`` inside ``shard_map``, so:

  * forward fills/drains the pipeline in M + S - 1 ticks (bubble
    fraction (S-1)/(M+S-1) — the standard GPipe bubble);
  * JAX AD differentiates straight through (ppermute transposes to the
    reverse shift), recovering the backward pipeline automatically;
  * per-stage remat bounds stashed activations to one microbatch per
    tick per stage.

The model stack plugs in by treating one superblock (or a run of them)
as ``stage_fn`` — see tests/test_pipeline.py for the wiring; the
production mesh would carry a ("stage", "data", "model") layout with
this transform on the outermost axis.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["pipeline"]


def pipeline(stage_fn: Callable, mesh: Mesh, axis: str = "stage",
             remat_stage: bool = True):
    """Build a pipelined apply: (stacked_params, microbatches) -> outputs.

    stage_fn(params_slice, x) -> y  must map (B, ...) -> (B, ...) with the
    same shape/dtype (a residual-stream stage).

    stacked_params: pytree with leading dim S (sharded over ``axis``);
    microbatches:   (M, B, ...) array (replicated over ``axis``).
    Returns (M, B, ...) outputs of the last stage.
    """
    n_stage = mesh.shape[axis]
    fn = jax.checkpoint(stage_fn) if remat_stage else stage_fn

    def run(params, mb):
        m = mb.shape[0]
        ticks = m + n_stage - 1

        def local(params_l, mb_l):
            # params_l: (1, ...) this device's stage; mb_l: (M, B, ...)
            p_here = jax.tree.map(lambda t: t[0], params_l)
            sid = jax.lax.axis_index(axis)
            state = jnp.zeros_like(mb_l[0])          # current activation
            outs = jnp.zeros_like(mb_l)              # last stage collects

            def tick(carry, t):
                state, outs = carry
                # stage 0 ingests microbatch t (when in range)
                feed = mb_l[jnp.clip(t, 0, m - 1)]
                x = jnp.where(sid == 0, feed, state)
                y = fn(p_here, x)
                # last stage emits microbatch (t - S + 1)
                out_idx = t - (n_stage - 1)
                valid = (out_idx >= 0) & (sid == n_stage - 1)
                outs = jax.lax.dynamic_update_index_in_dim(
                    outs,
                    jnp.where(valid, y, outs[jnp.clip(out_idx, 0, m - 1)]),
                    jnp.clip(out_idx, 0, m - 1), axis=0)
                # hop to the next stage
                nxt = jax.lax.ppermute(
                    y, axis, [(i, (i + 1) % n_stage)
                              for i in range(n_stage)])
                return (nxt, outs), None

            (state, outs), _ = jax.lax.scan(
                tick, (state, outs), jnp.arange(ticks))
            # only the last stage ever wrote into ``outs`` (others kept
            # zeros), so a psum over the stage axis replicates the result
            return jax.lax.psum(outs, axis)

        run_sharded = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(axis), P()),       # params sharded, mb replicated
            out_specs=P(),                  # replicated output
            check_vma=False,
        )
        return run_sharded(params, mb)

    return run
