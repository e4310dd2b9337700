"""RG-LRU linear scan as a Pallas TPU kernel.

h_t = a_t * h_{t-1} + b_t, elementwise over the LRU width.  Grid:
(B, W/BW, S/BS) — the sequence axis innermost (sequential); the running
state (1, BW) lives in VMEM scratch.  Within a block the recurrence is a
``fori_loop`` of fused multiply-adds over rows — VPU work (this kernel is
bandwidth-bound by construction: 2 loads + 1 store per element), so the
tile choice (BW = 128 lanes, BS = 256 rows) is about HBM->VMEM pipelining,
not the MXU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["rglru_scan_kernel", "rglru_scan_pallas"]


def rglru_scan_kernel(a_ref, b_ref, h0_ref, h_ref, state_ref):
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        state_ref[...] = h0_ref[0].astype(jnp.float32)      # (1, BW)

    a = a_ref[0].astype(jnp.float32)                        # (BS, BW)
    b = b_ref[0].astype(jnp.float32)

    def body(t, h):
        h = a[t][None, :] * h + b[t][None, :]
        # Index every axis with a slice: a bare int index reaches the
        # swap discharge rule as a scalar without a .shape and crashes
        # interpret mode, so the leading block axis uses pl.dslice too.
        h_ref[pl.ds(0, 1), pl.ds(t, 1), :] = h[:, None, :].astype(
            h_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, a.shape[0], body, state_ref[...])
    state_ref[...] = h


def rglru_scan_pallas(a, b, h0, *, block_s: int = 256, block_w: int = 128,
                      interpret: bool = False):
    """a, b (B, S, W); h0 (B, W) -> h (B, S, W) with h[:, t] the state
    after step t.  S, W must be multiples of the blocks (ops.py pads W;
    S padding with a=1, b=0 keeps trailing state exact)."""
    bb, s, w = a.shape
    assert s % block_s == 0 and w % block_w == 0, (s, w)

    grid = (bb, w // block_w, s // block_s)

    def abmap(i, jw, js):
        return (i, js, jw)

    def h0map(i, jw, js):
        return (i, 0, jw)

    h = pl.pallas_call(
        rglru_scan_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_s, block_w), abmap),
            pl.BlockSpec((1, block_s, block_w), abmap),
            pl.BlockSpec((1, 1, block_w), h0map),
        ],
        out_specs=pl.BlockSpec((1, block_s, block_w), abmap),
        out_shape=jax.ShapeDtypeStruct((bb, s, w), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)],
        interpret=interpret,
    )(a, b, h0[:, None, :])
    return h
