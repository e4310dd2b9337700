"""Dispatch layer for the delivery-sweep Pallas kernels.

Each op pads the live column window to a multiple of the column-tile
width, launches the kernel over a 1-D grid of column tiles, reduces the
per-tile stat partials, and slices the planes back — callers (the span
runners in ``sim.py`` / ``shard/spanner.py`` and the windowed driver's
retirement sweep in ``stream.py``) see exact ``(N, W)`` semantics.

Every op runs through the Pallas interpreter, the only mode these
kernels run in: the interpreter lowers to ordinary jitted XLA ops, so
the pallas backend is byte-identical to (and tests against) the jax
backend on CPU; the padding columns are inert (``arr=INF``,
``delivered=-1``, ``is_app=False``) and can never deliver, flush or
count.

The kernels do not compile for the TPU (DESIGN.md §2.6): Mosaic
rejects the dynamic row read in the scatter loop and the 1-D bool
blocks, and every block holds the whole process axis, which outgrows
VMEM at real N.  :func:`pallas_available` therefore reports them
unavailable on a TPU; ``repro.api`` surfaces the probe's note in
``--list`` and turns a failed probe into a ``SpecError`` when
``backend="pallas"`` is requested explicitly, so a TPU run never falls
back to the interpreter.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

from ..scenario import INF

__all__ = ["PallasUnavailableError", "pallas_available", "require_pallas",
           "deliver_sweep", "fused_sweep",
           "frontier_sweep", "retire_scan", "retire_scan_jit",
           "retire_reduce", "retire_reduce_jit", "latency_hist",
           "latency_hist_jit", "slot_frontier", "ring_apply",
           "pack_columns", "unpack_columns", "popcount_bytes"]

_INF = np.int32(INF)


class PallasUnavailableError(RuntimeError):
    """``backend="pallas"`` was requested but Pallas cannot initialize."""


@functools.lru_cache(maxsize=1)
def pallas_available() -> Tuple[bool, str]:
    """(ok, note): can the Pallas kernels run here, and how."""
    try:
        import jax
        from jax.experimental import pallas  # noqa: F401
    except Exception as exc:  # pragma: no cover - environment-dependent
        return False, f"jax/pallas import failed: {exc}"
    try:
        platform = jax.default_backend()
    except Exception as exc:  # pragma: no cover - environment-dependent
        return False, f"jax backend init failed: {exc}"
    if platform == "tpu":
        return False, ("the delivery-sweep kernels do not compile for the "
                       "TPU (DESIGN.md §2.6)")
    return True, (f"interpret mode on {platform} (byte-identical to the "
                  "jax backend; a correctness check, not a speed path)")


def require_pallas() -> None:
    ok, note = pallas_available()
    if not ok:
        raise PallasUnavailableError(
            f"backend='pallas' requested but Pallas cannot initialize "
            f"({note}); use backend='jax' or 'auto'")


def _tiles(w: int, block_w: Optional[int]) -> Tuple[int, int, int]:
    """(padded width, tile width, tile count) for a ``w``-column window."""
    bw = int(block_w) if block_w else max(w, 1)
    bw = max(1, min(bw, max(w, 1)))
    wp = -(-max(w, 1) // bw) * bw
    return wp, bw, wp // bw


def _pad_cols(x, wp: int, fill):
    import jax.numpy as jnp
    w = x.shape[-1]
    if w == wp:
        return x
    widths = [(0, 0)] * (x.ndim - 1) + [(0, wp - w)]
    return jnp.pad(x, widths, constant_values=fill)


def _t_arr(t):
    import jax.numpy as jnp
    return jnp.asarray(t, jnp.int32).reshape(1)


# --------------------------------------------------------------------- #
# Frontier bit-plane helpers (scan-compatible, plain lax)
#
# The scanned sharded fast body (shard/spanner.py) moves the per-round
# delivery frontier around the ring as a bit-packed uint8 plane — 8
# columns per byte — so the all-gather ships W/8 bytes per row and the
# stats come from byte popcounts instead of full-width boolean
# reductions.  These are ordinary jittable jnp ops (usable inside
# lax.scan and shard_map on any backend, no Pallas required) and use
# numpy packbits(bitorder="little") bit order, so hosts and kernels
# agree on the layout.
# --------------------------------------------------------------------- #
def _bit_shifts():
    import jax.numpy as jnp
    return jnp.left_shift(jnp.uint8(1), jnp.arange(8, dtype=jnp.uint8))


def pack_columns(b):
    """Bit-pack an ``(N, W)`` bool plane into ``(N, ceil(W/8))`` uint8
    (little-endian bit order; ragged tail bits are zero)."""
    import jax.numpy as jnp
    n, w = b.shape
    wp = -(-max(w, 1) // 8)
    if wp * 8 != w:
        b = jnp.concatenate(
            [b, jnp.zeros((n, wp * 8 - w), bool)], axis=1)
    sh = _bit_shifts()
    return jnp.sum(jnp.where(b.reshape(n, wp, 8), sh[None, None, :],
                             jnp.uint8(0)), axis=2, dtype=jnp.uint8)


def unpack_columns(p, w: int):
    """Inverse of :func:`pack_columns`: ``(N, Wp)`` uint8 back to the
    ``(N, w)`` bool plane."""
    import jax.numpy as jnp
    n, wp = p.shape
    sh = _bit_shifts()
    b = (p[:, :, None] & sh[None, None, :]) > 0
    b = b.reshape(n, wp * 8)
    return b[:, :w] if w != wp * 8 else b


def popcount_bytes(x):
    """Per-byte SWAR popcount of a uint8 array (branch-free, three
    shift/mask rounds — the classic Hacker's Delight reduction)."""
    import jax.numpy as jnp
    x = x - ((x >> 1) & jnp.uint8(0x55))
    x = (x & jnp.uint8(0x33)) + ((x >> 2) & jnp.uint8(0x33))
    return (x + (x >> 4)) & jnp.uint8(0x0F)


def deliver_sweep(arr, delivered, crashed, is_app, t, *,
                  block_w: Optional[int] = None):
    """Phase 5 over the live window: ``(delivered', napp, nping)``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from .kernel import deliver_sweep_kernel
    n, w = arr.shape
    wp, bw, nt = _tiles(w, block_w)
    out_del, napp, nping = pl.pallas_call(
        deliver_sweep_kernel,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((bw,), lambda i: (i,)),
            pl.BlockSpec((n, bw), lambda i: (0, i)),
            pl.BlockSpec((n, bw), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((n, bw), lambda i: (0, i)),
            pl.BlockSpec((1, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, wp), jnp.int32),
            jax.ShapeDtypeStruct((nt, n), jnp.int32),
            jax.ShapeDtypeStruct((nt, n), jnp.int32),
        ],
        interpret=True,
    )(_t_arr(t), crashed, _pad_cols(is_app, wp, False),
      _pad_cols(arr, wp, _INF), _pad_cols(delivered, wp, -1))
    return (out_del[:, :w], napp.sum(axis=0).astype(jnp.int32),
            nping.sum(axis=0).astype(jnp.int32))


def fused_sweep(arr, delivered, crashed, adj, delay, fwd_ok, is_app, t, *,
                block_w: Optional[int] = None):
    """Gating-free fused sweep: ``(arr', delivered', napp, nping)``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from .kernel import fused_sweep_kernel
    n, w = arr.shape
    k = adj.shape[1]
    wp, bw, nt = _tiles(w, block_w)
    out_arr, out_del, napp, nping = pl.pallas_call(
        functools.partial(fused_sweep_kernel, k=k, n=n),
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((bw,), lambda i: (i,)),
            pl.BlockSpec((n, k), lambda i: (0, 0)),
            pl.BlockSpec((n, k), lambda i: (0, 0)),
            pl.BlockSpec((n, k), lambda i: (0, 0)),
            pl.BlockSpec((n, bw), lambda i: (0, i)),
            pl.BlockSpec((n, bw), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((n, bw), lambda i: (0, i)),
            pl.BlockSpec((n, bw), lambda i: (0, i)),
            pl.BlockSpec((1, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, wp), jnp.int32),
            jax.ShapeDtypeStruct((n, wp), jnp.int32),
            jax.ShapeDtypeStruct((nt, n), jnp.int32),
            jax.ShapeDtypeStruct((nt, n), jnp.int32),
        ],
        interpret=True,
    )(_t_arr(t), crashed, _pad_cols(is_app, wp, False), adj, delay, fwd_ok,
      _pad_cols(arr, wp, _INF), _pad_cols(delivered, wp, -1))
    return (out_arr[:, :w], out_del[:, :w],
            napp.sum(axis=0).astype(jnp.int32),
            nping.sum(axis=0).astype(jnp.int32))


def frontier_sweep(arr, delivered, adj, delay, gate, do, fwd_ok, is_app,
                   t, *, block_w: Optional[int] = None):
    """Gated fused sweep (flush + forward): ``(arr', flush_sent)``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from .kernel import frontier_sweep_kernel
    n, w = arr.shape
    k = adj.shape[1]
    wp, bw, nt = _tiles(w, block_w)
    out_arr, flush = pl.pallas_call(
        functools.partial(frontier_sweep_kernel, k=k, n=n),
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((n, k), lambda i: (0, 0)),
            pl.BlockSpec((n, k), lambda i: (0, 0)),
            pl.BlockSpec((n, k), lambda i: (0, 0)),
            pl.BlockSpec((n, k), lambda i: (0, 0)),
            pl.BlockSpec((n, k), lambda i: (0, 0)),
            pl.BlockSpec((bw,), lambda i: (i,)),
            pl.BlockSpec((n, bw), lambda i: (0, i)),
            pl.BlockSpec((n, bw), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((n, bw), lambda i: (0, i)),
            pl.BlockSpec((1,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, wp), jnp.int32),
            jax.ShapeDtypeStruct((nt,), jnp.int32),
        ],
        interpret=True,
    )(_t_arr(t), adj, delay, gate, do, fwd_ok,
      _pad_cols(is_app, wp, False), _pad_cols(delivered, wp, -1),
      _pad_cols(arr, wp, _INF))
    return out_arr[:, :w], flush.sum().astype(jnp.int32)


def retire_scan(delivered, crashed, min_gate, *,
                block_w: Optional[int] = None):
    """Per-column retirement reductions: ``(cnt, alivedel, blocked)``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from .kernel import retire_scan_kernel
    n, w = delivered.shape
    wp, bw, nt = _tiles(w, block_w)
    cnt, alivedel, blocked = pl.pallas_call(
        retire_scan_kernel,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((n, bw), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, bw), lambda i: (0, i)),
            pl.BlockSpec((1, bw), lambda i: (0, i)),
            pl.BlockSpec((1, bw), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, wp), jnp.int32),
            jax.ShapeDtypeStruct((1, wp), jnp.int32),
            jax.ShapeDtypeStruct((1, wp), jnp.int32),
        ],
        interpret=True,
    )(crashed, jnp.asarray(min_gate, jnp.int32),
      _pad_cols(jnp.asarray(delivered, jnp.int32), wp, -1))
    return cnt[0, :w], alivedel[0, :w], blocked[0, :w]


def retire_reduce(arr, delivered, crashed, min_gate, rounds, *,
                  block_w: Optional[int] = None):
    """Per-column retirement *and* record reductions:
    ``(cnt, alivedel, blocked, arrcnt, sumdel)`` — the
    :func:`retire_scan` triple plus the first-receipt count and the
    delivered-round sum, so the windowed driver's pallas retirement
    path records a retired column from five scalars instead of
    re-reading its ``(N,)`` plane slices."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from .kernel import retire_reduce_kernel
    n, w = delivered.shape
    wp, bw, nt = _tiles(w, block_w)
    out = pl.pallas_call(
        retire_reduce_kernel,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((n, bw), lambda i: (0, i)),
            pl.BlockSpec((n, bw), lambda i: (0, i)),
        ],
        out_specs=[pl.BlockSpec((1, bw), lambda i: (0, i))] * 5,
        out_shape=[jax.ShapeDtypeStruct((1, wp), jnp.int32)] * 5,
        interpret=True,
    )(crashed, jnp.asarray(min_gate, jnp.int32), _t_arr(rounds),
      _pad_cols(jnp.asarray(arr, jnp.int32), wp, INF),
      _pad_cols(jnp.asarray(delivered, jnp.int32), wp, -1))
    return tuple(x[0, :w] for x in out)


@functools.lru_cache(maxsize=None)
def retire_reduce_jit(block_w: Optional[int] = None):
    """Cached jitted :func:`retire_reduce` (same treatment as
    :func:`retire_scan_jit`)."""
    import jax
    return jax.jit(functools.partial(retire_reduce, block_w=block_w))


def latency_hist(base, delivered, *, block_w: Optional[int] = None):
    """Per-column ``(W, NB)`` delivery-latency histogram: row p of
    column m counts in bucket(delivered[p, m] - base[m]) when the row
    delivered and the column carries a latency base (``base >= 0``).
    The bucket layout is the ``repro.obs.hist`` contract."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from ....obs.hist import NB
    from .kernel import latency_hist_kernel
    n, w = delivered.shape
    wp, bw, nt = _tiles(w, block_w)
    out = pl.pallas_call(
        functools.partial(latency_hist_kernel, nb=NB),
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((bw,), lambda i: (i,)),
            pl.BlockSpec((n, bw), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((bw, NB), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((wp, NB), jnp.int32),
        interpret=True,
    )(_pad_cols(jnp.asarray(base, jnp.int32), wp, -1),
      _pad_cols(jnp.asarray(delivered, jnp.int32), wp, -1))
    return out[:w]


@functools.lru_cache(maxsize=None)
def latency_hist_jit(block_w: Optional[int] = None):
    """Cached jitted :func:`latency_hist` (same treatment as
    :func:`retire_reduce_jit`)."""
    import jax
    return jax.jit(functools.partial(latency_hist, block_w=block_w))


@functools.lru_cache(maxsize=None)
def retire_scan_jit(block_w: Optional[int] = None):
    """Cached jitted :func:`retire_scan` for eager per-segment host
    calls (the windowed driver's retirement sweep): the span runners
    amortize their traces through ``lru_cache``d jitted scans, and this
    gives the host-side reduction the same treatment — one trace per
    plane shape instead of a fresh interpreter lowering every sweep."""
    import jax
    return jax.jit(functools.partial(retire_scan, block_w=block_w))


def slot_frontier(delivered, gate_k, delay_k, do_k, fwd_k, is_app, t, *,
                  gating: bool, block_w: Optional[int] = None):
    """One slot's ring contribution plane: ``(vals, win_cnt)``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from .kernel import slot_frontier_kernel
    n, w = delivered.shape
    wp, bw, nt = _tiles(w, block_w)
    vals, win = pl.pallas_call(
        functools.partial(slot_frontier_kernel, gating=gating),
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((bw,), lambda i: (i,)),
            pl.BlockSpec((n, bw), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((n, bw), lambda i: (0, i)),
            pl.BlockSpec((1,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, wp), jnp.int32),
            jax.ShapeDtypeStruct((nt,), jnp.int32),
        ],
        interpret=True,
    )(_t_arr(t), gate_k, delay_k, do_k, fwd_k,
      _pad_cols(is_app, wp, False), _pad_cols(delivered, wp, -1))
    return vals[:, :w], win.sum().astype(jnp.int32)


def ring_apply(arr, vals, tgt, off, *, block_w: Optional[int] = None):
    """Owner-local scatter-min of a visiting ring plane: ``arr'``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from .kernel import ring_apply_kernel
    n, w = arr.shape
    wp, bw, nt = _tiles(w, block_w)
    out = pl.pallas_call(
        functools.partial(ring_apply_kernel, n_loc=n),
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((n, bw), lambda i: (0, i)),
            pl.BlockSpec((n, bw), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((n, bw), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n, wp), jnp.int32),
        interpret=True,
    )(jnp.asarray(off, jnp.int32).reshape(1), tgt,
      _pad_cols(vals, wp, _INF), _pad_cols(arr, wp, _INF))
    return out[:, :w]
