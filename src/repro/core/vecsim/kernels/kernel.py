"""Pallas kernels for the vecsim per-round delivery sweep.

The protocol's per-hop work at scale is a handful of dense passes over
the live column window (DESIGN.md §2.6): the arrival-plane comparison
that turns arrivals into deliveries, the flush-window comparison over
gated links, and the scatter-min flood-forward of this round's
deliveries.  These kernels fuse those passes so each round touches the
``(N, W)`` planes once instead of once per phase:

  * :func:`fused_sweep_kernel` — the gating-free hot path (sustained
    traffic, no link additions): deliver-gate comparison, per-row
    NetStats counts and the K-slot forward scatter-min in ONE pass;
  * :func:`deliver_sweep_kernel` / :func:`frontier_sweep_kernel` — the
    gated split: pong detection (a cross-column gather) must observe
    post-delivery state, so delivery lands first, the pong ring runs in
    lax between, and the flush+forward scatter fuses into the second
    kernel (same fusion the sharded engine applies via ``gk_eff``);
  * :func:`retire_scan_kernel` — the per-column retirement reductions
    (delivery counts, alive-delivery counts, gate-blocked counts) the
    windowed driver decides retirement from;
  * :func:`slot_frontier_kernel` / :func:`ring_apply_kernel` — the
    per-shard decomposition: one slot's combined flush+forward value
    plane, and the owner-local scatter-min applied at each ring hop of
    the sharded frontier exchange.

Layout: the grid tiles the **column** axis only.  Forward/flush writes
for message column ``m`` land in column ``m`` of the target row, so
column tiles are fully independent grid programs; the process axis
stays whole inside each program because the scatter targets arbitrary
rows.  The scatter itself is a ``fori_loop`` over sender rows with a
dynamic-row read-modify-write — the Pallas idiom for a scatter the VPU
has no native primitive for.  Scatter-min over int32 is associative and
commutative, so the sequential in-kernel accumulation is bit-equal to
the backends' global ``np.minimum.at`` / ``.at[].min`` scatters.

Counter outputs are int32: per-tile partials are bounded by N·BW
(rows times tile width), which holds far past the engine's memory
ceiling; the int64 NetStats math happens in lax outside the kernels.

``ops.py`` runs every kernel through the Pallas interpreter (plain
jitted XLA ops) — the CPU testing mode under which the whole scenario
matrix cross-validates byte-identical against the jax backend.  These
kernels do not compile for the TPU (DESIGN.md §2.6): Mosaic rejects
the dynamic row read of a value in ``_scatter_links`` and the 1-D bool
blocks, and a whole-N block outgrows VMEM at real N.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..scenario import INF

__all__ = ["fused_sweep_kernel", "deliver_sweep_kernel",
           "frontier_sweep_kernel", "retire_scan_kernel",
           "retire_reduce_kernel", "latency_hist_kernel",
           "slot_frontier_kernel", "ring_apply_kernel"]

_INF = np.int32(INF)


def _deliver(t, arr, delivered, crashed, app):
    """Phase 5: arrivals -> deliveries, plus this round's app/ping
    per-row delivery counts for the NetStats accumulators."""
    newly = (arr == t) & (delivered < 0) & ~crashed[:, None]
    delivered = jnp.where(newly, t, delivered)
    new_del = delivered == t
    napp = (new_del & app[None, :]).sum(axis=1).astype(jnp.int32)
    nping = (new_del & ~app[None, :]).sum(axis=1).astype(jnp.int32)
    return delivered, napp, nping


def deliver_sweep_kernel(t_ref, crashed_ref, is_app_ref, arr_ref,
                         delivered_ref, out_del_ref, napp_ref, nping_ref):
    """Delivery gating over one column tile: ``arr == t`` arrivals not
    yet delivered (and not crashed) deliver at ``t``; emits the updated
    tile plus per-row app/ping delivery-count partials."""
    t = t_ref[0]
    delivered, napp, nping = _deliver(
        t, arr_ref[...], delivered_ref[...], crashed_ref[...],
        is_app_ref[...])
    out_del_ref[...] = delivered
    napp_ref[0, :] = napp
    nping_ref[0, :] = nping


def _scatter_links(t, out_arr_ref, delivered, app, adj_ref, delay_ref,
                   gate_ref, do_ref, fwd_ref, *, k: int, n: int,
                   gating: bool):
    """The K-slot scatter-min: for every sender row ``p`` and link slot
    ``kk``, min-combine the forward contribution (columns delivered this
    round, link forward-eligible) with the flush contribution (columns
    in the gate window, link flushing this round) and scatter the value
    row into the target's row of ``out_arr_ref``.  Row-sequential
    accumulation == the global scatter-min (min commutes)."""

    def body(p, _):
        row_del = delivered[p, :]
        new_row = row_del == t
        for kk in range(k):
            fwd_p = fwd_ref[p, kk]
            send_p = (fwd_p | do_ref[p, kk]) if gating else fwd_p

            @pl.when(send_p)
            def _send():
                tgt = adj_ref[p, kk]
                dk = (t + delay_ref[p, kk]).astype(jnp.int32)
                vals = jnp.where(new_row & fwd_p, dk, _INF)
                if gating:
                    win = ((row_del >= gate_ref[p, kk]) & (row_del < t)
                           & do_ref[p, kk] & app)
                    vals = jnp.minimum(vals, jnp.where(win, dk, _INF))
                out_arr_ref[tgt, :] = jnp.minimum(out_arr_ref[tgt, :], vals)
        return 0

    jax.lax.fori_loop(0, n, body, 0)


def fused_sweep_kernel(t_ref, crashed_ref, is_app_ref, adj_ref, delay_ref,
                       fwd_ref, arr_ref, delivered_ref, out_arr_ref,
                       out_del_ref, napp_ref, nping_ref, *, k: int, n: int):
    """The gating-free fused round sweep (phases 5 + 8): deliver-gate
    the tile, count, and scatter-min this round's deliveries over every
    forward-eligible link — one pass over the live column window."""
    t = t_ref[0]
    app = is_app_ref[...]
    delivered, napp, nping = _deliver(
        t, arr_ref[...], delivered_ref[...], crashed_ref[...], app)
    out_del_ref[...] = delivered
    napp_ref[0, :] = napp
    nping_ref[0, :] = nping
    out_arr_ref[...] = arr_ref[...]
    _scatter_links(t, out_arr_ref, delivered, app, adj_ref, delay_ref,
                   None, None, fwd_ref, k=k, n=n, gating=False)


def frontier_sweep_kernel(t_ref, adj_ref, delay_ref, gate_ref, do_ref,
                          fwd_ref, is_app_ref, delivered_ref, arr_ref,
                          out_arr_ref, flush_ref, *, k: int, n: int):
    """The gated fused sweep (phases 7 + 8) over one column tile:
    flush-window comparison on flushing links, forward values on safe
    links, one combined scatter-min into the arrival plane, and the
    per-tile flushed-message count.  ``delivered`` is post-phase-5 (the
    pong ring between the two kernels needs it)."""
    t = t_ref[0]
    delivered = delivered_ref[...]
    app = is_app_ref[...]
    out_arr_ref[...] = arr_ref[...]
    flushed = jnp.int32(0)
    for kk in range(k):
        win = ((delivered >= gate_ref[:, kk][:, None]) & (delivered < t)
               & do_ref[:, kk][:, None] & app[None, :])
        flushed += win.sum().astype(jnp.int32)
    flush_ref[0] = flushed
    _scatter_links(t, out_arr_ref, delivered, app, adj_ref, delay_ref,
                   gate_ref, do_ref, fwd_ref, k=k, n=n, gating=True)


def retire_scan_kernel(crashed_ref, min_gate_ref, delivered_ref, cnt_ref,
                       alivedel_ref, blocked_ref):
    """Per-column retirement reductions over one tile: total delivery
    count, alive-row delivery count (the all-alive-delivered rule), and
    the count of deliveries at-or-after the row's earliest open gate
    (the pending-flush blocker)."""
    delivered = delivered_ref[...]
    crashed = crashed_ref[...]
    got = delivered >= 0
    cnt_ref[0, :] = got.sum(axis=0).astype(jnp.int32)
    alivedel_ref[0, :] = (got & ~crashed[:, None]).sum(axis=0).astype(
        jnp.int32)
    blocked_ref[0, :] = (
        got & (delivered >= min_gate_ref[...][:, None])).sum(
        axis=0).astype(jnp.int32)


def retire_reduce_kernel(crashed_ref, min_gate_ref, rounds_ref, arr_ref,
                         delivered_ref, cnt_ref, alivedel_ref, blocked_ref,
                         arrcnt_ref, sumdel_ref):
    """:func:`retire_scan_kernel` plus the record-side reductions —
    first-receipt counts (``arr < rounds``) and the per-column
    delivered-round sum the latency aggregate is derived from
    (``lat = sumdel - cnt·birth``) — so retiring a column needs no
    ``(N, cols)`` host fetch beyond the decision itself.  ``sumdel`` is
    an int32 partial: exact while ``N·rounds < 2^31``, which holds
    through the engine's host-plane memory ceiling."""
    delivered = delivered_ref[...]
    crashed = crashed_ref[...]
    got = delivered >= 0
    cnt_ref[0, :] = got.sum(axis=0).astype(jnp.int32)
    alivedel_ref[0, :] = (got & ~crashed[:, None]).sum(axis=0).astype(
        jnp.int32)
    blocked_ref[0, :] = (
        got & (delivered >= min_gate_ref[...][:, None])).sum(
        axis=0).astype(jnp.int32)
    arrcnt_ref[0, :] = (arr_ref[...] < rounds_ref[0]).sum(axis=0).astype(
        jnp.int32)
    sumdel_ref[0, :] = jnp.where(got, delivered, 0).sum(axis=0).astype(
        jnp.int32)


def latency_hist_kernel(base_ref, delivered_ref, hist_ref, *, nb: int):
    """Per-column log-bucketed delivery-latency counts over one tile.

    Implements the ``repro.obs.hist`` bucket contract (16 exact buckets
    then power-of-two decades) with integer comparisons only, so the
    counts are byte-identical to the numpy/jnp bucketings.  Rows that
    never delivered (``delivered < 0``) and columns with no latency
    base (``base < 0``: ping columns, padding) count nowhere."""
    delivered = delivered_ref[...]
    base = base_ref[...]
    valid = (delivered >= 0) & (base >= 0)[None, :]
    lat = delivered - base[None, :]
    extra = jnp.zeros(lat.shape, jnp.int32)
    for k in range(5, 20):
        extra = extra + (lat >= (1 << k)).astype(jnp.int32)
    bidx = jnp.where(lat < 16, jnp.clip(lat, 0, 15),
                     jnp.minimum(16 + extra, nb - 1))
    for b in range(nb):
        hist_ref[:, b] = ((bidx == b) & valid).sum(axis=0).astype(
            jnp.int32)


def slot_frontier_kernel(t_ref, gate_ref, delay_ref, do_ref, fwd_ref,
                         is_app_ref, delivered_ref, vals_ref, win_ref,
                         *, gating: bool):
    """One link slot's combined flush+forward contribution plane for the
    sharded ring exchange: ``t + delay`` where the (local) sender row
    forwards this round's deliveries or flushes its gate window, INF
    elsewhere.  Also emits the per-tile flushed-message count."""
    t = t_ref[0]
    delivered = delivered_ref[...]
    dk = (t + delay_ref[...])[:, None].astype(jnp.int32)
    vals = jnp.where((delivered == t) & fwd_ref[...][:, None], dk, _INF)
    if gating:
        win = ((delivered >= gate_ref[...][:, None]) & (delivered < t)
               & do_ref[...][:, None] & is_app_ref[...][None, :])
        vals = jnp.minimum(vals, jnp.where(win, dk, _INF))
        win_ref[0] = win.sum().astype(jnp.int32)
    else:
        win_ref[0] = jnp.int32(0)
    vals_ref[...] = vals


def ring_apply_kernel(off_ref, tgt_ref, vals_ref, arr_ref, out_arr_ref,
                      *, n_loc: int):
    """One ring hop's owner-local application: scatter-min the visiting
    value plane's rows into the rows this shard owns (global target row
    in ``[off, off + n_loc)``); everything else passes through."""
    out_arr_ref[...] = arr_ref[...]
    off = off_ref[0]

    def body(p, _):
        tl = tgt_ref[p] - off

        @pl.when((tl >= 0) & (tl < n_loc))
        def _apply():
            out_arr_ref[tl, :] = jnp.minimum(out_arr_ref[tl, :],
                                             vals_ref[p, :])
        return 0

    jax.lax.fori_loop(0, n_loc, body, 0)
