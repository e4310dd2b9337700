"""``shard_map`` span runner: the windowed round body over a device mesh.

Partitioning (DESIGN.md §2.5): every per-process plane — ``arr`` /
``delivered`` ``(N, W)`` buffers, the ``(N, K)`` adjacency/delay/gating
tables, ``crashed``/``ever_del`` — is row-block sharded over a 1-D
``("shard",)`` mesh; schedules, the ``is_app`` column mask and the round
index stream are replicated.  Per round, three things cross shards:

  * **frontier exchange** — the flood-forward + flush scatter (monolithic
    phases 7/8) becomes a ring: each device's contribution plane per
    link slot (a value where sending, ``INF`` elsewhere, with its global
    target rows) visits every device via ``lax.ppermute``; each visit
    scatter-mins the rows it owns.  Scatter-min is associative and
    commutative on ints, so the result is bit-equal to the monolithic
    global scatter regardless of hop order.  On the jax backend the
    forward half runs at the width of the round's columns with a new
    delivery: the narrowest of :data:`FRONTIER_WIDTHS` that holds them
    (the same on every shard, a ``pmax``), the columns picked by a
    one-hot contraction, the link delay riding the ring and one
    contraction expanding the result back to W; full width above that.
    Flushes can reach columns whose wave has ended, so the flushing rows
    go at full width, rank-compacted in chunks of :data:`PONG_ROWS`
    rows;
  * **pong query ring** — pong detection reads ``delivered[q, s]`` at the
    gated link's remote target, for the candidate slots only (a live
    gate, flush pending, ping set, process up).  Their rows are compacted
    by rank and asked in chunks of :data:`PONG_ROWS` rows: each chunk's
    ``(PONG_ROWS, K)`` query triples (target, ping slot, answer) ride a
    second ring and come home after D hops.  Every shard runs the same
    number of chunks (a ``pmax`` of the per-shard count), so the ring's
    hops match; a round with no candidate runs none.  Elided
    entirely (with the whole gating machinery) when the scenario
    schedules no additions;
  * **stats psum** — the per-round series row is ``psum``-reduced so every
    shard returns the identical replicated ``(rounds, 6)`` series.

Everything else is owner-local: schedule events (removals, additions
with the Algorithm 2 gating decision, crashes, broadcasts) apply on the
shard owning their process row and drop elsewhere; arrivals/deliveries
are element-wise.  The retirement kernels at the bottom give the host
driver (``driver.py``) per-column aggregates (``psum`` over the mesh)
and a masked column-recycle, so state never leaves the devices between
segments.

The body mirrors ``sim.jax_span_runner`` operation for operation —
tests assert byte-identical delivered/series/NetStats against the
windowed engine at every device count.

**Scanned segments (DESIGN.md §2.7).**  With ``scan=True`` the whole
segment runs as one ``lax.scan`` over rounds *inside* the ``shard_map``
body: the host dispatches once per segment instead of once per round,
schedules arrive as stacked per-round scan inputs, the state tuple is
donated (``donate_argnums``), and the frontier exchange is
double-buffered — round ``r``'s ring contributions land in a
``pending`` carry plane and fold into ``arr`` at the top of round
``r + 1`` (every contribution values ``>= r + 1``, and nothing reads
``arr`` between the scatter and the fold, so the deferral is exact; a
residual fold after the scan covers the last round).  For segments
whose topology is static and whose gating machinery is quiescent, the
driver swaps in :func:`shard_fast_span_runner`, which additionally
keeps the live planes in int16, moves the frontier as a bit-packed
uint8 plane via an all-gather ring, and turns the per-target scatter
into gathers over host-built inverse-adjacency tables
(:func:`~repro.core.vecsim.shard.mesh.inverse_tables`).
"""

from __future__ import annotations

import functools

from ..scenario import INF
from ..sim import SERIES_FIELDS, _STATE_KEYS
from .mesh import shard_mesh

__all__ = ["shard_span_runner", "shard_fast_span_runner",
           "shard_retire_kernels", "shard_hist_runner",
           "shard_column_gather",
           "resolve_shard_backend", "resolve_scan", "STATE_KEYS",
           "INT16_LIMIT", "PONG_ROWS", "FRONTIER_WIDTHS",
           "SEGMENT_PROGRAMS", "PHASE_SCOPES"]

STATE_KEYS = _STATE_KEYS

# The jitted segment programs' names (their XLA modules are ``jit_<name>``)
# and the ``jax.named_scope`` phases inside them, which the compiled
# program carries in each instruction's ``op_name``: a profiler trace's
# device time is read back by these names (``bench/``, DESIGN.md §2.10),
# so they change only together with that reduction.
SEGMENT_PROGRAMS = ("segment_gated", "segment_fast")
PHASE_SCOPES = ("fold", "removals", "additions", "crashes", "broadcasts",
                "deliveries", "pong", "flush_forward", "stats",
                "retire_reduce", "convert")

# Rows of the (N/D, K) tables one chunk of the pong query reads (clamped
# to N/D).  A round answers its candidate rows in ceil(rows / PONG_ROWS)
# chunks, so a chunk is a fixed-shape gather of PONG_ROWS * K slots of
# ``delivered`` and the read volume follows the live gates, not N.
PONG_ROWS = 128

# Widths the gated body's frontier exchange may run at, narrowest first:
# the plane's lane tile and twice it.  A round runs at the narrowest
# that holds every column with a new delivery (the wave's columns), or
# at the window's full width when none does.
FRONTIER_WIDTHS = (128, 256)

# int16 ceiling of the fast scanned body: arrival rounds live in int16
# planes there, with this value standing in for INF.  The driver only
# selects the fast body when rounds + max_delay stays safely below it.
INT16_LIMIT = 32767


def resolve_scan(scan: str) -> str:
    """Resolve the sharded engine's ``scan`` knob — the one place the
    accepted names live.  ``"auto"`` resolves to ``"on"``: the scanned
    segment body is a pure jax program, so wherever the mesh runs at
    all it runs scanned; ``"off"`` keeps the per-round host-driven
    stepping (the byte-level reference path)."""
    if scan == "auto":
        return "on"
    if scan in ("on", "off"):
        return scan
    raise ValueError(f"unknown scan mode {scan!r} (the sharded segment "
                     "loop runs scan 'auto', 'on' or 'off')")


def resolve_shard_backend(backend: str) -> str:
    """Validate/resolve the sharded engine's round-body backend — the
    one place the accepted names live.  ``"jax"`` passes through,
    ``"pallas"`` requires the kernels to initialize, and ``"auto"``
    resolves to jax like the other engines."""
    if backend == "auto":
        backend = "jax"
    if backend == "pallas":
        from .. import kernels
        kernels.require_pallas()
    elif backend != "jax":
        raise ValueError(f"unknown sharded backend {backend!r} (the mesh "
                         "program runs backend 'jax' or 'pallas')")
    return backend


def _shift(d: int):
    """Forward ring permutation on the ``shard`` axis."""
    return [(i, (i + 1) % d) for i in range(d)]


def _column_partials(state, origins, rounds, off):
    """This shard's contribution to the per-column retirement
    aggregates — the single definition both consumers trace through:
    :func:`shard_retire_kernels`'s standalone ``reduce`` (the scan="off"
    and drain paths) and the fused reduce at the tail of the scanned
    span runners, so the device-resident retirement decisions cannot
    drift from the reference reduction.  Returns the 8-tuple
    ``(cnt, arrcnt, sumdel, alive, alivedel, blocked, ref, bdone)``
    *before* the mesh ``psum``; callers psum it across shards.

    Deliberately telemetry-free: the delivery-latency histogram is a
    separate retirement-time dispatch that counts only the retiring
    columns (:func:`shard_hist_runner` on accelerator meshes), so
    enabling telemetry never re-traces or slows the segment bodies
    (DESIGN.md §2.10).
    """
    import jax.numpy as jnp

    (arr, delivered, adj, delay, active, gate, flush, ping,
     crashed, ever_del) = state
    n_loc, w = arr.shape
    inf = jnp.int32(INF)
    got = delivered >= 0
    cnt = got.sum(axis=0).astype(jnp.int64)
    arrcnt = (arr < rounds).sum(axis=0).astype(jnp.int64)
    sumdel = jnp.where(got, delivered, 0).sum(axis=0).astype(jnp.int64)
    alive = (~crashed).sum().astype(jnp.int64)
    alivedel = (got & ~crashed[:, None]).sum(axis=0).astype(jnp.int64)
    gated = (gate >= 0) & active & ~crashed[:, None]
    min_gate = jnp.where(gated, gate, inf).min(axis=1)
    blocked = ((got & (delivered >= min_gate[:, None]))
               .sum(axis=0).astype(jnp.int64))
    pidx = jnp.where((ping >= 0) & ~crashed[:, None], ping,
                     w).reshape(-1)
    ref = jnp.zeros(w, jnp.int64).at[pidx].add(1, mode="drop")
    ol = origins - off
    owned = (ol >= 0) & (ol < n_loc) & (origins >= 0)
    ocl = jnp.clip(ol, 0, n_loc - 1)
    bdone = jnp.where(owned, got[ocl, jnp.arange(w)],
                      False).astype(jnp.int64)
    return (cnt, arrcnt, sumdel, alive, alivedel, blocked, ref, bdone)


@functools.lru_cache(maxsize=None)
def shard_span_runner(n_devices: int, k: int, pc: bool, always_gate: bool,
                      pong_delay: int, gating: bool = True,
                      backend: str = "jax", scan: bool = False):
    """Jitted sharded span runner; per-round (``scan=False``) it is
    ``(state, sched, ts) -> (state, stats)`` — the contract of
    :func:`~repro.core.vecsim.sim.jax_span_runner` with state as
    row-block-sharded global arrays.  Scanned (``scan=True``) it takes
    ``(state, sched, ts, origins, rounds)`` and additionally returns the
    fused per-column retirement aggregates (``_column_partials``,
    psum'd), so a segment is one dispatch with no standalone reduce;
    the aggregates carry a ninth entry, the segment's int64 counters by
    name where it has any: ``pong.queries`` and ``pong.chunks`` (the
    candidate slots the pong query asked and the chunks it ran) with
    gating live, ``frontier.columns`` and ``frontier.full_rounds`` (the
    columns with a new delivery summed over rounds, and the rounds whose
    exchange ran at full width) on the jax backend.
    Negative rounds in ``ts`` are padding and leave the state untouched.
    One compilation per (mesh, shape) signature, cached.

    ``backend="pallas"`` launches the delivery-sweep kernels
    (``vecsim.kernels``) per shard inside the ``shard_map`` body: the
    deliver sweep on the local row block, one ``slot_frontier`` kernel
    per link slot building the combined flush+forward contribution
    plane, and a ``ring_apply`` kernel at each ring hop scattering the
    visiting plane into the rows this shard owns.  The ring permutes
    and the pong query ring stay ``lax.ppermute`` — byte-identical to
    the jax body at every device count.

    ``scan=True`` is the device-resident segment loop: the ``lax.scan``
    over rounds moves *inside* the ``shard_map`` body, ``sched``'s
    event fields become stacked ``(seg_len, cap)`` per-round planes
    (``ColumnWindow.stacked_schedule``), the state argument is donated,
    and the frontier exchange double-buffers through a ``pending``
    carry plane: round ``r``'s ring scatter lands in ``pending`` and
    folds into ``arr`` at the top of round ``r + 1`` (exact — every
    contribution values ``>= r + 1`` and nothing reads ``arr`` in
    between), with a residual fold after the scan.  Byte-identical to
    ``scan=False`` per construction; ``tests/test_vecsim_scan.py``
    asserts it."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    backend = resolve_shard_backend(backend)
    pallas = backend == "pallas"
    if pallas:
        from .. import kernels as kx

    mesh = shard_mesh(n_devices)
    d = n_devices
    inf = jnp.int32(INF)
    perm = _shift(d)
    # the per-round counters real_step returns, summed per segment
    counter_names = ((("pong.queries", "pong.chunks") if pc and gating
                      else ())
                     + (() if pallas
                        else ("frontier.columns", "frontier.full_rounds")))

    def ranked(mask, m):
        """Rank-compact the rows where ``mask`` holds, for chunks of
        ``m`` rows: ``rank[r]`` counts them in ``[0, r]``, and every
        shard runs the same number of chunks (a ring's ppermutes must
        match)."""
        rank = jnp.cumsum(mask, dtype=jnp.int32)
        chunks = (rank[-1] + (m - 1)) // m
        if d > 1:
            chunks = jax.lax.pmax(chunks, "shard")
        return rank, chunks

    def chunk_rows(rank, c, m):
        """Chunk ``c``'s rows, those of rank ``c*m+1 .. c*m+m``; the
        row count (out of range, dropped) where there are fewer."""
        want = c * m + jnp.arange(1, m + 1, dtype=jnp.int32)
        return jnp.searchsorted(rank, want, side="left").astype(jnp.int32)

    def ring_min(dest, vals, tgt, off, apply=None):
        """Scatter-min ``vals`` (one row per global target ``tgt``) into
        ``dest``: the plane visits every shard around the ring, and each
        visit lands the rows that shard owns."""
        n_loc = dest.shape[0]
        tgt = tgt.astype(jnp.int32)
        for hop in range(d):
            if apply is not None:
                dest = apply(dest, vals, tgt)
            else:
                tl = tgt - off
                rows = jnp.where((tl >= 0) & (tl < n_loc), tl, n_loc)
                dest = dest.at[rows, :].min(vals, mode="drop")
            if hop < d - 1:
                vals = jax.lax.ppermute(vals, "shard", perm)
                tgt = jax.lax.ppermute(tgt, "shard", perm)
        return dest

    def forward(new_del, fwd, delay, adj, t, off, base):
        """This round's flood-forward contributions (``t + delay`` over
        every slot in ``fwd`` of a row with a new delivery), min-combined
        into ``base`` (or a fresh INF plane).  The exchange runs at the
        narrowest of :data:`FRONTIER_WIDTHS` that holds the columns with
        a new delivery anywhere on the mesh, at full width above that.
        Returns the plane, the active column count and whether the round
        ran at full width."""
        n_loc, width = new_del.shape

        def exchange(plane, value, acc, none):
            # one slot at a time, so one contribution plane is live
            def one(kk, acc):
                def col(a):
                    return jax.lax.dynamic_index_in_dim(a, kk, axis=1,
                                                        keepdims=False)
                vals = jnp.where(plane & col(fwd)[:, None],
                                 col(value)[:, None], none)
                return ring_min(acc, vals, col(adj), off)
            return jax.lax.fori_loop(0, k, one, acc)

        def full(*base):
            dest = base[0] if base else jnp.full((n_loc, width), inf)
            return exchange(new_del, t + delay, dest, inf)

        act = new_del.any(axis=0)
        if d > 1:
            # every shard picks the same width and columns
            act = jax.lax.pmax(act.astype(jnp.int32), "shard") > 0
        # the active columns' ranks: compact column c holds rank c + 1
        rank = jnp.cumsum(act, dtype=jnp.int32)
        n_act = rank[-1].astype(jnp.int64)
        widths = [c for c in FRONTIER_WIDTHS if c < width]
        if not widths:
            return full(*base), n_act, jnp.int64(1)

        def compact(c):
            def run(*base):
                # one-hot (W, c) selection of the active columns; with
                # 0/1 operands and one term per sum both contractions
                # are exact
                pick = rank[:, None] == jnp.arange(1, c + 1,
                                                   dtype=jnp.int32)
                sel = (act[:, None] & pick).astype(jnp.bfloat16)
                nd = jnp.dot(new_del.astype(jnp.bfloat16), sel) > 0
                # the exchange carries the link delay, not the round, so
                # int16 holds it (half the bytes of a scattered row)
                none = jnp.int16(INT16_LIMIT)
                lag = exchange(nd, delay.astype(jnp.int16),
                               jnp.full((n_loc, c), none), none)
                lag = jnp.where(lag < none, lag, 0).astype(jnp.bfloat16)
                e = jnp.dot(lag, sel.T).astype(jnp.int32)
                got = jnp.where(e > 0, t + e, inf)
                return jnp.minimum(base[0], got) if base else got
            return run

        # bf16 holds every integer delay in [1, 256] exactly, and
        # `e > 0` marks a contribution only if no delay is 0
        fits = jnp.all(~fwd | ((delay >= 1) & (delay <= 256)))
        if d > 1:
            fits = jax.lax.pmin(fits.astype(jnp.int32), "shard") > 0
        idx = sum((n_act > c).astype(jnp.int32) for c in widths)
        idx = jnp.where(fits, idx, len(widths))
        dest = jax.lax.switch(idx, [compact(c) for c in widths] + [full],
                              *base)
        return dest, n_act, (idx == len(widths)).astype(jnp.int64)

    def flush_rows(dest, delivered, do, gate, delay, adj, is_app, t, off):
        """Algorithm 2's flushes: each slot in ``do`` sends its row's
        app deliveries in ``[gate, t)`` at ``t + delay``.  The window can
        reach columns whose wave has ended, so these rows go at full
        width, rank-compacted in chunks of :data:`PONG_ROWS` rows (about
        one row flushes per round).  Returns the plane and the flushed
        (row, column) count."""
        n_loc, width = delivered.shape
        m = min(PONG_ROWS, n_loc)
        rank, chunks = ranked(do.any(axis=1), m)
        slots = jnp.arange(k, dtype=jnp.int32)[None, :]

        def one(c, carry):
            dest, sent = carry
            rows = chunk_rows(rank, c, m)
            r = jnp.minimum(rows, n_loc - 1)
            # point indices (row, slot) into the tables: see the pong query
            at = (r[:, None], slots)
            got = delivered[r][:, None, :]
            win = ((got >= gate[at][:, :, None]) & (got < t)
                   & (do[at] & (rows < n_loc)[:, None])[:, :, None]
                   & is_app[None, None, :])
            vals = jnp.where(win, (t + delay[at])[:, :, None], inf)
            dest = ring_min(dest, vals.reshape(m * k, width),
                            adj[at].reshape(-1), off)
            return dest, sent + win.sum(dtype=jnp.int64)

        return jax.lax.fori_loop(0, chunks, one, (dest, jnp.int64(0)))

    def real_step(sched, state, t, pending=None):
        deferred = pending is not None
        (arr, delivered, adj, delay, active, gate, flush, ping,
         crashed, ever_del) = state
        if deferred:
            # double-buffered frontier: the previous round's in-flight
            # ring contributions land now, before anything reads arr
            with jax.named_scope("fold"):
                arr = jnp.minimum(arr, pending)
        n_loc = arr.shape[0]
        width = arr.shape[1]
        me = jax.lax.axis_index("shard")
        off = (me * n_loc).astype(jnp.int32)
        is_app = sched["is_app"]
        stats = jnp.zeros(len(SERIES_FIELDS), jnp.int64)

        # -- 1. removals (owner-local; other shards drop) ---------------- #
        if sched["rm_round"].shape[0]:
            with jax.named_scope("removals"):
                sel = sched["rm_round"] == t
                pl = sched["rm_p"].astype(jnp.int32) - off
                p_ = jnp.where(sel & (pl >= 0) & (pl < n_loc), pl, n_loc)
                k_ = sched["rm_k"]
                active = active.at[p_, k_].set(False, mode="drop")
                gate = gate.at[p_, k_].set(-1, mode="drop")
                flush = flush.at[p_, k_].set(inf, mode="drop")
                ping = ping.at[p_, k_].set(-1, mode="drop")

        # -- 2. additions (+ Algorithm 2 gating, owner-local) ------------- #
        if sched["add_round"].shape[0]:
            with jax.named_scope("additions"):
                sel = sched["add_round"] == t
                add_p, add_k = sched["add_p"], sched["add_k"]
                add_slot = sched["add_slot"]
                pl = add_p.astype(jnp.int32) - off
                owned = (pl >= 0) & (pl < n_loc)
                p_ = jnp.where(sel & owned, pl, n_loc)
                adj = adj.at[p_, add_k].set(sched["add_q"], mode="drop")
                delay = delay.at[p_, add_k].set(sched["add_delay"],
                                                mode="drop")
                active = active.at[p_, add_k].set(True, mode="drop")
                if pc:
                    safe_links = active & (gate < 0)
                    safe_cnt = safe_links.sum(axis=1)
                    pcl = jnp.clip(pl, 0, n_loc - 1)
                    own_slot_safe = safe_links[pcl, add_k]
                    other_safe = (safe_cnt[pcl]
                                  - own_slot_safe.astype(jnp.int32)) >= 1
                    if always_gate:
                        want = other_safe
                    else:
                        has_del = ever_del | ((delivered >= 0)
                                              & is_app[None, :]).any(axis=1)
                        want = other_safe & has_del[pcl]
                    want = want & ~crashed[pcl] & owned
                    gsel = sel & want
                    pg = jnp.where(gsel, pl, n_loc)
                    gate = gate.at[pg, add_k].set(t, mode="drop")
                    flush = flush.at[pg, add_k].set(inf, mode="drop")
                    ping = ping.at[pg, add_k].set(add_slot, mode="drop")
                    delivered = delivered.at[pg, add_slot].set(t,
                                                               mode="drop")
                    csel = sel & ~want & owned
                    pc_ = jnp.where(csel, pl, n_loc)
                    gate = gate.at[pc_, add_k].set(-1, mode="drop")
                    flush = flush.at[pc_, add_k].set(inf, mode="drop")
                    ping = ping.at[pc_, add_k].set(-1, mode="drop")

        # -- 3. crashes (owner-local) ------------------------------------- #
        if sched["cr_round"].shape[0]:
            with jax.named_scope("crashes"):
                sel = sched["cr_round"] == t
                pl = sched["cr_pid"].astype(jnp.int32) - off
                p_ = jnp.where(sel & (pl >= 0) & (pl < n_loc), pl, n_loc)
                crashed = crashed.at[p_].set(True, mode="drop")

        # -- 4. broadcasts (owner-local) ---------------------------------- #
        if sched["bc_round"].shape[0]:
            with jax.named_scope("broadcasts"):
                ol = sched["bc_origin"].astype(jnp.int32) - off
                owned = (ol >= 0) & (ol < n_loc)
                ocl = jnp.clip(ol, 0, n_loc - 1)
                sel = (sched["bc_round"] == t) & owned & ~crashed[ocl]
                o_ = jnp.where(sel, ol, n_loc)
                delivered = delivered.at[o_, sched["bc_slot"]].max(
                    t, mode="drop")

        # -- 5. arrivals -> deliveries (element-wise, local) -------------- #
        with jax.named_scope("deliveries"):
            if pallas:
                delivered, napp32, nping32 = kx.deliver_sweep(
                    arr, delivered, crashed, is_app, t)
                napp = napp32.astype(jnp.int64)
                nping = nping32.astype(jnp.int64)
            else:
                newly = (arr == t) & (delivered < 0) & ~crashed[:, None]
                delivered = jnp.where(newly, t, delivered)

        # -- 6. pong detection: the compacted query ring ------------------ #
        counts = {}
        if pc and gating:
            with jax.named_scope("pong"):
                # The monolithic fire mask, less its one remote conjunct
                # delivered[adj, ping] >= 0: only these slots need asking.
                cand = ((gate >= 0) & (flush == inf) & (ping >= 0)
                        & ~crashed[:, None])
                m = min(PONG_ROWS, n_loc)
                rank, chunks = ranked(cand.any(axis=1), m)
                slots = jnp.arange(k, dtype=jnp.int32)[None, :]

                def ask(c, carry):
                    flush, fired = carry
                    rows = chunk_rows(rank, c, m)
                    # point indices (row, slot): the chip lays the tables
                    # out row-minor, and a row-slice gather would re-lay
                    # each whole table out every round
                    at = (jnp.minimum(rows, n_loc - 1)[:, None], slots)
                    want = cand[at] & (rows < n_loc)[:, None]
                    # the chunk's triples visit all D shards and come
                    # home with the answer filled in by the target's owner
                    q = jnp.clip(adj[at], 0, n_loc * d - 1).reshape(-1)
                    s = jnp.clip(ping[at], 0, width - 1).reshape(-1)
                    ans = jnp.full(q.shape, jnp.int32(-1))
                    for _hop in range(d):
                        ql = q - off
                        hit = (ql >= 0) & (ql < n_loc)
                        qcl = jnp.clip(ql, 0, n_loc - 1)
                        ans = jnp.where(hit, delivered[qcl, s], ans)
                        if d > 1:
                            q = jax.lax.ppermute(q, "shard", perm)
                            s = jax.lax.ppermute(s, "shard", perm)
                            ans = jax.lax.ppermute(ans, "shard", perm)
                    fire = want & (ans.reshape(want.shape) >= 0)
                    # a candidate's flush is INF, so min sets it
                    flush = flush.at[rows[:, None], slots].min(
                        jnp.where(fire, t + pong_delay, inf), mode="drop")
                    return flush, fired + fire.sum(dtype=jnp.int64)

                # chunks never share a row and no answer reads flush, so
                # this equals the read-everything-then-mask body exactly
                flush, fired = jax.lax.fori_loop(
                    0, chunks, ask, (flush, jnp.int64(0)))
                stats = stats.at[4].set(fired)
                counts["pong.queries"] = cand.sum(dtype=jnp.int64)
                counts["pong.chunks"] = chunks.astype(jnp.int64)

        # -- 7+8. flush + forward: the frontier exchange ------------------ #
        # The flush contributions (phase 7) and this round's flood-forward
        # contributions (phase 8) both value t + delay over the same link,
        # and scatter-min commutes, so each may take its own path into one
        # (N/D, W) plane.  A slot flushed this round becomes safe *before*
        # the forward pass, as in the monolithic body (gate_eff below).
        with jax.named_scope("flush_forward"):
            if pc and gating:
                flushing = flush == t
                do = flushing & active & ~crashed[:, None]
                gate_eff = jnp.where(flushing, -1, gate)
            else:
                do = jnp.zeros_like(active)
                gate_eff = gate
            ok = active & (gate_eff < 0) & (adj >= 0) & ~crashed[:, None]
            elig_cnt = ok.sum(axis=1, dtype=jnp.int64)
            # deferred mode writes a fresh pending plane (folded into arr
            # at the next round's entry); immediate mode min-combines
            # into arr directly, as the windowed reference does
            base = () if deferred else (arr,)
            if pallas:
                # slot kernels: combined flush+forward contribution planes
                # (a row with a delivery this round is never crashed, so
                # the jax body's has_new conjunct is implied by new_del)
                dest = base[0] if base else jnp.full_like(arr, inf)
                flush_sent = jnp.int64(0)
                for kk in range(k):
                    vals, win_cnt = kx.slot_frontier(
                        delivered, gate[:, kk], delay[:, kk], do[:, kk],
                        ok[:, kk], is_app, t, gating=pc and gating)
                    flush_sent += win_cnt.astype(jnp.int64)
                    dest = ring_min(
                        dest, vals, adj[:, kk], off,
                        apply=lambda a, v, g: kx.ring_apply(a, v, g, off))
            else:
                new_del = delivered == t
                napp = (new_del & is_app[None, :]).sum(axis=1)
                nping = (new_del & ~is_app[None, :]).sum(axis=1)
                has_new = new_del.any(axis=1) & ~crashed
                dest, cols, full = forward(new_del, ok & has_new[:, None],
                                           delay, adj, t, off, base)
                counts["frontier.columns"] = cols
                counts["frontier.full_rounds"] = full
                flush_sent = jnp.int64(0)
                if pc and gating:
                    dest, flush_sent = flush_rows(dest, delivered, do, gate,
                                                  delay, adj, is_app, t, off)
            if not deferred:
                arr = dest
            if pc and gating:
                gate = jnp.where(flushing, -1, gate)
                ping = jnp.where(flushing, -1, ping)
                flush = jnp.where(flushing, inf, flush)
        with jax.named_scope("stats"):
            stats = stats.at[0].set(napp.sum().astype(jnp.int64))
            stats = stats.at[1].set(
                (napp.astype(jnp.int64) * elig_cnt).sum())
            stats = stats.at[2].set(
                (nping.astype(jnp.int64) * elig_cnt).sum())
            stats = stats.at[3].set(flush_sent)
            stats = stats.at[5].set((gate >= 0).sum().astype(jnp.int64))
            stats = jax.lax.psum(stats, "shard")

        out = (arr, delivered, adj, delay, active, gate, flush, ping,
               crashed, ever_del)
        if deferred:
            return (out, dest), stats, counts
        return out, stats, counts

    def step(sched, state, t):
        t = t.astype(jnp.int32)
        return jax.lax.cond(
            t >= 0,
            lambda s: real_step(sched, s, t)[:2],
            lambda s: (s, jnp.zeros(len(SERIES_FIELDS), jnp.int64)),
            state)

    if scan:
        def scan_step(sched, carry, t):
            t = t.astype(jnp.int32)
            carry, stats, counts = jax.lax.cond(
                t >= 0,
                lambda c: real_step(sched, c[0], t, c[1]),
                lambda c: (c, jnp.zeros(len(SERIES_FIELDS), jnp.int64),
                           {n: jnp.int64(0) for n in counter_names}),
                carry)
            return carry, (stats, counts)

        def segment_gated(state, sched, ts, origins, rounds):
            is_app = sched["is_app"]
            events = {key: v for key, v in sched.items() if key != "is_app"}
            pending0 = jnp.full_like(state[0], inf)

            def body(carry, x):
                t, ev = x
                sch = dict(ev)
                sch["is_app"] = is_app
                return scan_step(sch, carry, t)

            (state, pending), (stats, counts) = jax.lax.scan(
                body, (tuple(state), pending0), (ts, events))
            # residual fold: the last round's in-flight frontier (padding
            # rounds skip real_step, so pending survives to here intact)
            with jax.named_scope("fold"):
                state = ((jnp.minimum(state[0], pending),)
                         + tuple(state[1:]))
            # fused retirement reduce (DESIGN.md §2.8): the per-column
            # aggregates the driver's retire() consumes come out of the
            # same dispatch as the segment itself, while the planes are
            # still hot — shared definition with shard_retire_kernels
            with jax.named_scope("retire_reduce"):
                me = jax.lax.axis_index("shard")
                off = (me * state[0].shape[0]).astype(jnp.int32)
                red = tuple(jax.lax.psum(x, "shard")
                            for x in _column_partials(state, origins,
                                                      rounds, off))
            if counts:
                # the segment's counters; all but the pong query's slot
                # count are already uniform across shards
                counts = {n: v.sum() for n, v in counts.items()}
                if "pong.queries" in counts:
                    counts["pong.queries"] = jax.lax.psum(
                        counts["pong.queries"], "shard")
                red += (counts,)
            return state, stats, red
        span = segment_gated
    else:
        def segment_stepped(state, sched, ts):
            return jax.lax.scan(lambda c, t: step(sched, c, t), state, ts)
        span = segment_stepped

    # check_vma=False: lax.cond trips shard_map's replication checker
    # (jax-ml/jax known limitation); the stats output really is
    # replicated — it comes out of an explicit psum on every branch.
    _run = jax.jit(jax.shard_map(
        span, mesh=mesh,
        in_specs=((P("shard"), P(), P(), P(), P()) if scan
                  else (P("shard"), P(), P())),
        out_specs=((P("shard"), P(), P()) if scan
                   else (P("shard"), P())),
        check_vma=False),
        # scanned segments own the live buffers for many rounds: donate
        # them so the carry updates in place instead of doubling the
        # peak (N, W) footprint
        donate_argnums=(0,) if scan else ())

    def run(state, sched, ts, origins=None, rounds=None):
        # x64 so the int64 stats accumulators (and their psum) are
        # honored; every state/schedule array carries an explicit dtype,
        # so nothing else widens — byte-parity with the windowed series.
        with jax.enable_x64(True):
            if scan:
                return _run(state, sched, ts, origins, rounds)
            return _run(state, sched, ts)

    run.jitted = _run
    return run


@functools.lru_cache(maxsize=None)
def shard_fast_span_runner(n_devices: int, classes_sig: tuple):
    """The scanned segment body specialized for quiescent segments: no
    link additions/removals in the segment and no live gating machinery
    anywhere in the run (the driver checks both before selecting it;
    crashes and broadcasts are fine — they ride stacked scan inputs).

    Same ``(state, ...) -> (state, stats, red)`` byte-contract as the
    scanned :func:`shard_span_runner` — including the fused retirement
    aggregates, computed on the widened int32 exit state — reached very
    differently (the N=1M hot path, DESIGN.md §2.7–2.8):

      * ``arr``/``delivered`` live in **int16** for the duration of the
        segment (entry/exit converts; ``INT16_LIMIT`` stands in for
        ``INF``; the driver guarantees ``rounds + max_delay`` fits);
      * the per-round delivery frontier ``delivered == t`` is
        **bit-packed** to ``(N/D, W/8)`` uint8 (8 columns/byte) — the
        per-round series comes from SWAR byte popcounts, and the ring
        moves W/8 bytes per row instead of 4W;
      * the frontier crosses shards as an **all-gather** (D-1
        ``ppermute`` hops, blocks concatenated in ring order), and each
        receiver row OR-combines its eligible in-neighbors' packed rows
        by *gathering* over the host-built per-delay-class inverse
        tables (``classes_sig`` = ``inverse_tables``'s ``(delay, B)``
        signature, the structural compile key) — sender eligibility is
        folded into the tables, and a crashed sender's frontier row is
        all-zero by construction, so no runtime edge masking remains;
      * the exchange is **double-buffered**: the gathered OR lands in a
        packed ``pending`` carry and folds into ``arr`` (value
        ``t + delay`` per class) at the next round's entry, with a
        residual fold after the scan — the same deferral contract as
        the generic scanned body;
      * stats stack through the scan and ``psum`` once per segment
        (integer sums, so the reassociation is exact), and the state
        argument is donated.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..kernels import pack_columns, popcount_bytes, unpack_columns

    mesh = shard_mesh(n_devices)
    d = n_devices
    inf = jnp.int32(INF)
    lim16 = jnp.int16(INT16_LIMIT)
    perm = _shift(d)
    classes = tuple(classes_sig)

    def segment_fast(state, tabs, ia_pack, sched, ts, origins, rounds):
        (arr, delivered, adj, delay, active, gate, flush, ping,
         crashed, ever_del) = state
        n_loc, width = arr.shape
        wp = -(-max(width, 1) // 8)
        me = jax.lax.axis_index("shard")
        off = (me * n_loc).astype(jnp.int32)
        n_glob = n_loc * d

        with jax.named_scope("convert"):
            arr16 = jnp.where(arr >= inf, lim16, arr.astype(jnp.int16))
            del16 = delivered.astype(jnp.int16)

        # Receiver-side gather positions into the all-gathered frontier,
        # hoisted out of the scan: ring hop j delivers the block owned
        # by shard (me - j) % d, so global source row s = blk*n_loc + r
        # sits at ((me - blk) % d) * n_loc + r.  "No source" entries
        # point past the end; the gather fills them with zero bytes.
        with jax.named_scope("flush_forward"):
            poss = []
            for ci, (dl, b) in enumerate(classes):
                ip = tabs[ci]
                blk = ip // n_loc
                pos = ((me - blk) % d) * n_loc + (ip - blk * n_loc)
                poss.append(jnp.where(ip >= n_glob, n_glob,
                                      pos).astype(jnp.int32))
        # per-row eligible-link count: static over the segment except
        # for crashes, which zero the whole row (matching the reference
        # body's per-slot `ok &= ~crashed`)
        with jax.named_scope("stats"):
            linkcnt = (active & (adj >= 0)).sum(axis=1).astype(jnp.int64)
            gated = (gate >= 0).sum().astype(jnp.int64)

        def fold(arr16, pend, tprev):
            # deferred packed frontier: contributions gathered during
            # round tprev arrive with value tprev + delay
            with jax.named_scope("fold"):
                for ci, (dl, b) in enumerate(classes):
                    pb = unpack_columns(pend[ci], width)
                    arr16 = jnp.where(
                        pb, jnp.minimum(arr16, tprev + jnp.int16(dl)),
                        arr16)
            return arr16

        def body(carry, x):
            arr16, del16, crs, pend, tprev = carry
            t, bc_r, bc_o, bc_s, cr_r, cr_p = x
            t16 = t.astype(jnp.int16)
            arr16 = fold(arr16, pend, tprev)
            # crashes / broadcasts (owner-local; sentinel rounds in the
            # stacked rows never match a real t)
            if cr_r.shape[0]:
                with jax.named_scope("crashes"):
                    pl = cr_p.astype(jnp.int32) - off
                    p_ = jnp.where((cr_r == t) & (pl >= 0) & (pl < n_loc),
                                   pl, n_loc)
                    crs = crs.at[p_].set(True, mode="drop")
            if bc_r.shape[0]:
                with jax.named_scope("broadcasts"):
                    ol = bc_o.astype(jnp.int32) - off
                    owned = (ol >= 0) & (ol < n_loc)
                    ocl = jnp.clip(ol, 0, n_loc - 1)
                    sel = (bc_r == t) & owned & ~crs[ocl]
                    o_ = jnp.where(sel, ol, n_loc)
                    del16 = del16.at[o_, bc_s].max(t16, mode="drop")
            # arrivals -> deliveries (padding rounds: t16 < 0 matches
            # no arr/delivered value, so everything below is a no-op)
            with jax.named_scope("deliveries"):
                newly = (arr16 == t16) & (del16 < 0) & ~crs[:, None]
                del16 = jnp.where(newly, t16, del16)
                # pack this round's frontier once; the barrier pins a
                # single materialization (XLA otherwise re-runs the
                # producer chain per consumer: stats, ring, and gather)
                g = jax.lax.optimization_barrier(
                    pack_columns(del16 == t16))
            with jax.named_scope("stats"):
                rowsum = jnp.sum(popcount_bytes(g), axis=1,
                                 dtype=jnp.int64)
                napp = jnp.sum(popcount_bytes(g & ia_pack[None, :]),
                               axis=1, dtype=jnp.int64)
                elig = jnp.where(crs, 0, linkcnt)
                z = jnp.int64(0)
                stats = jnp.stack([
                    napp.sum(), (napp * elig).sum(),
                    ((rowsum - napp) * elig).sum(), z, z,
                    jnp.where(t >= 0, gated, z)])
            # all-gather the packed frontier around the ring
            with jax.named_scope("flush_forward"):
                blocks = [g]
                for _hop in range(d - 1):
                    blocks.append(jax.lax.ppermute(blocks[-1], "shard",
                                                   perm))
                gg = jnp.concatenate(blocks, axis=0) if d > 1 else g
                pend_new = []
                for ci, (dl, b) in enumerate(classes):
                    pos = poss[ci]
                    acc = jnp.take(gg, pos[:, 0], axis=0, mode="fill",
                                   fill_value=0)
                    for col in range(1, b):
                        acc = acc | jnp.take(gg, pos[:, col], axis=0,
                                             mode="fill", fill_value=0)
                    pend_new.append(acc)
            return (arr16, del16, crs, tuple(pend_new), t16), stats

        pend0 = tuple(jnp.zeros((n_loc, wp), jnp.uint8) for _ in classes)
        xs = (ts.astype(jnp.int32), sched["bc_round"], sched["bc_origin"],
              sched["bc_slot"], sched["cr_round"], sched["cr_pid"])
        carry0 = (arr16, del16, crashed, pend0, jnp.int16(0))
        (arr16, del16, crashed, pend, tprev), stats = jax.lax.scan(
            body, carry0, xs)
        arr16 = fold(arr16, pend, tprev)
        with jax.named_scope("stats"):
            stats = jax.lax.psum(stats, "shard")
        with jax.named_scope("convert"):
            arr = jnp.where(arr16 >= lim16, inf, arr16.astype(jnp.int32))
            delivered = del16.astype(jnp.int32)
        state = (arr, delivered, adj, delay, active, gate, flush, ping,
                 crashed, ever_del)
        # fused retirement reduce on the widened exit state — same
        # shared reduction as the generic scanned body (DESIGN.md §2.8)
        with jax.named_scope("retire_reduce"):
            red = tuple(jax.lax.psum(x, "shard")
                        for x in _column_partials(state, origins, rounds,
                                                  off))
        return state, stats, red

    _run = jax.jit(jax.shard_map(
        segment_fast, mesh=mesh,
        in_specs=(P("shard"), P("shard"), P(), P(), P(), P(), P()),
        out_specs=(P("shard"), P(), P()),
        check_vma=False),
        donate_argnums=(0,))

    def run(state, tabs, ia_pack, sched, ts, origins, rounds):
        with jax.enable_x64(True):
            return _run(state, tabs, ia_pack, sched, ts, origins, rounds)

    run.jitted = _run
    return run


@functools.lru_cache(maxsize=None)
def shard_retire_kernels(n_devices: int):
    """The two device-side retirement kernels the driver calls between
    segments: ``reduce(state, origins, horizon_limit) -> per-column
    aggregates`` (psum-replicated across the mesh) and ``apply(state,
    retire_mask, app_retire, hung) -> state`` (fold ``ever_del``, clear
    hung gates, recycle columns).  Together they are the sharded twin of
    ``stream.execute_windowed``'s host-side ``retire`` /
    ``record_and_free`` — the host only ever sees (W,)-sized arrays.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    mesh = shard_mesh(n_devices)
    inf = jnp.int32(INF)

    def retire_reduce(state, origins, rounds):
        n_loc = state[0].shape[0]
        me = jax.lax.axis_index("shard")
        off = (me * n_loc).astype(jnp.int32)
        out = _column_partials(state, origins, rounds, off)
        return tuple(jax.lax.psum(x, "shard") for x in out)

    _reduce = jax.jit(jax.shard_map(
        retire_reduce, mesh=mesh,
        in_specs=(P("shard"), P(), P()),
        out_specs=P()))

    def retire_apply(state, retire, app_retire, hung):
        (arr, delivered, adj, delay, active, gate, flush, ping,
         crashed, ever_del) = state
        w = arr.shape[1]
        # app-delivery memory folds *before* the columns are wiped
        ever_del = ever_del | ((delivered >= 0)
                               & app_retire[None, :]).any(axis=1)
        # a gate whose ping column is being force-expired can never
        # resolve: clear it so the link goes safe (stream.retire's
        # horizon escape hatch, device-side)
        sel = (ping >= 0) & hung[jnp.clip(ping, 0, w - 1)]
        gate = jnp.where(sel, -1, gate)
        flush = jnp.where(sel, inf, flush)
        ping = jnp.where(sel, -1, ping)
        arr = jnp.where(retire[None, :], inf, arr)
        delivered = jnp.where(retire[None, :], -1, delivered)
        return (arr, delivered, adj, delay, active, gate, flush, ping,
                crashed, ever_del)

    _apply = jax.jit(jax.shard_map(
        retire_apply, mesh=mesh,
        in_specs=(P("shard"), P(), P(), P()),
        out_specs=P("shard")))

    def reduce_run(state, origins, rounds):
        with jax.enable_x64(True):
            return _reduce(state, origins, rounds)

    def apply_run(state, retire, app_retire, hung):
        with jax.enable_x64(True):
            return _apply(state, retire, app_retire, hung)

    reduce_run.jitted = _reduce
    apply_run.jitted = _apply
    return reduce_run, apply_run


@functools.lru_cache(maxsize=None)
def shard_hist_runner(n_devices: int):
    """On-device retirement-time delivery-latency histogram
    (``repro.obs.hist`` bucket contract): bucket each valid delivery's
    ``delivered - base`` latency in the sharded ``delivered`` plane,
    column by column, and psum the ``(NB,)`` totals across the mesh.
    ``base`` is one reference round per window column; a column with
    ``base < 0`` (not retiring, not an app column, or no reference
    round) contributes nothing, and neither does a negative latency,
    mirroring ``hist_np``'s ``v >= 0`` mask.

    The sharded driver folds with this runner on accelerator meshes and
    pulls only the ``(NB,)`` totals; on CPU meshes it keeps the host
    fold (``hist_gather``'s uint8 bucket plane + ``np.bincount``),
    where this shard_map reduce costs more than the transfer it saves.
    The two are byte-identical (``tests/test_obs.py``,
    ``tests/test_obs_trace.py`` and ``tests/test_vecsim_shard.py``
    parity-check them).

    The program reads the whole plane once, with no gather: one
    multi-output reduce over the row axis gives every column's count
    below each bucket edge (NB + 1 int32 sums of ``(W,)``, exact while
    a shard holds fewer than 2**31 rows), and a diff of their int64
    column totals gives the buckets.  A single shape per window, so it
    compiles once.  The bucketing is the cumulative-count formulation:
    integer ``value < upper_bound`` comparisons, byte-identical to
    ``bucket_index_np`` + bincount because both are pure integer
    threshold counts over the same bucket edges.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ....obs.hist import NB

    mesh = shard_mesh(n_devices)
    # bucket upper bounds: exact buckets 0..15, then power-of-two
    # decades [2**(4+j), 2**(5+j)); the last bucket is open-ended
    hi = [k + 1 for k in range(16)] + [1 << k for k in range(5, 20)]
    assert len(hi) + 1 == NB

    def hist_fold(delivered, base):
        valid = (delivered >= 0) & (base >= 0)[None, :]
        v = jnp.where(valid, delivered - base[None, :], -1)
        # per-column counts below each edge; the first (normally zero)
        # counts negative latencies so they fall out of bucket 0
        cum = jnp.stack([(valid & (v < 0)).sum(0, dtype=jnp.int32)]
                        + [(valid & (v < h)).sum(0, dtype=jnp.int32)
                           for h in hi]
                        + [valid.sum(0, dtype=jnp.int32)])
        tot = cum.astype(jnp.int64).sum(1)
        return jax.lax.psum(jnp.diff(tot), "shard")

    _run = jax.jit(jax.shard_map(
        hist_fold, mesh=mesh,
        in_specs=(P("shard"), P()),
        out_specs=P()))

    def run(delivered, base):
        with jax.enable_x64(True):
            return _run(delivered, base)

    run.jitted = _run
    return run


@functools.lru_cache(maxsize=None)
def shard_column_gather():
    """Jitted retiring-column gather for the flight recorder
    (``repro.obs.flight``): pull the delivered-plane rows of only the
    (power-of-two padded) sampled retiring columns before ``apply_run``
    recycles them.  Same O(sample) transfer pattern as the latency
    histogram's ``hist_gather``, minus the bucketing — provenance
    wants the raw per-receiver delivery rounds."""
    import jax
    import jax.numpy as jnp

    def column_gather(a, c):
        return jnp.take(a, c, axis=1)

    return jax.jit(column_gather)
