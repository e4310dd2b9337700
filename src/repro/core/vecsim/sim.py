"""Vectorized lockstep-round execution of a :class:`VecScenario`.

The whole network is dense arrays (DESIGN.md §2.4):

  * ``arr[q, m]``       — earliest known arrival round of message ``m`` at
    process ``q`` (INF = never);
  * ``delivered[q, m]`` — delivery round (-1 = not yet);
  * ``adj/delay/active``— the ``(N, K)`` out-link slot table;
  * ``gate/flush/ping`` — per-slot ping-phase machinery (Algorithm 2):
    ``gate`` is the round the link was gated (-1 = safe), ``ping`` the
    message slot its ping floods under, ``flush`` the round at which the
    pong arrives and the per-link buffer is flushed;
  * ``crashed[p]``      — silent-crash flag (Fig. 5b): the process stops
    delivering and forwarding, its links die silently.

Each round applies, in order: link removals, link additions (with the
Algorithm 2 gating decision), crashes, broadcasts, arrival deliveries,
pong detection, buffer flushes, and flood-forwarding of this round's
deliveries over safe links.  The phase order matches the event engine's
same-timestamp event order, which is what the cross-validation harness
(``crossval.py``) relies on.

The round body is written in *slot space*: schedules name the message
**column** each broadcast/ping occupies, and an ``is_app`` mask replaces
``[:, :m_app]`` prefix slicing.  The monolithic entry point
(:func:`run_vec`) uses the identity mapping (column ``i`` = message
``i``); the streaming windowed engine (``vecsim.stream``) reuses the
same spans over a fixed-width live-column buffer, which is what makes
windowed and monolithic runs byte-identical wherever both can run.

Three backends execute the identical semantics:

  * ``numpy``  — readable reference, mutation + ``np.minimum.at`` scatter;
  * ``jax``    — one ``lax.scan`` over rounds, jitted; the process axis is
    pure scatter/gather so the body matches ``repro.core.engine.step``;
  * ``pallas`` — the same scan with the per-round delivery sweep fused
    into Pallas kernels (``vecsim.kernels``, DESIGN.md §2.6); interpret
    mode on CPU only, as the kernels do not compile for the TPU.

Tests assert the backends produce byte-identical ``delivered``
matrices and per-round stats series on random scenarios.
"""

from __future__ import annotations

import functools
import warnings
from dataclasses import dataclass
from typing import Dict, Optional

import numpy as np

from ..types import LegacyEntryPointWarning, NetStats
from .scenario import INF, VecScenario

__all__ = ["VecRunResult", "run_vec", "execute_vec", "SERIES_FIELDS",
           "SlotSchedule", "full_schedule", "span_runner_for",
           "STACKED_SCHED_FIELDS", "stack_schedules"]

# Wire-size model shared with repro.core.base.control_bytes.
_CTRL_APP = 16    # AppMsg: (origin, counter)
_CTRL_PING = 24   # Ping:   (frm, to, id)

# Per-round stats emitted by both backends (int64 numpy (rounds, 6)).
SERIES_FIELDS = ("deliveries", "sent_app", "sent_ping", "flush_sent",
                 "pongs", "gated")


@dataclass
class SlotSchedule:
    """Slot-space schedules for a span of rounds.

    ``bc_slot``/``add_slot`` name the message *column* of each broadcast
    / link-addition ping; ``is_app`` marks which columns carry app
    messages.  Rounds are absolute.  The monolithic run uses the
    identity mapping (:func:`full_schedule`); the windowed engine remaps
    onto live buffer columns per segment."""

    is_app: np.ndarray       # (W,) bool
    bc_round: np.ndarray     # (B,)
    bc_origin: np.ndarray    # (B,)
    bc_slot: np.ndarray      # (B,)
    add_round: np.ndarray    # (E,)
    add_p: np.ndarray
    add_k: np.ndarray
    add_q: np.ndarray
    add_delay: np.ndarray
    add_slot: np.ndarray     # (E,) ping column of each addition
    rm_round: np.ndarray     # (R,)
    rm_p: np.ndarray
    rm_k: np.ndarray
    cr_round: np.ndarray     # (C,)
    cr_pid: np.ndarray


# Event fields of a SlotSchedule: everything except the per-column
# ``is_app`` mask, which is segment-wide rather than per-round.  These
# are the fields the scanned sharded runner consumes as stacked
# ``lax.scan`` inputs (one leading round axis), so the list is the
# contract between ``ColumnWindow.stacked_schedule`` and the runner.
STACKED_SCHED_FIELDS = tuple(
    name for name in SlotSchedule.__dataclass_fields__ if name != "is_app")


def sched_sentinel(name: str):
    """Padding sentinel of a :class:`SlotSchedule` event field — the one
    place the convention lives, shared by the per-round padding
    (``ColumnWindow.padded_schedule``) and the vectorized stacker
    (``ColumnWindow.stacked_schedule``) so the two schedule paths cannot
    drift.  Round fields pad with ``-2`` (never matches a real round, so
    a padded entry is dead in every body); ``add_delay`` pads with ``1``
    (a valid delay that is never read behind a sentinel round); every
    other field pads with ``0``."""
    if name.endswith("_round"):
        return -2
    return 1 if name == "add_delay" else 0


def stack_schedules(schedules) -> Dict[str, np.ndarray]:
    """Stack per-round padded :class:`SlotSchedule`\\ s along a leading
    round axis for device-side ``lax.scan`` consumption.

    Every schedule must be padded to identical caps (use
    ``ColumnWindow.padded_schedule`` with per-round caps) so each field
    stacks to a rectangular ``(rounds, cap)`` array.  ``is_app`` is
    shared across the span (column identity cannot change mid-segment —
    activation and retirement only happen at segment boundaries), so it
    is returned unstacked under its own key."""
    schedules = list(schedules)
    if not schedules:
        raise ValueError("stack_schedules needs at least one schedule")
    out = {name: np.stack([getattr(s, name) for s in schedules])
           for name in STACKED_SCHED_FIELDS}
    out["is_app"] = schedules[0].is_app
    return out


def full_schedule(scn: VecScenario) -> SlotSchedule:
    """Identity slot mapping: column ``i`` is message ``i``, ping of
    addition ``e`` is column ``m_app + e``."""
    m_app = scn.m_app
    is_app = np.zeros(scn.m_total, bool)
    is_app[:m_app] = True
    return SlotSchedule(
        is_app=is_app,
        bc_round=scn.bcast_round, bc_origin=scn.bcast_origin,
        bc_slot=np.arange(m_app, dtype=np.int32),
        add_round=scn.add_round, add_p=scn.add_p, add_k=scn.add_k,
        add_q=scn.add_q, add_delay=scn.add_delay,
        add_slot=(m_app + np.arange(scn.n_adds)).astype(np.int32),
        rm_round=scn.rm_round, rm_p=scn.rm_p, rm_k=scn.rm_k,
        cr_round=scn.crash_round, cr_pid=scn.crash_pid)


@dataclass
class VecRunResult:
    scenario: VecScenario
    delivered: np.ndarray          # (N, M_total) delivery round, -1 = never
    state: Dict[str, np.ndarray]   # final arrays (numpy)
    stats: NetStats
    series: np.ndarray             # (rounds, len(SERIES_FIELDS))
    snapshot: Optional[Dict[str, np.ndarray]] = None  # state after snap round
    backend: str = "numpy"

    @property
    def delivered_app(self) -> np.ndarray:
        return self.delivered[:, : self.scenario.m_app]

    def delivered_frac(self) -> float:
        """Fraction of (correct process, app message) pairs delivered."""
        ok = ~self.state["crashed"]
        d = self.delivered_app[ok]
        return float((d >= 0).mean()) if d.size else 1.0

    def mean_latency(self) -> float:
        """Mean rounds from broadcast to delivery over delivered pairs."""
        d = self.delivered_app
        got = d >= 0
        if not got.any():
            return float("nan")
        lat = d - self.scenario.bcast_round[None, :]
        return float(lat[got].mean())


def init_topo_state(scn: VecScenario, width: int) -> Dict[str, np.ndarray]:
    """Topology/gating state plus a ``width``-column message buffer."""
    n, k = scn.n, scn.k
    return dict(
        arr=np.full((n, width), INF, np.int32),
        delivered=np.full((n, width), -1, np.int32),
        adj=scn.adj0.astype(np.int32).copy(),
        delay=scn.delay0.astype(np.int32).copy(),
        active=(scn.adj0 >= 0).copy(),
        gate=np.full((n, k), -1, np.int32),
        flush=np.full((n, k), INF, np.int32),
        ping=np.full((n, k), -1, np.int32),
        crashed=np.zeros(n, bool),
        # app-delivery memory of columns already retired by the windowed
        # engine; always all-False on monolithic runs (the live columns
        # hold the complete history there).
        ever_del=np.zeros(n, bool),
    )


def _init_state(scn: VecScenario) -> Dict[str, np.ndarray]:
    return init_topo_state(scn, scn.m_total)


def stats_from_series(series: np.ndarray, first_receipts: int) -> NetStats:
    tot = series.sum(axis=0)
    deliveries, sent_app, sent_ping, flush_sent, pongs, _ = (
        int(x) for x in tot)
    sent = sent_app + sent_ping + flush_sent
    return NetStats(
        sent_messages=sent,
        sent_control=sent_ping + pongs,
        control_bytes=_CTRL_APP * (sent_app + flush_sent)
        + _CTRL_PING * sent_ping,
        oob_messages=pongs,
        deliveries=deliveries,
        duplicate_receipts=max(0, sent - first_receipts),
    )


# --------------------------------------------------------------------- #
# NumPy backend — one span of rounds over a slot-space schedule
# --------------------------------------------------------------------- #
def np_span(st: Dict[str, np.ndarray], sched: SlotSchedule, t0: int, t1: int,
            series: np.ndarray, *, pc: bool, always_gate: bool,
            pong_delay: int, gating: bool = True) -> None:
    """Advance ``st`` through rounds ``[t0, t1)`` in place, writing
    per-round stats into ``series[t0:t1]``.

    ``gating=False`` asserts the *whole scenario* schedules no link
    additions — the only source of gates — letting the span skip the
    pong/flush phases entirely (they are half the dense work per round
    on churn-free sustained traffic).  It must NOT be derived from a
    windowed segment's schedule: a segment without additions can still
    carry gates opened by an earlier segment."""
    arr, delivered = st["arr"], st["delivered"]
    adj, delay, active = st["adj"], st["delay"], st["active"]
    gate, flush, ping = st["gate"], st["flush"], st["ping"]
    crashed, ever_del = st["crashed"], st["ever_del"]
    n, k = adj.shape
    app_idx = np.nonzero(sched.is_app)[0]
    is_app = sched.is_app

    for t in range(t0, t1):
        row = series[t]
        # -- 1. removals ------------------------------------------------ #
        for e in np.nonzero(sched.rm_round == t)[0]:
            p, kk = int(sched.rm_p[e]), int(sched.rm_k[e])
            active[p, kk] = False
            gate[p, kk], flush[p, kk], ping[p, kk] = -1, INF, -1
        # -- 2. additions (+ Algorithm 2 gating decision) ---------------- #
        adds = np.nonzero(sched.add_round == t)[0]
        for e in adds:
            p, kk = int(sched.add_p[e]), int(sched.add_k[e])
            adj[p, kk] = int(sched.add_q[e])
            delay[p, kk] = int(sched.add_delay[e])
            active[p, kk] = True
            gate[p, kk], flush[p, kk], ping[p, kk] = -1, INF, -1
        if pc:
            for e in adds:
                p, kk = int(sched.add_p[e]), int(sched.add_k[e])
                if crashed[p]:
                    continue
                other_safe = any(active[p, j] and gate[p, j] < 0
                                 for j in range(k) if j != kk)
                has_del = bool(ever_del[p]) or bool(
                    (delivered[p, app_idx] >= 0).any())
                if other_safe and (always_gate or has_del):
                    slot = int(sched.add_slot[e])
                    gate[p, kk], ping[p, kk] = t, slot
                    delivered[p, slot] = t   # own ping floods from phase 8
        # -- 3. crashes (silent; links die with the process) ------------- #
        for e in np.nonzero(sched.cr_round == t)[0]:
            crashed[int(sched.cr_pid[e])] = True
        # -- 4. broadcasts ----------------------------------------------- #
        for i in np.nonzero(sched.bc_round == t)[0]:
            o, s = int(sched.bc_origin[i]), int(sched.bc_slot[i])
            if not crashed[o] and delivered[o, s] < 0:
                delivered[o, s] = t
        # -- 5. arrivals -> deliveries ------------------------------------ #
        newly = (arr == t) & (delivered < 0) & ~crashed[:, None]
        delivered[newly] = t
        # -- 6. pong detection -------------------------------------------- #
        if pc and gating:
            q_ = np.clip(adj, 0, n - 1)
            s_ = np.clip(ping, 0, delivered.shape[1] - 1)
            fire = ((gate >= 0) & (flush == INF) & (ping >= 0)
                    & (delivered[q_, s_] >= 0) & ~crashed[:, None])
            flush[fire] = t + pong_delay
            row[4] = int(fire.sum())
        # -- 7. flush buffered app messages over now-safe links ----------- #
        if pc and gating:
            flushing = np.nonzero((flush == t) & active & ~crashed[:, None])
            for p, kk in zip(*flushing):
                p, kk = int(p), int(kk)
                q, g, d = int(adj[p, kk]), int(gate[p, kk]), int(delay[p, kk])
                dp = delivered[p, app_idx]
                win = (dp >= g) & (dp < t)
                row[3] += int(win.sum())
                arr[q, app_idx] = np.minimum(
                    arr[q, app_idx],
                    np.where(win, np.int32(t + d), INF))
            cleared = flush == t
            gate[cleared], ping[cleared], flush[cleared] = -1, -1, INF
        # -- 8. forward this round's deliveries over safe links ----------- #
        # Sparse scatter: only the (process, message) cells delivered this
        # round generate sends, so scatter-min over their flat indices
        # instead of materializing dense (N, M) value planes per slot.
        new_del = delivered == t
        napp = (new_del & is_app[None, :]).sum(axis=1)
        nping = (new_del & ~is_app[None, :]).sum(axis=1)
        row[0] = int(napp.sum())
        rows_idx, cols_idx = np.nonzero(new_del)
        arr_flat = arr.reshape(-1)
        width = arr.shape[1]
        elig_cnt = np.zeros(n, np.int64)
        for kk in range(k):
            ok = (active[:, kk] & (gate[:, kk] < 0) & (adj[:, kk] >= 0)
                  & ~crashed)
            elig_cnt += ok
            if rows_idx.size == 0:
                continue
            sel = ok[rows_idx]
            if not sel.any():
                continue
            r, c = rows_idx[sel], cols_idx[sel]
            lin = adj[r, kk].astype(np.int64) * width + c
            np.minimum.at(arr_flat, lin,
                          (t + delay[r, kk]).astype(np.int32))
        row[1] = int((napp * elig_cnt).sum())
        row[2] = int((nping * elig_cnt).sum())
        row[5] = int((gate >= 0).sum())


def _run_np(scn: VecScenario, snapshot_round: Optional[int]):
    st = _init_state(scn)
    sched = full_schedule(scn)
    series = np.zeros((scn.rounds, len(SERIES_FIELDS)), np.int64)
    kw = dict(pc=scn.mode == "pc", always_gate=scn.always_gate,
              pong_delay=scn.pong_delay, gating=scn.n_adds > 0)
    snapshot = None
    if snapshot_round is None:
        np_span(st, sched, 0, scn.rounds, series, **kw)
    else:
        np_span(st, sched, 0, snapshot_round + 1, series, **kw)
        snapshot = {key: v.copy() for key, v in st.items()}
        np_span(st, sched, snapshot_round + 1, scn.rounds, series, **kw)
    return st, series, snapshot


# --------------------------------------------------------------------- #
# JAX backend — jitted lax.scan spans over slot-space schedules
# --------------------------------------------------------------------- #
_STATE_KEYS = ("arr", "delivered", "adj", "delay", "active", "gate",
               "flush", "ping", "crashed", "ever_del")


def _device_phase_lib(pc: bool, always_gate: bool):
    """Shared lax implementations of the schedule-event phases (1-4) and
    the pong-detection comparison (6), used by both the jax and pallas
    span runners so the two backends cannot drift apart on the
    event-application semantics."""
    import jax.numpy as jnp

    inf = jnp.int32(INF)

    def apply_events(sched, state, t):
        (arr, delivered, adj, delay, active, gate, flush, ping,
         crashed, ever_del) = state
        n = arr.shape[0]
        is_app = sched["is_app"]

        # -- 1. removals -------------------------------------------------- #
        if sched["rm_round"].shape[0]:
            sel = sched["rm_round"] == t
            p_, k_ = jnp.where(sel, sched["rm_p"], n), sched["rm_k"]
            active = active.at[p_, k_].set(False, mode="drop")
            gate = gate.at[p_, k_].set(-1, mode="drop")
            flush = flush.at[p_, k_].set(inf, mode="drop")
            ping = ping.at[p_, k_].set(-1, mode="drop")

        # -- 2. additions -------------------------------------------------- #
        if sched["add_round"].shape[0]:
            sel = sched["add_round"] == t
            add_p, add_k = sched["add_p"], sched["add_k"]
            add_slot = sched["add_slot"]
            p_ = jnp.where(sel, add_p, n)
            adj = adj.at[p_, add_k].set(sched["add_q"], mode="drop")
            delay = delay.at[p_, add_k].set(sched["add_delay"], mode="drop")
            active = active.at[p_, add_k].set(True, mode="drop")
            if pc:
                safe_links = active & (gate < 0)
                safe_cnt = safe_links.sum(axis=1)
                pcl = jnp.clip(add_p, 0, n - 1)
                own_slot_safe = safe_links[pcl, add_k]
                other_safe = (safe_cnt[pcl]
                              - own_slot_safe.astype(jnp.int32)) >= 1
                if always_gate:
                    want = other_safe
                else:
                    has_del = ever_del | ((delivered >= 0)
                                          & is_app[None, :]).any(axis=1)
                    want = other_safe & has_del[pcl]
                want = want & ~crashed[pcl]
                gsel = sel & want
                pg = jnp.where(gsel, add_p, n)
                gate = gate.at[pg, add_k].set(t, mode="drop")
                flush = flush.at[pg, add_k].set(inf, mode="drop")
                ping = ping.at[pg, add_k].set(add_slot, mode="drop")
                delivered = delivered.at[pg, add_slot].set(t, mode="drop")
                csel = sel & ~want
                pc_ = jnp.where(csel, add_p, n)
                gate = gate.at[pc_, add_k].set(-1, mode="drop")
                flush = flush.at[pc_, add_k].set(inf, mode="drop")
                ping = ping.at[pc_, add_k].set(-1, mode="drop")

        # -- 3. crashes ----------------------------------------------------- #
        if sched["cr_round"].shape[0]:
            sel = sched["cr_round"] == t
            p_ = jnp.where(sel, sched["cr_pid"], n)
            crashed = crashed.at[p_].set(True, mode="drop")

        # -- 4. broadcasts -------------------------------------------------- #
        if sched["bc_round"].shape[0]:
            origin = sched["bc_origin"]
            sel = ((sched["bc_round"] == t)
                   & ~crashed[jnp.clip(origin, 0, n - 1)])
            o_ = jnp.where(sel, origin, n)
            delivered = delivered.at[o_, sched["bc_slot"]].max(t, mode="drop")

        return (arr, delivered, adj, delay, active, gate, flush, ping,
                crashed, ever_del)

    def pong_fire(delivered, adj, gate, flush, ping, crashed):
        """Phase 6 comparison: which gated links observe their ping
        delivered at the link target this round."""
        n = delivered.shape[0]
        q_ = jnp.clip(adj, 0, n - 1)
        s_ = jnp.clip(ping, 0, delivered.shape[1] - 1)
        tgt_del = delivered[q_, s_]
        return ((gate >= 0) & (flush == inf) & (ping >= 0)
                & (tgt_del >= 0) & ~crashed[:, None])

    return apply_events, pong_fire


def state_to_device(st: Dict[str, np.ndarray]):
    import jax.numpy as jnp
    return tuple(jnp.asarray(st[key]) for key in _STATE_KEYS)


def state_to_host(state) -> Dict[str, np.ndarray]:
    # np.array (not asarray): views of jax CPU buffers are read-only and
    # the windowed driver mutates the host state between segments.
    return {key: np.array(v) for key, v in zip(_STATE_KEYS, state)}


def sched_to_device(sched: SlotSchedule) -> Dict[str, object]:
    import jax.numpy as jnp
    return {f.name: jnp.asarray(getattr(sched, f.name))
            for f in sched.__dataclass_fields__.values()}


@functools.lru_cache(maxsize=None)
def jax_span_runner(k: int, pc: bool, always_gate: bool, pong_delay: int,
                    gating: bool = True):
    """Jitted ``(state, sched, ts) -> (state, stats)`` span runner.  One
    compilation per distinct (state, sched, ts) shape signature; negative
    rounds in ``ts`` are padding and leave the state untouched.
    ``gating=False`` (scenario-wide no-additions promise, see
    :func:`np_span`) elides the pong/flush phases from the trace."""
    import jax
    import jax.numpy as jnp

    inf = jnp.int32(INF)

    def scatter_min(arr, rows, vals, valid):
        n = arr.shape[0]
        rows = jnp.where(valid, rows, n)          # out of bounds -> dropped
        return arr.at[rows, :].min(vals, mode="drop")

    apply_events, pong_fire = _device_phase_lib(pc, always_gate)

    def real_step(sched, state, t):
        # -- 1-4. removals / additions / crashes / broadcasts --------------- #
        (arr, delivered, adj, delay, active, gate, flush, ping,
         crashed, ever_del) = apply_events(sched, state, t)
        n = arr.shape[0]
        is_app = sched["is_app"]
        # int64: per-round send counts reach rate·N·k, which wraps int32
        # at the sustained scales this engine exists for (the numpy twin
        # accumulates in int64 too); the runner executes under enable_x64
        # so the dtype is honored.
        stats = jnp.zeros(len(SERIES_FIELDS), jnp.int64)

        # -- 5. arrivals -> deliveries -------------------------------------- #
        newly = (arr == t) & (delivered < 0) & ~crashed[:, None]
        delivered = jnp.where(newly, t, delivered)

        # -- 6. pong detection ---------------------------------------------- #
        if pc and gating:
            fire = pong_fire(delivered, adj, gate, flush, ping, crashed)
            flush = jnp.where(fire, t + pong_delay, flush)
            stats = stats.at[4].set(fire.sum().astype(jnp.int64))

        # -- 7. flush buffered app messages over now-safe links ------------- #
        if pc and gating:
            flush_sent = jnp.int64(0)
            for kk in range(k):
                do = (flush[:, kk] == t) & active[:, kk] & ~crashed
                win = ((delivered >= gate[:, kk][:, None])
                       & (delivered < t) & do[:, None] & is_app[None, :])
                flush_sent += win.sum().astype(jnp.int64)
                vals = jnp.where(
                    win, (t + delay[:, kk])[:, None].astype(jnp.int32), inf)
                arr = scatter_min(arr, adj[:, kk], vals, do)
            stats = stats.at[3].set(flush_sent)
            cleared = flush == t
            gate = jnp.where(cleared, -1, gate)
            ping = jnp.where(cleared, -1, ping)
            flush = jnp.where(cleared, inf, flush)

        # -- 8. forward this round's deliveries over safe links ------------- #
        new_del = delivered == t
        napp = (new_del & is_app[None, :]).sum(axis=1)
        nping = (new_del & ~is_app[None, :]).sum(axis=1)
        has_new = new_del.any(axis=1) & ~crashed
        elig_cnt = jnp.zeros(n, jnp.int64)
        for kk in range(k):
            ok = (active[:, kk] & (gate[:, kk] < 0) & (adj[:, kk] >= 0)
                  & ~crashed)
            elig_cnt += ok.astype(jnp.int64)
            fwd = ok & has_new
            vals = jnp.where(new_del & fwd[:, None],
                             (t + delay[:, kk])[:, None].astype(jnp.int32),
                             inf)
            arr = scatter_min(arr, adj[:, kk], vals, fwd)
        stats = stats.at[0].set(napp.sum().astype(jnp.int64))
        stats = stats.at[1].set((napp.astype(jnp.int64) * elig_cnt).sum())
        stats = stats.at[2].set((nping.astype(jnp.int64) * elig_cnt).sum())
        stats = stats.at[5].set((gate >= 0).sum().astype(jnp.int64))

        return (arr, delivered, adj, delay, active, gate, flush, ping,
                crashed, ever_del), stats

    def step(sched, state, t):
        t = t.astype(jnp.int32)
        return jax.lax.cond(
            t >= 0,
            lambda s: real_step(sched, s, t),
            lambda s: (s, jnp.zeros(len(SERIES_FIELDS), jnp.int64)),
            state)

    @jax.jit
    def _run(state, sched, ts):
        return jax.lax.scan(lambda c, t: step(sched, c, t), state, ts)

    def run(state, sched, ts):
        # x64 so the int64 stats accumulators are honored; every array in
        # the carry/schedule carries an explicit dtype, so nothing else
        # widens (tests assert byte-parity with the int64 numpy series)
        with jax.enable_x64(True):
            return _run(state, sched, ts)

    run.jitted = _run
    return run


@functools.lru_cache(maxsize=None)
def pallas_span_runner(k: int, pc: bool, always_gate: bool, pong_delay: int,
                       gating: bool = True):
    """Jitted ``(state, sched, ts) -> (state, stats)`` span runner with
    the per-round delivery sweep fused into Pallas kernels (DESIGN.md
    §2.6) — same contract and byte-identical results as
    :func:`jax_span_runner`.

    Schedule events and pong detection stay in lax (shared with the jax
    runner through :func:`_device_phase_lib`); the ``(N, W)``-plane
    phases launch kernels: the gating-free path runs the single fused
    deliver+forward sweep, the gated path splits at the pong boundary
    (deliver kernel, lax pong ring, flush+forward kernel).  The int64
    NetStats math runs in lax over the kernels' int32 per-row counts.
    """
    import jax
    import jax.numpy as jnp

    from . import kernels as kx

    kx.require_pallas()
    inf = jnp.int32(INF)
    apply_events, pong_fire = _device_phase_lib(pc, always_gate)

    def real_step(sched, state, t):
        # -- 1-4. removals / additions / crashes / broadcasts --------------- #
        (arr, delivered, adj, delay, active, gate, flush, ping,
         crashed, ever_del) = apply_events(sched, state, t)
        is_app = sched["is_app"]
        stats = jnp.zeros(len(SERIES_FIELDS), jnp.int64)

        if pc and gating:
            # -- 5. deliver-sweep kernel ------------------------------------ #
            delivered, napp, nping = kx.deliver_sweep(
                arr, delivered, crashed, is_app, t)
            # -- 6. pong detection (cross-column gather; lax) --------------- #
            fire = pong_fire(delivered, adj, gate, flush, ping, crashed)
            flush = jnp.where(fire, t + pong_delay, flush)
            stats = stats.at[4].set(fire.sum().astype(jnp.int64))
            # -- 7+8. fused flush + forward frontier-sweep kernel ----------- #
            # A slot flushing this round forwards as safe in the same
            # round (the monolithic body clears gates between phases 7
            # and 8): gk_eff mirrors that clearing for the fwd mask.
            do = (flush == t) & active & ~crashed[:, None]
            gk_eff = jnp.where(flush == t, -1, gate)
            fwd_ok = (active & (gk_eff < 0) & (adj >= 0)
                      & ~crashed[:, None])
            arr, flush_sent = kx.frontier_sweep(
                arr, delivered, adj, delay, gate, do, fwd_ok, is_app, t)
            stats = stats.at[3].set(flush_sent.astype(jnp.int64))
            cleared = flush == t
            gate = jnp.where(cleared, -1, gate)
            ping = jnp.where(cleared, -1, ping)
            flush = jnp.where(cleared, inf, flush)
        else:
            # -- 5+8. single fused deliver + forward sweep kernel ----------- #
            fwd_ok = (active & (gate < 0) & (adj >= 0) & ~crashed[:, None])
            arr, delivered, napp, nping = kx.fused_sweep(
                arr, delivered, crashed, adj, delay, fwd_ok, is_app, t)

        elig_cnt = fwd_ok.sum(axis=1).astype(jnp.int64)
        napp = napp.astype(jnp.int64)
        nping = nping.astype(jnp.int64)
        stats = stats.at[0].set(napp.sum())
        stats = stats.at[1].set((napp * elig_cnt).sum())
        stats = stats.at[2].set((nping * elig_cnt).sum())
        stats = stats.at[5].set((gate >= 0).sum().astype(jnp.int64))

        return (arr, delivered, adj, delay, active, gate, flush, ping,
                crashed, ever_del), stats

    def step(sched, state, t):
        t = t.astype(jnp.int32)
        return jax.lax.cond(
            t >= 0,
            lambda s: real_step(sched, s, t),
            lambda s: (s, jnp.zeros(len(SERIES_FIELDS), jnp.int64)),
            state)

    @jax.jit
    def _run(state, sched, ts):
        return jax.lax.scan(lambda c, t: step(sched, c, t), state, ts)

    def run(state, sched, ts):
        with jax.enable_x64(True):
            return _run(state, sched, ts)

    return run


def span_runner_for(backend: str):
    """The device span-runner factory for a backend name."""
    return pallas_span_runner if backend == "pallas" else jax_span_runner


def _run_jax(scn: VecScenario, snapshot_round: Optional[int],
             backend: str = "jax"):
    import jax.numpy as jnp

    run = span_runner_for(backend)(scn.k, scn.mode == "pc", scn.always_gate,
                                   scn.pong_delay, gating=scn.n_adds > 0)
    sched = sched_to_device(full_schedule(scn))
    state0 = state_to_device(_init_state(scn))
    if snapshot_round is None:
        final, series = run(state0, sched,
                            jnp.arange(scn.rounds, dtype=jnp.int32))
        return state_to_host(final), np.asarray(series, np.int64), None
    # split the scan at the snapshot and resume from it — no re-simulation
    snap_state, series_a = run(
        state0, sched, jnp.arange(snapshot_round + 1, dtype=jnp.int32))
    snapshot = state_to_host(snap_state)
    final, series_b = run(
        snap_state, sched, jnp.arange(snapshot_round + 1, scn.rounds,
                                      dtype=jnp.int32))
    series = np.concatenate([np.asarray(series_a, np.int64),
                             np.asarray(series_b, np.int64)])
    return state_to_host(final), series, snapshot


def resolve_backend(backend: str) -> str:
    """Resolve ``"auto"`` and validate explicit backend names.

    ``auto`` is the jax backend on every platform.  The Pallas kernels
    run only in interpret mode on CPU, where they are a byte-parity
    check and not a speed path, and they do not compile for the TPU
    (DESIGN.md §2.6).  ``backend="pallas"`` asked for by name raises
    :class:`~repro.core.vecsim.kernels.PallasUnavailableError` where the
    kernels cannot run."""
    if backend == "auto":
        return "jax"
    if backend == "pallas":
        from . import kernels
        kernels.require_pallas()
        return "pallas"
    if backend not in ("numpy", "jax"):
        raise ValueError(f"unknown backend {backend!r}")
    return backend


def execute_vec(scn: VecScenario, backend: str = "auto",
                snapshot_round: Optional[int] = None,
                window: Optional[int] = None,
                collect: Optional[str] = None, **window_kw):
    """Execute ``scn`` in lockstep rounds; returns delivery matrix, final
    state, ``NetStats`` (same schema as the exact simulator) and a
    per-round stats series.  ``snapshot_round`` additionally captures the
    full state right after that round (for mid-churn topology metrics).

    ``window`` switches to the streaming windowed engine
    (``vecsim.stream``): the message axis is processed through a fixed
    buffer of ``window`` live columns with O(N·window) memory, returning
    a :class:`~repro.core.vecsim.stream.WindowedRunResult` instead.
    ``collect`` and the extra keyword arguments (``horizon``,
    ``seg_len``) apply only to windowed runs.

    This is the engine implementation behind ``repro.api.run``; prefer
    the front door (``repro.api.run(RunSpec(...))``) in new code."""
    if window is not None:
        from .stream import execute_windowed
        return execute_windowed(scn, window, backend=backend,
                                snapshot_round=snapshot_round,
                                collect=collect if collect is not None
                                else "auto", **window_kw)
    if window_kw or collect is not None:
        extra = sorted(window_kw) + (["collect"] if collect is not None
                                     else [])
        raise TypeError(f"monolithic run_vec got windowed-only arguments "
                        f"{extra}")
    backend = resolve_backend(backend)
    if backend in ("jax", "pallas"):
        st, series, snapshot = _run_jax(scn, snapshot_round, backend)
    else:
        st, series, snapshot = _run_np(scn, snapshot_round)
    first_receipts = int((st["arr"] < scn.rounds).sum())
    stats = stats_from_series(series, first_receipts)
    return VecRunResult(scenario=scn, delivered=st["delivered"], state=st,
                        stats=stats, series=series, snapshot=snapshot,
                        backend=backend)


def run_vec(scn: VecScenario, backend: str = "auto",
            snapshot_round: Optional[int] = None,
            window: Optional[int] = None,
            collect: Optional[str] = None, **window_kw):
    """Legacy entry point — identical signature and behavior to
    :func:`execute_vec`, which it delegates to after emitting a
    :class:`~repro.core.types.LegacyEntryPointWarning`.  New code goes
    through the one front door: ``repro.api.run(RunSpec(...))``."""
    warnings.warn(
        "run_vec is a legacy entry point; use repro.api.run(RunSpec(...)) "
        "(see DESIGN.md §3)", LegacyEntryPointWarning, stacklevel=2)
    return execute_vec(scn, backend=backend, snapshot_round=snapshot_round,
                       window=window, collect=collect, **window_kw)
