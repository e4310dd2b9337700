"""``execute_sharded`` — the host driver of the device-sharded engine.

Structurally the twin of ``stream.execute_windowed``: the same
:class:`~repro.core.vecsim.stream.ColumnWindow` activates messages into
live columns, the same segment loop advances rounds, and the same
retirement *rules* recycle columns — but the state lives on the device
mesh for the whole run.  Segments execute through the ``shard_map`` span
runner, retirement decisions are made from ``psum``-reduced per-column
aggregates, and column recycling is a masked device-side update; the
host never materializes an ``(N, W)`` plane unless the run is small
enough to collect the full delivered matrix (``collect="full"``).

With ``scan="on"`` a segment costs one dispatch and O(W) host bytes
(DESIGN.md §2.8): the scanned span runners return the retirement
aggregates fused into the segment program itself (no standalone reduce
dispatch), schedules stage through segment-persistent device buffers
that skip re-upload when a field's content is unchanged — with the next
segment's activation-independent fields prefetched while the current
segment executes — and the fast body's inverse-adjacency tables are
cached by topology content across quiescent segments.

Byte-identity contract: for any scenario both engines can run, the
returned delivered matrix, per-round series, ``NetStats``, per-message
aggregates, ``peak_live`` and overflow behavior equal the windowed
engine's exactly, at every device count — asserted by
``tests/test_vecsim_shard.py`` and the differential fuzz suite.

Like the windowed engine, the segment loop is exposed as a stepper
(:class:`ShardedStepper`, one ``advance()`` per segment) so the live
serving front door (``vecsim.live``) can interleave admission control
between segments; :func:`execute_sharded` is the one-shot wrapper.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from ....obs.hist import NB
from ....obs.spans import NULL_RECORDER
from ..scenario import INF, VecScenario
from ..sim import SERIES_FIELDS, STACKED_SCHED_FIELDS, SlotSchedule, \
    init_topo_state, stats_from_series
from ..stream import ColumnWindow, WindowedRunResult
from .mesh import inverse_tables, pad_rows, resolve_devices, shard_mesh, \
    topology_digest
from .spanner import (INT16_LIMIT, STATE_KEYS, resolve_scan,
                      resolve_shard_backend, shard_column_gather,
                      shard_fast_span_runner, shard_retire_kernels,
                      shard_span_runner)

__all__ = ["ShardedRunResult", "ShardedStepper", "execute_sharded"]


@dataclass
class ShardedRunResult(WindowedRunResult):
    """A windowed-engine result produced by the sharded engine: same
    fields and semantics, plus the device count that executed it, the
    resolved segment-loop mode (``scan`` = "on"/"off") and — when the
    run was profiled — the per-segment host/device timing breakdown
    (``seg_profile``: one dict per segment with ``lo``/``hi`` round
    bounds, whether the fast body ran, and ``stage_s``/``dispatch_s``/
    ``block_s``/``retire_s`` wall components)."""

    n_devices: int = 1
    scan: str = "off"
    seg_profile: Optional[List[dict]] = field(default=None, repr=False)


def _padded_state(scn: VecScenario, w: int, n_pad: int) -> Dict[str, np.ndarray]:
    """Host-built initial state with inert padding rows: no links, no
    arrivals, crashed (so the all-alive-delivered retirement rule and
    the per-round stats never see them)."""
    st = init_topo_state(scn, w)
    n = scn.n
    if n_pad == n:
        return st
    extra = n_pad - n
    pad = dict(
        arr=np.full((extra, w), INF, np.int32),
        delivered=np.full((extra, w), -1, np.int32),
        adj=np.full((extra, scn.k), -1, np.int32),
        delay=np.ones((extra, scn.k), np.int32),
        active=np.zeros((extra, scn.k), bool),
        gate=np.full((extra, scn.k), -1, np.int32),
        flush=np.full((extra, scn.k), INF, np.int32),
        ping=np.full((extra, scn.k), -1, np.int32),
        crashed=np.ones(extra, bool),
        ever_del=np.zeros(extra, bool),
    )
    return {key: np.concatenate([st[key], pad[key]]) for key in st}


class _SegmentStager:
    """Segment-persistent schedule staging for the scanned path.

    Owns one device-resident buffer per stacked schedule field, reused
    across segments: a field is re-uploaded only when its host content
    actually changed (quiescent traffic/churn segments re-use the
    all-sentinel planes already on device), and the
    activation-independent fields of segment k+1 — everything except
    ``bc_slot``/``add_slot``/``is_app``, which depend on column
    assignment — are staged while segment k executes on the mesh
    (``prefetch``), overlapping the host fill + upload with device
    compute.  The schedule buffers are never donated, which is what
    makes the reuse sound."""

    #: fields whose segment content is known before ``activate`` runs
    PREFETCHABLE = (frozenset(STACKED_SCHED_FIELDS)
                    - {"bc_slot", "add_slot"}) | {"ts"}

    def __init__(self, cw: ColumnWindow, caps, seg_len: int, rounds: int,
                 put, rec=None):
        self.cw = cw
        self.caps = caps
        self.seg_len = seg_len
        self.rounds = rounds
        self.put = put
        self.host: Dict[str, np.ndarray] = {}
        self.dev: Dict[str, object] = {}
        self.pending: Optional[tuple] = None
        # telemetry: content-cache effectiveness (repro.obs), and a span
        # around each actual device upload when tracing
        self.uploads = 0
        self.skips = 0
        self.rec = rec if rec is not None else NULL_RECORDER
        self._sid_upload = self.rec.name("stager.upload")

    def _ts(self, lo: int, hi: int) -> np.ndarray:
        ts = np.full(self.seg_len, -3, np.int32)
        ts[: hi - lo] = np.arange(lo, hi, dtype=np.int32)
        return ts

    def _stage(self, key: str, host: np.ndarray):
        old = self.host.get(key)
        if old is None or not np.array_equal(old, host):
            # copy: some sources (e.g. ``is_app``) alias ColumnWindow
            # arrays that mutate in place between segments
            self.host[key] = np.array(host, copy=True)
            self.uploads += 1
            self.rec.begin(self._sid_upload)
            self.dev[key] = self.put(host)
            self.rec.end()
        else:
            self.skips += 1
        return self.dev[key]

    def _build(self, lo: int, hi: int, fields) -> Dict[str, object]:
        sst = self.cw.stacked_schedule(lo, hi, self.caps, self.seg_len,
                                       fields=fields)
        out = {key: self._stage(key, v) for key, v in sst.items()}
        if "ts" in fields:
            out["ts"] = self._stage("ts", self._ts(lo, hi))
        return out

    def prefetch(self, lo: int) -> None:
        """Stage segment ``[lo, lo + seg_len)``'s activation-independent
        fields now, while the previous segment still executes.  The
        prediction can miss (activation or a horizon sweep may shorten
        the next segment); ``stage`` then rebuilds — per-field content
        comparison keeps a mispredicted upload from ever being *used*.
        """
        hi = min(lo + self.seg_len, self.rounds)
        if lo >= hi:
            self.pending = None
            return
        self.pending = (lo, hi, self._build(lo, hi, self.PREFETCHABLE))

    def stage(self, lo: int, hi: int) -> Dict[str, object]:
        """Device arrays for segment ``[lo, hi)``: the prefetched fields
        when the prediction held, everything else built and compared
        now.  Always includes ``ts`` and ``is_app``."""
        rest = frozenset(("bc_slot", "add_slot", "is_app"))
        if self.pending is not None and self.pending[:2] == (lo, hi):
            out = dict(self.pending[2])
        else:
            out = self._build(lo, hi, self.PREFETCHABLE)
        out.update(self._build(lo, hi, rest))
        self.pending = None
        return out


class ShardedStepper:
    """The sharded engine, one segment per :meth:`advance` call — the
    device-mesh twin of :class:`~repro.core.vecsim.stream.WindowedStepper`
    with identical stepping semantics.  ``cw`` optionally supplies an
    externally-built :class:`ColumnWindow` (the live front door passes
    its growable subclass; when that window flags ``mutable_schedule``
    the scanned path skips cross-segment schedule prefetch, since the
    next segment's traffic is not yet admitted while this one runs)."""

    def __init__(self, scn: VecScenario, window: int,
                 n_devices: Optional[int] = None,
                 horizon: Optional[int] = None, seg_len: int = 32,
                 snapshot_round: Optional[int] = None,
                 collect: str = "auto",
                 backend: str = "jax",
                 scan: str = "auto",
                 profile: bool = False,
                 cw: Optional[ColumnWindow] = None,
                 obs=None):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        self._jax = jax
        self.backend = backend = resolve_shard_backend(backend)
        self.scan = scan = resolve_scan(scan)
        self.d = d = resolve_devices(n_devices)
        self.mesh = shard_mesh(d)
        self.w = w = int(window)
        if w < 1:
            raise ValueError("window must be >= 1")
        self.seg_len = seg_len = max(1, int(seg_len))
        self.scn = scn
        self.horizon = None if horizon is None else int(horizon)
        self.snapshot_round = snapshot_round
        n = scn.n
        self.n_pad = n_pad = pad_rows(n, d)
        self.rounds = rounds = scn.rounds
        self.pc = pc = scn.mode == "pc"
        self.gating = gating = scn.n_adds > 0

        self.cw = cw = cw if cw is not None else ColumnWindow(
            scn, w, horizon=horizon)
        self.m_app = cw.m_app_cap
        self.m_total = m_total = self.m_app + scn.n_adds
        if collect == "auto":
            collect = ("full" if n * max(m_total, 1) <= (1 << 26)
                       else "aggregate")
        if collect not in ("full", "aggregate"):
            raise ValueError(f"unknown collect mode {collect!r}")
        self.collect = collect

        self.row = row = NamedSharding(self.mesh, P("shard"))
        self.rep = rep = NamedSharding(self.mesh, P())
        st0 = _padded_state(scn, w, n_pad)
        self.state = tuple(jax.device_put(st0[key], row)
                           for key in STATE_KEYS)
        if scan == "on":
            # host mirror of the (padded) topology tables, advanced past
            # each segment's add/rm events so the fast body's inverse
            # tables are always built from the segment-entry topology
            self.topo_adj = st0["adj"].copy()
            self.topo_delay = st0["delay"].copy()
            self.topo_active = st0["active"].copy()
        del st0

        self.series = np.zeros((rounds, len(SERIES_FIELDS)), np.int64)
        self.delivered_full = (np.full((n, m_total), -1, np.int32)
                               if collect == "full" else None)
        self.deliv_count = np.zeros(m_total, np.int64)
        self.deliv_round_sum = np.zeros(m_total, np.int64)
        self.bcast_done = np.zeros(self.m_app, bool)
        self.expired = np.zeros(m_total, bool)
        self.first_receipts = 0
        self.lat_sum = 0
        self.lat_cnt = 0
        self.snapshot: Optional[Dict[str, np.ndarray]] = None
        self.seg_profile: Optional[List[dict]] = [] if profile else None
        self._clock = time.perf_counter
        self.t = 0

        # telemetry (repro.obs): the segment bodies are telemetry-free
        # either way — the latency histogram is a separate per-retirement
        # dispatch over only the retiring columns (shard_hist_runner), so
        # both arms of the CI overhead gate lean on the same traced
        # segment program
        self.obs = obs
        self.hist = obs is not None and obs.histograms
        self._rec = obs.spans if obs is not None else NULL_RECORDER
        self._sid = {name: self._rec.name(f"segment.{name}")
                     for name in ("stage", "dispatch", "block", "retire",
                                  "retire.pull", "retire.gather",
                                  "retire.fold", "retire.apply")}
        self._cid = {name: self._rec.name(f"retire.{name}")
                     for name in ("columns", "hist_bytes")}
        # segment programs dispatched so far, each with the abstract
        # arguments of its first dispatch (program_texts)
        self._programs: Dict[object, tuple] = {}
        # flight recorder (repro.obs.flight): host-side provenance
        # hooks riding the retiring-column gather — O(sample) transfer,
        # segment bodies untouched
        self._flight = getattr(obs, "flight", None)
        if self._flight is not None:
            self._pgather = shard_column_gather()

        self.caps = cw.segment_caps(rounds, seg_len)
        self.runner = shard_span_runner(d, scn.k, pc, scn.always_gate,
                                        scn.pong_delay, gating=gating,
                                        backend=backend, scan=scan == "on")
        self.reduce_run, self.apply_run = shard_retire_kernels(d)
        if self.hist:
            import jax.numpy as jnp

            from ....obs.hist import bucket_index_jnp

            # jitted retiring-column gather + on-device log bucketing:
            # the host pulls one uint8 index plane (NB = invalid, kept
            # out of the histogram by the bincount slice) instead of the
            # raw int32 delivered slice — 4x less transfer, and the
            # bucket fold rides the fused elementwise gather
            def hist_gather(a, c, b):
                d = jnp.take(a, c, axis=1)
                v = d - b[None, :]
                ok = (d >= 0) & (v >= 0)
                return jnp.where(ok, bucket_index_jnp(v),
                                 NB).astype(jnp.uint8)

            self._take = jax.jit(hist_gather)
        self.rounds_dev = jax.device_put(np.int32(rounds), rep)

        if scan == "on":
            self.caps_r = cw.round_caps(rounds)
            self.stager = _SegmentStager(cw, self.caps_r, seg_len, rounds,
                                         lambda a: jax.device_put(a, rep),
                                         rec=self._rec)
            # The fast body needs the gating machinery quiescent for the
            # whole run (gate/flush/ping state can straddle segments)
            # and the arrival clock to fit int16; per segment it
            # additionally needs a topology-quiescent span (no add/rm
            # events).
            max_dl = int(max(self.topo_delay.max(initial=1),
                             scn.add_delay.max(initial=1)))
            self.fast_allowed = (not (pc and gating)
                                 and rounds + max_dl < INT16_LIMIT - 1)
            self.fast_tabs: Optional[tuple] = None
            # inverse tables keyed by topology content: quiescent
            # stretches between (or cycling through) churn events
            # rebuild nothing
            self.tab_cache: Dict[bytes, tuple] = {}

    @property
    def done(self) -> bool:
        return self.t >= self.rounds

    def _seg_topo_events(self, lo: int, hi: int):
        cw = self.cw
        a0, a1 = np.searchsorted(cw.add_round_s, [lo, hi])
        r0, r1 = np.searchsorted(cw.rm_round_s, [lo, hi])
        return int(a0), int(a1), int(r0), int(r1)

    def _apply_topo_events(self, lo: int, hi: int) -> None:
        """Advance the host topology mirror past segment ``[lo, hi)``
        (same event semantics as the round body's phases 1-2: additions
        set adj/delay/active, removals deactivate in place)."""
        cw = self.cw
        a0, a1, r0, r1 = self._seg_topo_events(lo, hi)
        if a1 > a0:
            self.topo_adj[cw.add_p_s[a0:a1], cw.add_k_s[a0:a1]] = \
                cw.add_q_s[a0:a1]
            self.topo_delay[cw.add_p_s[a0:a1], cw.add_k_s[a0:a1]] = \
                cw.add_delay_s[a0:a1]
            self.topo_active[cw.add_p_s[a0:a1], cw.add_k_s[a0:a1]] = True
        if r1 > r0:
            self.topo_active[cw.rm_p_s[r0:r1], cw.rm_k_s[r0:r1]] = False
        if a1 > a0 or r1 > r0:
            self.fast_tabs = None

    def _fast_runner_and_tables(self):
        jax = self._jax
        if self.fast_tabs is None:
            key = topology_digest(self.topo_adj, self.topo_delay,
                                  self.topo_active)
            ent = self.tab_cache.get(key)
            if ent is None:
                sig, tabs = inverse_tables(self.topo_adj, self.topo_delay,
                                           self.topo_active)
                ent = (sig, tuple(jax.device_put(tb, self.row)
                                  for tb in tabs))
                if len(self.tab_cache) >= 16:
                    self.tab_cache.pop(next(iter(self.tab_cache)))
                self.tab_cache[key] = ent
            self.fast_tabs = ent
        sig, tabs = self.fast_tabs
        return shard_fast_span_runner(self.d, sig), tabs

    def host_state(self) -> Dict[str, np.ndarray]:
        return {key: np.asarray(v)[: self.scn.n]
                for key, v in zip(STATE_KEYS, self.state)}

    def _column_origins(self) -> np.ndarray:
        """Per-column broadcast origin (app columns only; -1 elsewhere),
        so the reduce kernel's owner shard can answer bcast_done."""
        cw = self.cw
        origins = np.full(self.w, -1, np.int32)
        app = cw.slot_app & (cw.slot_msg >= 0)
        if app.any():
            origins[app] = cw.bc_origin[cw.slot_msg[app]]
        return origins

    def _column_base(self) -> np.ndarray:
        """Per-column latency reference round for the on-device latency
        histogram (app columns only; -1 = no base, count nowhere).  The
        default base is the column's birth round — the batch engines'
        latency convention — overridden per message by
        ``obs.latency_base`` (live mode: the submission round, so the
        histogram includes queueing delay)."""
        cw = self.cw
        base = np.full(self.w, -1, np.int32)
        app = cw.slot_app & (cw.slot_msg >= 0)
        if app.any():
            lb = self.obs.latency_base if self.obs is not None else None
            if lb is not None:
                base[app] = lb[cw.slot_msg[app]]
            else:
                base[app] = cw.slot_birth[app]
        return base

    def _note_program(self, run, args) -> None:
        """Remember a segment program and the shapes, dtypes and
        shardings of its first dispatch's arguments (taken before the
        dispatch, which donates the state).  A runner replaced by a
        plain function (no ``jitted``) is not remembered."""
        if run in self._programs or not hasattr(run, "jitted"):
            return
        jax = self._jax
        self._programs[run] = (run.jitted, jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=getattr(x, "sharding", None)),
            args))

    def program_texts(self) -> List[str]:
        """The compiled HLO text of every segment program this stepper
        has dispatched (``HloModule jit_segment_gated``, ...,
        :data:`~repro.core.vecsim.shard.spanner.SEGMENT_PROGRAMS`).
        Each instruction's ``op_name`` metadata carries the named phase
        scope it belongs to (:data:`spanner.PHASE_SCOPES`), so a
        profiler trace's per-instruction device time (``fusion.186``,
        ``slice-start.107``) maps onto the body's phases.  Lowers and
        compiles again (or loads from the compile cache): call it after
        the run, never on the hot path."""
        jax = self._jax
        out = []
        with jax.enable_x64(True):
            for jitted, specs in self._programs.values():
                out.append(jitted.lower(*specs).compile().as_text())
        return out

    def _run_segment(self, lo: int, hi: int):
        """Dispatch segment ``[lo, hi)``; returns the (device) stats
        rows and, on the scanned path, the fused retirement aggregates.
        """
        jax, cw, seg_len = self._jax, self.cw, self.seg_len
        rec, sid = self._rec, self._sid
        t0 = self._clock()
        rec.begin(sid["stage"])
        if self.scan == "off":
            ts = np.full(seg_len, -3, np.int32)
            ts[: hi - lo] = np.arange(lo, hi, dtype=np.int32)
            ts_dev = jax.device_put(ts, self.rep)
            padded = cw.padded_schedule(lo, hi, self.caps)
            sched_dev = {f.name: jax.device_put(getattr(padded, f.name),
                                                self.rep)
                         for f in SlotSchedule.__dataclass_fields__
                         .values()}
            self._note_program(self.runner, (self.state, sched_dev, ts_dev))
            rec.end()
            t1 = self._clock()
            rec.begin(sid["dispatch"])
            self.state, stats = self.runner(self.state, sched_dev, ts_dev)
            rec.end()
            red = None
            fast = False
        else:
            a0, a1, r0, r1 = self._seg_topo_events(lo, hi)
            origins_dev = jax.device_put(self._column_origins(), self.rep)
            fast = self.fast_allowed and a1 == a0 and r1 == r0
            if fast:
                frun, tabs = self._fast_runner_and_tables()
                sched_dev = self.stager.stage(lo, hi)
                ia = np.packbits(
                    np.concatenate([cw.slot_app,
                                    np.zeros((-self.w) % 8, bool)]),
                    bitorder="little")
                ia_dev = self.stager._stage("__ia_pack", ia)
                args = (self.state, tabs, ia_dev,
                        {key: sched_dev[key]
                         for key in ("bc_round", "bc_origin", "bc_slot",
                                     "cr_round", "cr_pid")},
                        sched_dev["ts"], origins_dev, self.rounds_dev)
                self._note_program(frun, args)
                rec.end()
                t1 = self._clock()
                rec.begin(sid["dispatch"])
                self.state, stats, red = frun(*args)
                rec.end()
            else:
                sched_dev = self.stager.stage(lo, hi)
                ts_dev = sched_dev.pop("ts")
                args = (self.state, sched_dev, ts_dev, origins_dev,
                        self.rounds_dev)
                self._note_program(self.runner, args)
                rec.end()
                t1 = self._clock()
                rec.begin(sid["dispatch"])
                self.state, stats, red = self.runner(*args)
                rec.end()
            self._apply_topo_events(lo, hi)
        if self.seg_profile is not None:
            self.seg_profile.append(dict(lo=lo, hi=hi, fast=fast,
                                         stage_s=t1 - t0,
                                         dispatch_s=self._clock() - t1))
        return stats, red

    def _record_and_free(self, cols: np.ndarray, by_expiry: np.ndarray,
                         red, hung: np.ndarray,
                         t_now: Optional[int] = None) -> None:
        """Fold retired columns into the host aggregates and recycle
        their device-side planes — the sharded twin of the windowed
        driver's ``_record_and_free``."""
        if not len(cols):
            return
        cw = self.cw
        rec, sid = self._rec, self._sid
        cnt, arrcnt, sumdel, bdone = red[0], red[1], red[2], red[7]
        ids = cw.slot_msg[cols]
        self.deliv_count[ids] = cnt[cols]
        self.deliv_round_sum[ids] = sumdel[cols].astype(np.int64)
        self.expired[ids] |= by_expiry
        self.first_receipts += int(arrcnt[cols].sum())
        app = cw.slot_app[cols]
        if self.delivered_full is not None:
            self.delivered_full[:, ids] = \
                np.asarray(self.state[1][:, cols])[: self.scn.n]
        retire = np.zeros(self.w, bool)
        retire[cols] = True
        if app.any():
            acols = cols[app]
            births = cw.slot_birth[acols].astype(np.int64)
            self.lat_sum += int((sumdel[acols] - cnt[acols] * births).sum())
            self.lat_cnt += int(cnt[acols].sum())
            self.bcast_done[ids[app]] = bdone[acols] > 0
            if self.hist:
                # latency histogram over only the retiring app columns,
                # read while their delivered plane is still intact
                # (apply_run below recycles it): one jitted gather of
                # the retiring slice — padded to a few power-of-two
                # widths so it compiles a handful of shapes — with the
                # log bucketing fused on device, so the host pulls a
                # uint8 bucket-index plane and folds it with a single
                # bincount.  Cheap enough that the CI overhead gate's
                # enabled arm holds on a CPU mesh; shard_hist_runner is
                # the fully on-device twin for accelerator meshes
                # (parity-tested)
                base = self._column_base()
                r = min(max(8, 1 << (len(acols) - 1).bit_length()),
                        max(self.w, 8))
                cols_p = np.zeros(r, np.int32)
                base_p = np.full(r, self.rounds + 1, np.int32)
                cols_p[: len(acols)] = acols
                bb = base[acols]
                # negative base (no reference round) joins the padding
                # sentinel: latency < 0, bucketed to NB and sliced off
                base_p[: len(acols)] = np.where(bb >= 0, bb,
                                                self.rounds + 1)
                rec.begin(sid["retire.gather"])
                idx = np.asarray(self._take(self.state[1], cols_p,
                                            base_p))
                rec.end()
                rec.begin(sid["retire.fold"])
                counts = np.bincount(idx.ravel(), minlength=NB + 1)
                self.obs.add_hist(counts[:NB].astype(np.int64))
                rec.end()
                rec.counter(self._cid["hist_bytes"], idx.nbytes)
        fl = self._flight
        if fl is not None and fl.open_count and app.any():
            # sampled provenance: gather only the sampled retiring
            # columns' delivered rows (padded to a few power-of-two
            # widths, same shape discipline as the hist gather) while
            # the plane is intact — apply_run below recycles it
            aidx = ids[app]
            m = fl.sampled_mask(aidx)
            if m.any():
                scols = cols[app][m]
                r = min(max(8, 1 << (len(scols) - 1).bit_length()),
                        max(self.w, 8))
                cols_p = np.zeros(r, np.int32)
                cols_p[: len(scols)] = scols
                rows = np.asarray(self._pgather(self.state[1], cols_p))
                fl.on_retire(aidx[m], rows[: self.scn.n, : len(scols)],
                             self.t if t_now is None else t_now,
                             by_expiry[app][m])
        rec.begin(sid["retire.apply"])
        self.state = self.apply_run(self.state, retire,
                                    retire & cw.slot_app, hung)
        cw.free_cols(cols)
        rec.end()
        rec.counter(self._cid["columns"], len(cols))

    def _retire(self, t_now: int, red_dev=None) -> int:
        """Retire columns from the fused segment aggregates (scanned
        path) or a standalone ``reduce_run`` dispatch (per-round path
        and the drain)."""
        cw, w = self.cw, self.w
        live = cw.slot_msg >= 0
        if not live.any():
            return 0
        if red_dev is None:
            red_dev = self.reduce_run(
                self.state, self._column_origins(), self.rounds_dev)
        self._rec.begin(self._sid["retire.pull"])
        red = tuple(np.asarray(x) for x in red_dev)
        self._rec.end()
        (cnt, arrcnt, sumdel, alive, alivedel, blockcnt, refcnt,
         bdone) = red[:8]
        full_del = alivedel == int(alive)
        blocked = (blockcnt > 0) & cw.slot_app
        ref = refcnt > 0
        dead = (cnt == 0) & (cw.slot_birth < t_now)
        done = live & ~ref & ((full_del & ~blocked) | dead)
        by_exp = np.zeros(w, bool)
        hung = np.zeros(w, bool)
        if self.horizon is not None:
            by_exp = live & ~done & (t_now - cw.slot_birth > self.horizon)
            hung = by_exp & ref
            done |= by_exp
        fl = self._flight
        if fl is not None and fl.open_count:
            blk = np.nonzero(live & blocked & ~done)[0]
            if len(blk):
                bids = cw.slot_msg[blk]
                m = fl.sampled_mask(bids)
                if m.any():
                    fl.on_blocked(bids[m], t_now)
        cols = np.nonzero(done)[0]
        self._record_and_free(cols, by_exp[cols], red, hung, t_now)
        return len(cols)

    def advance(self) -> int:
        """Run one segment (activate -> dispatch -> retire); returns the
        new current round.  May raise
        :class:`~repro.core.vecsim.stream.WindowOverflowError` from
        ``activate`` with the engine state untouched since the previous
        segment boundary."""
        t = self.t
        if t >= self.rounds:
            return t
        t_end = min(t + self.seg_len, self.rounds)
        if self.snapshot_round is not None and t <= self.snapshot_round:
            t_end = min(t_end, self.snapshot_round + 1)
        b0 = self.cw.next_bc
        t_end = self.cw.activate(t, t_end)
        fl = self._flight
        if fl is not None and self.cw.next_bc > b0:
            b1 = self.cw.next_bc
            fl.on_activate(np.arange(b0, b1), self.cw.bc_origin[b0:b1],
                           self.cw.bc_round[b0:b1])
        stats_dev, red_dev = self._run_segment(t, t_end)
        if self.scan == "on" and not self.cw.mutable_schedule:
            # stage segment k+1's activation-independent schedule fields
            # while segment k executes on the mesh (pre-scripted runs
            # only: a live window admits segment k+1's traffic after
            # this segment completes, so there is nothing to prefetch)
            self.stager.prefetch(t_end)
        t0 = self._clock()
        self._rec.begin(self._sid["block"])
        self.series[t:t_end] = np.asarray(stats_dev, np.int64)[: t_end - t]
        if (self.snapshot_round is not None
                and t_end - 1 == self.snapshot_round):
            self.snapshot = self.host_state()
            self.snapshot["is_app"] = self.cw.slot_app.copy()
            self.snapshot["slot_msg"] = self.cw.slot_msg.copy()
        self._rec.end()
        t1 = self._clock()
        self._rec.begin(self._sid["retire"])
        self._retire(t_end, red_dev)
        self._rec.end()
        if self.seg_profile is not None:
            self.seg_profile[-1]["block_s"] = t1 - t0
            self.seg_profile[-1]["retire_s"] = self._clock() - t1
        if self.obs is not None:
            seg = self.series[t:t_end]
            self.obs.gauge("piggyback_bytes",
                           16 * int(seg[:, 1].sum() + seg[:, 3].sum())
                           + 24 * int(seg[:, 2].sum()))
            self.obs.gauge("window_occupancy",
                           int((self.cw.slot_msg >= 0).sum()))
        self.t = t_end
        return t_end

    def finish(self) -> ShardedRunResult:
        """Drain still-live columns and build the run result.  Whatever
        is still live keeps its end-of-run values, exactly like the
        windowed engine at ``t == rounds``.  The final boundary sweep
        often freed every column (apply_run mutated the state after the
        fused reduce, so its aggregates cannot be reused); skip the
        standalone reduce dispatch entirely when nothing is live."""
        cw = self.cw
        live_cols = cw.live_cols()
        if len(live_cols):
            red = tuple(np.asarray(x)
                        for x in self.reduce_run(
                            self.state, self._column_origins(),
                            self.rounds_dev))
            self._record_and_free(live_cols,
                                  np.zeros(len(live_cols), bool), red,
                                  np.zeros(self.w, bool))
        if self.obs is not None and self.scan == "on":
            self.obs.count("stager_uploads", self.stager.uploads)
            self.obs.count("stager_skips", self.stager.skips)
        stats = stats_from_series(self.series, self.first_receipts)
        return ShardedRunResult(
            scenario=self.scn, window=self.w, backend=self.backend,
            stats=stats, series=self.series, delivered=self.delivered_full,
            deliv_count=self.deliv_count, bcast_done=self.bcast_done,
            expired=self.expired, state=self.host_state(),
            snapshot=self.snapshot, peak_live=cw.peak_live,
            lat_sum=self.lat_sum, lat_cnt=self.lat_cnt,
            deliv_round_sum=self.deliv_round_sum,
            n_devices=self.d, scan=self.scan, seg_profile=self.seg_profile)


def execute_sharded(scn: VecScenario, window: int,
                    n_devices: Optional[int] = None,
                    horizon: Optional[int] = None, seg_len: int = 32,
                    snapshot_round: Optional[int] = None,
                    collect: str = "auto",
                    backend: str = "jax",
                    scan: str = "auto",
                    profile: bool = False,
                    obs=None) -> ShardedRunResult:
    """Run ``scn`` through a ``window``-column streaming buffer sharded
    over ``n_devices`` devices (``None`` = all visible).  Parameters
    match :func:`~repro.core.vecsim.stream.execute_windowed`; the
    engine *is* a jax mesh program, so ``backend`` only chooses how the
    per-shard round body executes: ``"jax"`` (plain lax, the default)
    or ``"pallas"`` (per-shard delivery-sweep kernel launches inside
    ``shard_map``, DESIGN.md §2.6); ``"auto"`` resolves to jax like the
    other engines.

    ``scan`` picks the segment loop (DESIGN.md §2.7/§2.8): ``"on"``
    (and ``"auto"``) runs each segment as one device-resident
    ``lax.scan`` over rounds — one host dispatch per segment with the
    retirement reduce fused into it, donated state, segment-persistent
    prefetched schedule buffers, and (for topology-quiescent segments)
    the bit-packed fast body; ``"off"`` keeps the per-round host-driven
    stepping.  The two modes are byte-identical
    (``tests/test_vecsim_scan.py``); ``"off"`` exists as the reference
    and escape hatch.

    ``profile=True`` records a per-segment host/device timing breakdown
    on the result (``seg_profile``), at the cost of a few clock reads
    per segment — results are unaffected.

    This is the engine implementation behind ``repro.api.run`` with
    ``engine="sharded"``; prefer the front door in new code."""
    stepper = ShardedStepper(scn, window, n_devices=n_devices,
                             horizon=horizon, seg_len=seg_len,
                             snapshot_round=snapshot_round, collect=collect,
                             backend=backend, scan=scan, profile=profile,
                             obs=obs)
    while not stepper.done:
        stepper.advance()
    return stepper.finish()
