"""Reduce a JAX profiler trace to device busy time, idle gaps and top ops.

``reduce_trace`` reads one ``.xplane.pb`` file with nothing but JAX
(``jax.profiler.ProfileData``) and returns, inside a window given on the
trace's own clock:

* ``busy_s``: the union of the intervals in which an operation ran on a
  device, averaged over the devices traced;
* ``window_s``: the length of the window; the idle share is
  ``1 - busy_s / window_s``;
* ``device_ops``: device time summed by operation name, largest first;
* ``idle_gaps``: idle device time summed by what the host was doing
  (the innermost host span over each gap's midpoint), largest first;
* ``collective_s`` and ``collective_exposed_s``: time in collective
  operations, and the part of it during which no other operation ran on
  that device.

Device operations are the events of the ``XLA Ops`` line of each
``/device:`` plane.  A trace without device planes (the CPU backend
runs its programs on host threads) falls back to host events that name
an HLO op, so the reduction can be tested anywhere.
"""

from __future__ import annotations

import warnings
from typing import Dict, Iterable, List, Sequence, Tuple

import numpy as np

COLLECTIVE_WORDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all", "send", "recv",
                    "allreduce", "allgather", "collectivepermute")


def merged(iv: np.ndarray) -> np.ndarray:
    """The union of ``(k, 2)`` half-open intervals as sorted disjoint
    runs."""
    if not len(iv):
        return np.zeros((0, 2), np.int64)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    # a run starts where an interval begins after every earlier end
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    out = np.zeros((int(new.sum()), 2), np.int64)
    out[:, 0] = iv[new, 0]
    np.maximum.at(out[:, 1], np.cumsum(new) - 1, iv[:, 1])
    return out


def union_length(iv: np.ndarray) -> int:
    """Total length covered by ``(k, 2)`` half-open intervals."""
    runs = merged(iv)
    return int((runs[:, 1] - runs[:, 0]).sum())


def clip(iv: np.ndarray, lo: int, hi: int) -> np.ndarray:
    c = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)],
                 axis=1) if len(iv) else np.zeros((0, 2), np.int64)
    return c[c[:, 1] > c[:, 0]]


def _stats(ev) -> dict:
    with warnings.catch_warnings():
        # the stats' builtin type lacks __module__; reading them warns
        warnings.simplefilter("ignore", DeprecationWarning)
        try:
            return dict(ev.stats)
        except (TypeError, ValueError):   # stats that cannot be listed
            return {}


def read_events(path: str):
    """``(device_ops, host_spans)`` of a trace file.  ``device_ops`` maps
    a device name to ``[(name, start_ns, end_ns), ...]``; ``host_spans``
    is ``[(name, start_ns, end_ns), ...]`` of every host event."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    dev: Dict[str, list] = {}
    host: list = []
    fallback: Dict[str, list] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" in lines:
                dev[plane.name] = _named_ops(lines)
            continue
        for line in plane.lines:
            for ev in line.events:
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                st = _stats(ev)
                if "hlo_op" in st:
                    mod = st.get("hlo_module")
                    name = f"{mod}/{ev.name}" if mod else ev.name
                    fallback.setdefault(
                        f"host-run:{st.get('device_ordinal', 0)}",
                        []).append((name, s, e))
                elif e > s:
                    host.append((ev.name, s, e))
    return (dev if dev else fallback), host


def _named_ops(lines) -> list:
    """A device's operations as ``(module/op, start, end)``: the op's
    instruction name (its HLO text up to ``=``) under the name of the
    program (``XLA Modules`` event) it ran in."""
    mods = sorted((int(ev.start_ns), int(ev.start_ns) + int(ev.duration_ns),
                   ev.name.split("(")[0])
                  for ev in (lines["XLA Modules"].events
                             if "XLA Modules" in lines else ()))
    starts = np.array([m[0] for m in mods], np.int64)
    out = []
    for ev in lines["XLA Ops"].events:
        s = int(ev.start_ns)
        e = s + int(ev.duration_ns)
        op = ev.name.split(" = ")[0].lstrip("%").strip()
        i = int(np.searchsorted(starts, s, side="right")) - 1
        if i >= 0 and s < mods[i][1]:
            op = f"{mods[i][2]}/{op}"
        out.append((op, s, e))
    return out


def find_span(host: Sequence[tuple], name: str) -> Tuple[int, int]:
    hits = [(s, e) for n, s, e in host if n == name]
    if not hits:
        raise ValueError(f"no host span named {name!r} in the trace")
    return min(s for s, _ in hits), max(e for _, e in hits)


def reduce_trace(device_ops: Dict[str, list], host: Sequence[tuple],
                 lo: int, hi: int, extra_host: Iterable[tuple] = (),
                 top: int = 10) -> dict:
    """Busy time, idle gaps, top ops and collective time in ``[lo, hi)``
    (trace clock, ns).  ``extra_host`` adds host spans already moved onto
    the trace clock (the program's own spans)."""
    if hi <= lo:
        raise ValueError("empty trace window")
    if not device_ops:
        raise ValueError("the trace holds no device operations")
    window = hi - lo
    busy, coll, exposed = [], 0, 0
    by_op: Dict[str, int] = {}
    gaps_all: List[np.ndarray] = []
    for name, evs in sorted(device_ops.items()):
        iv = np.array([(s, e) for _, s, e in evs], np.int64).reshape(-1, 2)
        keep = (iv[:, 1] > lo) & (iv[:, 0] < hi)
        iv_c = clip(iv[keep], lo, hi)
        busy.append(union_length(iv_c))
        names = [evs[i][0] for i in np.nonzero(keep)[0]]
        for nm, (s, e) in zip(names, iv_c):
            by_op[nm] = by_op.get(nm, 0) + int(e - s)
        is_coll = np.array([any(w in nm.lower() for w in COLLECTIVE_WORDS)
                            for nm in names], bool)
        if is_coll.any():
            c_iv = iv_c[is_coll]
            coll += int((c_iv[:, 1] - c_iv[:, 0]).sum())
            other = merged(iv_c[~is_coll])
            exposed += union_length(c_iv) - _overlap(merged(c_iv), other)
        runs = merged(iv_c)
        edges = np.concatenate([[lo], runs.ravel(), [hi]]).reshape(-1, 2)
        gaps_all.append(edges[edges[:, 1] > edges[:, 0]])
    n_dev = len(device_ops)
    gaps = np.concatenate(gaps_all) if gaps_all else np.zeros((0, 2))
    labels = _innermost(list(host) + list(extra_host),
                        (gaps[:, 0] + gaps[:, 1]) // 2)
    idle: Dict[str, int] = {}
    for label, (s, e) in zip(labels, gaps):
        idle[label] = idle.get(label, 0) + int(e - s)
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return dict(
        busy_s=sum(busy) / n_dev / 1e9, window_s=window / 1e9,
        devices=n_dev,
        device_ops=[[n, v / 1e9 / n_dev] for n, v in ops],
        idle_gaps=[[n, v / 1e9 / n_dev] for n, v in gaps],
        collective_s=coll / 1e9 / n_dev,
        collective_exposed_s=exposed / 1e9 / n_dev)


def _innermost(spans: Sequence[tuple], points: np.ndarray) -> List[str]:
    """For each point, the name of the shortest span that covers it.
    Spans are painted longest first, so a nested span overwrites the
    one around it."""
    order = np.argsort(points, kind="stable")
    pts = points[order]
    lab = np.full(len(pts), -1, np.int64)
    spans = [(n, int(s), int(e)) for n, s, e in spans if e > s]
    names = [n for n, _, _ in spans]
    for j in sorted(range(len(spans)),
                    key=lambda j: spans[j][1] - spans[j][2]):
        a, b = np.searchsorted(pts, [spans[j][1], spans[j][2]])
        lab[a:b] = j
    out = [names[j] if j >= 0 else "(no host span)" for j in lab]
    res = [""] * len(pts)
    for i, o in zip(order, out):
        res[i] = o
    return res


def _overlap(a: np.ndarray, b: np.ndarray) -> int:
    """Length of the intersection of two sets of disjoint sorted runs."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        lo = max(a[i, 0], b[j, 0])
        hi = min(a[i, 1], b[j, 1])
        if hi > lo:
            total += int(hi - lo)
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return total
