"""Device time by program and by named phase, from a JAX profiler trace.

Beside ``trace_reduce`` (whose ``read_events`` names each device
operation ``module/instruction``), inside a window on the trace's own
clock:

* ``read_modules``: each device's ``XLA Modules`` events, one per
  program run, as ``(module, start_ns, end_ns)``;
* ``device_programs``: device time summed by program (module) name,
  largest first, averaged over the devices;
* ``op_scopes``: from the compiled HLO text of the segment programs
  (``ShardedStepper.program_texts()``), the named phase scope of every
  instruction, read from its ``op_name`` metadata;
* ``device_scopes``: the device self time of the segment programs'
  operations summed by phase scope.  An operation's self time is its
  time less that of the operations nested inside it on the same line,
  so the enclosing ``while`` does not count the body twice; operations
  with no phase scope count under ``(unscoped)``.

The program and scope names are the program's contract
(``repro.core.vecsim.shard.spanner.SEGMENT_PROGRAMS`` and
``PHASE_SCOPES``, DESIGN.md §2.10): a jitted function ``f`` runs as the
XLA module ``jit_f``.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Sequence

import numpy as np

SEGMENT_PROGRAMS = ("jit_segment_gated", "jit_segment_fast")
PHASE_SCOPES = ("fold", "removals", "additions", "crashes", "broadcasts",
                "deliveries", "pong", "flush_forward", "stats",
                "retire_reduce", "convert")
UNSCOPED = "(unscoped)"

_MODULE = re.compile(r"^HloModule\s+([^\s,]+)", re.M)
_INSTR = re.compile(
    r'^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=[^\n]*?\bop_name="([^"]*)"', re.M)


def read_modules(path: str) -> Dict[str, list]:
    """``{device: [(module, start_ns, end_ns), ...]}`` of the ``XLA
    Modules`` line of each ``/device:`` plane; empty for a trace with
    no device planes."""
    from jax.profiler import ProfileData

    out: Dict[str, list] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                out[plane.name] = [
                    (ev.name.split("(")[0], int(ev.start_ns),
                     int(ev.start_ns) + int(ev.duration_ns))
                    for ev in line.events]
    return out


def device_programs(modules: Dict[str, list], lo: int, hi: int) -> list:
    """``[[module, seconds], ...]``: device time by program inside
    ``[lo, hi)``, largest first, averaged over the devices."""
    by: Dict[str, int] = {}
    for evs in modules.values():
        for name, s, e in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                by[name] = by.get(name, 0) + d
    n_dev = max(len(modules), 1)
    return [[n, v / 1e9 / n_dev]
            for n, v in sorted(by.items(), key=lambda kv: -kv[1])]


def op_scopes(texts: Iterable[str],
              scopes: Sequence[str] = PHASE_SCOPES) -> Dict[str, dict]:
    """``{module: {instruction: scope}}`` from compiled HLO texts: the
    innermost component of each instruction's ``op_name`` that is one
    of ``scopes``.  Instructions with no such component are left out."""
    wanted = frozenset(scopes)
    out: Dict[str, dict] = {}
    for text in texts:
        m = _MODULE.search(text)
        if m is None:
            continue
        table = out.setdefault(m.group(1), {})
        for name, op_name in _INSTR.findall(text):
            hit = [c for c in op_name.split("/") if c in wanted]
            if hit:
                table.setdefault(name, hit[-1])
    return out


def self_times(iv: np.ndarray) -> np.ndarray:
    """Each ``(start, end)`` interval's length less the part covered by
    the intervals nested directly inside it (one line of a device:
    an interval that starts inside another is its child)."""
    iv = np.asarray(iv, np.int64).reshape(-1, 2)
    own = iv[:, 1] - iv[:, 0]
    stack: List[int] = []
    for i in np.lexsort((-iv[:, 1], iv[:, 0])):
        s, e = iv[i]
        while stack and iv[stack[-1], 1] <= s:
            stack.pop()
        if stack:
            p = stack[-1]
            own[p] -= min(e, iv[p, 1]) - s
        stack.append(int(i))
    return own


def device_scopes(device_ops: Dict[str, list], lo: int, hi: int,
                  scopes_by_op: Dict[str, dict],
                  programs: Sequence[str] = SEGMENT_PROGRAMS) -> list:
    """``[[scope, seconds], ...]``: device self time inside ``[lo, hi)``
    of the operations of ``programs`` (``device_ops`` as
    ``trace_reduce.read_events`` names them, ``module/instruction``),
    summed by the scope ``scopes_by_op`` gives each, largest first,
    averaged over the devices."""
    by: Dict[str, int] = {}
    for evs in device_ops.values():
        iv = np.array([(s, e) for _, s, e in evs], np.int64).reshape(-1, 2)
        keep = np.nonzero((iv[:, 1] > lo) & (iv[:, 0] < hi)
                          & (iv[:, 1] > iv[:, 0]))[0]
        own = self_times(np.stack([np.maximum(iv[keep, 0], lo),
                                   np.minimum(iv[keep, 1], hi)], axis=1))
        for i, t in zip(keep, own):
            module, _, instr = evs[i][0].partition("/")
            if module not in programs:
                continue
            scope = scopes_by_op.get(module, {}).get(instr, UNSCOPED)
            by[scope] = by.get(scope, 0) + int(t)
    n_dev = max(len(device_ops), 1)
    return [[n, v / 1e9 / n_dev]
            for n, v in sorted(by.items(), key=lambda kv: -kv[1])]
