"""The benchmark's own traffic, topology and churn generators.

Every cell's inputs are drawn here, so a later change to the program's
builders cannot change what the benchmark offers.  Each generator is a
copy of the one the program ships for the same settings
(``repro.core.vecsim.scenario`` and ``repro.core.vecsim.live.arrivals``)
and draws the same numbers from the same random streams;
``bench/tests/test_bench_generators.py`` holds the copies to that at
small sizes.  Nothing here imports the program.

A traffic mix is a JSON file beside this module (``<name>.json``); a
deployment is a JSON file under ``bench/configs``.  ``build_inputs``
turns the two into plain numpy arrays: a broadcast-free base with link
churn (``churn_scenario`` in the program), whose overlay, delays and
churn endpoints come from ``--seed``, and the open-loop submission
trace the live loop draws (``build_arrivals``), which comes from the
mix's ``traffic_seed``.  The rounds of the churn events come from the
deployment's ``churn.round_seed``.  So every seed offers the same
broadcasts and the same number of link events in each round, over its
own overlay: the same work and the same compiled segment program.
"""

from __future__ import annotations

import math
from typing import Dict

import numpy as np


def _i32(a) -> np.ndarray:
    return np.asarray(a, np.int32)


# ------------------------------------------------------------ topologies
def ring_topology(seed: int, n: int, k: int, max_delay: int,
                  free_slots: int):
    """Directed ring on slot 0 plus distinct random out-links on slots
    ``1 .. k-1-free_slots``; the last ``free_slots`` stay empty."""
    rng = np.random.default_rng(seed)
    adj0 = np.full((n, k), -1, np.int32)
    adj0[:, 0] = (np.arange(n) + 1) % n
    n_extra = max(0, k - 1 - free_slots)
    for p in range(n):
        used = {p, int(adj0[p, 0])}
        j = 1
        while j <= n_extra and len(used) < n:
            q = int(rng.integers(0, n))
            if q not in used:
                adj0[p, j] = q
                used.add(q)
                j += 1
    delay0 = rng.integers(1, max_delay + 1, size=(n, k)).astype(np.int32)
    return adj0, delay0


def _perm_avoiding(rng, n: int, forbidden: np.ndarray) -> np.ndarray:
    perm = rng.permutation(n).astype(np.int64)
    me = np.arange(n)
    for it in range(1000):
        bad = perm == me
        for c in range(forbidden.shape[1]):
            bad |= perm == forbidden[:, c]
        idx = np.nonzero(bad)[0]
        if not len(idx):
            return perm
        if len(idx) == 1 or it % 7 == 6:
            others = rng.integers(0, n, size=len(idx))
            for i, j in zip(idx, others):
                perm[i], perm[j] = perm[j], perm[i]
        else:
            perm[idx] = perm[idx[rng.permutation(len(idx))]]
    raise RuntimeError("could not build a conflict-free permutation")


def kregular_topology(seed: int, n: int, k: int, max_delay: int,
                      free_slots: int):
    """Random k-regular digraph: slot 0 the directed ring, every further
    populated slot an independent random permutation."""
    if n < k + 2:
        raise ValueError("need n >= k + 2")
    rng = np.random.default_rng(seed)
    adj0 = np.full((n, k), -1, np.int64)
    adj0[:, 0] = (np.arange(n) + 1) % n
    for j in range(1, max(0, k - 1 - free_slots) + 1):
        adj0[:, j] = _perm_avoiding(rng, n, adj0[:, :j])
    delay0 = rng.integers(1, max_delay + 1, size=(n, k)).astype(np.int32)
    return adj0.astype(np.int32), delay0


TOPOLOGIES = {"ring": ring_topology, "kregular": kregular_topology}


def settle_rounds(n: int, k: int, max_delay: int, pong_delay: int = 1) -> int:
    diam = math.ceil(math.log(max(n, 2)) / math.log(max(k - 1, 2))) + 3
    return (diam + 2) * max_delay + 2 * pong_delay + 6


# --------------------------------------------------------------- traffic
def _spread_broadcasts(rng, n: int, m_app: int, lo: int, hi: int):
    seen = set()
    rounds, origins = [], []
    while len(rounds) < m_app:
        t, o = int(rng.integers(lo, hi)), int(rng.integers(0, n))
        if (o, t) not in seen:
            seen.add((o, t))
            rounds.append(t)
            origins.append(o)
    order = np.argsort(np.asarray(rounds), kind="stable")
    return (_i32(np.asarray(rounds)[order]), _i32(np.asarray(origins)[order]))


def _plan_adds(rng, n: int, k: int, adj0: np.ndarray, n_adds: int,
               lo: int, hi: int, max_delay: int):
    hi = max(hi, lo + 1)
    procs = rng.choice(n, size=min(n_adds, n), replace=False)
    add_round, add_p, add_k, add_q, add_delay = [], [], [], [], []
    for p in procs:
        p = int(p)
        used = {p} | {int(q) for q in adj0[p] if q >= 0}
        if len(used) >= n:
            continue
        while True:
            q = int(rng.integers(0, n))
            if q not in used:
                break
        add_round.append(int(rng.integers(lo, hi)))
        add_p.append(p)
        add_k.append(k - 1)
        add_q.append(q)
        add_delay.append(int(rng.integers(1, max_delay + 1)))
    order = np.argsort(np.asarray(add_round), kind="stable")
    return tuple(_i32(np.asarray(a)[order]) for a in
                 (add_round, add_p, add_k, add_q, add_delay))


def poisson_arrivals(seed: int, n: int, rate: float, messages: int):
    """The open-loop submission trace of the live loop's ``poisson``
    process: Poisson counts per round, drawn 1,024 rounds at a time,
    then uniform origins with replacement."""
    rng = np.random.default_rng(seed)
    chunks, t0, total = [], 0, 0
    while total < messages:
        cnt = rng.poisson(np.full(1024, float(rate)))
        chunks.append(cnt)
        total += int(cnt.sum())
        t0 += 1024
    counts = np.concatenate(chunks)
    rounds = np.repeat(np.arange(len(counts)),
                       counts)[:messages].astype(np.int32)
    origins = rng.integers(0, n, messages).astype(np.int32)
    return rounds, origins


# ------------------------------------------------------------- scenarios
def _empty():
    return np.zeros(0, np.int32)


def churn_span(n: int, k: int, max_delay: int, churn_window: int,
               m_app: int = 8, pong_delay: int = 1):
    """The rounds ``[lo, hi)`` over which ``churn_base`` spreads its
    link events."""
    lo = 2 * max(2, m_app // 3) + settle_rounds(n, k, max_delay, pong_delay)
    return lo, lo + churn_window


def churn_base(seed: int, n: int, k: int, max_delay: int, topology: str,
               n_adds: int, n_rms: int, churn_window: int,
               m_app: int = 8, pong_delay: int = 1) -> Dict[str, np.ndarray]:
    """Link additions and removals spread over ``churn_window`` rounds,
    with the broadcasts stripped (the program's ``churn_scenario`` as a
    live run's base; the stripped broadcasts are still drawn, because
    they share the random stream with the churn)."""
    adj0, delay0 = TOPOLOGIES[topology](seed, n, k, max_delay, 1)
    rng = np.random.default_rng(seed + 3)
    settle = settle_rounds(n, k, max_delay, pong_delay)
    early = max(2, m_app // 3)
    _spread_broadcasts(rng, n, early, 0, 2 * early)
    lo, hi = churn_span(n, k, max_delay, churn_window, m_app, pong_delay)
    adds = _plan_adds(rng, n, k, adj0, n_adds, lo, hi, max_delay)
    rm_round, rm_p, rm_k = [], [], []
    for _ in range(n_rms):
        p = int(rng.integers(0, n))
        kk = int(rng.integers(1, max(2, k - 1)))
        if adj0[p, kk] >= 0:
            rm_round.append(int(rng.integers(lo, hi)))
            rm_p.append(p)
            rm_k.append(kk)
    order = np.argsort(np.asarray(rm_round, np.int64), kind="stable")
    rms = tuple(_i32(np.asarray(a, np.int64)[order])
                for a in (rm_round, rm_p, rm_k))
    rounds = int(hi) + 4 + settle
    return dict(n=n, k=k, rounds=rounds, adj0=adj0, delay0=delay0,
                bcast_round=_empty(), bcast_origin=_empty(),
                add_round=adds[0], add_p=adds[1], add_k=adds[2],
                add_q=adds[3], add_delay=adds[4], rm_round=rms[0],
                rm_p=rms[1], rm_k=rms[2], pong_delay=pong_delay)


def redraw_churn_rounds(sc: dict, round_seed: int, lo: int,
                        hi: int) -> dict:
    """``sc`` with the rounds of its link additions and removals drawn
    anew from ``round_seed``, uniform over ``[lo, hi)``; each event
    keeps its endpoints and its place in the order.  Every overlay with
    as many events then has the same number of them in each round."""
    out = dict(sc)
    rng = np.random.default_rng(round_seed)
    for key in ("add_round", "rm_round"):
        out[key] = _i32(np.sort(rng.integers(lo, hi, len(sc[key]))))
    return out


def build_inputs(config: dict, mix: dict, seed: int) -> dict:
    """The scenario arrays and the submission trace of one cell under
    ``seed``."""
    d, ch = config["deployment"], config["deployment"]["churn"]
    base = churn_base(seed, d["n"], d["k"], d["max_delay"], d["topology"],
                      ch["n_adds"], ch["n_rms"], ch["churn_window"])
    a_seed = int(mix["traffic_seed"])
    arr_round, arr_origin = poisson_arrivals(a_seed, d["n"], mix["rate"],
                                             mix["messages"])
    lo, hi = churn_span(d["n"], d["k"], d["max_delay"], ch["churn_window"])
    sc = redraw_churn_rounds(base, int(ch["round_seed"]), lo, hi)
    return dict(scenario=sc, arr_round=arr_round, arr_origin=arr_origin,
                arrival_seed=a_seed)
