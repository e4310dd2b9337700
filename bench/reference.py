"""Plain reference of PC-broadcast flooding, independent of the program.

The program simulates rounds in lockstep over a window of message
columns.  This module computes the same results another way: every
message's delivery round at every process is the solution of one
fixed-point equation,

    D[q] = min(D0[q], min over in-links (p -> q) of relax(D[p])),

where ``D0`` holds the source (the origin at its broadcast round, or
the process that opened a link at the round it opened it, for that
link's ping) and ``relax`` says when a message delivered at ``p`` in
round ``d`` reaches ``q`` over one link:

* the link must exist in round ``d`` (added at or before ``d``, removed
  after ``d``), else the message never crosses it;
* a new link is *gated* from the round it is added until its flush
  round ``f`` (``pong_delay`` rounds after its ping reaches the far end)
  when the adding process has delivered some app message before that
  round: an app message delivered inside the gate is buffered and
  arrives at ``f + delay``, a ping does not cross at all;
* otherwise the message arrives at ``d + delay``.

Every value depends only on smaller rounds, so the equation has one
solution, and plain (Jacobi) iteration from ``D0`` reaches it: once an
iteration changes nothing, the result is that solution.  The gating is
solved inside the same iteration for the block that holds the pings.
From ``D`` the module derives what the program reports: per-message
delivery counts and delivery-round sums, the per-round series
(deliveries, app sends, ping sends, flushed sends, pongs, gated links)
and the latency histogram.

It runs on the device after the program's state is freed, in blocks of
message columns, and imports nothing of the program.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np

INF = 2 ** 30
NB = 32      # latency histogram buckets: 0..15 exact, then powers of two
HCAP = 1024  # longest flood, in rounds, that one block may hold


def bucket_index(v) -> np.ndarray:
    """Latency bucket: 0..15 exact; ``16 + j`` for ``[2**(4+j),
    2**(5+j))``; 31 from ``2**19`` on."""
    v = np.asarray(v, np.int64)
    extra = np.zeros(v.shape, np.int64)
    for b in range(5, 20):
        extra += v >= (1 << b)
    return np.where(v < 16, np.clip(v, 0, 15), np.minimum(16 + extra, NB - 1))


@dataclass
class Links:
    """Every link that exists before ``t_end``.  The ``(L, N)`` tables
    are in-link layers: layer ``l`` holds the ``l``-th in-link of each
    process (``src == N``: none).  The flat ``out_*`` arrays list the
    same links by sender."""

    src: np.ndarray
    delay: np.ndarray
    t_on: np.ndarray     # first round the link exists (-1: from the start)
    t_off: np.ndarray    # first round it no longer exists
    add: np.ndarray      # index of the addition that made it, -1: initial
    out_p: np.ndarray
    out_on: np.ndarray
    out_off: np.ndarray
    out_add: np.ndarray


def build_links(sc: dict, t_end: int) -> Links:
    n, k = int(sc["n"]), int(sc["k"])
    adj0, delay0 = np.asarray(sc["adj0"]), np.asarray(sc["delay0"])
    p0, j0 = np.nonzero(adj0 >= 0)
    t_off0 = np.full(len(p0), INF, np.int64)
    slot = np.full(n * k, -1, np.int64)
    slot[p0 * k + j0] = np.arange(len(p0))
    rm_r = np.asarray(sc["rm_round"], np.int64)
    rm = rm_r < t_end
    i = slot[np.asarray(sc["rm_p"], np.int64)[rm] * k
             + np.asarray(sc["rm_k"], np.int64)[rm]]
    np.minimum.at(t_off0, i[i >= 0], rm_r[rm][i >= 0])
    a_idx = np.nonzero(np.asarray(sc["add_round"]) < t_end)[0]
    a_p = np.asarray(sc["add_p"], np.int64)[a_idx]
    a_k = np.asarray(sc["add_k"], np.int64)[a_idx]
    a_t = np.asarray(sc["add_round"], np.int64)[a_idx]
    # an addition onto a populated slot ends the old link
    old = slot[a_p * k + a_k]
    np.minimum.at(t_off0, old[old >= 0], a_t[old >= 0])
    src = np.concatenate([p0, a_p])
    dst = np.concatenate([adj0[p0, j0],
                          np.asarray(sc["add_q"])[a_idx]]).astype(np.int64)
    dly = np.concatenate([delay0[p0, j0],
                          np.asarray(sc["add_delay"])[a_idx]])
    t_on = np.concatenate([np.full(len(p0), -1, np.int64), a_t])
    t_off = np.concatenate([t_off0, np.full(len(a_idx), INF, np.int64)])
    add = np.concatenate([np.full(len(p0), -1, np.int64), a_idx])
    order = np.argsort(dst, kind="stable")
    dst_s = dst[order]
    layer = np.arange(len(dst_s)) - np.searchsorted(dst_s, dst_s)
    # a multiple of 8 layers, so that overlays of one deployment share
    # their compiled programs
    n_layers = -(-(int(layer.max()) + 1 if len(layer) else 1) // 8) * 8

    def table(vals, fill):
        out = np.full((n_layers, n), fill, np.int32)
        out[layer, dst_s] = vals[order]
        return out

    return Links(src=table(src, n), delay=table(dly, 1),
                 t_on=table(t_on, INF), t_off=table(t_off, INF),
                 add=table(add, -1), out_p=src, out_on=t_on,
                 out_off=t_off, out_add=add)


@functools.lru_cache(maxsize=None)
def _programs():
    import jax
    import jax.numpy as jnp
    from jax import lax

    inf = jnp.int32(INF)

    def relax(tabs, gate_on, gate_f, D, d0, is_app):
        src, dly, ton, toff, add = tabs

        def layer(l, acc):
            d = jnp.take(D, src[l], axis=0, mode="fill", fill_value=INF)
            e = add[l]
            ei = jnp.maximum(e, 0)
            g = jnp.where(e >= 0, gate_on[ei], inf)[:, None]
            f = jnp.where(e >= 0, gate_f[ei], inf)[:, None]
            dl = dly[l][:, None]
            off = toff[l][:, None]
            exists = (d < inf) & (ton[l][:, None] <= d) & (d < off)
            gated = (g <= d) & (d < f)
            flushed = jnp.where(f < off, f + dl, inf)
            val = jnp.where(gated, jnp.where(is_app[None, :], flushed, inf),
                            d + dl)
            return jnp.minimum(acc, jnp.where(exists, val, inf))

        return lax.fori_loop(0, src.shape[0], layer, d0)

    def sources(n, width, rows, cols, rounds):
        return jnp.full((n, width), inf).at[rows, cols].set(rounds,
                                                              mode="drop")

    @functools.partial(jax.jit, static_argnums=(0, 1))
    def solve_fixed(n, width, tabs, gate_on, gate_f, rows, cols, rounds,
                    is_app, max_iter):
        """Iterate with the gating known until nothing changes."""
        d0 = sources(n, width, rows, cols, rounds)

        def body(c):
            i, D, _ = c
            Dn = relax(tabs, gate_on, gate_f, D, d0, is_app)
            return i + 1, Dn, jnp.any(Dn != D)

        return lax.while_loop(lambda c: c[2] & (c[0] < max_iter), body,
                              (jnp.int32(0), d0, jnp.bool_(True)))

    @functools.partial(jax.jit, static_argnums=(0, 1))
    def solve_gated(n, width, tabs, add_p, add_q, add_t, ping_col, rows,
                    cols, rounds, is_app, pong_delay, max_iter):
        """Iterate the block that holds every ping, solving with it
        which additions gate and when each one flushes."""
        d0_app = sources(n, width, rows, cols, rounds)

        def gating(D):
            had = jnp.min(jnp.where(is_app[None, :], D, inf), axis=1)
            gated = had[add_p] < add_t
            pong = D[add_q, ping_col]
            f = jnp.where(gated & (pong < inf), pong + pong_delay, inf)
            d0 = d0_app.at[jnp.where(gated, add_p, n), ping_col].set(
                add_t, mode="drop")
            return jnp.where(gated, add_t, inf), f, d0

        def body(c):
            i, D, _ = c
            gate_on, f, d0 = gating(D)
            Dn = relax(tabs, gate_on, f, D, d0, is_app)
            return i + 1, Dn, jnp.any(Dn != D)

        i, D, changed = lax.while_loop(
            lambda c: c[2] & (c[0] < max_iter), body,
            (jnp.int32(0), d0_app, jnp.bool_(True)))
        gate_on, f, _ = gating(D)
        return i, D, changed, gate_on, f

    @jax.jit
    def offsets(D, start, weight):
        """Per column, how many processes deliver ``h`` rounds after
        the column's start: plainly, and weighted by ``weight``."""
        rel = jnp.where(D < inf, D - start[None, :], -1)
        hmax = jnp.max(rel)

        def body(h, acc):
            eq = rel == h
            c = eq.sum(axis=0, dtype=jnp.int32)
            w = jnp.where(eq, weight[:, None], 0).sum(axis=0,
                                                       dtype=jnp.int32)
            return acc[0].at[:, h].set(c), acc[1].at[:, h].set(w)

        z = jnp.zeros((D.shape[1], HCAP), jnp.int32)
        cnt, cntw = lax.fori_loop(0, jnp.minimum(hmax + 1, HCAP), body,
                                  (z, z))
        return cnt, cntw, hmax, (D < inf).sum(axis=0, dtype=jnp.int32)

    return solve_fixed, solve_gated, offsets


@dataclass
class Result:
    app_count: np.ndarray    # (M,) processes that deliver each message
    app_sum: np.ndarray      # (M,) sum of their delivery rounds
    app_last: np.ndarray     # (M,) last delivery round (INF: none)
    app_hist: np.ndarray     # (M, NB) latency histogram from app_base
    ping_count: np.ndarray   # (E,) the same for each addition's ping
    ping_sum: np.ndarray
    ping_last: np.ndarray
    gated: np.ndarray        # (E,) whether each addition gated
    flush: np.ndarray        # (E,) its flush round (INF: none)
    series: np.ndarray       # (t_end, 6) the program's per-round fields
    iterations: int


def _padded_sources(origin, rounds, pad: int = 256):
    """Source cells of a block (row, column, round), padded with
    dropped entries to a multiple of ``pad``."""
    import jax.numpy as jnp
    c = len(origin)
    w = max(pad, -(-c // pad) * pad)
    rows = np.full(w, 1 << 30, np.int32)     # out of range: dropped
    rows[:c] = origin
    rr = np.zeros(w, np.int32)
    rr[:c] = rounds
    return (jnp.asarray(rows), jnp.asarray(np.arange(w, dtype=np.int32)),
            jnp.asarray(rr))


def _rows(D, idx: np.ndarray, pad: int = 1024) -> np.ndarray:
    """``D[idx]`` on the host, gathered at a padded length so that a
    few shapes serve every run."""
    import jax.numpy as jnp
    w = max(pad, -(-len(idx) // pad) * pad)
    p = np.zeros(w, np.int32)
    p[:len(idx)] = idx
    return np.asarray(jnp.take(D, jnp.asarray(p), axis=0))[:len(idx)]


def _hists(cnt: np.ndarray, start: np.ndarray, base: np.ndarray):
    """Per-message latency histograms from delivery-offset counts."""
    out = np.zeros((cnt.shape[0], NB), np.int64)
    lat = (start - base)[:, None] + np.arange(cnt.shape[1])[None, :]
    m = (cnt > 0) & (lat >= 0)
    rows = np.broadcast_to(np.arange(cnt.shape[0])[:, None], cnt.shape)
    np.add.at(out, (rows[m], bucket_index(lat[m])), cnt[m])
    return out


def solve(sc: dict, app_round, app_origin, t_end: int,
          app_base=None, block: int = 512,
          max_iter: int = 1 << 20) -> Result:
    """Delivery rounds of every app message (``app_round`` sorted,
    ``app_origin``) that starts before ``t_end`` and of every ping of an
    addition before ``t_end``, reduced to what the program reports over
    rounds ``[0, t_end)``.  Latencies count from ``app_base`` (default:
    the broadcast round)."""
    import jax.numpy as jnp

    solve_fixed, solve_gated, offsets = _programs()
    n = int(sc["n"])
    if (np.asarray(sc.get("crash_round", np.zeros(0))) < t_end).any():
        raise ValueError("the reference models no crashes")
    if ((np.asarray(sc["rm_k"]) == 0).any()
            or (np.asarray(sc["adj0"])[:, 0] < 0).any()):
        raise ValueError("slot 0 must hold a link that is never removed")
    lk = build_links(sc, t_end)
    tabs = tuple(jnp.asarray(a) for a in (lk.src, lk.delay, lk.t_on,
                                          lk.t_off, lk.add))
    app_round = np.asarray(app_round, np.int64)
    if len(app_round) > 1 and (np.diff(app_round) < 0).any():
        raise ValueError("app messages must be sorted by round")
    m = int(np.searchsorted(app_round, t_end))
    app_round = app_round[:m]
    app_origin = np.asarray(app_origin, np.int64)[:m]
    base = (app_round if app_base is None
            else np.asarray(app_base, np.int64)[:m])
    e_n = len(np.asarray(sc["add_round"]))
    a_idx = np.nonzero(np.asarray(sc["add_round"]) < t_end)[0]
    a_p = np.asarray(sc["add_p"], np.int64)[a_idx]
    a_t = np.asarray(sc["add_round"], np.int64)[a_idx]
    pong_delay = int(sc.get("pong_delay", 1))

    # sends per delivery: a process's count of safe out-links, fixed
    # before any churn, corrected per delivery round for the processes
    # that churn
    elig0 = np.bincount(lk.out_p[lk.out_on < 0], minlength=n)
    rm_hit = np.asarray(sc["rm_p"], np.int64)[
        np.asarray(sc["rm_round"]) < t_end]
    churned = np.unique(np.concatenate([a_p, rm_hit]))
    pos = np.full(n, -1, np.int64)
    pos[churned] = np.arange(len(churned))
    sel = pos[lk.out_p] >= 0
    ch_row = pos[lk.out_p[sel]]
    ch_on, ch_off = lk.out_on[sel], lk.out_off[sel]
    ch_add = lk.out_add[sel]
    weight = jnp.asarray(elig0.astype(np.int32))

    series = np.zeros((t_end + 1, 6), np.int64)   # row t_end: discard
    out = Result(app_count=np.zeros(m, np.int64),
                 app_sum=np.zeros(m, np.int64),
                 app_last=np.full(m, INF, np.int64),
                 app_hist=np.zeros((m, NB), np.int64),
                 ping_count=np.zeros(e_n, np.int64),
                 ping_sum=np.zeros(e_n, np.int64),
                 ping_last=np.full(e_n, INF, np.int64),
                 gated=np.zeros(e_n, bool),
                 flush=np.full(e_n, INF, np.int64),
                 series=series, iterations=0)
    gate_on = np.full(e_n, INF, np.int64)

    def fold(D, start, is_app):
        """Fold one converged block into the series; returns per-column
        offset counts, totals, round sums and last rounds."""
        cnt, cntw, hmax, fin = (np.asarray(x) for x in offsets(
            D, jnp.asarray(np.minimum(start, INF).astype(np.int32)),
            weight))
        if int(hmax) >= HCAP:
            raise ValueError(f"a flood lasts {int(hmax)} rounds")
        if (cnt.sum(axis=1) != fin).any():
            raise ValueError("delivery counts do not add up")
        h = np.arange(HCAP)
        rnd = np.minimum(start[:, None] + h[None, :], t_end)
        for fld, w, mask in ((0, cnt, is_app), (1, cntw, is_app),
                             (2, cntw, ~is_app)):
            np.add.at(series[:, fld], rnd[mask].ravel(),
                      w[mask].ravel().astype(np.int64))
        if len(churned):
            rows = _rows(D, churned)
            dd = rows[ch_row]
            g = np.where(ch_add >= 0, gate_on[np.maximum(ch_add, 0)], INF)
            f = np.where(ch_add >= 0, out.flush[np.maximum(ch_add, 0)], INF)
            safe = ((dd < INF) & (ch_on[:, None] <= dd)
                    & (dd < ch_off[:, None])
                    & ~((g[:, None] <= dd) & (dd < f[:, None])))
            exact = np.zeros(rows.shape, np.int64)
            np.add.at(exact, ch_row, safe.astype(np.int64))
            corr = exact - elig0[churned][:, None]
            for fld, mask in ((1, is_app), (2, ~is_app)):
                mm = (rows < t_end) & (corr != 0) & mask[None, :]
                np.add.at(series[:, fld], rows[mm], corr[mm])
        tot = cnt.sum(axis=1).astype(np.int64)
        sums = (cnt.astype(np.int64) * (start[:, None] + h[None, :])).sum(1)
        last = np.where(tot > 0, start + np.where(cnt > 0, h[None, :],
                                                  -1).max(axis=1), INF)
        return cnt, tot, sums, last

    gidx = np.zeros(0, np.int64)
    flush_hits = np.zeros(e_n, np.int64)

    def app_outputs(D, lo, c, cnt, tot, sums, last):
        out.app_count[lo:lo + c] = tot[:c]
        out.app_sum[lo:lo + c] = sums[:c]
        out.app_last[lo:lo + c] = last[:c]
        out.app_hist[lo:lo + c] = _hists(cnt[:c], app_round[lo:lo + c],
                                         base[lo:lo + c])
        if len(gidx):
            # app messages a gated link buffers until its flush
            rows = _rows(D, np.asarray(sc["add_p"], np.int64)[gidx])[:, :c]
            flush_hits[gidx] += ((rows >= gate_on[gidx][:, None])
                                 & (rows < out.flush[gidx][:, None])
                                 ).sum(axis=1)

    first = 0
    if len(a_idx):
        k_app = min(m, 64)
        while True:
            # padded to a multiple of 256 columns, so that runs of one
            # cell share the compiled program
            width = -(-(len(a_idx) + k_app) // 256) * 256
            is_app = np.zeros(width, bool)
            is_app[:k_app] = True
            ping_col = k_app + np.arange(len(a_idx))
            it, D, changed, g_on, g_f = solve_gated(
                n, width, tabs, jnp.asarray(a_p.astype(np.int32)),
                jnp.asarray(np.asarray(sc["add_q"], np.int32)[a_idx]),
                jnp.asarray(a_t.astype(np.int32)),
                jnp.asarray(ping_col.astype(np.int32)),
                *_padded_sources(app_origin[:k_app], app_round[:k_app]),
                jnp.asarray(is_app), jnp.int32(pong_delay),
                jnp.int32(max_iter))
            if bool(changed):
                raise ValueError("the reference did not converge")
            out.iterations += int(it)
            # exact only if no app message left out of this block could
            # reach an adding process before its addition
            had = np.asarray(jnp.min(D[:, :k_app], axis=1,
                                     initial=INF)) if k_app else \
                np.full(n, INF)
            nxt = int(app_round[k_app]) if k_app < m else INF
            late = a_t > nxt
            if k_app >= m or (had[a_p[late]] < a_t[late]).all():
                break
            k_app = min(m, 2 * k_app)
        gate_on[a_idx] = np.asarray(g_on, np.int64)
        out.flush[a_idx] = np.asarray(g_f, np.int64)
        out.gated[a_idx] = gate_on[a_idx] < INF
        gidx = a_idx[out.gated[a_idx]]
        start = np.concatenate([app_round[:k_app], a_t, np.full(
            width - k_app - len(a_idx), INF, np.int64)])
        cnt, tot, sums, last = fold(D, start, is_app)
        app_outputs(D, 0, k_app, cnt, tot, sums, last)
        pings = slice(k_app, k_app + len(a_idx))
        out.ping_count[a_idx] = tot[pings]
        out.ping_sum[a_idx] = sums[pings]
        out.ping_last[a_idx] = last[pings]
        pong = out.flush[gidx] - pong_delay
        np.add.at(series[:, 4], np.minimum(pong, t_end), 1)
        diff = np.zeros(t_end + 2, np.int64)
        np.add.at(diff, np.minimum(gate_on[gidx], t_end + 1), 1)
        np.add.at(diff, np.minimum(out.flush[gidx], t_end + 1), -1)
        series[:t_end, 5] += np.cumsum(diff)[:t_end]
        del D
        first = k_app
    # (a scenario without additions still passes one all-INF entry)
    g_on_dev = jnp.asarray(np.minimum(np.append(gate_on, INF),
                                      INF).astype(np.int32))
    g_f_dev = jnp.asarray(np.minimum(np.append(out.flush, INF),
                                     INF).astype(np.int32))
    for lo in range(first, m, block):
        c = min(m, lo + block) - lo
        is_app = np.zeros(block, bool)
        is_app[:c] = True
        it, D, changed = solve_fixed(
            n, block, tabs, g_on_dev, g_f_dev,
            *_padded_sources(app_origin[lo:lo + c], app_round[lo:lo + c]),
            jnp.asarray(is_app), jnp.int32(max_iter))
        if bool(changed):
            raise ValueError("the reference did not converge")
        out.iterations += int(it)
        start = np.concatenate([app_round[lo:lo + c],
                                np.full(block - c, INF, np.int64)])
        cnt, tot, sums, last = fold(D, start, is_app)
        app_outputs(D, lo, c, cnt, tot, sums, last)
        del D
    fl = out.flush[gidx]
    np.add.at(series[:, 3], np.minimum(fl, t_end), flush_hits[gidx])
    out.series = series[:t_end]
    return out
