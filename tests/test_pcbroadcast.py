"""PC-broadcast (Algorithm 2): the Fig. 3 scenario is fixed; ping phases
flush buffers in order; link removal is harmless (Lemma 1)."""

import pytest

from repro.core import (Network, PCBroadcast, check_trace, msg_id,
                        ring_plus_random)
from tests.test_rbroadcast import fig3_topology


@pytest.mark.parametrize("ping_mode", ["flood", "route"])
def test_fig3_fixed_by_ping_phase(ping_mode):
    net, (A, B, D) = fig3_topology(PCBroadcast, ping_mode=ping_mode)
    net.procs[A].broadcast("a")
    net.run(until=1.0)
    net.connect(A, D, delay=0.1)          # gated: unsafe until pong
    assert D not in net.procs[A].Q
    assert D in net.procs[A].B
    net.procs[A].broadcast("a'")          # buffered for D, sent to B
    net.run()
    rep = check_trace(net.trace, all_pids={A, B, D})
    assert rep.ok, rep.summary()
    # Link became safe after the phase:
    assert D in net.procs[A].Q and D not in net.procs[A].B
    # D delivered a before a':
    order = [m.payload for m in net.procs[D].delivered_log]
    assert order.index("a") < order.index("a'")


@pytest.mark.parametrize("ping_mode", ["flood", "route"])
def test_buffered_messages_flushed_in_order(ping_mode):
    """Messages delivered during the phase arrive over the new link in
    delivery order (Lemma 3's FIFO flush).  always_gate=True exercises the
    paper's unconditional gating (nothing delivered yet)."""
    net, (A, B, D) = fig3_topology(PCBroadcast, ping_mode=ping_mode,
                                   always_gate=True)
    net.connect(A, D, delay=0.05)
    assert D in net.procs[A].B
    for i in range(5):
        net.procs[A].broadcast(f"m{i}")   # all delivered during the phase
    assert len(net.procs[A].B[D][1]) == 5
    net.run()
    rep = check_trace(net.trace, all_pids={A, B, D})
    assert rep.ok, rep.summary()
    payloads = [m.payload for m in net.procs[D].delivered_log]
    assert payloads == [f"m{i}" for i in range(5)]


def test_sole_link_is_immediately_safe():
    """|Q| <= 1 at open(q): no alternate path exists, no gating (Alg. 2)."""
    net = Network(seed=0)
    net.add_process(PCBroadcast(0))
    net.add_process(PCBroadcast(1))
    net.connect(0, 1)
    assert 1 in net.procs[0].Q and not net.procs[0].B


def test_link_removals_preserve_causality():
    """Lemma 1: removals neither reorder nor (absent partition) lose."""
    net = Network(seed=4, default_delay=2.0)
    n = 10
    for pid in range(n):
        net.add_process(PCBroadcast(pid))
    ring_plus_random(net, range(n), k=4)
    net.run()  # let bootstrap ping phases settle
    net.procs[0].broadcast("before")
    net.run(until=net.time + 1.0)
    # Remove a batch of links (keeping the ring => still connected).
    removed = 0
    for (a, b), lk in list(net.links.items()):
        if lk.alive and (b != (a + 1) % n) and removed < 8:
            net.disconnect(a, b)
            removed += 1
    net.procs[3].broadcast("after")
    net.run()
    rep = check_trace(net.trace, all_pids=set(range(n)))
    assert rep.ok, rep.summary()


@pytest.mark.parametrize("ping_mode", ["flood", "route"])
def test_churn_storm_stays_causal(ping_mode):
    """Random adds/removes interleaved with broadcasts: never a violation."""
    import random
    rng = random.Random(7)
    net = Network(seed=7, default_delay=lambda t, r: r.uniform(0.5, 3.0),
                  oob_delay=0.2)
    n = 16
    for pid in range(n):
        net.add_process(PCBroadcast(pid, ping_mode=ping_mode))
    ring_plus_random(net, range(n), k=3)
    for step in range(30):
        horizon = net.time + rng.uniform(0.5, 2.0)
        net.run(until=horizon)
        op = rng.random()
        if op < 0.4:
            net.procs[rng.randrange(n)].broadcast(("msg", step))
        elif op < 0.7:
            a, b = rng.randrange(n), rng.randrange(n)
            if a != b and not net.has_link(a, b):
                net.connect(a, b)
        else:
            cands = [(a, b) for (a, b), lk in net.links.items()
                     if lk.alive and b != (a + 1) % n]
            if cands:
                net.disconnect(*rng.choice(cands))
    net.run()
    rep = check_trace(net.trace, all_pids=set(range(n)))
    # Causality + integrity must hold unconditionally:
    assert rep.causal_ok and not rep.double_deliveries, rep.summary()
    # The ring survived, so agreement holds too:
    assert rep.ok, rep.summary()


@pytest.mark.parametrize("fallback", [True, False])
def test_fresh_joiner_bootstrap(fallback):
    """DESIGN.md §2.2: a process whose only links are new has no safe
    path for the pings of its inbound links.  ``direct_ping_fallback``
    sends such a ping over the gated link itself, so the phase completes
    and the joiner delivers every later broadcast; without it the
    inbound links stay gated.  A graceful departure mid-run leaves the
    survivors causal either way."""
    n, joiner, leaver = 8, 8, 5
    net = Network(seed=3, default_delay=1.0, oob_delay=0.5)
    for pid in range(n):
        net.add_process(PCBroadcast(pid, ping_mode="route",
                                    direct_ping_fallback=fallback))
    ring_plus_random(net, range(n), k=3)
    for pid in (0, 4):
        net.procs[pid].broadcast(("history", pid))
    net.run()
    # history reaches the joiner by state transfer, not over the overlay
    net.add_process(PCBroadcast(joiner, ping_mode="route",
                                direct_ping_fallback=fallback))
    neighbors = (1, 3, 6)
    for q in neighbors:
        net.connect(joiner, q)
        net.connect(q, joiner)
    # the joiner's own links are safe (it has delivered nothing); every
    # inbound one is gated behind a ping with no safe route
    assert set(net.procs[joiner].Q) == set(neighbors)
    assert all(joiner in net.procs[q].B for q in neighbors)
    net.run()
    net.depart(leaver)
    net.procs[2].broadcast("late")
    net.procs[joiner].broadcast("hello")
    net.run()
    rep = check_trace(net.trace, crashed={leaver}, check_agreement=False)
    assert rep.causal_ok and not rep.double_deliveries, rep.summary()
    got = {pid: {m.payload for m in net.procs[pid].delivered_log}
           for pid in net.procs if pid != leaver}
    assert all("hello" in g and "late" in g
               for pid, g in got.items() if pid != joiner)
    if fallback:
        assert all(joiner in net.procs[q].Q and joiner not in net.procs[q].B
                   for q in neighbors)
        assert got[joiner] == {"late", "hello"}
    else:
        assert all(joiner in net.procs[q].B and joiner not in net.procs[q].Q
                   for q in neighbors)
        assert got[joiner] == {"hello"}
