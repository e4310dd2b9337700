"""End-to-end behaviour test of the exact protocol stack (Fig. 7 style):
a dynamic overlay with churn, lossy pongs and silent crashes, checked by
the happens-before oracle and the overhead/reachability diagnostics."""

import pytest

from repro.core import (BoundedPCBroadcast, Network, SprayOverlay,
                        check_trace, ring_plus_random)
from repro.obs import (overhead_per_message, safe_graph,
                       mean_shortest_path, unsafe_link_stats)


def test_end_to_end_protocol_under_realistic_conditions():
    """A 100-process dynamic overlay with variable delays, churn, lossy
    pongs and silent crashes: PC-broadcast keeps every safety property
    while control overhead stays O(1) per message."""
    import random
    rng = random.Random(42)
    net = Network(seed=42,
                  default_delay=lambda t, r: r.uniform(0.05, 1.5),
                  oob_delay=lambda t, r: r.uniform(0.05, 0.5),
                  oob_loss=0.05)
    n = 100
    for pid in range(n):
        net.add_process(BoundedPCBroadcast(
            pid, ping_mode="route", max_size=32, max_retry=6,
            ping_timeout=20.0))
    ring_plus_random(net, range(n), k=5)
    overlay = SprayOverlay(net, range(n), period=40.0)
    overlay.start()

    crashed = set()
    for step in range(60):
        net.run(until=net.time + rng.uniform(0.3, 1.2))
        r = rng.random()
        if r < 0.6:
            pid = rng.randrange(n)
            if pid not in crashed:
                net.procs[pid].broadcast(("payload", step))
        elif r < 0.65 and len(crashed) < 5:
            victim = rng.randrange(n)
            # never crash ring members' predecessor chain entirely; ring
            # keeps the overlay unpartitioned for the remaining processes
            if victim not in crashed and victim % 10 != 0:
                net.crash(victim)
                crashed.add(victim)
    overlay.stop()
    net.run(until=net.time + 2000.0)

    rep = check_trace(net.trace, crashed=crashed, check_agreement=False)
    assert rep.causal_ok, rep.summary()
    assert not rep.double_deliveries, rep.summary()
    assert rep.n_broadcasts >= 30
    # O(1) overhead: a handful of id bytes per FIFO message, far below one
    # vector-clock entry per process (8 bytes x 100).
    assert overhead_per_message(net) < 40.0
    # Network stays usable: safe graph reaches most correct processes
    # (crash holes are only repaired while the overlay churns, so demand
    # high-but-not-total reachability after it stops).
    from repro.obs.graphs import _bfs_depths
    g = safe_graph(net)
    alive = [p for p in range(n) if p not in crashed]
    reach = [len(_bfs_depths(g, s)) / len(alive) for s in alive[:5]]
    assert sum(reach) / len(reach) > 0.8, reach
