"""95th percentile (nearest rank) of the wall-clock delivery latency of
every message submitted inside the window: from the end of the tick
whose segment simulated its submission round to the end of the tick in
which its column retired (host clock)."""

import math

import numpy as np


def read(ctx):
    lat = np.sort(np.asarray(ctx.get("latencies_ms", ()), float))
    if not len(lat):
        return None
    return float(lat[max(1, math.ceil(0.95 * len(lat))) - 1])
