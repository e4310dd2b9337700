"""Deliveries per second: every delivery the engine's per-round series
counts over the rounds simulated in the window, over the window's wall
seconds (host clock)."""


def read(ctx):
    if ctx["window_s"] <= 0:
        return None
    return ctx["deliveries"] / ctx["window_s"]
