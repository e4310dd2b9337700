"""Host milliseconds per simulated round spent waiting for the device:
the window's ``segment.block`` spans (program spans) over the rounds
simulated."""


def read(ctx):
    total = sum(d for n, _, d in ctx.get("spans", ())
                if n == "segment.block")
    if not total or not ctx["rounds"]:
        return None
    return total / ctx["rounds"] / 1e6
