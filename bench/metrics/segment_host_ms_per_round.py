"""Host milliseconds per simulated round in the segment pipeline's own
steps: the ``segment.stage``, ``segment.dispatch`` and ``segment.retire``
spans of the window (program spans), over the rounds simulated."""

NAMES = ("segment.stage", "segment.dispatch", "segment.retire")


def read(ctx):
    total = sum(d for n, _, d in ctx.get("spans", ()) if n in NAMES)
    if not total or not ctx["rounds"]:
        return None
    return total / ctx["rounds"] / 1e6
