"""Host milliseconds per simulated round folding the retiring columns'
latency buckets into the histogram: the window's ``segment.retire.fold``
spans (program spans: the host ``bincount`` of the pulled bucket-index
plane and the histogram add) over the rounds simulated."""


def read(ctx):
    total = sum(d for n, _, d in ctx.get("spans", ())
                if n == "segment.retire.fold")
    if not total or not ctx["rounds"]:
        return None
    return total / ctx["rounds"] / 1e6
