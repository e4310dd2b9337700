"""Host milliseconds per simulated round gathering the retiring
columns' latency buckets: the window's ``segment.retire.gather`` spans
(program spans: the dispatch of the ``hist_gather`` device program and
the pull of its uint8 bucket-index plane to the host) over the rounds
simulated."""


def read(ctx):
    total = sum(d for n, _, d in ctx.get("spans", ())
                if n == "segment.retire.gather")
    if not total or not ctx["rounds"]:
        return None
    return total / ctx["rounds"] / 1e6
