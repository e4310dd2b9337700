"""Host milliseconds per live tick outside the segment: each window
tick's ``tick`` span minus its ``tick.advance`` span (program spans),
averaged over the window's ticks."""


def read(ctx):
    ticks = sorted((s, d) for n, s, d in ctx.get("spans", ())
                   if n == "tick")
    adv = sorted((s, d) for n, s, d in ctx.get("spans", ())
                 if n == "tick.advance")
    if not ticks:
        return None
    total, j = 0, 0
    for s, d in ticks:
        inner = 0
        while j < len(adv) and adv[j][0] < s + d:
            if adv[j][0] >= s:
                inner += adv[j][1]
            j += 1
        total += d - inner
    return total / len(ticks) / 1e6
