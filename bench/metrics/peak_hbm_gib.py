"""Peak device memory of the fullest chip at the end of the window
(``memory_stats()["peak_bytes_in_use"]``), in GiB."""


def read(ctx):
    peak = ctx.get("peak_bytes") or 0
    return peak / 2 ** 30 if peak > 0 else None
