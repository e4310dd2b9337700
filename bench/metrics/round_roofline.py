"""Share of the HBM roofline in the traced stretch: the least time the
simulated rounds need (``peaks.least_bytes_per_round`` summed over them,
at the chip's HBM peak from ``bench/peaks.py``) over the device-busy
time the profiler trace shows for them, in percent."""


def read(ctx):
    tr = ctx.get("trace")
    work = ctx.get("round_work")
    if not tr or not work or tr["busy_s"] <= 0:
        return None
    peak = ctx["peaks"].peaks_for(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * work / peak / tr["busy_s"]
