"""Idle share of the device over the traced stretch: one minus the
union of device-operation intervals over the stretch (profiler trace),
averaged over the chips, in percent."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
