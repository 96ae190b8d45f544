"""Share of the traced window in which no operation ran on the device
(1 - union of the device's operation intervals / window)."""


def read(ctx):
    t = ctx.trace
    return 1.0 - t["busy_s"] / t["window_s"] if t and t["window_s"] > 0 else None
