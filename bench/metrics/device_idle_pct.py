"""Device: share of the profiled window in which no operation ran on the
chip, 1 - (union of device-op intervals / window)."""


def read(ctx):
    p = ctx.profile
    if not p or p["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - p["busy_s"] / p["window_s"])
