"""Share of the traced window in which no operation ran on device 0:
1 - the union of its operation intervals / the window (device trace)."""


def read(ctx):
    if ctx.trace is None:
        return None
    t = ctx.trace
    return 100.0 * (1.0 - t["busy_s"]["0"] / t["window_s"])
