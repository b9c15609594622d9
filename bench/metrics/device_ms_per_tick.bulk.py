"""Device-busy time per dispatched tick: the union of the device's
operation intervals in the traced window, over the ticks in it."""


def read(ctx):
    ticks = ctx["counters"]["ticks"]
    if not ticks or ctx["trace"]["busy_s"] <= 0:
        return None
    return ctx["trace"]["busy_s"] / ticks * 1e3
