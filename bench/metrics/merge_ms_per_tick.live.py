"""Time of ``runner.collect`` per tick after the device result is
ready: readback, host CTC merge and read-until verdicts."""


def read(ctx):
    ticks = ctx["counters"]["ticks"]
    if not ticks:
        return None
    return ctx["spans"].total_s("collect.merge") / ticks * 1e3
