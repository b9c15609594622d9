"""Host self time of ``engine.step`` per dispatched tick, outside the
runner's dispatch and collect: admission, scheduling and booking."""


def read(ctx):
    s, ticks = ctx["spans"], ctx["counters"]["ticks"]
    if not ticks:
        return None
    own = s.total_s("step") - s.total_s("dispatch") - s.total_s("collect")
    return own / ticks * 1e3
