"""Wall time of ``engine.warmup()``: compiling (or loading from the
persistent cache) and running the tick program once."""


def read(ctx):
    return ctx["warmup_s"]
