"""Host time of the engine's admission and scheduling (the program's
``serving.admit`` and ``serving.schedule`` spans, the window copy of
each streamed read included) per dispatched tick."""
from bench import program_spans


def read(ctx):
    return program_spans.ms_per_tick(ctx, ("serving.admit",
                                         "serving.schedule"))
