"""Host time of copying the tick's log-probs to the host (the program's
``serving.readback`` spans) per dispatched tick."""
from bench import program_spans


def read(ctx):
    return program_spans.ms_per_tick(ctx, ("serving.readback",))
