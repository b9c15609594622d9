"""Host time of the per-row argmax and CTC merge (the program's
``serving.ctc_merge`` spans) per dispatched tick."""
from bench import program_spans


def read(ctx):
    return program_spans.ms_per_tick(ctx, ("serving.ctc_merge",))
