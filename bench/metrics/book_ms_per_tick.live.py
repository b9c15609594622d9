"""Host time of the engine's booking after the runner returns (the
program's ``serving.book`` spans: tokens, finishes, ejections) per
dispatched tick."""
from bench import program_spans


def read(ctx):
    return program_spans.ms_per_tick(ctx, ("serving.book",))
