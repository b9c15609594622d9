"""p95 over the streamed windows dispatched in the window of their wait
from the sample that made them coverable to their dispatch (the
program's ``serving.window_wait`` spans)."""
from bench import program_spans


def read(ctx):
    return program_spans.p95_ms(ctx, "serving.window_wait")
