"""p95 over the read-until ejections in the window of the time from the
sample that made the deciding window coverable to the ejection's
booking (the program's ``serving.verdict`` spans)."""
from bench import program_spans


def read(ctx):
    return program_spans.p95_ms(ctx, "serving.verdict")
