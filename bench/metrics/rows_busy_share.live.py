"""Share of the tick rows computed that carried a real window."""


def read(ctx):
    c = ctx["counters"]
    if not c["ticks"]:
        return None
    return 100.0 * c["rows"] / (c["ticks"] * ctx["n_slots"])
