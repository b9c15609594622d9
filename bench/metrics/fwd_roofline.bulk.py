"""The least time of the window's useful work, tick by tick the larger
of its FLOPs over the peak and its irreducible bytes over the HBM
bandwidth, over the device time of the forward program (``jit_fwd``)
in the trace."""
from bench import flops


def read(ctx):
    program_s = ctx["trace"]["program_s"]
    if not program_s or not ctx["spans"].ticks:
        return None
    bw = ctx["peaks"]["hbm_bytes_per_s"]
    least = sum(flops.least_time_s(flops.tick_work(ctx["cfg"], f, s),
                                   ctx["peak_flops"], bw)
                for f, s in ctx["spans"].ticks)
    return 100.0 * least / program_s
