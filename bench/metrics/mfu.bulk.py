"""Useful model FLOPs per core sample times the traced run's core
samples per second, over the chip's peak for the configuration's dtype
(``bench/flops.py``, ``bench/peaks.json``)."""
from bench import flops, reference


def read(ctx):
    rate = ctx["counters"]["samples"] / ctx["window_s"]
    if rate <= 0:
        return None
    cfg = ctx["cfg"]
    per_sample = flops.flops_per_frame(cfg) / reference.total_stride(cfg)
    return 100.0 * per_sample * rate / ctx["peak_flops"]
