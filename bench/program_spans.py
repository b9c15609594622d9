"""The program's own spans (``repro.serving.tracing``) in a run's window.

While the profiler collects, the engine and the basecaller runner
record ``serving.*`` spans in the program's default tracer, on
``time.perf_counter``, the clock of the benchmark's own ``window`` span.
The readers of the per-layer metrics that rest on them take the records
that overlap the window, clip them to it and divide by the number of
``serving.dispatch`` spans that start in it (the dispatched ticks).

A program without the tracer, a window with no dispatch, or one whose
records the tracer's ring overwrote gives ``None``.
"""
from __future__ import annotations

from typing import Iterable, List, Optional, Tuple

import numpy as np


def window_records(ctx) -> Optional[Tuple[float, float, List, int]]:
    """``(t0, t1, records, ticks)`` of the run's window, or None."""
    try:
        from repro.serving import tracing
    except ImportError:
        return None
    win = [(a, b) for name, a, b in ctx["spans"].events if name == "window"]
    if not win:
        return None
    t0, t1 = win[0]
    tracer = tracing.default()
    if not tracer.intact(t0, t1):
        return None
    recs = tracer.between(t0, t1)
    ticks = sum(1 for r in recs
                if r.name == "serving.dispatch" and t0 <= r.start <= t1)
    if not ticks:
        return None
    return t0, t1, recs, ticks


def ms_per_tick(ctx, names: Iterable[str]) -> Optional[float]:
    """Seconds inside the window of the spans named ``names``, in ms a
    dispatched tick."""
    got = window_records(ctx)
    if got is None:
        return None
    t0, t1, recs, ticks = got
    names = set(names)
    total = sum(min(r.end, t1) - max(r.start, t0) for r in recs
                if r.name in names)
    return total / ticks * 1e3


def p95_ms(ctx, name: str) -> Optional[float]:
    """p95, in ms, of the whole length of the spans named ``name`` that
    end inside the window."""
    got = window_records(ctx)
    if got is None:
        return None
    t0, t1, recs, _ = got
    d = [r.end - r.start for r in recs if r.name == name and t0 <= r.end <= t1]
    if not d:
        return None
    return float(np.percentile(d, 95)) * 1e3
