"""Host spans and counters, recorded from the benchmark's own files.

The engine and its runner are wrapped at three public methods of the
runner protocol: ``engine.step``, ``engine.runner.dispatch`` and
``engine.runner.collect``. ``collect`` first waits for the tick's device
result (``collect.wait``) and then times the readback, CTC merge and
read-until verdicts (``collect.merge``), so device wait and host merge
are told apart. The traffic driver's appends and waits are spans too.

Spans are kept in memory as ``(name, start_s, end_s)``. In a traced run
each is also a ``jax.profiler.TraceAnnotation`` named ``bench.<name>``,
on the profiler's clock, so idle device gaps can be attributed to what
the host was doing.
"""
from __future__ import annotations

import contextlib
import time
from collections import defaultdict
from typing import Dict, List, Tuple

import jax

PREFIX = "bench."


class Spans:
    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.on = False                 # record only inside the window
        self.events: List[Tuple[str, float, float]] = []
        self.counters: Dict[str, float] = defaultdict(float)
        self.ticks: List[Tuple[int, int]] = []   # (frames, samples) a tick

    @contextlib.contextmanager
    def span(self, name: str):
        if not self.on:
            yield
            return
        ann = (jax.profiler.TraceAnnotation(PREFIX + name) if self.annotate
               else contextlib.nullcontext())
        with ann:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.events.append((name, t0, time.perf_counter()))

    def count(self, name: str, n: float = 1.0) -> None:
        if self.on:
            self.counters[name] += n

    def total_s(self, name: str) -> float:
        return sum(t1 - t0 for n, t0, t1 in self.events if n == name)


def instrument(engine, spans: Spans, stride: int) -> None:
    """Wrap the engine's tick and its runner's dispatch/collect.

    Counters: ``ticks`` (dispatched ticks), ``rows`` (rows carrying a
    real window), ``samples`` and ``frames`` (core signal samples and
    CTC frames whose bases ``collect`` returned), and the same per tick
    in ``spans.ticks``."""
    runner = engine.runner
    step, dispatch, collect = engine.step, runner.dispatch, runner.collect

    def traced_step():
        with spans.span("step"):
            step()

    def traced_dispatch(works):
        with spans.span("dispatch"):
            handle = dispatch(works)
        spans.count("ticks")
        spans.count("rows", sum(w is not None for w in works))
        return handle

    def traced_collect(handle, discard=frozenset()):
        with spans.span("collect"):
            with spans.span("collect.wait"):
                jax.block_until_ready(handle)
            with spans.span("collect.merge"):
                out = collect(handle, discard=discard)
        frames = samples = 0
        for i, w in enumerate(handle[0]):
            if w is not None and i not in discard:
                samples += w.n_units
                frames += -(-w.n_units // stride)
        spans.count("samples", samples)
        spans.count("frames", frames)
        if spans.on:
            spans.ticks.append((frames, samples))
        return out

    engine.step = traced_step
    runner.dispatch = traced_dispatch
    runner.collect = traced_collect
