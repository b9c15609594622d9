"""Spans of the serving program, on the profiler's clock.

A span is recorded only while a profiler session is collecting
(``jax.profiler.TraceAnnotation.is_enabled()``): with no session a span
costs that one check. An operator turns spans on the documented JAX
way, ``jax.profiler.start_trace(log_dir)`` or a client capturing from
``jax.profiler.start_server(port)``. While a session collects, each
span is recorded twice:

- as a ``jax.profiler.TraceAnnotation`` named ``serving.<name>`` (the
  tick as a ``StepTraceAnnotation``), so the trace shows it on the host
  beside the device ops it enqueued and waited for;
- in memory, as a :class:`Record` on ``time.perf_counter``: name, start,
  end, the index of the enclosing span and integer attributes (``rid``,
  ``slot``, ``rows``).

Records live in a bounded ring (:data:`CAPACITY` entries); the oldest
are overwritten and counted in ``dropped``. :meth:`Tracer.between`
returns the records that overlap a window and :meth:`Tracer.intact`
says whether any record of that window was overwritten.
:meth:`Tracer.record` stores a span whose start was known earlier (the
queue wait of a streamed window, the delay of a read-until verdict);
such spans are kept in memory only.

Spans of the engine (``repro.serving.engine``) and the basecaller
runner (``repro.serving.runner``), by name:

``serving.tick``         a dispatching engine tick (``rows``)
``serving.admit``        admission: chunking, runner admit, open stream
``serving.schedule``     building the tick's work list
``serving.dispatch``     packing the batch, enqueueing the forward
                         (``rows`` carrying a window)
``serving.device_wait``  waiting for the tick's device result
``serving.readback``     copying log-probs (and logits) to the host
``serving.ctc_merge``    argmax, CTC merge, read-until accumulation
                         (``rows``)
``serving.book``         booking tokens, finishes and ejections
``serving.window_wait``  recorded: a streamed window's enabling sample
                         to its dispatch (``rid``, ``slot``)
``serving.verdict``      recorded: a read's deciding window's enabling
                         sample to its ejection (``rid``)

Request counters stay in :class:`repro.serving.metrics.ServingMetrics`.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import Callable, Dict, List, Optional

import jax

PREFIX = "serving."
CAPACITY = 1 << 17

_OFF = contextlib.nullcontext()


@dataclasses.dataclass(eq=False, slots=True)
class Record:
    """One span: ``end`` is None while it is open; ``parent`` is the
    ``index`` of the enclosing span, or None."""

    index: int
    name: str
    start: float
    end: Optional[float]
    parent: Optional[int]
    attrs: Dict[str, int]


class _Span:
    __slots__ = ("tracer", "name", "attrs", "step_num", "ann", "rec")

    def __init__(self, tracer: "Tracer", name: str, attrs: Dict[str, int],
                 step_num: Optional[int]):
        self.tracer, self.name, self.attrs = tracer, name, attrs
        self.step_num = step_num

    def __enter__(self) -> Record:
        if self.step_num is None:
            self.ann = jax.profiler.TraceAnnotation(self.name, **self.attrs)
        else:
            self.ann = jax.profiler.StepTraceAnnotation(
                self.name, step_num=self.step_num, **self.attrs)
        self.ann.__enter__()
        self.rec = self.tracer._open(self.name, self.attrs)
        return self.rec

    def __exit__(self, *exc) -> None:
        self.tracer._close(self.rec)
        self.ann.__exit__(*exc)


class Tracer:
    """Span recorder; ``active`` says whether to record (default: a
    profiler session is collecting)."""

    def __init__(self, capacity: int = CAPACITY,
                 active: Optional[Callable[[], bool]] = None):
        self.capacity = int(capacity)
        self.active = (active if active is not None
                       else jax.profiler.TraceAnnotation.is_enabled)
        self.clear()

    def clear(self) -> None:
        self._ring: List[Optional[Record]] = [None] * self.capacity
        self._n = 0                         # records ever made
        self._stack: List[Record] = []      # open spans, innermost last
        self.dropped = 0
        self._dropped_end = -math.inf       # latest end of a dropped record

    def span(self, name: str, step_num: Optional[int] = None, **attrs: int):
        """Context manager timing a block as ``serving.<name>``; with
        ``step_num`` it is a step of the trace (the engine's tick)."""
        if not self.active():
            return _OFF
        return _Span(self, PREFIX + name, attrs, step_num)

    def record(self, name: str, start: float, end: float,
               **attrs: int) -> None:
        """Store ``serving.<name>`` over ``[start, end]`` (in memory)."""
        if self.active():
            self._add(PREFIX + name, start, end, attrs)

    def between(self, t0: float, t1: float) -> List[Record]:
        """Closed records that overlap ``[t0, t1]``, oldest first."""
        n, cap = self._n, self.capacity
        ring = (self._ring[:n] if n <= cap
                else self._ring[n % cap:] + self._ring[:n % cap])
        return [r for r in ring
                if r.end is not None and r.end >= t0 and r.start <= t1]

    def intact(self, t0: float, t1: float) -> bool:
        """No record that overlapped ``[t0, t1]`` was overwritten."""
        return self._dropped_end < t0

    # ---------------------------------------------------------- internal
    def _add(self, name: str, start: float, end: Optional[float],
             attrs: Dict[str, int]) -> Record:
        parent = self._stack[-1].index if self._stack else None
        rec = Record(self._n, name, start, end, parent, attrs)
        i = self._n % self.capacity
        old = self._ring[i]
        if old is not None:
            self.dropped += 1
            self._dropped_end = max(self._dropped_end,
                                    math.inf if old.end is None else old.end)
        self._ring[i] = rec
        self._n += 1
        return rec

    def _open(self, name: str, attrs: Dict[str, int]) -> Record:
        rec = self._add(name, time.perf_counter(), None, attrs)
        self._stack.append(rec)
        return rec

    def _close(self, rec: Record) -> None:
        rec.end = time.perf_counter()
        self._stack.remove(rec)


_DEFAULT = Tracer()


def default() -> Tracer:
    """The process-wide tracer the engine and runners use unless given
    another."""
    return _DEFAULT
