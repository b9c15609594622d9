"""Open-loop flow-cell channels: live read-until.

Reads the mix's JSON (``kind: "channels"``). Each of ``channels``
channels streams one read after another at ``sample_rate`` samples/s,
with ``gap_s`` between reads; channel starts are staggered over the
first ``stagger_s`` seconds. The reads come from one list of
``pool_reads``, the same on every seed: lengths at the midpoint
quantiles of a log-normal in bases (``median_bases``, ``sigma``,
``cap_bases``, ``floor_bases``) at ``dwell`` samples a base, and a share
``target_share`` of them on-target (pore squiggle), the rest off-target
(white noise, as the read-until head tells them apart). The seed draws
the list's order, the channels' start order and the signals.

The schedule is fixed by the seed and never waits for the system: an
off-target read delivers its samples until ``offtarget_budget_s`` after
the sample that completes its ``eject_after_chunks``-th window, as the
pore would before it is unblocked, and the channel's next read follows
after the gap. Samples are appended every ``append_every_s`` seconds of
signal, and a read's end is announced with ``finish()`` when its last
sample is due (off-target reads cut at their budget are never
finished: they wait for their verdict).

Latencies are taken from the schedule's due times to the return of the
engine step after which the request shows the outcome:

- ``eject_p95_ms``: every off-target read longer than
  ``eject_after_chunks`` cores (a shorter one finishes whatever the
  verdict), from when the sample completing its
  ``eject_after_chunks``-th window was due (``eject_after_chunks *
  core + halo`` samples, or its last sample for a shorter read) to
  ``req.ejected``;
- ``read_lag_p95_ms``: every on-target read, from its last sample's due
  time to ``req.finished``.

An event due in the window and not delivered by its end counts at its
censored latency (window end minus due time).
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench.traffic import squiggle


class Read:
    __slots__ = ("channel", "start", "n", "deliver", "on_target", "signal",
                 "req", "sent", "due_event", "done_at")

    def __init__(self, channel, start, n, deliver, on_target, signal,
                 due_event):
        self.channel, self.start, self.n = channel, start, n
        self.deliver, self.on_target, self.signal = deliver, on_target, signal
        self.due_event = due_event
        self.req = None
        self.sent = 0
        self.done_at: Optional[float] = None


class Channels:
    end_to_end = ("eject_p95_ms", "read_lag_p95_ms")

    def __init__(self, mix: Dict, seed: int, seconds: float, geometry):
        self.n_slots = int(mix["channels"])
        self.chunk_samples = int(mix["chunk_samples"])
        self.eject_after_chunks = int(mix["eject_after_chunks"])
        core, halo = geometry["core"], geometry["halo"]
        rate = float(mix["sample_rate"])
        self.rate = rate
        self.block = max(int(round(mix["append_every_s"] * rate)), 1)
        self.core = core
        rng = np.random.default_rng([seed, 2])
        table = squiggle.pore_table()
        n_ch = self.n_slots
        starts = rng.permutation(n_ch) * (mix["stagger_s"] / n_ch)
        decide = self.eject_after_chunks * core + halo
        budget = int(round(mix["offtarget_budget_s"] * rate))
        dwell = float(mix["dwell"])
        self.reads: List[Read] = []
        self.by_channel: List[List[Read]] = [[] for _ in range(n_ch)]
        # one list of reads, the same sizes and labels on every seed:
        # lengths at log-normal quantiles, every 1/target_share-th read
        # on-target, in a seeded order; channel c streams reads c, c + N,
        # c + 2N, ... of it
        pool = int(mix["pool_reads"])
        lengths = squiggle.read_lengths(
            pool, median=mix["median_bases"], sigma=mix["sigma"],
            cap=mix["cap_bases"], floor=mix["floor_bases"])
        n_on = int(round(pool * mix["target_share"]))
        targets = np.zeros(pool, bool)
        targets[np.linspace(0, pool - 1, n_on).astype(int)] = True
        order = rng.permutation(pool)
        for c in range(n_ch):
            t = float(starts[c])
            j = c
            while t < seconds:
                bases, on = int(lengths[order[j % pool]]), \
                    bool(targets[order[j % pool]])
                j += n_ch
                if on:
                    sig = squiggle.read_signal(rng, table, bases, dwell=dwell)
                    n = deliver = sig.shape[0]
                else:
                    n = int(round(bases * dwell))
                    deliver = min(n, decide + budget)
                    sig = squiggle.noise_signal(rng, n)[:deliver]
                if on:
                    due_event = t + n / rate                # last sample
                elif n > self.eject_after_chunks * core:     # decided
                    due_event = t + min(decide, n) / rate
                else:
                    due_event = None                        # never decided
                r = Read(c, t, n, deliver, on, sig, due_event)
                self.reads.append(r)
                self.by_channel[c].append(r)
                t += deliver / rate + mix["gap_s"]
        self.cursor = [0] * n_ch          # index of the channel's live read
        self.open: List[Read] = []        # submitted, outcome not seen
        self.submitted = 0

    def prepare(self, engine) -> None:
        from repro.serving.stream import StreamingRequest
        self._Req = StreamingRequest
        self._clock = engine.metrics.clock

    def feed(self, engine, now: float) -> None:
        """Submit reads whose start is due and append every block of
        samples that is due by ``now``."""
        for c, reads in enumerate(self.by_channel):
            k = self.cursor[c]
            while k < len(reads) and reads[k].start <= now:
                r = reads[k]
                if r.req is None:
                    r.req = self._Req(self.submitted, clock=self._clock)
                    self.submitted += 1
                    engine.submit(r.req)
                    self.open.append(r)
                due = min(r.deliver, int((now - r.start) * self.rate))
                if due < r.deliver:
                    due -= due % self.block
                if due > r.sent and not r.req.done:
                    r.req.append(r.signal[r.sent:due])
                    r.sent = due
                    if due == r.n:
                        r.req.finish()
                if r.sent >= r.deliver or r.req.done:
                    k += 1              # the channel moves to its next read
                else:
                    break
            self.cursor[c] = k

    def after_step(self, engine, now: float) -> None:
        still = []
        for r in self.open:
            if r.req.done:
                r.done_at = now
            else:
                still.append(r)
        self.open = still

    def next_due(self, now: float) -> Optional[float]:
        """Time of the next sample block or read start."""
        nxt = math.inf
        for c, reads in enumerate(self.by_channel):
            k = self.cursor[c]
            if k >= len(reads):
                continue
            r = reads[k]
            if r.req is None:
                nxt = min(nxt, r.start)
            else:
                step = min(r.sent + self.block, r.deliver)
                nxt = min(nxt, r.start + step / self.rate)
        return None if nxt == math.inf else nxt

    def close(self, engine, t_end: float) -> Dict[str, float]:
        ejects, lags, halves = [], [], ([], [])
        for r in self.reads:
            if r.due_event is None or r.due_event > t_end:
                continue
            hit = (r.req is not None and r.done_at is not None
                   and (r.req.finished if r.on_target else r.req.ejected))
            lat = max((r.done_at if hit else t_end) - r.due_event, 0.0) * 1e3
            (lags if r.on_target else ejects).append(lat)
            if not r.on_target:
                halves[r.due_event > t_end / 2].append(lat)
        # a backlog that grows shows as a later half slower than the first
        self.n_events = {"eject": len(ejects), "read_lag": len(lags),
                         "eject_p95_by_half_ms": [
                             float(np.percentile(h, 95)) if h else None
                             for h in halves]}
        self.attempted = self.submitted
        self.failed = sum(1 for r in self.reads
                          if r.req is not None and r.req.rejected)
        out = {}
        if ejects:
            out["eject_p95_ms"] = float(np.percentile(ejects, 95))
        if lags:
            out["read_lag_p95_ms"] = float(np.percentile(lags, 95))
        return out

    def served(self) -> List[Tuple[object, np.ndarray]]:
        """On-target reads the engine finished in the window."""
        return [(r.req, r.signal) for r in self.reads
                if r.on_target and r.req is not None and r.req.finished]

    def judged(self) -> List[Tuple[object, np.ndarray]]:
        """Reads with a verdict the engine reached in the window: every
        read it ejected or finished whose deciding window was not its
        last (a read that ends in that window finishes, whatever the
        verdict)."""
        return [(r.req, r.signal) for r in self.reads
                if r.req is not None and r.req.done
                and r.n > self.eject_after_chunks * self.core]


def make(mix: Dict, seed: int, seconds: float, geometry) -> Channels:
    return Channels(mix, seed, seconds, geometry)
