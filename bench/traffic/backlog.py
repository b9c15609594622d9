"""Closed-queue backlog: offline basecalling of a finished run.

Reads the mix's JSON (``kind: "backlog"``): a pool of ``pool_reads``
reads whose lengths in bases are the midpoint quantiles of a log-normal
(``median_bases``, ``sigma``, capped at ``cap_bases``, at least
``floor_bases``), simulated at ``dwell`` samples a base, submitted whole
in a seeded order and repeated as needed, so that at least
``queued_per_slot * n_slots`` reads always wait. Every seed has the same
read lengths; the bases, the signal and the order differ.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from bench.traffic import squiggle


class Backlog:
    end_to_end = ("samples_per_s",)

    def __init__(self, mix: Dict, seed: int, seconds: float, geometry):
        self.n_slots = int(mix["n_slots"])
        self.chunk_samples = int(mix["chunk_samples"])
        self.eject_after_chunks = 0
        rng = np.random.default_rng([seed, 1])
        table = squiggle.pore_table()
        n = int(mix["pool_reads"])
        lengths = squiggle.read_lengths(
            n, median=mix["median_bases"], sigma=mix["sigma"],
            cap=mix["cap_bases"], floor=mix["floor_bases"])
        self.pool = [squiggle.read_signal(rng, table, int(b),
                                          dwell=mix["dwell"])
                     for b in lengths]
        self.order = rng.permutation(n)
        self.depth = int(mix["queued_per_slot"]) * self.n_slots
        self.next = 0
        self.read_of: Dict[int, int] = {}       # rid -> pool index

    def _submit(self, engine, Request) -> None:
        rid = self.next
        idx = int(self.order[rid % len(self.order)])
        self.read_of[rid] = idx
        engine.submit(Request(rid=rid, signal=self.pool[idx]))
        self.next += 1

    def prepare(self, engine) -> None:
        from repro.serving.engine import Request
        self._Request = Request
        while len(engine.queue) < self.depth:
            self._submit(engine, Request)

    def feed(self, engine, now: float) -> None:
        while len(engine.queue) < self.depth:
            self._submit(engine, self._Request)

    def after_step(self, engine, now: float) -> None:
        pass

    def next_due(self, now: float) -> Optional[float]:
        return None                             # the queue is never empty

    def close(self, engine, t_end: float) -> Dict[str, float]:
        self.done = {rid: r for rid, r in engine.completed.items()}
        self.attempted = self.next
        self.failed = sum(r.rejected for r in self.done.values())
        return {}

    def served(self) -> List[Tuple[object, np.ndarray]]:
        """Reads the engine finished in the window, one per pool read."""
        seen, out = set(), []
        for rid, r in sorted(self.done.items()):
            idx = self.read_of[rid]
            if r.finished and idx not in seen:
                seen.add(idx)
                out.append((r, self.pool[idx]))
        return out

    def judged(self) -> List:
        return []


def make(mix: Dict, seed: int, seconds: float, geometry) -> Backlog:
    return Backlog(mix, seed, seconds, geometry)
