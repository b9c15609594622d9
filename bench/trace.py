"""Reduction of a profiler trace to device busy time and its gaps.

A trace is read into plain events ``{"plane", "line", "name",
"start_ns", "dur_ns"}`` (:func:`load_events`), which the tests keep as a
small recorded JSON file. From them:

- busy: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` lines of ``/device:TPU:*`` planes), clipped to
  the traced window (the host span ``bench.window``), averaged over the
  devices;
- the forward program's device time: the ``XLA Modules`` events whose
  name holds the program's name;
- the device operations that took most time;
- the idle gaps between busy intervals, each attributed to the host
  span (``bench.*``) that covers most of it.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
# when host spans nest, the innermost activity names the gap
HOST_ORDER = ("bench.collect.merge", "bench.collect.wait", "bench.dispatch",
              "bench.append", "bench.wait", "bench.step")
HOST_NAMES = {"bench.collect.merge": "merge", "bench.collect.wait":
              "collect_wait", "bench.dispatch": "dispatch",
              "bench.append": "append", "bench.wait": "wait_traffic",
              "bench.step": "engine"}


def load_events(trace_dir: str) -> List[Dict]:
    """Events of the newest ``.xplane.pb`` under ``trace_dir``: device
    op and module events and the benchmark's host spans."""
    import jax
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    out = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if not device and not ev.name.startswith("bench."):
                    continue
                out.append({"plane": plane.name, "line": line.name,
                            "name": ev.name, "start_ns": float(ev.start_ns),
                            "dur_ns": float(ev.duration_ns)})
    return out


def op_name(hlo: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ")[0].lstrip("%")


def _union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _clip(intervals, lo: float, hi: float):
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            yield a, b


def window_ns(events: Sequence[Dict]) -> Tuple[float, float]:
    w = [e for e in events if e["name"] == WINDOW]
    if not w:
        raise ValueError("trace holds no bench.window span")
    return w[0]["start_ns"], w[0]["start_ns"] + w[0]["dur_ns"]


def devices(events: Sequence[Dict]) -> List[str]:
    return sorted({e["plane"] for e in events
                   if e["plane"].startswith(DEVICE_PREFIX)})


def busy_intervals(events: Sequence[Dict], plane: str, lo: float,
                   hi: float) -> List[List[float]]:
    ops = ((e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in events
           if e["plane"] == plane and e["line"] == OPS_LINE)
    return _union(_clip(ops, lo, hi))


def reduce(events: Sequence[Dict], program: str = "fwd",
           top: int = 10) -> Dict:
    """Busy and window seconds, the forward program's device seconds,
    the top device ops and the idle seconds by host activity."""
    lo, hi = window_ns(events)
    planes = devices(events)
    if not planes:
        raise ValueError("trace holds no TPU device plane")
    busy = [busy_intervals(events, p, lo, hi) for p in planes]
    busy_s = sum(b - a for iv in busy for a, b in iv) / len(planes) / 1e9
    modules = [(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in events
               if e["line"] == MODULES_LINE and program in e["name"]]
    program_s = sum(b - a for a, b in _clip(modules, lo, hi)) \
        / len(planes) / 1e9
    op_s: Dict[str, float] = defaultdict(float)
    for e in events:
        if e["line"] == OPS_LINE:
            for a, b in _clip([(e["start_ns"],
                                e["start_ns"] + e["dur_ns"])], lo, hi):
                op_s[op_name(e["name"])] += (b - a) / 1e9
    top_ops = sorted(op_s.items(), key=lambda kv: -kv[1])[:top]
    gaps = idle_by_host(events, busy[0], lo, hi)
    return {"busy_s": busy_s, "window_s": (hi - lo) / 1e9,
            "program_s": program_s if modules else None,
            "device_ops": [[n, s] for n, s in top_ops],
            "idle_gaps": [[n, s] for n, s in
                          sorted(gaps.items(), key=lambda kv: -kv[1])[:top]]}


def _gaps(busy: List[List[float]], lo: float, hi: float):
    t = lo
    for a, b in busy:
        if a > t:
            yield t, a
        t = max(t, b)
    if hi > t:
        yield t, hi


def idle_by_host(events: Sequence[Dict], busy: List[List[float]],
                 lo: float, hi: float) -> Dict[str, float]:
    """Idle seconds between busy intervals, each gap given to the
    innermost host activity that covers at least half of it, else to
    the activity covering most of it (``outside_spans`` when none)."""
    spans = {}
    for name in HOST_NAMES:
        iv = _union((e["start_ns"], e["start_ns"] + e["dur_ns"])
                    for e in events if e["name"] == name)
        spans[name] = (iv, [a for a, _ in iv])
    out: Dict[str, float] = defaultdict(float)
    for a, b in _gaps(busy, lo, hi):
        cover = {}
        for name, (iv, starts) in spans.items():
            i = max(bisect.bisect_right(starts, a) - 1, 0)
            c = 0.0
            while i < len(iv) and iv[i][0] < b:
                c += max(0.0, min(b, iv[i][1]) - max(a, iv[i][0]))
                i += 1
            if c > 0:
                cover[name] = c
        if not cover:
            who = "outside_spans"
        else:
            who = next((HOST_NAMES[n] for n in HOST_ORDER
                        if cover.get(n, 0.0) >= 0.5 * (b - a)),
                       HOST_NAMES[max(cover, key=cover.get)])
        out[who] += (b - a) / 1e9
    return dict(out)


def reduce_dir(trace_dir: str, program: str = "fwd") -> Optional[Dict]:
    return reduce(load_events(trace_dir), program)
