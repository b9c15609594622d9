"""The readers of the per-layer metrics that rest on the program's own
spans (``repro.serving.tracing``), on synthetic records and a fake run
context (CPU only)."""
import builtins
import importlib.util
import types
from pathlib import Path

import numpy as np
import pytest

from repro.serving import tracing

METRICS = Path(__file__).resolve().parent.parent / "metrics"
WINDOW = (10.0, 20.0)
WAITS = [0.001 * k for k in range(1, 21)]       # 1..20 ms, ending inside
VERDICTS = [0.1 + 0.002 * k for k in range(10)]


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _ctx():
    return {"spans": types.SimpleNamespace(
        events=[("append", 10.0, 10.1), ("window",) + WINDOW])}


@pytest.fixture
def tracer(monkeypatch):
    """The program's default tracer, holding four ticks of a 10 s
    window and spans that reach over either edge of it."""
    t = tracing.Tracer(active=lambda: True)
    rec = t.record
    rec("dispatch", 9.5, 9.6, rows=3)            # before the window
    for a in (11.0, 13.0, 15.0, 17.0):
        rec("dispatch", a, a + 0.01, rows=3)
    rec("admit", 9.8, 10.2)                      # 0.2 s inside
    rec("schedule", 12.0, 12.5)
    rec("book", 11.5, 11.6)
    rec("book", 19.9, 20.3)                      # 0.1 s inside
    rec("readback", 13.1, 13.3)
    rec("ctc_merge", 13.3, 14.1)
    for d in WAITS:
        rec("window_wait", 12.0 - d, 12.0, rid=1, slot=0)
    rec("window_wait", 19.0, 21.0, rid=2, slot=1)    # ends after it
    for d in VERDICTS:
        rec("verdict", 15.0 - d, 15.0, rid=3)
    rec("verdict", 5.0, 9.0, rid=4)              # ends before it
    monkeypatch.setattr(tracing, "_DEFAULT", t)
    return t


WANT = {
    "schedule_ms_per_tick.bulk": (0.2 + 0.5) / 4 * 1e3,
    "schedule_ms_per_tick.live": (0.2 + 0.5) / 4 * 1e3,
    "book_ms_per_tick.bulk": (0.1 + 0.1) / 4 * 1e3,
    "book_ms_per_tick.live": (0.1 + 0.1) / 4 * 1e3,
    "readback_ms_per_tick.bulk": 0.2 / 4 * 1e3,
    "ctc_merge_ms_per_tick.bulk": 0.8 / 4 * 1e3,
    "window_wait_p95_ms.live": float(np.percentile(WAITS, 95)) * 1e3,
    "verdict_p95_ms.live": float(np.percentile(VERDICTS, 95)) * 1e3,
}


@pytest.mark.parametrize("name,want", list(WANT.items()))
def test_program_span_reader(tracer, name, want):
    read = _reader(name)
    assert read(_ctx()) == pytest.approx(want, rel=1e-9)
    # nothing to read: no dispatch in the window, or records of the
    # window overwritten by the ring
    assert read({"spans": types.SimpleNamespace(
        events=[("window", 30.0, 40.0)])}) is None
    tracer._dropped_end = WINDOW[0]
    assert read(_ctx()) is None


def test_program_span_readers_without_the_tracer(monkeypatch):
    """On a program that has no ``repro.serving.tracing`` every reader
    returns None and raises nothing."""
    real = builtins.__import__

    def no_tracing(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "repro.serving" and "tracing" in (fromlist or ()):
            raise ImportError("cannot import name 'tracing'")
        return real(name, globals, locals, fromlist, level)
    monkeypatch.setattr(builtins, "__import__", no_tracing)
    for name in WANT:
        assert _reader(name)(_ctx()) is None, name
