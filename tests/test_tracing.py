"""Program spans (``repro.serving.tracing``): recorded only while a
profiler session collects, nested with parents and attributes, kept in
a bounded ring, written to the trace beside the benchmark's own spans,
and recorded by a basecaller engine serving live streams."""
import glob
import math
import os

import jax
import numpy as np
import pytest

from repro.config import get_config
from repro.models import api
from repro.models.basecaller import classifier as rc
from repro.models.basecaller import model as bc
from repro.serving import ServingEngine, tracing
from repro.serving.stream import ReadUntil, StreamingRequest

CHUNK = 300          # core samples per window (bonito-smoke: stride 3)


def _on():
    return tracing.Tracer(active=lambda: True)


def _names(records):
    return [r.name for r in records]


def test_span_outside_a_profiler_session_records_nothing():
    calls = []

    def active():
        calls.append(1)
        return jax.profiler.TraceAnnotation.is_enabled()

    t = tracing.Tracer(active=active)
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with t.span("tick", step_num=0, rows=3):
        with t.span("dispatch", rows=3):
            pass
    t.record("window_wait", 0.0, 1.0, rid=1, slot=0)
    assert len(calls) == 3                  # one check a span, nothing else
    assert t.between(-math.inf, math.inf) == [] and t.dropped == 0
    assert tracing.Tracer().active == jax.profiler.TraceAnnotation.is_enabled


def test_spans_nest_with_parents_and_attrs_inside_start_trace(tmp_path):
    t = tracing.Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with t.span("schedule"):
                pass
            with t.span("tick", step_num=7, rows=2) as tick:
                with t.span("dispatch", rows=2):
                    jax.block_until_ready(jax.numpy.ones(4) * 2)
                with t.span("book"):
                    t.record("verdict", tick.start, tick.start + 1e-3,
                             rid=11)
            t.record("window_wait", tick.start - 1.0, tick.start, rid=5,
                     slot=1)
    finally:
        jax.profiler.stop_trace()
    recs = t.between(-math.inf, math.inf)
    by = {r.name: r for r in recs}
    assert _names(recs) == ["serving.schedule", "serving.tick",
                            "serving.dispatch", "serving.book",
                            "serving.verdict", "serving.window_wait"]
    assert [r.index for r in recs] == list(range(6))
    assert by["serving.schedule"].parent is None
    assert by["serving.tick"].parent is None
    assert by["serving.dispatch"].parent == by["serving.tick"].index
    assert by["serving.book"].parent == by["serving.tick"].index
    assert by["serving.verdict"].parent == by["serving.book"].index
    assert by["serving.window_wait"].parent is None
    assert by["serving.tick"].attrs == {"rows": 2}
    assert by["serving.dispatch"].attrs == {"rows": 2}
    assert by["serving.verdict"].attrs == {"rid": 11}
    assert by["serving.window_wait"].attrs == {"rid": 5, "slot": 1}
    tick = by["serving.tick"]
    for r in recs:
        assert r.end >= r.start
        if r.parent == tick.index:
            assert tick.start <= r.start and r.end <= tick.end
    # the window query clips nothing itself: overlap decides
    assert _names(t.between(tick.start, tick.start)) == [
        "serving.tick", "serving.verdict", "serving.window_wait"]

    # the annotations are in the written trace, on the host plane that
    # holds the benchmark's bench.* spans, under serving.
    files = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert files
    data = jax.profiler.ProfileData.from_file(files[0])
    planes = {}
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("serving.", "bench.")):
                    planes.setdefault(ev.name, set()).add(plane.name)
    assert {"serving.schedule", "serving.tick", "serving.dispatch",
            "serving.book", "bench.window"} <= set(planes)
    assert planes["serving.dispatch"] == planes["bench.window"]
    # recorded spans are kept in memory only
    assert "serving.window_wait" not in planes
    assert not any(n.startswith("bench.") for n in _names(recs))


def test_ring_drops_the_oldest_and_counts_them():
    t = tracing.Tracer(capacity=4, active=lambda: True)
    for k in range(6):
        t.record("window_wait", float(k), k + 0.5, rid=k, slot=0)
    recs = t.between(-math.inf, math.inf)
    assert [r.attrs["rid"] for r in recs] == [2, 3, 4, 5]
    assert [r.index for r in recs] == [2, 3, 4, 5]
    assert t.dropped == 2
    assert not t.intact(1.0, 9.0)           # record 1 ended at 1.5
    assert t.intact(1.6, 9.0)
    assert [r.attrs["rid"] for r in t.between(3.2, 4.1)] == [3, 4]
    t.clear()
    assert t.between(-math.inf, math.inf) == [] and t.dropped == 0


@pytest.fixture(scope="module")
def smoke():
    cfg = get_config("bonito-smoke")
    params = api.init_params(jax.random.key(0), cfg)
    return cfg, params


@pytest.mark.parametrize("async_dispatch", [False, True])
def test_engine_records_window_wait_and_verdict_spans(smoke, async_dispatch):
    """Two live streams on a smoke-width basecaller whose read-until
    threshold ejects every read after its first classified window: each
    dispatched window waits in ``window_wait``, each ejection is a
    ``verdict`` booked from the same enabling event, and the dispatch
    nests under ``serving.tick`` (so does the harvest when the tick is
    synchronous; the async engine harvests it one step later)."""
    cfg, params = smoke
    t = _on()
    ru = ReadUntil(params=rc.init_params(jax.random.key(3)),
                   eject_after_chunks=1, threshold=1e9)
    eng = ServingEngine(params, cfg, n_slots=2, chunk_samples=CHUNK,
                        read_until=ru, tracer=t,
                        async_dispatch=async_dispatch)
    assert eng.runner.tracer is t
    reqs = [StreamingRequest(rid=r) for r in (41, 42)]
    for r in reqs:
        eng.submit(r)
    eng.step()                              # admits; nothing coverable
    eng.step()                              # idle fast path
    recs = t.between(-math.inf, math.inf)
    assert _names(recs) == ["serving.admit", "serving.schedule"]
    assert eng.metrics.idle_ticks == 1
    halo = bc.chunk_halo(cfg)
    rs = np.random.RandomState(0)
    for r in reqs:
        r.append(rs.randn(CHUNK + halo + 50).astype(np.float32))
    for _ in range(4):
        if not eng.busy:
            break
        eng.step()
    assert all(r.ejected for r in reqs)
    recs = t.between(-math.inf, math.inf)
    by_index = {r.index: r for r in recs}
    ticks = [r for r in recs if r.name == "serving.tick"]
    assert len(ticks) == 1 and ticks[0].attrs == {"rows": 2}
    one = {r.name: r for r in recs}
    assert {"serving.dispatch", "serving.device_wait", "serving.readback",
            "serving.ctc_merge", "serving.book"} <= set(one)
    assert one["serving.dispatch"].parent == ticks[0].index
    frames = bc.span_frames(bc.window_spans(cfg, CHUNK + 2 * halo)) * 2
    assert one["serving.dispatch"].attrs == {"rows": 2, "frames": frames}
    assert one["serving.ctc_merge"].attrs == {"rows": 2}
    if not async_dispatch:
        for name in ("serving.device_wait", "serving.readback",
                     "serving.ctc_merge", "serving.book"):
            assert one[name].parent == ticks[0].index, name
    waits = [r for r in recs if r.name == "serving.window_wait"]
    assert sorted(r.attrs["rid"] for r in waits) == [41, 42]
    assert sorted(r.attrs["slot"] for r in waits) == [0, 1]
    for w in waits:
        assert w.start <= w.end <= one["serving.dispatch"].start
    verdicts = [r for r in recs if r.name == "serving.verdict"]
    assert sorted(r.attrs["rid"] for r in verdicts) == [41, 42]
    for v, w in zip(sorted(verdicts, key=lambda r: r.attrs["rid"]),
                    sorted(waits, key=lambda r: r.attrs["rid"])):
        assert v.start == w.start           # the deciding window's enable
        assert by_index[v.parent].name == "serving.book"
        assert v.end >= one["serving.ctc_merge"].end
    # emit latency is booked for every harvested streamed window
    assert eng.metrics.summary()["emit_events"] == 2


def test_forward_names_each_block_and_the_head_in_hlo():
    cfg = get_config("rubicall-smoke")
    params = api.init_params(jax.random.key(0), cfg)
    W = 4 * bc.total_stride(cfg) * 8 + 2 * bc.chunk_halo(cfg)
    fwd = jax.jit(lambda p, s, w, a, n: bc.forward_window(p, s, w, cfg, a, n))
    text = fwd.lower(params, bc.init_state(cfg),
                     np.zeros((2, W, 1), np.float32),
                     np.zeros((2,), np.int32),
                     np.zeros((2,), np.int32)).as_text(debug_info=True)
    for i in range(cfg.n_blocks):
        assert f"/block{i:02d}/" in text, i
    assert "/head/" in text
