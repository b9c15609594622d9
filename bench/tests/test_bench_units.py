"""Unit tests of the benchmark's yardstick: FLOP counts, peaks, traffic
determinism and the trace reduction (CPU only)."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import flops, peaks, trace
from bench.traffic import backlog, channels

DATA = Path(__file__).with_name("data")
GEOMETRY = {"core": 300, "halo": 297, "stride": 3}


@pytest.mark.parametrize("c_in,c,k,reps,skips,want", [
    # RUBICALL block 01: one separable conv, K 9, 344 -> 344
    (344, 344, 9, 1, False, 2 * (9 * 344 + 344 * 344)),
    # Bonito block B1: 5 repeats, K 33, 344 -> 464, pointwise skip
    (344, 464, 33, 5, True, 2 * (33 * 344 + 344 * 464)
     + 4 * 2 * (33 * 464 + 464 * 464) + 2 * 344 * 464),
])
def test_flops_one_block_by_hand(c_in, c, k, reps, skips, want):
    cfg = {"arch": "sepconv", "channels": [c_in, c], "kernel_sizes": [1, k],
           "strides": [1, 1], "repeats": [1, reps], "use_skips": skips,
           "n_bases": 0}
    stem = flops.flops_per_frame(dict(cfg, channels=[c_in],
                                      kernel_sizes=[1], repeats=[1]))
    assert flops.flops_per_frame(cfg) - stem == want


@pytest.mark.parametrize("path,mflop", [
    pytest.param("configs/rubicall.json", 7.153154, id="rubicall"),
    # Bonito-style, skips: stem 2(9 + 32) + 2 x 32, three blocks
    # 2(K x 32 + 32 x 32) + 2 x 32 x 32 for K 33, 39, 51, head 2 x 32 x 5
    pytest.param("tests/data/bonito_smoke.json",
                 (146 + 6208 + 6592 + 7360 + 320) / 1e6, id="bonito"),
])
def test_flops_whole_model(path, mflop):
    from bench import reference
    cfg = reference.load_config(DATA.parent.parent / path)
    assert flops.flops_per_frame(cfg) / 1e6 == pytest.approx(mflop, abs=1e-6)


def test_tick_work_and_least_time():
    cfg = {"arch": "sepconv", "channels": [4], "kernel_sizes": [3],
           "strides": [1], "repeats": [1], "use_skips": False, "n_bases": 5,
           "dtype": "bfloat16"}
    w = flops.tick_work(cfg, frames=10, samples=10)
    assert w["flops"] == 10 * (2 * (3 * 1 + 1 * 4) + 2 * 4 * 5)
    assert w["bytes"] == (3 + 4 + 20) * 2 + 4 * 4 * 4 + 40 + 200
    assert flops.least_time_s(w, 1.0, 1e9) == w["flops"]


def test_reference_compiles_once_per_padded_length(caplog):
    """Reads of different lengths in one padding bucket share one
    program: nothing compiled per read enters the compile cache."""
    import logging
    import jax
    from bench import reference
    cfg = reference.load_config(DATA / "rubicall_smoke.json")
    params, state = jax.eval_shape(
        lambda k, c: reference.make_weights(k, cfg, c), jax.random.key(0),
        jax.ShapeDtypeStruct((1, 4096, 1), np.float32))
    params, state = jax.tree_util.tree_map(
        lambda a: np.ones(a.shape, a.dtype), (params, state))
    ref = reference.Reference(cfg, params, state)
    ref.log_probs(np.ones(3000, np.float32))
    with jax.log_compiles(), caplog.at_level(logging.WARNING):
        caplog.clear()
        lp = ref.log_probs(np.ones(4001, np.float32))
    assert lp.shape == (1334, 5)
    assert not [r for r in caplog.records if "ompil" in r.getMessage()]


def test_peaks_lookup_and_unknown_device():
    p = peaks.peaks("TPU v5 lite")
    assert p["bfloat16_flops"] == 197e12 and p["int8_ops"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert peaks.compute_peak("TPU v5 lite", "bfloat16") == 197e12
    for kind in ("TPU v4", "cpu", "source"):
        with pytest.raises(KeyError):
            peaks.peaks(kind)


def _mix(name):
    return json.loads((DATA / f"{name}.json").read_text())


@pytest.mark.parametrize("gen,mix", [(backlog, "bulk_tiny"),
                                     (channels, "live_tiny")])
def test_traffic_is_a_function_of_the_seed(gen, mix):
    def draw(seed):
        t = gen.make(_mix(mix), seed, 2.0, GEOMETRY)
        if gen is backlog:
            return [t.pool[i] for i in t.order]
        return [np.concatenate([[r.channel, r.start, r.n, r.on_target],
                                r.signal]) for r in t.reads]

    def same(x, y):
        return len(x) == len(y) and all(
            p.shape == q.shape and np.array_equal(p, q) for p, q in zip(x, y))
    big = 2 ** 31 + 977
    a, b, c = draw(big), draw(big), draw(big + 1)
    assert len(a) > 0 and same(a, b) and not same(a, c)


def test_live_schedule_is_open_loop_and_fills_the_window():
    mix = _mix("live_tiny")
    t = channels.make(mix, 5, 2.0, GEOMETRY)
    for reads in t.by_channel:
        for r0, r1 in zip(reads, reads[1:]):
            assert r1.start == pytest.approx(
                r0.start + r0.deliver / mix["sample_rate"] + mix["gap_s"])
        assert reads[0].start < mix["stagger_s"] and reads[-1].start < 2.0
    decide = 2 * GEOMETRY["core"] + GEOMETRY["halo"]
    for r in t.reads:
        if not r.on_target:
            budget = round(mix["offtarget_budget_s"] * mix["sample_rate"])
            assert r.deliver == min(r.n, decide + budget)


# ------------------------------------------------------------- trace


def _events():
    return json.loads((DATA / "trace_small.json").read_text())


def test_trace_busy_union_and_idle_share():
    ev = _events()
    red = trace.reduce(ev)
    lo, hi = trace.window_ns(ev)
    plane = trace.devices(ev)[0]
    busy = trace.busy_intervals(ev, plane, lo, hi)
    # a union: sorted, disjoint, inside the window
    assert all(a < b for a, b in busy)
    assert all(b0 < a1 for (_, b0), (a1, _) in zip(busy, busy[1:]))
    assert busy[0][0] >= lo and busy[-1][1] <= hi
    ops = sum(e["dur_ns"] for e in ev if e["line"] == trace.OPS_LINE)
    assert 0 < red["busy_s"] * 1e9 <= ops
    assert red["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["program_s"] > 0


def test_trace_top_ops_and_gap_attribution():
    ev = _events()
    red = trace.reduce(ev, top=3)
    times = [s for _, s in red["device_ops"]]
    assert len(times) <= 3 and times == sorted(times, reverse=True)
    idle = sum(s for _, s in red["idle_gaps"])
    full = trace.reduce(ev, top=100)
    assert sum(s for _, s in full["idle_gaps"]) == pytest.approx(
        full["window_s"] - full["busy_s"])
    assert idle <= full["window_s"] - full["busy_s"] + 1e-12
    names = {n for n, _ in full["idle_gaps"]}
    assert names <= set(trace.HOST_NAMES.values()) | {"outside_spans"}


def test_trace_synthetic_intervals():
    dev = "/device:TPU:0"

    def op(a, d, name="fusion.1", line=trace.OPS_LINE):
        return {"plane": dev, "line": line, "name": name, "start_ns": a,
                "dur_ns": d}

    def host(name, a, d):
        return {"plane": "/host:CPU", "line": "python", "name": name,
                "start_ns": a, "dur_ns": d}
    ev = [host("bench.window", 0, 100), host("bench.step", 0, 100),
          host("bench.collect.merge", 40, 30),
          op(10, 20), op(20, 15, "fusion.2"), op(80, 30),
          op(5, 90, "jit_fwd", trace.MODULES_LINE)]
    red = trace.reduce(ev)
    assert red["busy_s"] * 1e9 == pytest.approx(25 + 20)
    assert red["window_s"] * 1e9 == 100
    assert red["program_s"] * 1e9 == pytest.approx(90)
    gaps = dict(red["idle_gaps"])
    assert gaps["merge"] * 1e9 == pytest.approx(45)     # 35..80
    assert gaps["engine"] * 1e9 == pytest.approx(10)    # 0..10
    with pytest.raises(ValueError):
        trace.reduce([e for e in ev if e["name"] != "bench.window"])
