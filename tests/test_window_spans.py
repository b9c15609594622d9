"""The window forward computes only the frames its core needs
(``model.window_spans``): equal, frame for frame, to the whole-window
SAME forward sliced to the core, for every read-edge case a serving tick
carries; and the runner's ``serving.dispatch`` span counts those frames."""
import importlib.util
from dataclasses import replace
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.config import QuantPolicy, get_config
from repro.core.quant.policy import quantize_tree
from repro.models.basecaller import model as bc
from repro.serving import ServingEngine, tracing
from repro.serving.stream import StreamingRequest


def _no_act_quant(cfg):
    """Drop per-tensor activation fake-quant (its scale is taken over
    whatever extent is computed, so no two extents can agree), keeping
    the per-layer weight bit-widths — as the benchmark serves RUBICALL."""
    q = cfg.quant
    return replace(cfg, quant=replace(
        q, act_bits=0, overrides=tuple((p, (w, 0)) for p, (w, _) in
                                       q.overrides)))


def _case(name):
    """(cfg, params): a smoke config, or a variant of one."""
    if name == "rubicall-smoke-int8":
        # packed int8 weights: stride-1 square blocks take the fused
        # qconv1d route (eval only)
        cfg = replace(get_config("rubicall-smoke"),
                      quant=QuantPolicy(weight_bits=8, act_bits=0))
        params = bc.init_params(jax.random.key(1), cfg)
        return cfg, quantize_tree(params, QuantPolicy(weight_bits=8,
                                                      act_bits=0), min_size=1)
    if name.endswith("-repeats"):
        # several repeats a block: one span per repeat, skip over them
        cfg = replace(get_config(name[:-len("-repeats")]),
                      repeats=(1, 2, 3, 1))
    else:
        cfg = _no_act_quant(get_config(name))
    params = bc.init_params(jax.random.key(0), cfg)
    if cfg.head_bias:
        # a bias that moves the log-probs (init_params draws zeros)
        params["head_b"] = jax.random.normal(jax.random.key(2), (cfg.n_bases,))
    return cfg, params


@pytest.mark.parametrize("name", ["rubicall-smoke", "bonito-smoke",
                                  "causalcall-smoke", "bonito-smoke-repeats",
                                  "rubicall-smoke-int8",
                                  # full stem, residuals, swish, head bias
                                  "bonito-r9.4.1-smoke",
                                  "bonito-r9.4.1-smoke-repeats"])
def test_window_forward_equals_full_window_forward_on_the_core(name):
    cfg, params = _case(name)
    state = bc.init_state(cfg)
    st, halo = bc.total_stride(cfg), bc.chunk_halo(cfg)
    core = 4 * st * 8
    W = core + 2 * halo
    # one row per read-edge case: read head (negative start), a full
    # interior window, a read tail shorter than the core, an idle row
    start = np.array([-halo, 2 * core - halo, 3 * core - halo, 0], np.int32)
    read_len = np.array([10 * core, 10 * core, 3 * core + core // 2 + 1, 0],
                        np.int32)
    window = np.random.RandomState(3).randn(4, W, 1).astype(np.float32)

    full = jax.jit(lambda p, s, w, a, n: bc.forward(
        p, s, w, cfg, train=False, bounds=(a, n))[0])
    want = np.asarray(full(params, state, window, start, read_len))
    assert want.shape[1] == W // st
    want = want[:, halo // st:(W - halo) // st]
    got = np.asarray(jax.jit(lambda p, s, w, a, n: bc.forward_window(
        p, s, w, cfg, a, n))(params, state, window, start, read_len))
    assert got.shape == (4, core // st, cfg.n_bases)
    np.testing.assert_array_equal(got, want)
    # the spans engage: fewer frames than every conv over the window
    spans = bc.window_spans(cfg, W)
    assert bc.span_frames(spans) < sum(cfg.repeats) * (W // st)


def test_dispatch_counts_the_frames_the_spans_compute():
    cfg = get_config("bonito-smoke")
    params = bc.init_params(jax.random.key(0), cfg)
    st, halo = bc.total_stride(cfg), bc.chunk_halo(cfg)
    chunk, slots = 300, 3
    W = chunk + 2 * halo
    spans = bc.window_spans(cfg, W)
    # the last block computes exactly the core's frames
    assert spans[-1][-1] == (halo // st, (W - halo) // st)
    # hand count: one repeat a block in the smoke config
    assert [len(b) for b in spans] == [1] * cfg.n_blocks
    per_row = sum(b[0].hi - b[0].lo for b in spans)
    assert per_row < cfg.n_blocks * W // st

    t = tracing.Tracer(active=lambda: True)
    eng = ServingEngine(params, cfg, n_slots=slots, chunk_samples=chunk,
                        tracer=t)
    req = StreamingRequest(rid=7)
    eng.submit(req)
    req.append(np.random.RandomState(0).randn(chunk + halo + 50)
               .astype(np.float32))
    req.finish()
    eng.run()
    dispatches = [r for r in t.between(-np.inf, np.inf)
                  if r.name == "serving.dispatch"]
    assert dispatches
    # one read: one row of the three carries a window, and only that
    # row's frames count
    for r in dispatches:
        assert r.attrs == {"rows": 1, "frames": per_row}


def _chip_smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_windowed_matches_whole_read(monkeypatch):
    """The one-chip smoke's own comparison, at smoke width: its
    ``windowed`` (the runner's compiled tick, window by window) gives the
    whole-read forward's posteriors, so a change to what the tick
    returns cannot leave the smoke behind."""
    cs = _chip_smoke()
    monkeypatch.setattr(cs, "N_READS", 2)
    monkeypatch.setattr(cs, "READ_BASES", 900)
    cfg = cs.weights_only(get_config("rubicall-smoke"))
    params = bc.init_params(jax.random.key(0), cfg)
    signals = cs.simulated_reads()
    state = cs.batch_stats_state(cfg, params, signals)
    _, served, post, _, _ = cs.serve_reads(cfg, params, state, signals)
    ref = cs.whole_read(cfg, params, state)(signals)
    for p, r in zip(post, ref):
        assert p.shape == r.shape
        np.testing.assert_allclose(p, r, rtol=0, atol=1e-5)
    assert served == [cs.calls(r) for r in ref]
    assert sum(map(len, served)) > 0
