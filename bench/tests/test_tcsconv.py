"""Bonito's released CTC model as the ``tcsconv`` block structure (CPU
only): its operation, byte, receptive-field and parameter counts by
hand at full width; the program's whole-read forward, the structure's
float32 reference and a float64 forward written from the equations
agreeing on seeded weights at a smoke cut (4 blocks, 32 channels, full
stem, residuals, swish, head bias); a sound run of the harness at that
cut; and, at full depth, bf16 serving holding where the float8 control
does not.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, flops, reference
from bench import run as bench_run
from bench.tests.test_arch import DATA, _weights_and_read
from bench.tests.test_bench_run import _run
from bench.tests.test_bench_run import (  # noqa: F401 (autouse fixture)
    _test_mixes_no_compile_cache)
from repro.models import api
from repro.models.basecaller import model as bc

SMOKE = "bonito_r941_smoke"


def test_bonito_counts_by_hand():
    cfg = reference.load_config(bench_run.ROOT / "bench/configs/bonito.json")

    def sep(k, ci, c):            # depthwise + pointwise, weights
        return k * ci + ci * c
    # blocks (filters x repeat, K): 344 x 1 (9, full, stride 3), 424 x 2
    # (115), 464 x 7 (5), 456 x 4 (123), 440 x 9 (9), 280 x 6 (31), 384
    # x 1 (67), 48 x 1 (15, full); a residual 1x1 in blocks 1-5
    convs = (9 * 344
             + sep(115, 344, 424) + sep(115, 424, 424)
             + sep(5, 424, 464) + 6 * sep(5, 464, 464)
             + sep(123, 464, 456) + 3 * sep(123, 456, 456)
             + sep(9, 456, 440) + 8 * sep(9, 440, 440)
             + sep(31, 440, 280) + 5 * sep(31, 280, 280)
             + sep(67, 280, 384) + 15 * 384 * 48)
    residual = 344 * 424 + 424 * 464 + 464 * 456 + 456 * 440 + 440 * 280
    head = 48 * 5
    # two FLOPs a weight a frame (the stem's taps once per output frame),
    # and the head's bias adds one a base
    assert flops.flops_per_frame(cfg) == 2 * (convs + residual + head) + 5
    assert flops.flops_per_frame(cfg) == 13241621.0
    # a BatchNorm after every conv and residual: its channels, summed
    bn = (344 + 2 * 424 + 7 * 464 + 4 * 456 + 9 * 440 + 6 * 280 + 384 + 48
          + 424 + 464 + 456 + 440 + 280)
    # bf16 weights, float32 head bias and BatchNorm (4 statistics)
    assert flops.weight_bytes(cfg) == 2 * (convs + residual + head) \
        + 4 * 5 + 4 * 4 * bn
    assert reference.total_stride(cfg) == 3
    # 1 + the stem's 8 taps, then (K - 1) x 3 for every later conv
    assert reference.receptive_field(cfg) == 1 + 8 + 3 * (
        2 * 114 + 7 * 4 + 4 * 122 + 9 * 8 + 6 * 30 + 66 + 14) == 3237
    # the program's parameters: conv and head weights and the head's bias
    # (6,620,813), and each BatchNorm's scale and bias
    program = bench_run.program_config(cfg)
    assert convs + residual + head + 5 == 6620813
    assert api.count_params_analytic(program) == 6620813 + 2 * bn
    assert bc.receptive_field(program) == 3237
    assert bc.total_stride(program) == 3


def _numpy_log_probs(cfg, params, state, read):
    """Bonito's forward from its equations, over one unpadded read in
    float64."""
    params, state = jax.tree_util.tree_map(
        lambda a: np.asarray(a, np.float64), (params, state))

    def conv(x, w, stride=1, depthwise=False):
        k = w.shape[0]
        xp = np.pad(x, ((k // 2, k // 2), (0, 0)))
        n_out = -(-x.shape[0] // stride)
        win = np.stack([xp[t * stride:t * stride + k] for t in range(n_out)])
        if depthwise:                           # (T, K, C) x (K, 1, C)
            return np.einsum("tkc,kc->tc", win, w[:, 0])
        return np.einsum("tkc,kcd->td", win, w)

    def bn(p, s, h):
        return (h - s["mean"]) / np.sqrt(s["var"] + 1e-3) * p["scale"] \
            + p["bias"]

    def swish(h):
        return h / (1.0 + np.exp(-h))

    x = np.asarray(read, np.float64)[:, None]
    for i, stride in enumerate(cfg["strides"]):
        p, st = params[f"block{i:02d}"], state[f"block{i:02d}"]
        h = x
        for j in range(cfg["repeats"][i]):
            rp, s = p[f"rep{j}"], st[f"rep{j}"]["bn"]
            sj = stride if j == 0 else 1
            if cfg["separable"][i]:
                h = conv(h, rp["dw"], sj, depthwise=True) @ rp["pw"][0]
            else:
                h = conv(h, rp["conv"], sj)
            h = bn(rp["bn"], s, h)
            if j < cfg["repeats"][i] - 1:
                h = swish(h)
        if cfg["residual"][i]:
            h = h + bn(p["skip_bn"], st["skip_bn"],
                       x[::stride] @ p["skip_pw"][0])
        x = swish(h)
    logits = x @ params["head_pw"][0] + params["head_b"]
    m = logits.max(axis=1, keepdims=True)
    return logits - m - np.log(np.exp(logits - m).sum(axis=1, keepdims=True))


def test_program_reference_and_numpy_agree():
    cfg = reference.load_config(DATA / f"{SMOKE}.json")
    params, state, read = _weights_and_read(cfg)
    assert params["block00"]["rep0"]["conv"].dtype == jnp.bfloat16
    assert params["block01"]["skip_pw"].dtype == jnp.bfloat16
    assert params["head_b"].dtype == jnp.float32
    lp = reference.Reference(cfg, params, state).log_probs(read)
    f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    got, _ = bc.forward(f32, state, jnp.asarray(read[None, :, None]),
                        bench_run.program_config(cfg), train=False)
    got = np.asarray(got)[0]
    want = _numpy_log_probs(cfg, params, state, read)
    assert lp.shape == got.shape == want.shape == (-(-read.shape[0] // 3), 5)
    # the head's bias moves the log-probs: the forward without it differs
    no_bias = _numpy_log_probs(
        cfg, dict(params, head_b=np.zeros(5, np.float32)), state, read)
    assert np.max(np.abs(no_bias - want)) > 1.0
    # both float32, the same convs in another order (the reference pads
    # the read to 2**16 samples and asks for HIGHEST precision): only the
    # rounding of float32 sums, on log-probs of up to ~1e3 nats (the
    # structure's head gain)
    np.testing.assert_allclose(got, lp, rtol=1e-5, atol=1e-4)
    # float32 against float64 through sums of up to 123 taps x 32
    # channels and four BatchNorms: float32 rounding, ~1e-6 relative
    np.testing.assert_allclose(lp, want, rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


def test_sound_run_is_correct():
    """A whole run of the harness on the smoke cut, served through
    ``api.make_serving_engine`` in float32: every compared frame on the
    reference's best path."""
    res, _ = _run(SMOKE, "bulk_tiny")
    assert res["correct"], res["checks"]
    assert res["checks"]["gap_mean"]["value"] == 0.0     # float32 path
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"]["samples_per_s"]["value"] > 0


def test_control_fails_where_sound_bf16_holds(monkeypatch):
    """At Bonito's full depth (8 blocks of 31 convs, at most 32 channels
    here), bf16 serving holds and the float8 control, judged by the same
    comparison, does not."""
    rc = reference.load_config(bench_run.ROOT / "bench/configs/bonito.json")
    narrow = dict(rc, channels=[min(c, 32) for c in rc["channels"]])
    monkeypatch.setattr(reference, "load_config",
                        lambda path: dict(narrow))
    program_config = bench_run.program_config

    def narrow_program(cfg):
        base = program_config(dict(cfg, channels=rc["channels"]))
        return dataclasses.replace(base, channels=tuple(cfg["channels"]),
                                   d_model=32)
    monkeypatch.setattr(bench_run, "program_config", narrow_program)
    seed = 2 ** 31 + 11
    res, checked = _run(mix="bulk_short", seed=seed, seconds=6.0)
    assert res["correct"], res["checks"]
    ctl = control.controls(checked, seed, ["float8_e4m3fn"])
    assert not ctl["float8_e4m3fn"]["correct"], ctl


@pytest.mark.parametrize("key,value", [
    ("separable", [True, True, True, True]),
    ("residual", [False, True, True, False]),
    ("activation", "relu"), ("norm_eps", 1e-5), ("head_bias", False)])
def test_program_config_names_the_key_that_differs(key, value):
    rc = reference.load_config(DATA / f"{SMOKE}.json")
    assert bench_run.program_config(rc).n_blocks == 4
    with pytest.raises(SystemExit, match=f"has {key}="):
        bench_run.program_config(dict(rc, **{key: value}))
