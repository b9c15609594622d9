"""Time-channel separable conv blocks with per-block flags: Bonito's
released CTC model (QuartzNet-style).

Block i has ``repeats[i]`` convs of ``kernel_sizes[i]`` taps; the first
maps the block's input channels to ``channels[i]`` at stride
``strides[i]``, the others ``channels[i]`` to ``channels[i]`` at stride
1. Where ``separable[i]`` a conv is a depthwise conv (``groups = C_in``,
no bias) then a 1x1 conv ``C_in -> C_out`` (no bias); otherwise it is
one full conv ``C_in -> C_out`` (no bias). Every conv is followed by
BatchNorm (eval, eps ``norm_eps``) and, except the block's last, by the
activation (``activation``: swish or relu). Where ``residual[i]`` the
block's input goes through a 1x1 conv (no bias) and BatchNorm, and is
added to the last conv's BatchNorm output. The activation then follows,
in every block. The head is a 1x1 conv to ``n_bases`` with a bias where
``head_bias``, then a log-softmax. A read is zero outside its samples:
positions at or past its length are zeroed before every conv.

Useful work, per output CTC frame: a separable conv
``2 * (K * C_in + C_in * C_out)``, a full conv ``2 * K * C_in * C_out``
(a stem with stride s computes its taps once per output frame), a
residual ``2 * C_in * C_out``, the head ``2 * C * n_bases`` plus
``n_bases`` for its bias.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import DTYPE_BYTES
from bench.reference import (HEAD_GAIN, HIGHEST, _normal, _quant_weight,
                             _round_to, weight_bits)

SERVED = ("dw", "pw", "conv", "skip_pw", "head_pw")
ACTIVATIONS = {"swish": jax.nn.swish, "relu": jax.nn.relu}
# The gaps that bench/check.py limits are logit differences, so they
# scale with the head's weights: this gain on the head (and its bias) is
# the unit in which this structure's gaps are read. Swish blocks with
# residuals, unlike RUBICALL's, barely amplify rounding: at HEAD_GAIN
# alone sound bf16 serving reads gap_max ~0.015-0.025 nats and the
# float8 control 0.27-0.47, both under check.py's 3.5 (CPU, PERF.md).
# A power of two, so the served bf16 head rounds as it would at gain 1
# and every gap is exactly LOGIT_GAIN times its gain-1 value.
LOGIT_GAIN = 32.0
# std of the head's bias at gain 1: the add is exercised, and no label
# wins by it
HEAD_BIAS_STD = 0.1


def program_keys(cfg: Dict) -> Dict:
    return {"channels": tuple(cfg["channels"]),
            "kernel_sizes": tuple(cfg["kernel_sizes"]),
            "strides": tuple(cfg["strides"]),
            "repeats": tuple(cfg["repeats"]),
            "separable": tuple(bool(v) for v in cfg["separable"]),
            "residual": tuple(bool(v) for v in cfg["residual"]),
            "activation": cfg["activation"], "norm_eps": cfg["norm_eps"],
            "head_bias": cfg["head_bias"], "n_bases": cfg["n_bases"],
            "n_blocks": len(cfg["channels"])}


def total_stride(cfg: Dict) -> int:
    return int(np.prod(cfg["strides"]))


def receptive_field(cfg: Dict) -> int:
    r, s = 1, 1
    for i in range(len(cfg["channels"])):
        for j in range(cfg["repeats"][i]):
            r += (cfg["kernel_sizes"][i] - 1) * s
            if j == 0:
                s *= int(cfg["strides"][i])
    return r


def _conv_sizes(cfg: Dict):
    """Per conv, (block, repeat, K, C_in, C_out); per residual, repeat
    None and K 1."""
    c_in = 1
    for i, c in enumerate(cfg["channels"]):
        k = cfg["kernel_sizes"][i]
        for j in range(cfg["repeats"][i]):
            yield i, j, k, (c_in if j == 0 else c), c
        if cfg["residual"][i]:
            yield i, None, 1, c_in, c
        c_in = c


def _weights_of(cfg: Dict, i: int, j, k: int, ci: int, c: int) -> int:
    if j is None or not cfg["separable"][i]:
        return k * ci * c
    return k * ci + ci * c


def flops_per_frame(cfg: Dict) -> float:
    total = sum(2.0 * _weights_of(cfg, *sz) for sz in _conv_sizes(cfg))
    c = cfg["channels"][-1]
    return total + 2.0 * c * cfg["n_bases"] \
        + (cfg["n_bases"] if cfg["head_bias"] else 0)


def weight_bytes(cfg: Dict) -> float:
    """Conv weights in the served dtype; BatchNorm (scale, bias, mean,
    var) and the head's bias in float32."""
    sizes = list(_conv_sizes(cfg))
    conv = sum(_weights_of(cfg, *sz) for sz in sizes) \
        + cfg["channels"][-1] * cfg["n_bases"]
    bn = sum(4 * sz[-1] for sz in sizes)
    bias = cfg["n_bases"] if cfg["head_bias"] else 0
    return float(conv * DTYPE_BYTES[cfg["dtype"]] + 4 * (bn + bias))


# --------------------------------------------------------------- weights


def _bn_params(c: int):
    return {"scale": jnp.ones((c,), jnp.float32),
            "bias": jnp.zeros((c,), jnp.float32)}


def raw_weights(key, cfg: Dict):
    """Full convs N(0, 2 / (K C_in)), depthwise N(0, 1 / K), pointwise
    N(0, 2 / C_in), residual N(0, 1 / C_in), head N(0, (LOGIT_GAIN
    HEAD_GAIN)^2 / C), head bias N(0, (LOGIT_GAIN HEAD_BIAS_STD)^2)."""
    params = {}
    n_blocks = len(cfg["channels"])
    keys = jax.random.split(key, n_blocks + 2)
    c_in = 1
    for i in range(n_blocks):
        c, k, reps = (cfg["channels"][i], cfg["kernel_sizes"][i],
                      cfg["repeats"][i])
        bk = jax.random.split(keys[i], 2 * reps + 1)
        blk = {}
        for j in range(reps):
            ci = c_in if j == 0 else c
            if cfg["separable"][i]:
                rep = {"dw": _normal(bk[2 * j], (k, 1, ci), 1.0 / np.sqrt(k)),
                       "pw": _normal(bk[2 * j + 1], (1, ci, c),
                                     np.sqrt(2.0 / ci))}
            else:
                rep = {"conv": _normal(bk[2 * j], (k, ci, c),
                                       np.sqrt(2.0 / (k * ci)))}
            blk[f"rep{j}"] = dict(rep, bn=_bn_params(c))
        if cfg["residual"][i]:
            blk["skip_pw"] = _normal(bk[-1], (1, c_in, c),
                                     np.sqrt(1.0 / c_in))
            blk["skip_bn"] = _bn_params(c)
        params[f"block{i:02d}"] = blk
        c_in = c
    params["head_pw"] = _normal(keys[-2], (1, c_in, cfg["n_bases"]),
                                LOGIT_GAIN * HEAD_GAIN / np.sqrt(c_in))
    if cfg["head_bias"]:
        params["head_b"] = _normal(keys[-1], (cfg["n_bases"],),
                                   LOGIT_GAIN * HEAD_BIAS_STD)
    return params


def unit_state(cfg: Dict):
    state = {}
    for i, c in enumerate(cfg["channels"]):
        bn = {"mean": jnp.zeros((c,), jnp.float32),
              "var": jnp.ones((c,), jnp.float32)}
        s = {f"rep{j}": {"bn": dict(bn)} for j in range(cfg["repeats"][i])}
        if cfg["residual"][i]:
            s["skip_bn"] = dict(bn)
        state[f"block{i:02d}"] = s
    return state


# --------------------------------------------------------------- forward


def _conv(x, w, *, stride: int = 1, groups: int = 1,
          precision: Optional[str] = None):
    total = w.shape[0] - 1
    return jax.lax.conv_general_dilated(
        _round_to(x, precision), _round_to(w, precision),
        window_strides=(stride,), padding=((total // 2, total - total // 2),),
        feature_group_count=groups, dimension_numbers=("NWC", "WIO", "NWC"),
        precision=HIGHEST)


def _bn(p, s, x, eps: float, calibrate: bool):
    if calibrate:
        s = {"mean": jnp.mean(x, axis=(0, 1)), "var": jnp.var(x, axis=(0, 1))}
    y = (x - s["mean"]) * jax.lax.rsqrt(s["var"] + eps) * p["scale"] \
        + p["bias"]
    return y, s


def forward(params, state, signal, read_len, cfg: Dict, *,
            precision: Optional[str] = None, calibrate: bool = False):
    act = ACTIVATIONS[cfg["activation"]]
    eps = cfg["norm_eps"]
    x = signal.astype(jnp.float32)
    new_state = {}
    s_in = 1
    for i in range(len(cfg["channels"])):
        tag = f"block{i:02d}"
        p, st = params[tag], state[tag]
        stride = int(cfg["strides"][i])
        reps = cfg["repeats"][i]
        ns = {}
        h = x
        for j in range(reps):
            s = s_in if j == 0 else s_in * stride
            pos = jnp.arange(h.shape[1], dtype=jnp.int32) * s
            h = h * (pos[None, :] < read_len[:, None])[..., None]
            rp = p[f"rep{j}"]
            st_j = stride if j == 0 else 1
            if cfg["separable"][i]:
                dw = _quant_weight(rp["dw"],
                                   weight_bits(cfg, f"{tag}/rep{j}/dw"))
                pw = _quant_weight(rp["pw"],
                                   weight_bits(cfg, f"{tag}/rep{j}/pw"))
                h = _conv(h, dw, stride=st_j, groups=h.shape[-1],
                          precision=precision)
                h = _conv(h, pw, precision=precision)
            else:
                w = _quant_weight(rp["conv"],
                                  weight_bits(cfg, f"{tag}/rep{j}/conv"))
                h = _conv(h, w, stride=st_j, precision=precision)
            h, bs = _bn(rp["bn"], st[f"rep{j}"]["bn"], h, eps, calibrate)
            ns[f"rep{j}"] = {"bn": bs}
            if j < reps - 1:
                h = act(h)
        if cfg["residual"][i]:
            sk = _conv(x, p["skip_pw"].astype(jnp.float32),
                       precision=precision)[:, ::stride]
            sk, bs = _bn(p["skip_bn"], st["skip_bn"], sk, eps, calibrate)
            ns["skip_bn"] = bs
            h = h + sk
        x = act(h)
        new_state[tag] = ns
        s_in *= stride
    logits = _conv(x, params["head_pw"].astype(jnp.float32),
                   precision=precision)
    if cfg["head_bias"]:
        logits = logits + params["head_b"]
    return jax.nn.log_softmax(logits, axis=-1), new_state, x
