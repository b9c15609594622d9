"""Separable-conv blocks: RUBICALL's structure, and Bonito-style skips.

Block i has ``repeats[i]`` repeats of depthwise conv (``kernel_sizes[i]``
taps, stride ``strides[i]`` on the first) -> pointwise conv to
``channels[i]`` -> BatchNorm (eval, eps 1e-5) -> ReLU, with no ReLU
after the last repeat. With ``use_skips`` every block adds a pointwise
projection of its input with its own BatchNorm before the block's ReLU.
Weights are rounded to the configured per-layer bit-widths on a
symmetric per-output-channel grid. The head is a pointwise conv to
``n_bases`` with no bias, then a log-softmax. A read is zero outside
its samples: positions at or past its length are zeroed before every
repeat, as an unpadded read's convs would see them.

Useful work, per output CTC frame: a separable conv costs
``2 * (K * C_in + C_in * C_out)``, a pointwise skip ``2 * C_in * C_out``
and the head ``2 * C * n_bases``; a stem conv with stride s computes
its depthwise taps once per output frame.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import DTYPE_BYTES
from bench.reference import (HEAD_GAIN, HIGHEST, _normal, _quant_weight,
                             _round_to, weight_bits)

SERVED = ("dw", "pw", "skip_pw", "head_pw")


def program_keys(cfg: Dict) -> Dict:
    return {"channels": tuple(cfg["channels"]),
            "kernel_sizes": tuple(cfg["kernel_sizes"]),
            "strides": tuple(cfg["strides"]),
            "repeats": tuple(cfg["repeats"]),
            "use_skips": cfg["use_skips"], "n_bases": cfg["n_bases"],
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


def flops_per_frame(cfg: Dict) -> float:
    total = 0.0
    c_in = 1
    for i, c in enumerate(cfg["channels"]):
        k = cfg["kernel_sizes"][i]
        for j in range(cfg["repeats"][i]):
            ci = c_in if j == 0 else c
            total += 2.0 * (k * ci + ci * c)
        if cfg["use_skips"]:
            total += 2.0 * c_in * c
        c_in = c
    return total + 2.0 * c_in * cfg["n_bases"]


def weight_bytes(cfg: Dict) -> float:
    """Conv weights in the served dtype plus BatchNorm (scale, bias,
    mean, var) in float32."""
    wb = DTYPE_BYTES[cfg["dtype"]]
    conv, bn = 0, 0
    c_in = 1
    for i, c in enumerate(cfg["channels"]):
        k = cfg["kernel_sizes"][i]
        for j in range(cfg["repeats"][i]):
            ci = c_in if j == 0 else c
            conv += k * ci + ci * c
            bn += 4 * c
        if cfg["use_skips"]:
            conv += c_in * c
            bn += 4 * c
        c_in = c
    conv += c_in * cfg["n_bases"]
    return float(conv * wb + bn * 4)


# --------------------------------------------------------------- weights


def raw_weights(key, cfg: Dict):
    params = {}
    n_blocks = len(cfg["channels"])
    keys = jax.random.split(key, n_blocks + 1)
    c_in = 1
    for i in range(n_blocks):
        c, k, reps = (cfg["channels"][i], cfg["kernel_sizes"][i],
                      cfg["repeats"][i])
        bk = jax.random.split(keys[i], 2 * reps + 1)
        blk = {}
        for j in range(reps):
            ci = c_in if j == 0 else c
            blk[f"rep{j}"] = {
                "dw": _normal(bk[2 * j], (k, 1, ci), 1.0 / np.sqrt(k)),
                "pw": _normal(bk[2 * j + 1], (1, ci, c), np.sqrt(2.0 / ci)),
                "bn": {"scale": jnp.ones((c,), jnp.float32),
                       "bias": jnp.zeros((c,), jnp.float32)}}
        if cfg["use_skips"]:
            blk["skip_pw"] = _normal(bk[-1], (1, c_in, c),
                                     np.sqrt(1.0 / c_in))
            blk["skip_bn"] = {"scale": jnp.ones((c,), jnp.float32),
                              "bias": jnp.zeros((c,), jnp.float32)}
        params[f"block{i:02d}"] = blk
        c_in = c
    params["head_pw"] = _normal(keys[-1], (1, c_in, cfg["n_bases"]),
                                HEAD_GAIN / np.sqrt(c_in))
    return params


def unit_state(cfg: Dict):
    state = {}
    for i, c in enumerate(cfg["channels"]):
        bn = {"mean": jnp.zeros((c,), jnp.float32),
              "var": jnp.ones((c,), jnp.float32)}
        s = {f"rep{j}": {"bn": dict(bn)} for j in range(cfg["repeats"][i])}
        if cfg["use_skips"]:
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


def _bn(p, s, x, calibrate: bool):
    if calibrate:
        s = {"mean": jnp.mean(x, axis=(0, 1)), "var": jnp.var(x, axis=(0, 1))}
    y = (x - s["mean"]) * jax.lax.rsqrt(s["var"] + 1e-5) * p["scale"] \
        + p["bias"]
    return y, s


def forward(params, state, signal, read_len, cfg: Dict, *,
            precision: Optional[str] = None, calibrate: bool = False):
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
            dw = _quant_weight(rp["dw"], weight_bits(cfg, f"{tag}/rep{j}/dw"))
            pw = _quant_weight(rp["pw"], weight_bits(cfg, f"{tag}/rep{j}/pw"))
            h = _conv(h, dw, stride=stride if j == 0 else 1,
                      groups=h.shape[-1], precision=precision)
            h = _conv(h, pw, precision=precision)
            h, bs = _bn(rp["bn"], st[f"rep{j}"]["bn"], h, calibrate)
            ns[f"rep{j}"] = {"bn": bs}
            if j < reps - 1:
                h = jax.nn.relu(h)
        if cfg["use_skips"]:
            sk = _conv(x, p["skip_pw"].astype(jnp.float32),
                       precision=precision)[:, ::stride]
            sk, bs = _bn(p["skip_bn"], st["skip_bn"], sk, calibrate)
            ns["skip_bn"] = bs
            h = h + sk
        x = jax.nn.relu(h)
        new_state[tag] = ns
        s_in *= stride
    logits = _conv(x, params["head_pw"].astype(jnp.float32),
                   precision=precision)
    return jax.nn.log_softmax(logits, axis=-1), new_state, x
