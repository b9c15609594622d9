"""A block structure for the tests: full (not separable) convs.

Block i is one conv of ``kernel_sizes[i]`` taps and stride
``strides[i]`` from all input channels to ``channels[i]`` -> BatchNorm
(eval, eps 1e-3) -> swish. The head is a pointwise conv to ``n_bases``
with a bias, then a log-softmax. A read is zero outside its samples,
zeroed before every conv.

Useful work, per output frame: ``2 * K * C_in * C_out`` a block (the
stem's taps once per output frame), ``2 * C * n_bases + n_bases`` the
head.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import DTYPE_BYTES
from bench.reference import HEAD_GAIN, HIGHEST, _normal, _round_to

SERVED = ("conv", "head_pw")


def program_keys(cfg: Dict) -> Dict:
    return {"n_bases": cfg["n_bases"]}


def total_stride(cfg: Dict) -> int:
    return int(np.prod(cfg["strides"]))


def receptive_field(cfg: Dict) -> int:
    r, s = 1, 1
    for k, stride in zip(cfg["kernel_sizes"], cfg["strides"]):
        r += (k - 1) * s
        s *= stride
    return r


def flops_per_frame(cfg: Dict) -> float:
    total, c_in = 0.0, 1
    for c, k in zip(cfg["channels"], cfg["kernel_sizes"]):
        total += 2.0 * k * c_in * c
        c_in = c
    return total + 2.0 * c_in * cfg["n_bases"] + cfg["n_bases"]


def weight_bytes(cfg: Dict) -> float:
    conv, c_in = 0, 1
    for c, k in zip(cfg["channels"], cfg["kernel_sizes"]):
        conv += k * c_in * c
        c_in = c
    conv += c_in * cfg["n_bases"]
    bn = 4 * sum(cfg["channels"])
    return float(conv * DTYPE_BYTES[cfg["dtype"]] + 4 * cfg["n_bases"]
                 + 4 * bn)


def raw_weights(key, cfg: Dict):
    keys = jax.random.split(key, len(cfg["channels"]) + 2)
    params, c_in = {}, 1
    for i, (c, k) in enumerate(zip(cfg["channels"], cfg["kernel_sizes"])):
        params[f"block{i:02d}"] = {
            "conv": _normal(keys[i], (k, c_in, c), np.sqrt(2.0 / (k * c_in))),
            "bn": {"scale": jnp.ones((c,), jnp.float32),
                   "bias": jnp.zeros((c,), jnp.float32)}}
        c_in = c
    params["head_pw"] = _normal(keys[-2], (1, c_in, cfg["n_bases"]),
                                HEAD_GAIN / np.sqrt(c_in))
    params["head_b"] = _normal(keys[-1], (cfg["n_bases"],), 1.0)
    return params


def unit_state(cfg: Dict):
    return {f"block{i:02d}": {"mean": jnp.zeros((c,), jnp.float32),
                              "var": jnp.ones((c,), jnp.float32)}
            for i, c in enumerate(cfg["channels"])}


def _conv(x, w, stride, precision):
    total = w.shape[0] - 1
    return jax.lax.conv_general_dilated(
        _round_to(x, precision), _round_to(w.astype(jnp.float32), precision),
        window_strides=(stride,), padding=((total // 2, total - total // 2),),
        dimension_numbers=("NWC", "WIO", "NWC"), precision=HIGHEST)


def forward(params, state, signal, read_len, cfg: Dict, *,
            precision: Optional[str] = None, calibrate: bool = False):
    x = signal.astype(jnp.float32)
    new_state, s_in = {}, 1
    for i, stride in enumerate(cfg["strides"]):
        tag = f"block{i:02d}"
        pos = jnp.arange(x.shape[1], dtype=jnp.int32) * s_in
        x = x * (pos[None, :] < read_len[:, None])[..., None]
        h = _conv(x, params[tag]["conv"], stride, precision)
        st = state[tag]
        if calibrate:
            st = {"mean": jnp.mean(h, axis=(0, 1)),
                  "var": jnp.var(h, axis=(0, 1))}
        bn = params[tag]["bn"]
        h = (h - st["mean"]) * jax.lax.rsqrt(st["var"] + 1e-3) \
            * bn["scale"] + bn["bias"]
        x = jax.nn.swish(h)
        new_state[tag] = st
        s_in *= stride
    logits = _conv(x, params["head_pw"], 1, precision) + params["head_b"]
    return jax.nn.log_softmax(logits, axis=-1), new_state, x
