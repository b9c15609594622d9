"""Useful operations and irreducible bytes of the basecaller forward.

Counted from the configuration's shapes, per output CTC frame of a busy
row: only what the output requires. A separable conv costs
``2 * (K * C_in + C_in * C_out)`` per frame, a pointwise skip
``2 * C_in * C_out`` and the head ``2 * C * n_bases``; a stem conv
with stride s computes its depthwise taps once per output frame. Halo
frames, idle rows and recomputation are not work, so no implementation
of the same outputs can read more than 100% of a peak by this count.

Bytes are the weights once per tick (in the served dtype, BatchNorm in
float32), the signal samples in (float32) and the log-probs out
(float32).
"""
from __future__ import annotations

from typing import Dict

DTYPE_BYTES = {"bfloat16": 2, "float32": 4, "int8": 1}


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


def tick_work(cfg: Dict, frames: int, samples: int) -> Dict[str, float]:
    """Operations and bytes of one tick that returns ``frames`` core
    frames from ``samples`` core signal samples."""
    return {"flops": flops_per_frame(cfg) * frames,
            "bytes": weight_bytes(cfg) + 4.0 * samples
            + 4.0 * cfg["n_bases"] * frames}


def least_time_s(work: Dict[str, float], peak_flops: float,
                 peak_bytes_per_s: float) -> float:
    return max(work["flops"] / peak_flops, work["bytes"] / peak_bytes_per_s)
