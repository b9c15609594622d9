"""Useful operations and irreducible bytes of the basecaller forward.

Counted from the configuration's shapes, per output CTC frame of a busy
row: only what the output requires, as the configuration's block
structure (``bench/arch/<arch>.py``) counts it. Halo frames, idle rows
and recomputation are not work, so no implementation of the same
outputs can read more than 100% of a peak by this count.

Bytes are the weights once per tick (in the served dtype, BatchNorm in
float32), the signal samples in (float32) and the log-probs out
(float32).
"""
from __future__ import annotations

from typing import Dict

from bench import reference

DTYPE_BYTES = {"bfloat16": 2, "float32": 4, "int8": 1}


def flops_per_frame(cfg: Dict) -> float:
    return reference.arch(cfg).flops_per_frame(cfg)


def weight_bytes(cfg: Dict) -> float:
    """Weights in the served dtype plus BatchNorm statistics in
    float32."""
    return reference.arch(cfg).weight_bytes(cfg)


def tick_work(cfg: Dict, frames: int, samples: int) -> Dict[str, float]:
    """Operations and bytes of one tick that returns ``frames`` core
    frames from ``samples`` core signal samples."""
    return {"flops": flops_per_frame(cfg) * frames,
            "bytes": weight_bytes(cfg) + 4.0 * samples
            + 4.0 * cfg["n_bases"] * frames}


def least_time_s(work: Dict[str, float], peak_flops: float,
                 peak_bytes_per_s: float) -> float:
    return max(work["flops"] / peak_flops, work["bytes"] / peak_bytes_per_s)
