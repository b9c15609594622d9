"""Peaks of a device, from ``peaks.json`` by ``device_kind``."""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

TABLE = Path(__file__).with_name("peaks.json")

# the configuration's compute dtype -> the peak that bounds it
PEAK_OF_DTYPE = {"bfloat16": "bfloat16_flops", "int8": "int8_ops"}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table's entry for ``device_kind``; a device that is not
    in the table is an error, never a default."""
    table = json.loads(TABLE.read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{TABLE.name}; known: "
                       f"{sorted(k for k in table if k != 'source')}")
    return {k: float(v) for k, v in table[device_kind].items()}


def compute_peak(device_kind: str, dtype: str) -> float:
    return peaks(device_kind)[PEAK_OF_DTYPE[dtype]]
