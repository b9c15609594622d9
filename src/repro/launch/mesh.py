"""Production mesh construction.

A FUNCTION (not module-level constant) so importing never touches jax
device state. Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod: (pod=2, data=16, model=16) = 512 chips; 'pod' is a pure
data-parallel axis (gradient all-reduce crosses DCI once per step).
"""
from __future__ import annotations

import jax

AUTO = jax.sharding.AxisType.Auto     # GSPMD propagates every axis


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AUTO,) * len(axes))


def make_host_mesh(model_parallel: int = 1):
    """Best-effort mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    mp = model_parallel if n % model_parallel == 0 else 1
    return jax.make_mesh((n // mp, mp), ("data", "model"),
                         axis_types=(AUTO, AUTO))


def mesh_devices(mesh) -> int:
    return int(mesh.devices.size)
