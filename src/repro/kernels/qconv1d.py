"""Quantized separable conv-1D block kernel (RUBICALL's layer on TPU).

Fuses depthwise(k) -> pointwise(CxC) -> (folded-BN scale+shift) -> ReLU,
with int8 weights dequantised in VMEM.

Tiling: grid (B,) — one basecalling chunk per grid step. A full chunk at
RUBICALL sizes ((T=2048..4096) x C=344, fp32) is 2.8-5.6 MB, inside the
128 MiB of a v5e core's VMEM, so the halo problem disappears: the
depthwise conv is k shifted multiply-adds (VPU) over the in-VMEM chunk
and the pointwise conv is one (T, C) x (C, C) MXU matmul. Weight HBM
bytes ride at int8 — the RUBICALL-MP mixed-precision win. The chunk,
its double buffers and the float32 temporaries outgrow the compiler's
default 16 MiB scoped-VMEM window at a serving window's length, so the
call raises that limit (``VMEM_LIMIT``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

VMEM_LIMIT = 96 * 2 ** 20      # of a v5e core's 128 MiB

def _qconv_kernel(x_ref, dw_ref, pw_ref, dws_ref, pws_ref, g_ref, b_ref,
                  o_ref, *, k: int, relu: bool):
    xp = x_ref[0].astype(jnp.float32)                # (T + k - 1, C)
    T = xp.shape[0] - (k - 1)
    dw = dw_ref[...].astype(jnp.float32) * dws_ref[...]   # (k, C)
    acc = jnp.zeros((T, xp.shape[-1]), jnp.float32)
    for i in range(k):                               # depthwise: shifted FMAs
        acc += xp[i:i + T] * dw[i]
    pw = pw_ref[...].astype(jnp.float32) * pws_ref[...]   # (C, C)
    y = jax.lax.dot_general(acc, pw, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y = y * g_ref[...] + b_ref[...]                  # folded BatchNorm
    if relu:
        y = jnp.maximum(y, 0.0)
    o_ref[0] = y.astype(o_ref.dtype)


def qconv1d_block_p(x: jax.Array, dw_q: jax.Array, pw_q: jax.Array,
                    dw_scale: jax.Array, pw_scale: jax.Array,
                    gamma: jax.Array, beta: jax.Array, *,
                    relu: bool = True,
                    interpret: bool | None = None) -> jax.Array:
    """x: (B, T + k - 1, C) — time axis pre-padded with the (k-1) halo;
    dw_q: (k, C) int8; pw_q: (C, C) int8; scales per-channel f32 (1, C);
    gamma/beta: (1, C) folded BN. Returns (B, T, C)."""
    B, Tp, C = x.shape
    k = dw_q.shape[0]
    T = Tp - (k - 1)
    if interpret is None:       # resolved at call time (ops.py owns this)
        from repro.kernels.ops import interpret_default
        interpret = interpret_default()
    kern = functools.partial(_qconv_kernel, k=k, relu=relu)
    return pl.pallas_call(
        kern,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Tp, C), lambda b: (b, 0, 0)),
            pl.BlockSpec((k, C), lambda b: (0, 0)),
            pl.BlockSpec((C, C), lambda b: (0, 0)),
            pl.BlockSpec((1, C), lambda b: (0, 0)),
            pl.BlockSpec((1, C), lambda b: (0, 0)),
            pl.BlockSpec((1, C), lambda b: (0, 0)),
            pl.BlockSpec((1, C), lambda b: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, T, C), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T, C), x.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(x, dw_q, pw_q, dw_scale, pw_scale, gamma, beta)
