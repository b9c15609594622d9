"""Quantized-weight matmul kernel (the RUBICALL-MP hot-spot on TPU).

x (M, K) bf16/f32 @ w_q (K, N) int8 (+ per-output-channel scales) -> (M, N).

Tiling: grid (M/bm, N/bn, K/bk) with K innermost (sequential on TPU), an
fp32 VMEM accumulator tile, and MXU-aligned 128-multiple block shapes.
The int8 weight tile dequantizes in VMEM right before the MXU dot, so
weight HBM traffic is 1 byte/elem (0.5 for int4) instead of 2 — the
paper's RUBICALL-MP vs RUBICALL-FP memory-roofline win, TPU-style.

int4: two nibbles per byte along K (``core.quant.policy.pack_int4``);
the kernel sign-extends in-register (in int32), halving weight bytes
again.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

def _qmm_kernel(x_ref, wq_ref, scale_ref, o_ref, acc_ref, *, nsteps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = wq_ref[...].astype(jnp.float32)          # int8 tile -> f32 in VMEM
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nsteps - 1)
    def _done():
        o_ref[...] = (acc_ref[...] * scale_ref[...]).astype(o_ref.dtype)


def _qmm4_kernel(xe_ref, xo_ref, wq_ref, scale_ref, o_ref, acc_ref, *,
                 nsteps: int):
    """int4: packed row r holds original rows (2r, 2r+1). Mosaic has no
    8-bit shifts, so the nibbles sign-extend in int32, and instead of
    interleaving them back into rows the even/odd columns of x (split by
    the wrapper) meet the low/high nibbles in two dots."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    packed = wq_ref[...].astype(jnp.int32)
    lo = (packed << 28) >> 28                     # sign-extended low nibble
    hi = (packed << 24) >> 28                     # sign-extended high nibble
    dims = (((1,), (0,)), ((), ()))
    acc_ref[...] += (
        jax.lax.dot_general(xe_ref[...].astype(jnp.float32),
                            lo.astype(jnp.float32), dims,
                            preferred_element_type=jnp.float32)
        + jax.lax.dot_general(xo_ref[...].astype(jnp.float32),
                              hi.astype(jnp.float32), dims,
                              preferred_element_type=jnp.float32))

    @pl.when(pl.program_id(2) == nsteps - 1)
    def _done():
        o_ref[...] = (acc_ref[...] * scale_ref[...]).astype(o_ref.dtype)


def int4_k_block(K: int) -> int:
    """K tile of the int4 kernel: each half (x's even or odd columns,
    the packed rows) must be a 128-lane multiple or the whole axis."""
    return min(256, K)


def qmatmul_p(x: jax.Array, w_q: jax.Array, scale: jax.Array, *,
              bits: int = 8, bm: int = 128, bn: int = 128, bk: int = 128,
              interpret: bool | None = None) -> jax.Array:
    """x: (M, K); w_q: (K, N) int8 [bits=8] or (K//2, N) packed [bits=4];
    scale: (1, N) f32. Returns (M, N) in x.dtype. int4 tiles K by
    :func:`int4_k_block` (``bk`` is ignored)."""
    M, K = x.shape
    N = w_q.shape[-1]
    if bits == 4:
        assert K % 2 == 0
        bk = int4_k_block(K)
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    nsteps = K // bk
    if interpret is None:       # resolved at call time (ops.py owns this)
        from repro.kernels.ops import interpret_default
        interpret = interpret_default()

    x_spec = lambda w: pl.BlockSpec((bm, w), lambda i, j, k: (i, k))
    if bits == 8:
        kern = functools.partial(_qmm_kernel, nsteps=nsteps)
        xs, x_specs = (x,), [x_spec(bk)]
        w_spec = pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))
    else:
        assert bits == 4
        kern = functools.partial(_qmm4_kernel, nsteps=nsteps)
        xs, x_specs = (x[:, 0::2], x[:, 1::2]), [x_spec(bk // 2)] * 2
        w_spec = pl.BlockSpec((bk // 2, bn), lambda i, j, k: (k, j))

    return pl.pallas_call(
        kern,
        grid=(M // bm, N // bn, nsteps),
        in_specs=[
            *x_specs,
            w_spec,
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(*xs, w_q, scale)
