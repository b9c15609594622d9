"""Public jit'd wrappers around the Pallas kernels.

The wrappers own layout glue (GQA head folding, halo padding,
PackedTensor unwrapping) so models call a clean API, plus the
decode-attention BACKEND DISPATCH (:func:`decode_gqa` /
:func:`decode_mla`): ``xla`` is the masked-dense gather reference,
``pallas`` the fused paged kernels reading straight from the block
arena — the single-token variant for decode ticks (C == 1) and the
multi-token chunk variant (per-query causal mask) for chunk prefill.

``interpret`` defaults are resolved at CALL time by
:func:`interpret_default` — NOT frozen at import, so a backend change
after import (or a test forcing interpret mode via
``REPRO_PALLAS_INTERPRET``) behaves correctly.
"""
from __future__ import annotations

import functools
import os
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.quant.policy import PackedTensor
from repro.kernels import paged_attention as pa
from repro.kernels.flash_attention import flash_attention_p
from repro.kernels.qconv1d import qconv1d_block_p
from repro.kernels.qmatmul import qmatmul_p
from repro.kernels.ssd_scan import ssd_scan_p


def interpret_default() -> bool:
    """Pallas interpret default, resolved when a kernel is CALLED (the
    old per-module ``INTERPRET = jax.default_backend() == "cpu"``
    constants froze the answer at import time, so flipping the backend
    afterwards ran compiled kernels on CPU or interpret on TPU).
    ``REPRO_PALLAS_INTERPRET=1|0`` force-overrides off the TPU (tests);
    on a TPU the kernels always compile, and asking for the interpreter
    there is an error rather than a silent slow path."""
    env = os.environ.get("REPRO_PALLAS_INTERPRET", "")
    wants = env not in ("", "0", "false", "no")
    if jax.default_backend() == "tpu":
        if wants:
            raise RuntimeError(
                f"REPRO_PALLAS_INTERPRET={env!r} on a TPU backend: the "
                f"Pallas interpreter is a CPU test tool; unset it")
        return False
    if env:
        return wants
    return jax.default_backend() == "cpu"


ATTN_BACKENDS = ("auto", "xla", "pallas")


def resolve_attn_backend(name: Optional[str] = None) -> str:
    """Resolve a decode-attention backend choice to ``xla``/``pallas``.

    ``auto`` (or None) picks the fused Pallas kernel on a SINGLE-chip
    TPU and the XLA gather reference everywhere else: the fused path
    is not shard_map'd yet, so on a multi-chip mesh only the reference
    carries the GSPMD flash-decoding partitioning (sequence over
    'model'); and interpret-mode Pallas is a correctness tool (CPU CI
    exercises the kernel body with it), not a fast path. Forcing
    ``pallas`` overrides both considerations.
    """
    name = name or "auto"
    if name not in ATTN_BACKENDS:
        raise ValueError(f"attn backend {name!r} not in {ATTN_BACKENDS}")
    if name == "auto":
        return ("pallas" if jax.default_backend() == "tpu"
                and jax.device_count() == 1 else "xla")
    return name


# ---------------------------------------------------------------------------
# Decode-attention backend dispatch


def decode_gqa(q: jax.Array, k: jax.Array, v: jax.Array, pos: jax.Array,
               t: jax.Array, *, window: int = 0,
               table: Optional[jax.Array] = None,
               backend: Optional[str] = None,
               k_scale: Optional[jax.Array] = None,
               v_scale: Optional[jax.Array] = None,
               interpret: Optional[bool] = None,
               shard_kv: Optional[Callable] = None) -> jax.Array:
    """Decode attention over slot-pool KV — the one read path both
    attention layouts share.

    q: (B, C, H, hd); pos: (B, L); t: (B, C) (< 0 = pad row).
    ``table`` None: k/v are contiguous per-slot rows (B, L, Hkv, hd).
    ``table`` (B, T): k/v are shared heads-major arenas (n_blocks, Hkv,
    block_len, hd) and the table maps logical to arena blocks (-1 =
    unassigned). Returns (B, C, H*hd).

    ``backend`` ``xla``/None: the gather reference — materialises the
    (B, T*block_len) logical view per call. ``pallas``: the fused
    kernels — single-token steps (C == 1; the decode tick) run
    ``gqa_paged_p``, multi-token chunk steps (C > 1) run
    ``gqa_paged_chunk_p`` with a per-query causal mask; both apply the
    identical masking contract, so emitted tokens do not depend on the
    backend. The contiguous layout runs fused too, transposed into a
    B-block arena with an identity table. ``shard_kv`` optionally
    constrains the gathered reads (flash-decoding sharding annotation;
    reference path only).

    ``k_scale``/``v_scale``: int8-arena dequant scales (n_blocks, Hkv,
    block_len) fp32 — paged layout only. The fused path DMAs them
    alongside their value blocks and dequantizes in-register; the
    reference gathers them with the SAME clamped indices and
    dequantizes through the identical :func:`pa.dequantize_kv`
    expression, so backend token-parity holds at int8 too.
    """
    B, C, H, hd = q.shape
    quantized = k_scale is not None
    if quantized and table is None:
        raise ValueError("int8 KV scales require the paged layout "
                         "(contiguous caches store bf16/fp8 directly)")
    if backend == "pallas":
        if table is None:                  # B blocks of L, heads-major
            karena, varena = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
            tbl = jnp.arange(B, dtype=jnp.int32)[:, None]
        else:
            karena, varena, tbl = k, v, table
        Hkv = karena.shape[1]
        if C == 1:
            group = H // Hkv
            qh = q.reshape(B, Hkv, group, hd)
            o = pa.gqa_paged_p(qh, karena, varena, pos, t[:, 0], tbl,
                               window=window, k_scale=k_scale,
                               v_scale=v_scale, interpret=interpret)
            return o.reshape(B, 1, H * hd)
        return pa.gqa_paged_chunk_p(q, karena, varena, pos, t, tbl,
                                    window=window, k_scale=k_scale,
                                    v_scale=v_scale, interpret=interpret)
    if table is not None:
        Hkv, bl = k.shape[1], k.shape[2]
        gidx = jnp.maximum(table, 0)
        Leff = table.shape[1] * bl

        def view(a):                       # (B, T, Hkv, bl, ...) -> logical
            a = jnp.swapaxes(a[gidx], 2, 3)
            return a.reshape((B, Leff) + a.shape[3:])
        k_read, v_read = view(k), view(v)
        if quantized:
            k_read = pa.dequantize_kv(k_read, view(k_scale))
            v_read = pa.dequantize_kv(v_read, view(v_scale))
        if shard_kv is not None:
            k_read = shard_kv(k_read)
            v_read = shard_kv(v_read)
    else:
        k_read, v_read = k, v
    return pa.gqa_reference(q, k_read, v_read, pos, t, window=window)


def decode_mla(q_abs: jax.Array, q_rope: jax.Array, c: jax.Array,
               k_rope: jax.Array, pos: jax.Array, t: jax.Array, *,
               scale: float, table: Optional[jax.Array] = None,
               backend: Optional[str] = None,
               c_scale: Optional[jax.Array] = None,
               kr_scale: Optional[jax.Array] = None,
               interpret: Optional[bool] = None,
               shard_kv: Optional[Callable] = None,
               shard_s: Optional[Callable] = None) -> jax.Array:
    """Absorbed-form MLA decode over the latent cache (see
    :func:`decode_gqa` for the backend/fallback contract).

    q_abs: (B, C, H, kvr); q_rope: (B, C, H, rope_d); ``table`` None:
    c/k_rope are (B, L, kvr|rope_d) rows, else latent arenas
    (n_blocks, block_len, ...). ``c_scale``/``kr_scale``: int8 latent
    dequant scales (n_blocks, block_len) fp32, same backend contract
    as the GQA scales. Returns o_lat (B, C, H, kvr) fp32 — the caller
    applies the absorbed value projection."""
    B, C, H, kvr = q_abs.shape
    quantized = c_scale is not None
    if quantized and table is None:
        raise ValueError("int8 latent scales require the paged layout")
    if backend == "pallas":
        if table is None:
            carena, krarena = c, k_rope
            tbl = jnp.arange(B, dtype=jnp.int32)[:, None]
        else:
            carena, krarena, tbl = c, k_rope, table
        if C == 1:
            o = pa.mla_paged_p(q_abs[:, 0], q_rope[:, 0], carena, krarena,
                               pos, t[:, 0], tbl, scale=scale,
                               c_scale=c_scale, kr_scale=kr_scale,
                               interpret=interpret)
            return o[:, None]
        return pa.mla_paged_chunk_p(q_abs, q_rope, carena, krarena, pos,
                                    t, tbl, scale=scale, c_scale=c_scale,
                                    kr_scale=kr_scale, interpret=interpret)
    if table is not None:
        bl = c.shape[1]
        gidx = jnp.maximum(table, 0)
        Leff = table.shape[1] * bl
        c_read = c[gidx].reshape(B, Leff, kvr)
        kr_read = k_rope[gidx].reshape(B, Leff, k_rope.shape[-1])
        if quantized:
            c_read = pa.dequantize_kv(c_read,
                                      c_scale[gidx].reshape(B, Leff))
            kr_read = pa.dequantize_kv(kr_read,
                                       kr_scale[gidx].reshape(B, Leff))
        if shard_kv is not None:
            c_read = shard_kv(c_read)
            kr_read = shard_kv(kr_read)
    else:
        c_read, kr_read = c, k_rope
    return pa.mla_reference(q_abs, q_rope, c_read, kr_read, pos, t,
                            scale=scale, shard_s=shard_s)


# The public wrappers resolve ``interpret=None`` BEFORE the jit
# boundary: a concrete bool is the static arg, so flipping the backend
# or REPRO_PALLAS_INTERPRET after a first call retraces instead of
# silently reusing the stale cached program (resolving inside the
# traced body would freeze the first answer under the `None` cache key).


def qmatmul(x: jax.Array, w, scale=None, *, bits: int = 8,
            interpret=None) -> jax.Array:
    """x: (..., K) @ quantized w -> (..., N). Accepts a PackedTensor or a
    raw (int8 data, scale) pair."""
    interpret = interpret_default() if interpret is None else interpret
    return _qmatmul_jit(x, w, scale, bits=bits, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def _qmatmul_jit(x, w, scale, *, bits, interpret):
    if isinstance(w, PackedTensor):
        bits, scale, w = w.bits, w.scale, w.data
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    scale2 = jnp.asarray(scale, jnp.float32).reshape(1, -1)
    out = qmatmul_p(x2, w, scale2, bits=bits, interpret=interpret)
    return out.reshape(lead + (out.shape[-1],))


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, interpret=None) -> jax.Array:
    """q: (B, Sq, H, d); k/v: (B, Sk, Hkv, d). See the jitted body."""
    interpret = interpret_default() if interpret is None else interpret
    return _flash_attention_jit(q, k, v, causal=causal,
                                interpret=interpret)


@functools.partial(jax.jit, static_argnames=("causal", "interpret"))
def _flash_attention_jit(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True, interpret=None) -> jax.Array:
    """q: (B, Sq, H, d); k/v: (B, Sk, Hkv, d) — GQA folded into batch rows
    so each kernel row sees one (head, kv-head) pair without repeat."""
    B, Sq, H, d = q.shape
    Hkv = k.shape[2]
    group = H // Hkv
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, d)
    # kv row for query head h is h // group
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), group, axis=1).reshape(
        B * H, k.shape[1], d)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), group, axis=1).reshape(
        B * H, v.shape[1], d)
    o = flash_attention_p(qf, kf, vf, causal=causal, interpret=interpret)
    return o.reshape(B, H, Sq, d).transpose(0, 2, 1, 3)


def qconv1d_block(x: jax.Array, dw, pw, gamma, beta, *, relu: bool = True,
                  same: bool = True, interpret=None) -> jax.Array:
    """x: (B, T, C); dw/pw: PackedTensor (int8). Fused RUBICALL block.
    ``same=False``: ``x`` already carries the k-1 frames of context, and
    the output has T - (k - 1) frames."""
    interpret = interpret_default() if interpret is None else interpret
    return _qconv1d_block_jit(x, dw, pw, gamma, beta, relu=relu, same=same,
                              interpret=interpret)


@functools.partial(jax.jit, static_argnames=("relu", "same", "interpret"))
def _qconv1d_block_jit(x, dw, pw, gamma, beta, *, relu, same, interpret):
    k = dw.orig_shape[0]
    pad = (k - 1) // 2
    xp = jnp.pad(x, ((0, 0), (pad, k - 1 - pad), (0, 0))) if same else x
    return qconv1d_block_p(
        xp, dw.data.reshape(k, -1), pw.data,
        jnp.asarray(dw.scale, jnp.float32).reshape(1, -1),
        jnp.asarray(pw.scale, jnp.float32).reshape(1, -1),
        gamma.reshape(1, -1).astype(jnp.float32),
        beta.reshape(1, -1).astype(jnp.float32),
        relu=relu, interpret=interpret)


def ssd_chunk_scan(x, dt, A, Bm, Cm, D, *, chunk: int = 256,
                   interpret=None):
    """x: (B, S, nh, hd); dt: (B, S, nh); A/D: (nh,); Bm/Cm: (B, S, N).

    Folds (batch, head) into kernel rows; B/C shared across heads."""
    interpret = interpret_default() if interpret is None else interpret
    return _ssd_chunk_scan_jit(x, dt, A, Bm, Cm, D, chunk=chunk,
                               interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssd_chunk_scan_jit(x, dt, A, Bm, Cm, D, *, chunk, interpret):
    B, S, nh, hd = x.shape
    N = Bm.shape[-1]
    xr = x.transpose(0, 2, 1, 3).reshape(B * nh, S, hd)
    dtr = dt.transpose(0, 2, 1).reshape(B * nh, S)
    Ar = jnp.tile(A, B)
    Dr = jnp.tile(D, B)
    Br = jnp.repeat(Bm[:, None], nh, axis=1).reshape(B * nh, S, N)
    Cr = jnp.repeat(Cm[:, None], nh, axis=1).reshape(B * nh, S, N)
    y = ssd_scan_p(xr, dtr, Ar, Br, Cr, Dr, chunk=chunk,
                   interpret=interpret)
    return y.reshape(B, nh, S, hd).transpose(0, 2, 1, 3)
