"""Quantized 1-D conv blocks — the RUBICALL/Bonito building material.

Block = R repeats of a conv -> BN -> activation, with no activation
after the last repeat, then an optional residual branch (a pointwise
projection of the block input + its own BN, added before the block's
activation — QuartzNet/Bonito style), then the activation. A repeat's
conv is separable (grouped depthwise conv -> pointwise conv) or, where
``cfg.block_separable(i)`` is false, one full ``(K, C_in, C_out)`` conv;
``cfg.block_residual(i)`` says which blocks carry the residual. The
activation is ReLU or swish (``cfg.activation``, ReLU fake-quantized
by the policy's activation bits) and every BN, applied or folded, uses
``cfg.norm_eps``.

Residual branches are gated by a per-block ``skip_gate`` in [0, 1] so
SkipClip can anneal them away without retracing; a gate of exactly 0 is
algebraically identical to the skip-free (RUBICALL) topology.

TPU notes: the depthwise+pointwise pair is the Pallas ``qconv1d`` hot-spot
(VMEM-tiled over time); XLA path uses conv_general_dilated (NWC).
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models.lm.common import Params, truncated_normal_init

State = Dict[str, jax.Array]


class Span(NamedTuple):
    """Frames ``[lo, hi)``, indexed as in the whole-window SAME forward,
    so a frame keeps its index however little of the window is computed."""
    lo: int
    hi: int


def conv_kernel_of(w, dtype) -> jax.Array:
    """Conv weight leaf -> its ``(K, Cg, Cout)`` array form, dequantizing
    serving-time :class:`PackedTensor` storage on read (packed 2-D — see
    ``core.quant.policy.quantize_tree``; ``orig_shape`` keeps the conv
    layout)."""
    from repro.core.quant.policy import PackedTensor, dequantize
    if isinstance(w, PackedTensor):
        return dequantize(w, dtype).reshape(w.orig_shape)
    return w.astype(dtype)


def _maybe_quant(w: jax.Array, x: jax.Array, cfg: ModelConfig, tag: str):
    if cfg.quant.enabled:
        from repro.core.quant.fake_quant import fake_quant
        wb, ab = cfg.quant.bits_for(tag)
        if wb:
            w = fake_quant(w, wb, axis=w.ndim - 1)
        if ab:
            x = fake_quant(x, ab, axis=None)
    return w, x


def conv_pads(k: int, dilation: int = 1, causal: bool = False
              ) -> Tuple[int, int]:
    """SAME padding (left, right) of a K-tap conv: one-sided if causal."""
    total = dilation * (k - 1)
    return (total, 0) if causal else (total // 2, total - total // 2)


def conv_input_span(lo: int, hi: int, k: int, stride: int = 1,
                    dilation: int = 1, causal: bool = False
                    ) -> Tuple[int, int]:
    """Input frames ``[lo', hi')`` that output frames ``[lo, hi)`` of the
    SAME conv read; frames outside the input grid are its zero padding."""
    left = conv_pads(k, dilation, causal)[0]
    return (lo * stride - left,
            (hi - 1) * stride - left + dilation * (k - 1) + 1)


def conv1d(x: jax.Array, w: jax.Array, *, stride: int = 1, groups: int = 1,
           dilation: int = 1, causal: bool = False,
           same: bool = True) -> jax.Array:
    """x: (B, S, Cin); w: (K, Cin//groups, Cout). ``same=False``: no
    padding — ``x`` already carries every input frame the outputs read."""
    pad = (conv_pads(w.shape[0], dilation, causal) if same else (0, 0),)
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride,), padding=pad,
        rhs_dilation=(dilation,), feature_group_count=groups,
        dimension_numbers=("NWC", "WIO", "NWC"))


def make_bn_params(c: int) -> Params:
    return {"scale": jnp.ones((c,), jnp.float32),
            "bias": jnp.zeros((c,), jnp.float32)}


def make_bn_state(c: int) -> State:
    return {"mean": jnp.zeros((c,), jnp.float32),
            "var": jnp.ones((c,), jnp.float32)}


def batchnorm(p: Params, s: State, x: jax.Array, *, train: bool, eps: float,
              momentum: float = 0.9) -> Tuple[jax.Array, State]:
    xf = x.astype(jnp.float32)
    if train:
        mean = jnp.mean(xf, axis=(0, 1))
        var = jnp.var(xf, axis=(0, 1))
        new_s = {"mean": momentum * s["mean"] + (1 - momentum) * mean,
                 "var": momentum * s["var"] + (1 - momentum) * var}
    else:
        mean, var = s["mean"], s["var"]
        new_s = s
    y = (xf - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return y.astype(x.dtype), new_s


def make_sep_conv_params(rng, c_in: int, c_out: int, k: int) -> Params:
    r = jax.random.split(rng, 2)
    return {
        "dw": truncated_normal_init(r[0], (k, 1, c_in), stddev=0.2),
        "pw": truncated_normal_init(r[1], (1, c_in, c_out)),
        "bn": make_bn_params(c_out),
    }


def make_conv_params(rng, k: int, c_in: int, c_out: int,
                     stddev: float = 0.2) -> jax.Array:
    """Plain (non-separable) ``(K, Cin, Cout)`` conv kernel: the read-until
    classifier head's convs, and a block's full convs
    (:func:`make_full_conv_params`)."""
    return truncated_normal_init(rng, (k, c_in, c_out), stddev=stddev)


def make_full_conv_params(rng, c_in: int, c_out: int, k: int) -> Params:
    return {"conv": make_conv_params(rng, k, c_in, c_out,
                                     stddev=(k * c_in) ** -0.5),
            "bn": make_bn_params(c_out)}


def sep_conv_state(c_out: int) -> State:
    return {"bn": make_bn_state(c_out)}


ACTIVATIONS = {"relu": jax.nn.relu, "swish": jax.nn.swish}


def _bn_act(p: Params, s: State, h: jax.Array, cfg: ModelConfig, tag: str,
            *, train: bool, act: bool) -> Tuple[jax.Array, State]:
    """A conv's BatchNorm, then (``act``) the activation with the
    policy's activation fake-quant."""
    h, bn_s = batchnorm(p["bn"], s["bn"], h, train=train, eps=cfg.norm_eps)
    if act:
        h = activate(h, cfg, tag)
    return h, {"bn": bn_s}


def activate(h: jax.Array, cfg: ModelConfig, tag: str) -> jax.Array:
    h = ACTIVATIONS[cfg.activation](h)
    if cfg.quant.enabled:
        from repro.core.quant.fake_quant import fake_quant
        _, ab = cfg.quant.bits_for(tag + "/act")
        if ab:
            h = fake_quant(h, ab)
    return h


def sep_conv(p: Params, s: State, x: jax.Array, cfg: ModelConfig, tag: str,
             *, stride: int = 1, dilation: int = 1, causal: bool = False,
             train: bool = True, act: bool = True, same: bool = True
             ) -> Tuple[jax.Array, State]:
    from repro.core.quant.policy import PackedTensor
    c_in = x.shape[-1]
    dw_p, pw_p = p["dw"], p["pw"]
    if (isinstance(dw_p, PackedTensor) and isinstance(pw_p, PackedTensor)
            and not train and stride == 1 and dilation == 1 and not causal
            and cfg.activation == "relu"
            and dw_p.bits == 8 and pw_p.bits == 8
            and pw_p.orig_shape[-2] == pw_p.orig_shape[-1]
            and cfg.quant.bits_for(tag + "/pw")[0] in (4, 8)):
        # Fused Pallas route (the config carries QABAS bit-widths for
        # this layer and both weights serve packed): depthwise ->
        # pointwise -> folded-BN -> ReLU in one VMEM-resident kernel
        # over the int8 bytes. Eval-mode only — BN folds its running
        # stats into the per-channel scale/shift, so state passes
        # through unchanged.
        from repro.kernels.ops import qconv1d_block
        rs = s["bn"]
        g = p["bn"]["scale"] * jax.lax.rsqrt(rs["var"] + cfg.norm_eps)
        b = p["bn"]["bias"] - rs["mean"] * g
        h = qconv1d_block(x, dw_p, pw_p, g, b, relu=act, same=same)
        if act and cfg.quant.enabled:
            from repro.core.quant.fake_quant import fake_quant
            _, ab = cfg.quant.bits_for(tag + "/act")
            if ab:
                h = fake_quant(h, ab)
        return h, {"bn": rs}
    dw = conv_kernel_of(dw_p, x.dtype)
    if isinstance(dw_p, PackedTensor):
        xq = x          # storage is already quantized — no fake-quant
    else:
        dw, xq = _maybe_quant(dw, x, cfg, tag + "/dw")
    h = conv1d(xq, dw, stride=stride, groups=c_in, dilation=dilation,
               causal=causal, same=same)
    pw = conv_kernel_of(pw_p, x.dtype)
    if isinstance(pw_p, PackedTensor):
        hq = h
    else:
        pw, hq = _maybe_quant(pw, h, cfg, tag + "/pw")
    h = conv1d(hq, pw)
    return _bn_act(p, s, h, cfg, tag, train=train, act=act)


def full_conv(p: Params, s: State, x: jax.Array, cfg: ModelConfig, tag: str,
              *, stride: int = 1, dilation: int = 1, causal: bool = False,
              train: bool = True, act: bool = True, same: bool = True
              ) -> Tuple[jax.Array, State]:
    """One full ``(K, C_in, C_out)`` conv -> BN -> activation (``act``)."""
    w, xq = _maybe_quant(p["conv"].astype(x.dtype), x, cfg, tag + "/conv")
    h = conv1d(xq, w, stride=stride, dilation=dilation, causal=causal,
               same=same)
    return _bn_act(p, s, h, cfg, tag, train=train, act=act)


def make_block_params(rng, cfg: ModelConfig, i: int, c_in: int) -> Params:
    """Block i of the config's channels/kernel_sizes/repeats tables."""
    c_out = cfg.channels[i]
    k = cfg.kernel_sizes[i]
    reps = cfg.repeats[i]
    keys = jax.random.split(rng, reps + 1)
    make = (make_sep_conv_params if cfg.block_separable(i)
            else make_full_conv_params)
    p: Params = {f"rep{j}": make(keys[j], c_in if j == 0 else c_out, c_out, k)
                 for j in range(reps)}
    if cfg.block_residual(i):
        p["skip_pw"] = truncated_normal_init(keys[-1], (1, c_in, c_out))
        p["skip_bn"] = make_bn_params(c_out)
    return p


def block_state(cfg: ModelConfig, i: int) -> State:
    c_out = cfg.channels[i]
    s: State = {f"rep{j}": sep_conv_state(c_out)
                for j in range(cfg.repeats[i])}
    if cfg.block_residual(i):
        s["skip_bn"] = make_bn_state(c_out)
    return s


def _mask_outside(h: jax.Array, bounds, s: int, lo: int = 0) -> jax.Array:
    """Zero positions outside the read (streamed-chunk serving).

    ``bounds = (start, read_len)`` are traced scalars — or ``(B,)``
    vectors when the serving runner batches every slot's window into
    one forward; each batch row then masks against its own read edges
    (rows with ``read_len == 0`` mask everything: inactive slots).
    Position ``i`` holds frame ``lo + i`` and, at cumulative stride
    ``s``, anchors global sample ``start + (lo + i)*s``. The whole-read
    forward's convs implicitly zero-pad beyond the read; a chunk
    window's halo positions beyond the read edge would otherwise carry
    BatchNorm-biased values into the next K>1 conv, breaking the
    chunked == whole-read bit-parity the BasecallerRunner relies on.
    """
    if bounds is None:
        return h
    start, read_len = bounds
    idx = (lo + jnp.arange(h.shape[1], dtype=jnp.int32)) * s
    if jnp.ndim(start) == 1:            # per-row bounds (batched serving)
        gpos = start[:, None] + idx[None, :]
        ok = (gpos >= 0) & (gpos < read_len[:, None])
        return h * ok[:, :, None].astype(h.dtype)
    gpos = start + idx
    ok = (gpos >= 0) & (gpos < read_len)
    return h * ok[None, :, None].astype(h.dtype)


def block_forward(p: Params, s: State, x: jax.Array, cfg: ModelConfig,
                  i: int, *, train: bool = True,
                  skip_gate: Optional[jax.Array] = None,
                  dilation: int = 1, causal: bool = False,
                  bounds=None, s_in: int = 1,
                  span: Optional[Tuple[Span, ...]] = None
                  ) -> Tuple[jax.Array, State]:
    """``span``: None computes every frame (SAME padding). Otherwise
    ``x`` holds frames ``span[0]`` and repeat ``j`` computes only its
    output frames ``span[j + 1]``, by unpadded convs over the frames
    they read (:func:`conv_input_span`), which ``x`` or the repeat
    before must hold."""
    reps = cfg.repeats[i]
    stride = cfg.strides[i]
    k = cfg.kernel_sizes[i]
    tag = f"block{i:02d}"
    separable = cfg.block_separable(i)
    new_s: State = {}
    h = x
    for j in range(reps):
        last = (j == reps - 1)
        st = stride if j == 0 else 1
        s_rep = s_in if j == 0 else s_in * stride
        # each K > 1 conv (grouped or full) must see zeros beyond the read
        # edge, exactly like the whole-read forward's implicit padding;
        # the pointwise convs / BN / activation in between are
        # positionwise and cannot smear out-of-read values inward, so
        # masking the repeat inputs is sufficient
        if span is None:
            h = _mask_outside(h, bounds, s_rep)
        else:
            at, out = span[j], span[j + 1]
            lo, hi = conv_input_span(out.lo, out.hi, k, st, dilation, causal)
            h = _mask_outside(h[:, lo - at.lo:hi - at.lo], bounds, s_rep, lo)
        kw = dict(stride=st, dilation=dilation, causal=causal, train=train,
                  act=not last, same=span is None)
        if separable:
            h, ns = sep_conv(p[f"rep{j}"], s[f"rep{j}"], h, cfg,
                             f"{tag}/rep{j}", **kw)
        else:
            with jax.named_scope("full"):
                h, ns = full_conv(p[f"rep{j}"], s[f"rep{j}"], h, cfg,
                                  f"{tag}/rep{j}", **kw)
        new_s[f"rep{j}"] = ns
    if cfg.block_residual(i) and "skip_pw" in p:
        gate = 1.0 if skip_gate is None else skip_gate
        sk_in = x
        if span is not None:
            # the output frames' own input frames, every stride-th
            at, out = span[0], span[-1]
            sk_in = x[:, out.lo * stride - at.lo:
                      (out.hi - 1) * stride + 1 - at.lo]
        with jax.named_scope("residual"):
            sk = conv1d(sk_in, conv_kernel_of(p["skip_pw"], x.dtype))
            if stride > 1:
                sk = sk[:, ::stride]
            sk, bn_s = batchnorm(p["skip_bn"], s["skip_bn"], sk, train=train,
                                 eps=cfg.norm_eps)
        new_s["skip_bn"] = bn_s
        h = h + gate * sk
    return activate(h, cfg, tag), new_s
