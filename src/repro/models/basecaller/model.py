"""Basecaller model family: RUBICALL (skip-free, mixed-precision), the
Bonito-style SkipClip teacher (skips, FP), Bonito's released R9.4.1
model (full and separable convs, per-block residuals, swish, a head
with bias) and the Causalcall-style TCN — one parametric implementation
driven by :class:`ModelConfig` (blocks: :mod:`.blocks`).

Input: normalized squiggle chunks (B, S, 1). Output: CTC log-probs
(B, S/stem_stride, 5) from a 1x1 conv head, with a bias where
``cfg.head_bias``.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.models.basecaller import blocks as bl
from repro.models.basecaller.ctc import ctc_loss
from repro.models.lm.common import Params, truncated_normal_init

State = Dict[str, jax.Array]


def init_params(rng, cfg: ModelConfig) -> Params:
    keys = jax.random.split(rng, cfg.n_blocks + 1)
    p: Params = {}
    c_in = 1
    for i in range(cfg.n_blocks):
        p[f"block{i:02d}"] = bl.make_block_params(keys[i], cfg, i, c_in)
        c_in = cfg.channels[i]
    p["head_pw"] = truncated_normal_init(keys[-1], (1, c_in, cfg.n_bases))
    if cfg.head_bias:
        p["head_b"] = jnp.zeros((cfg.n_bases,), jnp.float32)
    return p


def init_state(cfg: ModelConfig) -> State:
    return {f"block{i:02d}": bl.block_state(cfg, i)
            for i in range(cfg.n_blocks)}


def _causal(cfg: ModelConfig) -> bool:
    return cfg.name.startswith("causalcall")


def _dilation(cfg: ModelConfig, i: int) -> int:
    """Block i's conv dilation: 1, 2, 4, 8, 16 cycling for causalcall."""
    return 2 ** (i % 5) if _causal(cfg) else 1


def forward(params: Params, state: State, signal: jax.Array,
            cfg: ModelConfig, *, train: bool = True,
            skip_gates: Optional[jax.Array] = None,
            bounds=None, spans=None) -> Tuple[jax.Array, State]:
    """signal: (B, S, 1) -> (log_probs (B, T, n_bases), new_state).

    ``skip_gates``: (n_blocks,) in [0,1] — SkipClip's anneal handle.
    ``bounds``: optional traced ``(start, read_len)`` scalars for
    streamed-chunk serving: the window anchors global sample ``start``
    (may be negative at the read head) of a ``read_len``-sample read,
    and positions outside the read are re-zeroed before every K > 1
    conv so chunked outputs match the whole-read forward bit-exactly.
    ``spans``: optional :func:`window_spans` — each block computes only
    those frames, and T is the last block's span; None computes every
    frame with SAME padding (T = S / stride).
    """
    x = signal.astype(cfg.dtype)
    new_state: State = {}
    causal = _causal(cfg)
    at = bl.Span(0, x.shape[1])
    s_in = 1
    for i in range(cfg.n_blocks):
        gate = None if skip_gates is None else skip_gates[i]
        span = None if spans is None else (at,) + spans[i]
        # the scope names each block's device ops in profiler traces
        with jax.named_scope(f"block{i:02d}"):
            x, ns = bl.block_forward(params[f"block{i:02d}"],
                                     state[f"block{i:02d}"], x, cfg, i,
                                     train=train, skip_gate=gate,
                                     dilation=_dilation(cfg, i),
                                     causal=causal, bounds=bounds,
                                     s_in=s_in, span=span)
        new_state[f"block{i:02d}"] = ns
        s_in *= int(cfg.strides[i])
        if spans is not None:
            at = spans[i][-1]
    with jax.named_scope("head"):
        logits = bl.conv1d(x, bl.conv_kernel_of(params["head_pw"], x.dtype)
                           ).astype(jnp.float32)
        if cfg.head_bias:
            logits = logits + params["head_b"].astype(jnp.float32)
        log_probs = jax.nn.log_softmax(logits, axis=-1)
    return log_probs, new_state


# ---------------------------------------------------------------------------
# Streamed (chunked) basecalling — the serving BasecallerRunner's substrate.
#
# A read is processed as fixed-size CORE windows of ``core`` samples,
# each padded with a HALO of real neighbouring samples on both sides.
# Because every op in the network is local (convs) or positionwise
# (BN-eval, ReLU, log-softmax), a core frame whose full receptive field
# lies inside the padded window is BIT-IDENTICAL to the whole-read
# forward's frame — zero-padding at the window edges only corrupts
# frames within the receptive field of an edge, and the halo keeps
# those out of the core. Read edges zero-pad in both paths, so chunked
# frames == offline frames exactly, and the incremental CTC merge
# (repro.models.basecaller.ctc) equals the offline decode.


def total_stride(cfg: ModelConfig) -> int:
    """Cumulative downsampling squiggle samples -> CTC frames."""
    s = 1
    for st in cfg.strides[:cfg.n_blocks]:
        s *= int(st)
    return s


def receptive_field(cfg: ModelConfig) -> int:
    """Receptive field of one output frame, in input samples (both
    conv dilation — causalcall — and strides accounted)."""
    r, s = 1, 1
    for i in range(cfg.n_blocks):
        dil = _dilation(cfg, i)
        for j in range(cfg.repeats[i]):
            r += (cfg.kernel_sizes[i] - 1) * dil * s
            if j == 0:
                s *= int(cfg.strides[i])
    return r


def chunk_halo(cfg: ModelConfig) -> int:
    """Halo (samples each side) that guarantees core frames are exact:
    the full receptive field, rounded up to a stride multiple so chunk
    boundaries stay frame-aligned."""
    st = total_stride(cfg)
    return -(-receptive_field(cfg) // st) * st


def chunk_windows(signal: np.ndarray, core: int, halo: int, stride: int
                  ) -> List[Tuple[np.ndarray, int, int]]:
    """Slice one read into model-input windows.

    signal: (S,) float squiggle (normalized). Returns a list of
    ``(window (core + 2*halo, 1) float32, n_frames, n_samples)`` —
    ``n_frames`` core CTC frames are valid (``ceil(n_samples/stride)``;
    the rest of the last window is zero padding, exactly what the
    whole-read forward's implicit edge padding sees).
    """
    sig = np.asarray(signal, np.float32).reshape(-1)
    S = sig.shape[0]
    out: List[Tuple[np.ndarray, int, int]] = []
    W = core + 2 * halo
    for a in range(0, S, core):
        valid = min(core, S - a)
        window = np.zeros((W, 1), np.float32)
        lo, hi = a - halo, a + core + halo
        src = sig[max(lo, 0):min(hi, S)]
        off = max(lo, 0) - lo
        window[off:off + src.shape[0], 0] = src
        out.append((window, -(-valid // stride), valid))
    return out


def window_spans(cfg: ModelConfig, n_samples: int
                 ) -> Tuple[Tuple[bl.Span, ...], ...]:
    """Per block, per repeat: the output frames a window of
    ``n_samples = core + 2 * chunk_halo(cfg)`` samples needs for its core,
    on the whole-window SAME forward's frame grid. The last block's span
    is the core's frames; walking back, each conv needs the frames its
    outputs read (:func:`blocks.conv_input_span`). The halo covers the
    receptive field, so no span reaches the SAME padding: every frame
    computed is a frame the whole-window forward computes."""
    halo, st = chunk_halo(cfg), total_stride(cfg)
    if n_samples <= 2 * halo or n_samples % st:
        raise ValueError(f"a window of {n_samples} samples has no core of "
                         f"whole frames inside its {halo}-sample halos")
    spans: List[List[bl.Span]] = [[] for _ in range(cfg.n_blocks)]
    lo, hi = halo // st, (n_samples - halo) // st
    for i in reversed(range(cfg.n_blocks)):
        for j in reversed(range(cfg.repeats[i])):
            spans[i].insert(0, bl.Span(lo, hi))
            lo, hi = bl.conv_input_span(
                lo, hi, cfg.kernel_sizes[i],
                int(cfg.strides[i]) if j == 0 else 1, _dilation(cfg, i),
                _causal(cfg))
    if lo < 0 or hi > n_samples:
        raise ValueError(f"block00 would read samples [{lo}, {hi}) outside "
                         f"the {n_samples}-sample window")
    return tuple(tuple(b) for b in spans)


def span_frames(spans) -> int:
    """Frames one row of the window forward computes: every conv's
    output span (for one-repeat blocks, one span a block)."""
    return sum(sp.hi - sp.lo for block in spans for sp in block)


def forward_window(params: Params, state: State, window: jax.Array,
                   cfg: ModelConfig, start: jax.Array, read_len: jax.Array
                   ) -> jax.Array:
    """Eval-mode forward over one padded window (B, W, 1) -> CTC
    log-probs of its core frames (B, core/stride, n_bases), core =
    W - 2 * chunk_halo(cfg). Each block computes only the frames the
    core still needs (:func:`window_spans`), so a core frame sees the
    same samples, weights and read-edge masks as in the whole-window
    SAME forward. ``start``/``read_len`` are traced
    scalars — or ``(B,)`` vectors when the serving runner co-batches
    every slot's window into one forward, each row masking against its
    own read edges (global sample of window[0] — negative at the read
    head — and the read's length); either way the read-edge masking
    retraces nothing. The jitted hot loop of the serving
    BasecallerRunner (one compile — all windows share W)."""
    log_probs, _ = forward(params, state, window, cfg, train=False,
                           bounds=(start, read_len),
                           spans=window_spans(cfg, window.shape[1]))
    return log_probs


def loss_fn(params: Params, state: State, batch: Dict, cfg: ModelConfig,
            *, skip_gates: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, Tuple[Dict, State]]:
    log_probs, new_state = forward(params, state, batch["signal"], cfg,
                                   train=True, skip_gates=skip_gates)
    loss = ctc_loss(log_probs, batch["labels"], batch["label_lengths"])
    return loss, ({"ctc_loss": loss}, new_state)
