"""Plain float32 reference of the basecaller cells, and the weights.

Independent of the program: it reads the architecture from the cell's
configuration file (``bench/configs/<name>.json``) and imports nothing
from ``repro``. The file's ``arch`` names its block structure, a module
``bench/arch/<arch>.py`` that provides

- ``raw_weights(key, cfg)`` and ``unit_state(cfg)``: the weights at
  unit gain in float32 and BatchNorm statistics of mean 0 and variance
  1 (a dict holding ``mean`` and ``var``), in the pytree layout the
  program serves; the CTC head's weight is ``params["head_pw"]``,
  shaped (1, C, n_bases);
- ``SERVED``: the names of the weight leaves served in the compute
  dtype;
- ``forward(params, state, signal, read_len, cfg, *, precision,
  calibrate)``: the whole-read float32 forward of (B, S, 1) signal
  with every conv operand rounded to ``precision`` (the control; see
  ``_round_to``) and, with ``calibrate``, BatchNorm statistics taken
  from its inputs; returns the log-probs, the state and the head's
  input features;
- ``total_stride(cfg)`` and ``receptive_field(cfg)``, in samples;
- ``flops_per_frame(cfg)`` and ``weight_bytes(cfg)``, for
  ``bench/flops.py``;
- ``program_keys(cfg)``: the fields of the program's registered
  configuration, with the values this file gives them, that
  ``bench/run.py`` compares.

This module holds what no block structure changes:

- the weight maker: every weight and BatchNorm statistic of a cell,
  made on the device from the seed in one jitted call (the ``SERVED``
  leaves in bfloat16, the rest float32) and calibrated on simulated
  reads;
- the weight bit-widths and the rounding of a conv operand;
- ``Reference``, the compiled whole-read forward;
- the read-until classifier head;
- the banded CTC Viterbi alignment that scores served bases against
  reference log-probs.
"""
from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

ARCHS = Path(__file__).resolve().parent / "arch"
HIGHEST = jax.lax.Precision.HIGHEST
BLANK = 0
# logit scale of the random CTC head: head weights of std
# HEAD_GAIN / sqrt(C), centered (see make_weights), give posteriors whose
# best label averages ~0.5 (CPU, 28 blocks x 64 channels)
HEAD_GAIN = 6.0
# BatchNorm running variance = the variance measured at unit scale plus
# this share of the layer's mean variance, so every block is slightly
# contractive. Without it the random network is chaotic: channels whose
# input barely varies are divided by a tiny deviation and amplify
# rounding layer after layer. At 28 blocks x 64 channels (CPU) bf16
# served bases then lie 0.22 nats a frame below the float32 reference
# on average, against 0.005 with the floor, while a float8 forward reads
# 0.3 with it.
VAR_FLOOR = 0.2


def load_config(path) -> Dict:
    cfg = json.loads(Path(path).read_text())
    if "arch" not in cfg:
        raise ValueError(f"{path} names no 'arch': each configuration "
                         f"names its block structure, a module in {ARCHS}")
    arch(cfg)
    return cfg


@functools.lru_cache(maxsize=None)
def _load_arch(path: Path):
    if not path.is_file():
        raise ValueError(f"no block structure module {path}")
    spec = importlib.util.spec_from_file_location(f"bench_arch_{path.stem}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def arch(cfg: Dict):
    """The module of ``cfg``'s block structure, ``ARCHS/<arch>.py``."""
    return _load_arch(ARCHS / f"{cfg['arch']}.py")


def weight_bits(cfg: Dict, tag: str) -> int:
    """Weight bit-width of layer ``tag`` (``block07/rep0/dw``): the
    first override whose pattern occurs in the tag, else the default;
    0 leaves the weight in its stored precision."""
    q = cfg.get("quant") or {}
    for pat, bits in q.get("overrides", ()):
        if pat in tag:
            return int(bits)
    return int(q.get("weight_bits", 0))


def total_stride(cfg: Dict) -> int:
    return arch(cfg).total_stride(cfg)


def receptive_field(cfg: Dict) -> int:
    return arch(cfg).receptive_field(cfg)


# --------------------------------------------------------------- weights


def _normal(key, shape, std):
    return std * jax.random.normal(key, shape, jnp.float32)


def make_weights(key, cfg: Dict, calib: jax.Array):
    """All of a cell's weights from ``key``, on the device.

    Conv weights are drawn at unit gain and rounded to bfloat16, the
    type they are served in. BatchNorm running statistics are the batch
    statistics of each BatchNorm's input over ``calib`` ((B, S, 1)
    normalized squiggle), taken layer by layer through the served
    weights, as a trained model's running statistics keep activations at
    unit scale, and then raised by ``VAR_FLOOR``. Call under
    ``jax.jit`` with ``cfg`` static."""
    mod = arch(cfg)
    params = jax.tree_util.tree_map_with_path(
        lambda path, w: w.astype(jnp.bfloat16)
        if path[-1].key in mod.SERVED else w, mod.raw_weights(key, cfg))
    n = jnp.full((calib.shape[0],), calib.shape[1], jnp.int32)
    _, state, _ = mod.forward(params, mod.unit_state(cfg), calib, n, cfg,
                              calibrate=True)
    _floor_var(state)
    # no label may win by a bias: each head column is made orthogonal to
    # the mean served feature, so every label's mean logit is 0 on every
    # seed
    _, _, feats = mod.forward(params, state, calib, n, cfg)
    mu = jnp.mean(feats, axis=(0, 1))
    w = params["head_pw"][0]
    w = w - jnp.outer(mu, mu @ w) / jnp.dot(mu, mu)
    params["head_pw"] = w[None].astype(jnp.bfloat16)
    return params, state


def _floor_var(state):
    """Raise every BatchNorm variance in ``state`` by ``VAR_FLOOR`` of
    its layer's mean variance, in place."""
    if "var" in state:
        state["var"] = state["var"] + VAR_FLOOR * jnp.mean(state["var"])
        return
    for s in state.values():
        _floor_var(s)


# --------------------------------------------------------------- forward


def _round_to(x, precision: Optional[str]):
    """Round a conv operand to ``precision`` and back to float32."""
    if precision is None or precision == "float32":
        return x
    if precision == "float8_e4m3fn":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    if precision == "int8":            # symmetric, one scale per row
        s = jnp.maximum(jnp.max(jnp.abs(x), axis=tuple(range(1, x.ndim)),
                                keepdims=True), 1e-8) / 127.0
        return jnp.clip(jnp.round(x / s), -128, 127) * s
    raise ValueError(f"unknown precision {precision!r}")


def _quant_weight(w, bits: int):
    w = w.astype(jnp.float32)
    if bits <= 0 or bits >= 32:
        return w
    qmax = 2.0 ** (bits - 1) - 1.0
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=(0, 1), keepdims=True),
                    1e-8) / qmax
    return jnp.clip(jnp.round(w / s), -qmax - 1, qmax) * s


class Reference:
    """Whole-read forward of one configuration, one compiled program per
    padded length (powers of two from 2**16 samples)."""

    MIN_PAD = 1 << 16

    def __init__(self, cfg: Dict, params, state, precision=None):
        self.cfg = cfg
        self.params, self.state = params, state
        self.stride = total_stride(cfg)
        forward = arch(cfg).forward
        self._fwd = jax.jit(
            lambda p, s, x, n: forward(p, s, x, n, cfg,
                                       precision=precision)[0])
        self._last: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}

    def log_probs(self, signal: np.ndarray) -> np.ndarray:
        """(S,) squiggle -> (ceil(S / stride), n_bases) log-probs; the
        reads of the last call with the same array are remembered."""
        if id(signal) in self._last:
            return self._last[id(signal)][1]
        lp = self._log_probs(signal)
        self._last[id(signal)] = (signal, lp)
        return lp

    def _log_probs(self, signal: np.ndarray) -> np.ndarray:
        n = int(signal.shape[0])
        pad = max(self.MIN_PAD, 1 << (n - 1).bit_length())
        x = np.zeros((1, pad, 1), np.float32)
        x[0, :n, 0] = signal
        lp = self._fwd(self.params, self.state, x,
                       np.asarray([n], np.int32))
        # cut on the host: a slice on the device would compile one
        # program per read length, and fill the persistent compile cache
        return np.asarray(lp)[0, :-(-n // self.stride)]


# ----------------------------------------------------- read-until head


def classifier_weights(key, channels: Tuple[int, int] = (8, 16),
                       kernel: int = 5):
    """Read-until head weights: a local-variation detector.

    Two channels of the first conv compute ``relu(x[t] - x[t+1])`` and
    ``relu(x[t+1] - x[t])``, the second conv averages them, and the
    logit is ``bias - gain * mean |dx|``. Pore squiggle is piecewise
    constant (mean |dx| ~ 0.3 after med/MAD normalization) and white
    noise is not (~1.1), so the head separates the traffic's on-target
    reads from its off-target ones. The other channels carry small
    random weights from ``key``."""
    c0, c1 = channels
    k0, k1, k2 = jax.random.split(key, 3)
    w0 = _normal(k0, (kernel, 1, c0), 0.01)
    w0 = w0.at[:2, 0, 0].set(jnp.asarray([1.0, -1.0]))
    w0 = w0.at[:2, 0, 1].set(jnp.asarray([-1.0, 1.0]))
    w1 = _normal(k1, (kernel, c0, c1), 0.01)
    w1 = w1.at[:, :2, 0].set(1.0 / kernel)
    head_w = _normal(k2, (c1, 1), 0.01).at[0, 0].set(-8.0)
    return {"conv0": w0, "conv1": w1, "head_w": head_w,
            "head_b": jnp.asarray([5.0], jnp.float32)}


def classifier_logits(params, window) -> jax.Array:
    """(B, W, 1) windows -> (B,) on-target logits, float32."""
    def conv(x, w):
        total = w.shape[0] - 1
        return jax.lax.conv_general_dilated(
            x, w, window_strides=(4,),
            padding=((total // 2, total - total // 2),),
            dimension_numbers=("NWC", "WIO", "NWC"), precision=HIGHEST)
    h = jax.nn.relu(conv(window.astype(jnp.float32), params["conv0"]))
    h = jax.nn.relu(conv(h, params["conv1"]))
    g = jnp.mean(h, axis=1)
    return jnp.matmul(g, params["head_w"], precision=HIGHEST)[:, 0] \
        + params["head_b"][0]


# ------------------------------------------------------------------ CTC


def _bucket(n: int, least: int) -> int:
    return max(least, 1 << (int(n) - 1).bit_length())


@functools.partial(jax.jit, static_argnames=("width",))
def _banded_viterbi(cost, ext, skip, T, S, *, width: int):
    """Forward pass of the banded CTC alignment over padded inputs:
    ``cost`` (T_pad, K), ``ext``/``skip`` (S_pad,), real sizes ``T``,
    ``S``. Returns the final costs of the window, its start, and per
    frame the window start and the step taken into each state."""
    inf = jnp.float32(jnp.inf)
    ar = jnp.arange(width)
    d0 = jnp.where(ar < jnp.minimum(S, 2), cost[0, ext[ar]], inf)

    def step(carry, t):
        d, lo = carry
        best = lo + jnp.argmin(d)
        nlo = jnp.clip(best - width // 2, 0, jnp.maximum(S - width, 0))
        idx = nlo + ar
        src = nlo - lo + jnp.arange(-2, width)
        prev = jnp.where((src >= 0) & (src < width),
                         d[jnp.clip(src, 0, width - 1)], inf)
        stay, one = prev[2:], prev[1:-1]
        two = jnp.where(skip[jnp.minimum(idx, ext.shape[0] - 1)],
                        prev[:-2], inf)
        moves = jnp.stack([stay, one, two])
        choice = jnp.argmin(moves, axis=0).astype(jnp.int8)
        acc = jnp.min(moves, axis=0)
        c = cost[t, ext[jnp.minimum(idx, ext.shape[0] - 1)]]
        nd = jnp.where(idx < S, acc + c, inf)
        live = t < T
        return ((jnp.where(live, nd, d), jnp.where(live, nlo, lo)),
                (jnp.where(live, choice, 0), jnp.where(live, nlo, lo)))

    (d, lo), (back, los) = jax.lax.scan(
        step, (d0, jnp.int32(0)), jnp.arange(1, cost.shape[0]))
    return d, lo, back, los


def viterbi_gaps(log_probs: np.ndarray, labels, band: int = 64
                 ) -> Optional[np.ndarray]:
    """Per-frame gaps of the best CTC alignment of ``labels`` under
    ``log_probs``: ``max_k lp[t, k] - lp[t, path_t]`` for the path that
    collapses to ``labels`` with the least total gap. Returns None when
    no such path exists within the band (a sequence the reference
    cannot have produced).

    Banded: at each frame only the ``4 * band + 3`` extended-label
    states around the best state so far are kept, which holds the
    served bases' alignment while they stay near the reference's. Runs
    on the host's CPU device, inputs padded to powers of two."""
    lp = np.asarray(log_probs, np.float32)
    T = lp.shape[0]
    y = np.asarray(labels, np.int64)
    n = y.shape[0]
    if n > T or T == 0:
        return None
    S = 2 * n + 1
    width = 4 * band + 3
    T_pad, S_pad = _bucket(T, 1024), _bucket(S, width)
    cost = np.zeros((T_pad, lp.shape[1]), np.float32)
    cost[:T] = lp.max(axis=1, keepdims=True) - lp
    ext = np.zeros(S_pad, np.int32)
    ext[1:S:2] = y
    skip = np.zeros(S_pad, bool)
    skip[3:S:2] = y[1:] != y[:-1]
    with jax.default_device(jax.devices("cpu")[0]):
        d, lo, back, los = jax.device_get(_banded_viterbi(
            cost, ext, skip, np.int32(T), np.int32(S), width=width))
    lo = int(lo)
    ends = [s for s in (S - 1, S - 2) if s >= 0 and 0 <= s - lo < width]
    if not ends or not np.isfinite(min(d[s - lo] for s in ends)):
        return None
    s = min(ends, key=lambda e: d[e - lo])
    path = np.zeros(T, np.int64)
    for t in range(T - 1, 0, -1):
        path[t] = s
        s -= int(back[t - 1, s - los[t - 1]])
    path[0] = s
    if s > 1:
        return None
    ext_np = np.zeros(S, np.int64)
    ext_np[1::2] = y
    return cost[np.arange(T), ext_np[path]]
