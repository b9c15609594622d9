"""Pore-model squiggle simulator, the traffic's read signal.

A copy of the program's simulator (``repro.data.squiggle``), kept here
so that the yardstick cannot move with the program: random bases, a
seeded 6-mer -> current table, per-base dwell ``1 + Poisson(dwell - 1)``
samples, Gaussian noise and a slow drift, then med/MAD normalization.
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np

K = 6


def pore_table(seed: int = 7, k: int = K) -> np.ndarray:
    return np.random.RandomState(seed).randn(4 ** k).astype(np.float32)


def _kmer_index(seq: np.ndarray, k: int = K) -> np.ndarray:
    idx = np.zeros(len(seq) - k + 1, np.int64)
    for i in range(k):
        idx = idx * 4 + seq[i:len(seq) - k + 1 + i]
    return idx


def normalize(sig: np.ndarray) -> np.ndarray:
    med = np.median(sig)
    mad = np.median(np.abs(sig - med)) + 1e-6
    return ((sig - med) / (1.4826 * mad)).astype(np.float32)


def read_signal(rng: np.random.Generator, table: np.ndarray, n_bases: int,
                *, dwell: float = 9.0, noise: float = 0.18,
                drift: float = 0.01) -> np.ndarray:
    """Normalized squiggle of ``n_bases`` random bases."""
    seq = rng.integers(0, 4, n_bases + K - 1)
    levels = table[_kmer_index(seq)]
    sig = np.repeat(levels, 1 + rng.poisson(dwell - 1, len(levels)))
    sig = sig + noise * rng.standard_normal(len(sig)).astype(np.float32)
    sig = sig + drift * np.cumsum(rng.standard_normal(len(sig))) \
        / np.sqrt(max(len(sig), 1))
    return normalize(sig.astype(np.float32))


def noise_signal(rng: np.random.Generator, n_samples: int) -> np.ndarray:
    """Normalized white noise: an off-target read as the read-until
    head sees it."""
    return normalize(rng.standard_normal(n_samples).astype(np.float32))


def read_lengths(n: int, *, median: float, sigma: float, cap: int,
                 floor: int = 1) -> np.ndarray:
    """``n`` read lengths in bases at the midpoint quantiles of a
    log-normal, ascending: every seed gets the same sizes, and the
    traffic draws only their order from the seed."""
    z = np.asarray([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(np.log(median) + sigma * z)
    return np.clip(np.round(x), floor, cap).astype(np.int64)
