"""What decides ``correct``: served bases and verdicts against the
plain float32 reference.

Once the window has closed, a sample of the reads the engine finished
in it, drawn from the seed with the longest among them, is run through
the reference's whole-read forward. Each read's served bases are
aligned to the reference log-probs by the best CTC path that collapses
to them (:func:`bench.reference.viterbi_gaps`); at every frame the gap
is how far the path's label lies below the reference's best label, in
nats. Two numbers are compared: the mean gap over all compared frames
and the widest. A read whose bases no path can give fails outright.

In a read-until cell, every read the engine ejected or finished after
its deciding window is also judged: the reference head scores the
read's first ``eject_after_chunks`` windows, and the engine must have
ejected it exactly when their mean logit is below the threshold. Reads
whose reference mean lies within ``VERDICT_MARGIN`` of the threshold
are not judged, since the program's float32 head runs at the chip's
default matmul precision.

Limits and the readings they were set from are in ``PERF.md``.
"""
from __future__ import annotations

from types import SimpleNamespace
from typing import Dict, List, Tuple

import numpy as np

from bench import reference as ref_mod

N_SAMPLE = 8
# Gaps in nats, set between the readings of sound bf16 runs (lower) and
# of the float8 control (upper); PERF.md, "Correctness limits".
GAP_MEAN_LIMIT = 0.2
GAP_MAX_LIMIT = 3.5
VERDICT_MARGIN = 0.5
THRESHOLD = 0.0


def sample_reads(served: List[Tuple[object, np.ndarray]], seed: int,
                 n: int = N_SAMPLE) -> List[Tuple[object, np.ndarray]]:
    """``n`` served reads drawn from the seed, the longest among them."""
    if not served:
        return []
    longest = max(range(len(served)), key=lambda i: served[i][1].shape[0])
    rest = [i for i in range(len(served)) if i != longest]
    rng = np.random.default_rng([seed, 3])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [served[longest]] + [served[rest[i]] for i in sorted(pick)]


def base_gaps(reference, sample) -> Dict[str, float]:
    total, frames, worst, tokens = 0.0, 0, 0.0, 0
    for req, signal in sample:
        lp = reference.log_probs(signal)
        g = ref_mod.viterbi_gaps(lp, req.out_tokens)
        tokens += len(req.out_tokens)
        if g is None:
            return {"gap_mean": float("inf"), "gap_max": float("inf"),
                    "tokens": tokens}
        total += float(g.sum())
        frames += g.shape[0]
        worst = max(worst, float(g.max()))
    return {"gap_mean": total / max(frames, 1), "gap_max": worst,
            "tokens": tokens}


def windows(signal: np.ndarray, core: int, halo: int, k: int) -> np.ndarray:
    """The first ``k`` halo-padded windows of a read, (k, W, 1)."""
    W = core + 2 * halo
    out = np.zeros((k, W, 1), np.float32)
    for j in range(k):
        lo = j * core - halo
        src = signal[max(lo, 0):max(min(lo + W, signal.shape[0]), 0)]
        off = max(lo, 0) - lo
        out[j, off:off + src.shape[0], 0] = src
    return out


def verdicts(cls_params, judged, core: int, halo: int, k: int
             ) -> Dict[str, int]:
    """Reads whose ejection differs from the reference head's verdict."""
    import jax
    score = jax.jit(ref_mod.classifier_logits)
    wrong = counted = 0
    for req, signal in judged:
        mean = float(np.mean(np.asarray(
            score(cls_params, windows(signal, core, halo, k)))))
        if abs(mean - THRESHOLD) < VERDICT_MARGIN:
            continue
        counted += 1
        wrong += int(bool(req.ejected) != (mean < THRESHOLD))
    return {"verdict_mismatch": wrong, "verdicts": counted}


def compare(reference, served, judged, seed: int, *, cls_params=None,
            core: int = 0, halo: int = 0, k: int = 0) -> Dict[str, Dict]:
    """The numbers compared, each with its limit, and whether all hold.

    Returns ``{"checks": {name: {"value", "limit", "op"}}, "correct": bool,
    "info": {...}}``."""
    sample = sample_reads(served, seed)
    checks = {"reads_compared": {"value": len(sample), "limit": 1,
                                "op": ">="}}
    info: Dict[str, float] = {}
    ok = len(sample) >= 1
    if sample:
        g = base_gaps(reference, sample)
        checks["gap_mean"] = {"value": g["gap_mean"],
                              "limit": GAP_MEAN_LIMIT, "op": "<="}
        checks["gap_max"] = {"value": g["gap_max"],
                             "limit": GAP_MAX_LIMIT, "op": "<="}
        ok &= g["gap_mean"] <= GAP_MEAN_LIMIT
        ok &= g["gap_max"] <= GAP_MAX_LIMIT
        info.update(tokens_compared=g["tokens"])
    if cls_params is not None:
        v = verdicts(cls_params, judged, core, halo, k)
        checks["verdict_mismatch"] = {"value": v["verdict_mismatch"],
                                      "limit": 0, "op": "<="}
        checks["verdicts_judged"] = {"value": v["verdicts"], "limit": 1,
                                     "op": ">="}
        ok &= v["verdict_mismatch"] == 0 and v["verdicts"] >= 1
    return {"checks": checks, "correct": bool(ok), "info": info,
            "sample": sample}


def greedy_bases(log_probs: np.ndarray) -> List[int]:
    """Greedy CTC decoding: the best label of every frame, repeats
    merged, blanks dropped."""
    best = np.argmax(log_probs, axis=-1)
    keep = np.ones(best.shape, bool)
    keep[1:] = best[1:] != best[:-1]
    return [int(b) for b in best[keep] if b != ref_mod.BLANK]


def control_served(control, sample) -> List[Tuple[object, np.ndarray]]:
    """The control in the program's place: the sampled reads basecalled
    by ``control`` (the reference in a lower precision), whole read,
    greedy. :func:`compare` judges them as it judges served reads."""
    return [(SimpleNamespace(out_tokens=greedy_bases(control.log_probs(s))),
             s) for _, s in sample]
