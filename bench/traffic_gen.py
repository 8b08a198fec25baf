"""The one generator of open-loop serving traffic.

A mix file gives the rate, the length distributions and their clips.
Every seed gets the same multiset of prompt lengths, output lengths and
gaps between arrivals (stratified quantiles of the distributions), in
another order, with other token ids: the seed changes which request
comes when, not how much work a window holds.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclass
class Planned:
    rid: int
    due: float                 # seconds after the window opens
    prompt: np.ndarray         # int32 token ids
    max_new: int


def lognormal_quantiles(spec: Dict, n: int) -> np.ndarray:
    """``n`` stratified draws of a lognormal with the given median and
    sigma, rounded and clipped to [min, max]."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def plan(mix: Dict, seed: int, seconds: float, vocab: int) -> List[Planned]:
    rate = float(mix["rate"])
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    rng = np.random.default_rng(seed)
    gaps = rng.permutation(gaps)
    # arrivals fall inside the window: the same scale for every seed
    due = np.cumsum(gaps) - gaps[0]
    due *= seconds / (due[-1] + float(np.mean(gaps))) if n > 1 else 0.0
    prompts = rng.permutation(lognormal_quantiles(mix["prompt"], n))
    outs = rng.permutation(lognormal_quantiles(mix["output"], n))
    return [Planned(rid=i, due=float(due[i]),
                    prompt=rng.integers(0, vocab, int(prompts[i]),
                                        dtype=np.int64).astype(np.int32),
                    max_new=int(outs[i]))
            for i in range(n)]


def buckets(lo: int, hi: int) -> List[int]:
    """Powers of two from the one holding ``lo`` to the one holding
    ``hi``: the prompt-length buckets the engine pads admissions to."""
    b = 1 << max(0, lo - 1).bit_length()
    out = []
    while True:
        out.append(b)
        if b >= hi:
            return out
        b *= 2


def percentile(values: List[float], p: float) -> float:
    """Nearest-rank percentile; an unanswered request counts as
    infinite."""
    v = sorted(values)
    return v[max(0, math.ceil(p / 100.0 * len(v)) - 1)]
