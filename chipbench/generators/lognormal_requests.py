"""Requests of a serving mix whose prompt and answer lengths are
lognormal.

The mix file (``chipbench/traffic/<name>.json``) gives ``loop``
(``open`` or ``closed``), the length distributions ``prompt_len`` and
``output_len`` (``median``, ``sigma``, ``min``, ``max``), and for an open
loop ``rate_per_s``, for a closed one ``pool``.  Lengths and gaps are
fixed quantiles of the distributions, in one shuffled order that the mix
alone fixes; the seed draws the token ids.  So every seed offers the same
work in the same order: how long a queue grows depends on the order of
arrivals, and a run's spread should be the system's, not the schedule's.
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np

from chipbench.harness.traffic import quantiles, rng_for


def lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` token counts: the distribution's quantiles at (i + 1/2)/n,
    clipped to [min, max], in ascending order."""
    z = np.array([NormalDist().inv_cdf(q) for q in quantiles(n)])
    raw = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(np.rint(raw), dist["min"], dist["max"]).astype(np.int64)


def requests(mix: dict, seed: int, vocab: int, seconds: float) -> list[dict]:
    """Each request ``{"prompt", "max_new"}``, in the order they are sent.
    An open loop sends ``rate_per_s`` x ``seconds`` of them, each with its
    ``due_s``: the gaps are a Poisson process's (exponential quantiles),
    shuffled and scaled so that the first is due at 0 and the last before
    ``seconds``.  A closed loop's clients draw from ``pool`` of them."""
    if mix["loop"] == "open":
        n = max(1, round(mix["rate_per_s"] * seconds))
    else:
        n = mix["pool"]
    rng = rng_for(0, mix["loop"])
    p = rng.permutation(lengths(mix["prompt_len"], n))
    o = rng.permutation(lengths(mix["output_len"], n))
    toks = rng_for(seed, "tokens")
    out = [{"prompt": toks.integers(0, vocab, int(p[i]), dtype=np.int32),
            "max_new": int(o[i])} for i in range(n)]
    if mix["loop"] == "open":
        gaps = rng.permutation(-np.log1p(-quantiles(n)))
        due = seconds * np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) \
            / gaps.sum()
        for r, d in zip(out, due):
            r["due_s"] = float(d)
    return out
