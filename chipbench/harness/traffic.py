"""What every generator and reader shares: generators drawn from a seed,
quantile points, and the nearest-rank percentile."""
from __future__ import annotations

import math

import numpy as np


def rng_for(seed: int, stream: str = "") -> np.random.Generator:
    """Independent generator per (seed, stream); any whole-number seed."""
    words = [int(seed) % (1 << 64)] + [ord(c) for c in stream]
    return np.random.default_rng(np.random.SeedSequence(words))


def device_seed(seed: int) -> int:
    """A 31-bit integer for ``jax.random.PRNGKey``, derived from ``seed``."""
    return int(rng_for(seed, "device").integers(0, 1 << 31))


def quantiles(n: int) -> np.ndarray:
    """The points (i + 1/2)/n, i < n."""
    return (np.arange(n) + 0.5) / n


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in [0, 100]) of a non-empty sample."""
    s = sorted(values)
    k = max(0, math.ceil(q / 100.0 * len(s)) - 1)
    return float(s[k])
