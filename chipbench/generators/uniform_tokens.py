"""Training batches of uniform random token ids: the mix file gives
``global_batch`` and ``seq_len``; every row of every step differs."""
from __future__ import annotations

import numpy as np

from chipbench.harness.traffic import rng_for


def batches(mix: dict, seed: int, vocab: int):
    """Endless batches ``{"tokens", "labels"}`` [global_batch, seq_len]
    int32, drawn from the seed."""
    B, S = mix["global_batch"], mix["seq_len"]
    rng = rng_for(seed, "train")
    while True:
        t = rng.integers(0, vocab, (B, S + 1), dtype=np.int32)
        yield {"tokens": t[:, :-1], "labels": t[:, 1:]}
