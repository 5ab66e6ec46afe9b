"""The train loop's synthetic token stream, written out from its
definition: the data every train cell feeds, whatever the architecture,
so that any reference can draw the batches the program trained on."""
from __future__ import annotations

import numpy as np


def synthetic_batch(vocab: int, seq: int, rows: int, seed: int,
                    step: int) -> np.ndarray:
    """Row i of step s: Zipf(1.1) unigrams from
    ``default_rng(SeedSequence([seed, s, i]))`` with the second half of
    the row a copy of the first (an induction pattern)."""
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    probs = 1.0 / ranks ** 1.1
    probs = probs / probs.sum()
    out = np.empty((rows, seq), np.int32)
    half = seq // 2
    for i in range(rows):
        rng = np.random.default_rng(np.random.SeedSequence([seed, step, i]))
        row = rng.choice(vocab, size=seq, p=probs)
        row[half:2 * half] = row[:half]
        out[i] = row
    return out
