"""Training traffic: the token rows of each step, made from the seed.

The same stream as the program's synthetic data (``repro.data.pipeline.
synthetic_batch``, copied here so the reference reads its rows from the
benchmark and not from the program): for step ``t`` a generator seeded
with ``seed + t * 1_000_003`` draws, per row, a start token and 4095
steps of -3..3, and the row is their running sum modulo the vocabulary.
Every row of every step differs, and every seed gives rows of the same
length and count.
"""
from __future__ import annotations

import numpy as np


def rows(seed: int, step: int, global_batch: int, seq_len: int,
         vocab: int) -> np.ndarray:
    """(global_batch, seq_len) int32 tokens of one step; rank r of R takes
    rows [r * global_batch / R, (r + 1) * global_batch / R)."""
    rng = np.random.default_rng(seed + step * 1_000_003)
    start = rng.integers(0, vocab, size=(global_batch, 1))
    walk = rng.integers(-3, 4, size=(global_batch, seq_len - 1))
    toks = np.concatenate([start, start + np.cumsum(walk, axis=1)], axis=1)
    return np.mod(toks, vocab).astype(np.int32)
