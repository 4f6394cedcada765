"""The benchmark's own copy of the training cell's batches, for the
reference: the same order-3 pattern stream the program's pipeline
(``repro.data.SyntheticLMDataset``) is specified to produce for a seed and
a step, written here so that the reference takes no input the program made.
"""
from __future__ import annotations

import numpy as np


def batch(vocab, seq_len, seed, step, batch_size, *, n_patterns=64, order=3):
    table = np.random.default_rng(seed).integers(
        0, vocab, size=(n_patterns,), dtype=np.int32)
    rng = np.random.default_rng((seed * 1_000_003 + step) * 97)
    toks = np.empty((batch_size, seq_len + 1), dtype=np.int32)
    toks[:, :order] = rng.integers(0, vocab, size=(batch_size, order))
    noise = rng.random((batch_size, seq_len + 1)) < 0.05
    rand = rng.integers(0, vocab, size=(batch_size, seq_len + 1))
    for t in range(order, seq_len + 1):
        nxt = table[toks[:, t - order:t].sum(axis=1) % n_patterns]
        toks[:, t] = np.where(noise[:, t], rand[:, t], nxt)
    return toks[:, :-1], toks[:, 1:]
