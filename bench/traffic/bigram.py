"""Seeded training batches: token streams of a random bigram chain.

The generator of ``data/synthetic.py`` (``BigramLM``, large-vocabulary
branch), copied so the yardstick cannot move with the program: every token
has 8 uniformly drawn successors, each row starts at a uniform token and
follows uniformly chosen successors. The program receives only the arrays.
"""
from __future__ import annotations

import numpy as np


def batches(seed: int, vocab: int, n: int, batch: int, seq: int) -> dict:
    """``n`` batches of ``batch`` rows of ``seq`` tokens: {"tokens",
    "labels"} int32 arrays of shape (n, batch, seq), labels shifted by one.
    Every row of every batch differs (distinct start and successor draws)."""
    rng = np.random.default_rng([seed, vocab, n, batch, seq])
    succ = rng.integers(0, vocab, size=(vocab, 8), dtype=np.int64).astype(np.int32)
    rows = n * batch
    toks = np.empty((rows, seq + 1), np.int32)
    toks[:, 0] = rng.integers(0, vocab, size=rows)
    choice = rng.integers(0, 8, size=(rows, seq))
    for t in range(seq):
        toks[:, t + 1] = succ[toks[:, t], choice[:, t]]
    toks = toks.reshape(n, batch, seq + 1)
    return {"tokens": toks[:, :, :-1], "labels": toks[:, :, 1:]}
