"""Seeded token batches for a training cell.

The same order-1 Markov chain with copy motifs as the repository's
synthetic pipeline (sparse successor table of ``branching`` ids per token;
with probability ``motif_prob`` a row repeats a recent span of
``motif_len`` tokens), generated for all rows of the pool at once instead
of token by token in Python, so that a pool of batches costs well under a
second of set-up.  Rows are drawn independently, so every row differs.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def markov_rows(rng: np.random.Generator, n_rows: int, length: int,
                vocab: int, *, branching: int, motif_len: int,
                motif_prob: float) -> np.ndarray:
    """``n_rows`` token rows of ``length`` ids below ``vocab``."""
    table = rng.integers(0, vocab, size=(vocab, branching), dtype=np.int64)
    toks = np.empty((n_rows, length), np.int64)
    toks[:, 0] = rng.integers(0, vocab, size=n_rows)
    left = np.zeros(n_rows, np.int64)        # motif tokens still to copy
    src = np.zeros(n_rows, np.int64)         # next position to copy from
    rows = np.arange(n_rows)
    for i in range(1, length):
        nxt = table[toks[:, i - 1], rng.integers(0, branching, size=n_rows)]
        start = (left == 0) & (rng.random(n_rows) < motif_prob)
        if motif_len < i < length - motif_len:
            begin = rng.integers(0, i - motif_len, size=n_rows)
            src = np.where(start, begin, src)
            left = np.where(start, motif_len, left)
        copying = left > 0
        toks[:, i] = np.where(copying, toks[rows, np.minimum(src, i - 1)],
                              nxt)
        src = np.where(copying, src + 1, src)
        left = np.where(copying, left - 1, left)
    return toks


def batch_pool(seed: int, n_batches: int, batch: int, seq_len: int,
               vocab: int, spec: Dict) -> List[Dict[str, np.ndarray]]:
    """``n_batches`` batches of ``batch`` rows: next-token ``tokens`` and
    ``labels`` of ``seq_len`` positions each, drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    toks = markov_rows(rng, n_batches * batch, seq_len + 1, vocab,
                       branching=spec["branching"],
                       motif_len=spec["motif_len"],
                       motif_prob=spec["motif_prob"])
    toks = toks.reshape(n_batches, batch, seq_len + 1)
    return [{"tokens": t[:, :-1].astype(np.int32),
             "labels": t[:, 1:].astype(np.int32)} for t in toks]
