"""The benchmark's token generator, a copy of the program's Markov-chain
stream (``repro.data.pipeline.synthetic_token_batches``) kept here so the
yardstick does not move when the program's data code changes.

Tokens follow a first-order Markov chain with a skewed stationary
distribution (each token has 8 successors), so the cross-entropy has
structure to learn; every batch is drawn from (seed, batch index).
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def _rng(seed: int, step: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, step]))


def batches(vocab_size: int, batch: int, seq_len: int, seed: int,
            count: int) -> List[Dict[str, np.ndarray]]:
    """``count`` batches {"tokens", "labels"} of shape (batch, seq_len),
    int32, labels the tokens shifted by one."""
    base = np.random.default_rng(seed)
    succ = base.integers(0, vocab_size, size=(vocab_size, 8))
    logits = base.normal(size=(vocab_size, 8)).astype(np.float64)
    probs = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    cdfs = probs.cumsum(-1)
    out = []
    for step in range(count):
        r = _rng(seed, step)
        toks = np.empty((batch, seq_len + 1), np.int32)
        toks[:, 0] = r.integers(0, vocab_size, size=batch)
        unif = r.random((batch, seq_len))
        for t in range(seq_len):
            cur = toks[:, t]
            choice = (unif[:, t:t + 1] < cdfs[cur]).argmax(-1)
            toks[:, t + 1] = succ[cur, choice]
        out.append({"tokens": toks[:, :-1], "labels": toks[:, 1:].copy()})
    return out
