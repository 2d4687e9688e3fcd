"""Token batches for train cells: a Zipf unigram draw over the vocabulary.

Token id i (0-based) has probability proportional to (i + 1) ** -a, the
unigram of the program's synthetic corpus (``SyntheticLM``), drawn by
inverting the CDF for every position at once rather than one token at a
time.  Every batch of a run comes from ``--seed``; the same seed gives the
same batches.
"""
from __future__ import annotations

import numpy as np


def zipf_cdf(n: int, a: float) -> np.ndarray:
    p = np.arange(1, n + 1, dtype=np.float64) ** -float(a)
    cdf = np.cumsum(p)
    return cdf / cdf[-1]


def draw(rng: np.random.Generator, cdf: np.ndarray, shape) -> np.ndarray:
    idx = np.searchsorted(cdf, rng.random(shape), side="right")
    return np.minimum(idx, cdf.shape[0] - 1)


def batches(traffic: dict, vocab: int, global_batch: int, n_batches: int,
            seed: int) -> np.ndarray:
    """(n_batches, global_batch, seq + 1) int32 token ids; a step trains on
    ids[:, :-1] against labels ids[:, 1:]."""
    rng = np.random.default_rng([seed, 0x70CE])
    cdf = zipf_cdf(vocab, traffic["zipf_a"])
    shape = (n_batches, global_batch, traffic["seq_len"] + 1)
    return draw(rng, cdf, shape).astype(np.int32)
