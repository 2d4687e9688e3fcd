"""LDA word-topic traffic for PS cells.

Each worker clock resamples ``tokens_per_clock`` tokens: a word id drawn
from a Zipf distribution over the vocabulary (probability of the word of
frequency rank r proportional to r ** -a, ranks scattered over the table by
a seeded permutation, as a vocabulary in alphabetical order would be), its
old topic uniform, and its new topic, with probability ``p_view``, the
topic with the most counts for that word in the worker's (stale) view, and
otherwise uniform.  The delta is -1 at (word, old) and +1 at (word, new):
integer counts, exact in float32 in any order.
"""
from __future__ import annotations

import numpy as np


class LDATraffic:
    def __init__(self, traffic: dict, rows: int, topics: int, seed: int):
        self.rows, self.topics = rows, topics
        self.tokens = int(traffic["tokens_per_clock"])
        self.p_view = float(traffic["p_view"])
        p = np.arange(1, rows + 1, dtype=np.float64) ** -float(
            traffic["word_zipf_a"])
        cdf = np.cumsum(p)
        self.cdf = cdf / cdf[-1]
        self.perm = np.random.default_rng([seed, 0x1DA]).permutation(rows)

    def initial_counts(self, seed: int) -> np.ndarray:
        rng = np.random.default_rng([seed, 0xC0])
        return rng.integers(0, 8, (self.rows, self.topics)).astype(np.float32)

    def words(self, rng: np.random.Generator, k: int) -> np.ndarray:
        rank = np.minimum(np.searchsorted(self.cdf, rng.random(k)),
                          self.rows - 1)
        return self.perm[rank]

    def resample(self, rng: np.random.Generator, counts: np.ndarray):
        """(word, old topic, new topic) of every token of one clock."""
        wd = self.words(rng, self.tokens)
        old = rng.integers(0, self.topics, self.tokens)
        new = np.where(rng.random(self.tokens) < self.p_view,
                       counts[wd].argmax(1),
                       rng.integers(0, self.topics, self.tokens))
        return wd, old, new

    def dense_delta(self, wd, old, new) -> np.ndarray:
        delta = np.zeros((self.rows, self.topics), np.float32)
        np.add.at(delta, (wd, old), -1.0)
        np.add.at(delta, (wd, new), 1.0)
        return delta
