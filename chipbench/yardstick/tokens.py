"""Seeded synthetic token rows: a Markov chain over token classes.

The distribution follows the repo's ``SyntheticLMPipeline`` (vocab mapped
onto 64 classes, a Dirichlet(0.3) transition matrix between classes,
tokens drawn uniformly within a class), kept here so the benchmark's
inputs do not move when the program changes. The draw is vectorised over
rows, so a 16 x 2048 batch takes milliseconds of host time.

Every batch is a pure function of (seed, step), and every row of every
step is drawn from its own random numbers. Seeds of any size are taken.
"""
from __future__ import annotations

import numpy as np


class MarkovTokens:
    def __init__(self, vocab: int, rows: int, seq: int, seed: int,
                 classes: int = 64, alpha: float = 0.3):
        self.vocab, self.rows, self.seq, self.seed = vocab, rows, seq, seed
        rng = np.random.default_rng([seed, 0])
        trans = rng.dirichlet(np.full(classes, alpha), size=classes)
        self._cum = np.cumsum(trans, axis=1)
        self._cum[:, -1] = 1.0
        class_of = rng.integers(0, classes, size=vocab)
        members = [np.flatnonzero(class_of == c) for c in range(classes)]
        members = [m if len(m) else np.array([c % vocab])
                   for c, m in enumerate(members)]
        self._count = np.array([len(m) for m in members])
        self._start = np.concatenate([[0], np.cumsum(self._count)[:-1]])
        self._flat = np.concatenate(members).astype(np.int32)
        self._classes = classes

    def batch(self, step: int) -> np.ndarray:
        """(rows, seq) int32 tokens of `step`."""
        rng = np.random.default_rng([self.seed, 1, step])
        cls = np.empty((self.seq, self.rows), np.int64)
        cls[0] = rng.integers(0, self._classes, size=self.rows)
        u = rng.random((self.seq - 1, self.rows))
        for t in range(1, self.seq):
            row = self._cum[cls[t - 1]]                     # (rows, classes)
            cls[t] = np.minimum((u[t - 1][:, None] > row).sum(axis=1),
                                self._classes - 1)
        pick = rng.random((self.seq, self.rows))
        idx = self._start[cls] + (pick * self._count[cls]).astype(np.int64)
        return np.ascontiguousarray(self._flat[idx].T)
