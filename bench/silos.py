"""Non-IID silo token streams: the traffic generator of the training cells.

A copy of the program's synthetic corpus (``repro.data.pipeline``), kept with
the benchmark so that no change to the program can change the traffic. Each
silo samples tokens from a Zipf unigram distribution whose support is rotated
per silo and tilted by a Dirichlet mixture over 16 vocabulary groups, mixed
half and half with a fixed bigram step, so that the loss is learnable.
"""
from __future__ import annotations

import numpy as np


class Silo:
    """Deterministic stream of (tokens, labels) batches for one silo."""

    def __init__(self, vocab: int, seq_len: int, batch: int, n_nodes: int,
                 node_id: int, seed: int, dirichlet_alpha: float, zipf_s: float):
        self.vocab, self.seq_len, self.batch = vocab, seq_len, batch
        rng = np.random.default_rng(seed + 7919 * node_id)
        ranks = np.arange(1, vocab + 1, dtype=np.float64)
        base = np.roll(1.0 / ranks ** zipf_s, (node_id * vocab) // max(n_nodes, 1))
        tilt = rng.dirichlet(np.full(16, dirichlet_alpha))
        w = np.ones(vocab)
        for g, t in zip(np.array_split(np.arange(vocab), 16), tilt):
            w[g] *= t * 16
        self.probs = base * w / (base * w).sum()
        self.delta = int(rng.integers(1, vocab - 1))
        self._rng = np.random.default_rng(seed + 104729 * (node_id + 1))

    def next_batch(self) -> tuple[np.ndarray, np.ndarray]:
        b, s = self.batch, self.seq_len
        toks = np.empty((b, s + 1), dtype=np.int32)
        toks[:, 0] = self._rng.choice(self.vocab, size=b, p=self.probs)
        unigram = self._rng.choice(self.vocab, size=(b, s), p=self.probs)
        use_bigram = self._rng.random((b, s)) < 0.5
        for t in range(s):
            bigram = (toks[:, t] + self.delta) % self.vocab
            toks[:, t + 1] = np.where(use_bigram[:, t], bigram, unigram[:, t])
        return toks[:, :-1], toks[:, 1:]


def batch_pool(*, vocab: int, seq_len: int, batch_per_node: int, n_nodes: int,
               seed: int, size: int, dirichlet_alpha: float, zipf_s: float
               ) -> list[tuple[np.ndarray, np.ndarray]]:
    """``size`` global batches, each the silos' batches stacked in node order."""
    silos = [Silo(vocab, seq_len, batch_per_node, n_nodes, u, seed,
                  dirichlet_alpha, zipf_s) for u in range(n_nodes)]
    pool = []
    for _ in range(size):
        parts = [s.next_batch() for s in silos]
        pool.append((np.concatenate([p[0] for p in parts]),
                     np.concatenate([p[1] for p in parts])))
    return pool
