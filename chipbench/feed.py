"""The token stream a cell trains on, drawn from the seed.

A copy of the program's synthetic generator (``repro.data.tokens``):
Zipfian unigram draws with a sticky bigram, ``next = (prev*7 + 3) % V``
with probability ``sticky``.  Every batch holds rows that differ.  It
stands in for the task's own pipeline as ``next_batch()``, so the window
pays the same host work per round as a user's loader of this kind, and
the reference can draw the same batches again from the seed.
"""
from __future__ import annotations

import numpy as np


class TokenFeed:
    def __init__(self, vocab: int, seq: int, batch: int, seed: int,
                 sticky: float = 0.5):
        self.vocab, self.seq, self.batch = vocab, seq, batch
        self.sticky = sticky
        self._rng = np.random.default_rng(seed)
        ranks = np.arange(1, vocab + 1, dtype=np.float64)
        self._unigram = (1.0 / ranks) / np.sum(1.0 / ranks)

    def next_batch(self) -> tuple[np.ndarray, np.ndarray]:
        b, s, v = self.batch, self.seq, self.vocab
        toks = np.empty((b, s + 1), np.int32)
        toks[:, 0] = self._rng.choice(v, b, p=self._unigram)
        sticky = self._rng.random((b, s)) < self.sticky
        fresh = self._rng.choice(v, (b, s), p=self._unigram)
        for t in range(s):
            nxt = (toks[:, t].astype(np.int64) * 7 + 3) % v
            toks[:, t + 1] = np.where(sticky[:, t], nxt, fresh[:, t])
        return toks[:, :-1], toks[:, 1:]


def for_cell(cell, seed: int) -> TokenFeed:
    t = cell.traffic
    return TokenFeed(cell.config["vocab_size"], t["seq_len"],
                     t["fl_devices"] * t["sequences_per_device"], seed,
                     sticky=t["tokens"]["sticky"])
