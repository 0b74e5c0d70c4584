"""The benchmark's one traffic generator for training cells.

A copy of the program's ``SyntheticLM`` (``repro.data.pipeline``): a
Zipf unigram with induced bigram structure, seeded and seekable, so
that routing is uneven as it is on natural text and the cross-entropy
has signal.  A traffic mix (``bench/traffic/<mix>.json``) gives its
parameters; the program receives only the generated batches.
"""

from __future__ import annotations

import numpy as np


class SyntheticLM:
    def __init__(self, vocab_size: int, seq_len: int, global_batch: int,
                 seed: int, n_heavy: int = 64, heavy_prob: float = 0.7):
        self.vocab_size, self.seq_len = vocab_size, seq_len
        self.global_batch, self.seed = global_batch, seed
        self.n_heavy, self.heavy_prob = n_heavy, heavy_prob
        rng = np.random.default_rng(seed)
        ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
        self.unigram = (1.0 / ranks) / np.sum(1.0 / ranks)
        self.bigram = rng.integers(0, vocab_size, size=(vocab_size, n_heavy))

    def batch(self, step: int):
        """(tokens, labels), each (global_batch, seq_len) int32."""
        rng = np.random.default_rng((self.seed, step))
        B, L, V = self.global_batch, self.seq_len, self.vocab_size
        toks = np.empty((B, L + 1), np.int32)
        toks[:, 0] = rng.choice(V, size=B, p=self.unigram)
        follow = rng.random((B, L)) < self.heavy_prob
        succ_idx = rng.integers(0, self.n_heavy, size=(B, L))
        rand_tok = rng.choice(V, size=(B, L), p=self.unigram)
        for t in range(L):
            toks[:, t + 1] = np.where(
                follow[:, t], self.bigram[toks[:, t], succ_idx[:, t]],
                rand_tok[:, t])
        return toks[:, :-1], toks[:, 1:]


def make(mix: dict, vocab_size: int, seed: int) -> SyntheticLM:
    return SyntheticLM(vocab_size, mix["seq_len"], mix["global_batch"], seed,
                       n_heavy=mix["n_heavy"], heavy_prob=mix["heavy_prob"])
