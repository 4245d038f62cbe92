"""Seeded inputs of the training cells.

``TokenStream`` is a copy of the token stream the training loop builds
for itself (Zipf marginal over the vocabulary, and with probability 1/2 a
token that is a fixed function of the one before), so that the reference
can rebuild, from the seed alone, the batches the program trained on.
"""

from __future__ import annotations

import numpy as np


class TokenStream:
    def __init__(self, vocab_size: int, seed: int, zipf_a: float = 1.2,
                 repeat_p: float = 0.5):
        self.vocab_size = vocab_size
        self.seed = seed
        self.repeat_p = repeat_p
        ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
        pmf = ranks ** (-zipf_a)
        self._pmf = pmf / pmf.sum()

    def batch(self, step: int, batch: int, seq_len: int) -> np.ndarray:
        """[batch, seq_len + 1] int32 tokens, fixed by (seed, step)."""
        rng = np.random.default_rng((self.seed, step))
        n = batch * (seq_len + 1)
        iid = rng.choice(self.vocab_size, size=n, p=self._pmf)
        use_prev = rng.random(n) < self.repeat_p
        prev = np.roll(iid, 1)
        out = np.where(use_prev, (3 * prev + 7) % self.vocab_size, iid)
        return out.reshape(batch, seq_len + 1).astype(np.int32)

    def inputs(self, step: int, batch: int, seq_len: int):
        """(tokens, labels), each [batch, seq_len]."""
        raw = self.batch(step, batch, seq_len)
        return raw[:, :-1], raw[:, 1:]


def jax_seed(seed: int) -> int:
    """A 31-bit key for ``jax.random.key`` from any whole-number seed."""
    return int(np.random.SeedSequence(abs(int(seed))).generate_state(1)[0]
               & 0x7FFFFFFF)
