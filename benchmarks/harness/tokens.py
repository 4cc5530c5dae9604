"""Training data from a seed: a token stream at the model's own vocabulary.

The approach is ``chip_smoke.py``'s (copied, not imported): every symbol
appears at least once, the rest are Zipf-drawn, and the whole is permuted.
That makes the embedding, the LM head and the chunked cross-entropy the
published shapes, and gives the loss something to learn (the unigram
distribution), so "the last loss is below the first" is a real check. The
stream is made as token ids and never as text: the program's ``CharView``
needs only ``data``, ``block_size`` and ``vocab_size`` of its parent.
"""

from __future__ import annotations

import numpy as np


def token_stream(seed: int, n_tokens: int, vocab: int,
                 zipf_exponent: float = 1.0) -> np.ndarray:
    if n_tokens < vocab:
        raise ValueError(f"a stream of {n_tokens} tokens cannot hold every "
                         f"one of {vocab} symbols")
    rng = np.random.Generator(np.random.PCG64(seed))
    weights = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** zipf_exponent
    drawn = rng.choice(vocab, size=n_tokens - vocab, p=weights / weights.sum())
    ids = np.concatenate([np.arange(vocab), drawn])
    return rng.permutation(ids).astype(np.int32)


class TokenStream:
    """What ``data.char_dataset.CharView`` reads of its parent dataset."""

    def __init__(self, data: np.ndarray, block_size: int, vocab_size: int):
        self.data = data
        self.block_size = block_size
        self.vocab_size = vocab_size
