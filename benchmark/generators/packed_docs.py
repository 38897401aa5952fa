"""The one general generator of training batches: documents of drawn length,
their tokens drawn from a Zipf law over a seeded permutation of the
vocabulary (so there is a unigram distribution to learn and the loss falls),
each closed by an end-of-text id and packed end to end into rows of
`seq_len`. A fixed token batch every step; the same stream for the same seed.
"""
from __future__ import annotations

import numpy as np

from .request_stream import draw


def batches(mix, seed, vocab_size, batch, seq_len):
    """Yield `(ids, labels)`, int32 `[batch, seq_len]`, without end."""
    lengths = np.random.default_rng([seed, 1])
    tokens = np.random.default_rng([seed, 2])
    eot = vocab_size - 1
    ranks = np.arange(1, vocab_size, dtype=np.float64)
    cdf = np.cumsum(ranks ** -float(mix["tokens"]["zipf_a"]))
    cdf /= cdf[-1]
    ids_of_rank = tokens.permutation(vocab_size - 1)   # never the eot id
    need = batch * (seq_len + 1)
    buf = np.empty(0, np.int32)
    while True:
        parts = [buf]
        have = len(buf)
        while have < need:
            n = draw(lengths, mix["doc_len"])
            doc = ids_of_rank[np.searchsorted(cdf, tokens.random(n - 1))]
            parts += [doc.astype(np.int32), np.asarray([eot], np.int32)]
            have += n
        flat = np.concatenate(parts)
        rows, buf = flat[:need].reshape(batch, seq_len + 1), flat[need:]
        yield np.ascontiguousarray(rows[:, :-1]), \
            np.ascontiguousarray(rows[:, 1:])
