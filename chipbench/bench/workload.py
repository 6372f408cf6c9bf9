"""The one traffic generator: reads a mix's parameters and a seed.

Training: batches of the noisy affine bigram language (`next = (a * prev +
b) mod V`, replaced by a uniform token with probability `temperature`),
vectorised over rows. Every row starts from its own random token, so no two
rows are alike.
"""
from __future__ import annotations

import numpy as np


def train_batches(seed: int, *, vocab: int, batch: int, seq: int, n: int,
                  temperature: float):
    """`n` batches of {"tokens", "labels", "loss_mask"} (B, S)."""
    rng = np.random.default_rng([int(seed), 1])
    a = int(rng.integers(1, vocab - 1)) | 1
    b = int(rng.integers(0, vocab))
    rows = n * batch
    toks = np.empty((rows, seq + 1), np.int64)
    toks[:, 0] = rng.integers(0, vocab, rows)
    noise = rng.random((rows, seq)) < temperature
    rand = rng.integers(0, vocab, (rows, seq))
    for t in range(seq):
        toks[:, t + 1] = np.where(noise[:, t], rand[:, t],
                                  (a * toks[:, t] + b) % vocab)
    toks = toks.astype(np.int32).reshape(n, batch, seq + 1)
    mask = np.ones((batch, seq), np.float32)
    return [{"tokens": toks[i, :, :-1], "labels": toks[i, :, 1:],
             "loss_mask": mask} for i in range(n)]
