"""Training batches from the seed: a copy of the program's ``increment``
task (``data/synthetic.py``; listed in PERF.md for a later PR to fold):
``token[t+1] = token[t] + 1 mod vocab``, every row from a different start.
The generator is the benchmark's, so the reference and the program read the
same rows; the program's ``prefetch_batches`` carries them to the device."""

from __future__ import annotations

from typing import Iterator

import numpy as np


def increment_batches(batch: int, seq: int, vocab: int, seed: int) -> Iterator[dict]:
    rng = np.random.default_rng(int(seed))
    offsets = np.arange(seq)[None, :]
    while True:
        start = rng.choice(vocab, size=(batch, 1), replace=False)
        yield {"tokens": ((start + offsets) % vocab).astype(np.int32),
               "loss_mask": np.ones((batch, seq), np.float32)}
