"""Token batches from the seed: uniform ids over the whole vocabulary.

Step ``i`` of a run with seed ``s`` draws its rows from
``PCG64([s, i])``, so a seed fixes every batch, every seed gives batches
of the same shape, and the rows of one step and of different steps all
differ.  Labels are the tokens shifted by one position.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def batch(seed: int, step: int, rows: int, seq: int,
          vocab: int) -> Dict[str, np.ndarray]:
    rng = np.random.Generator(np.random.PCG64([seed, step]))
    ids = rng.integers(0, vocab, size=(rows, seq + 1), dtype=np.int32)
    return {"tokens": ids[:, :-1], "labels": ids[:, 1:]}
