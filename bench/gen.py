"""Inputs made from the seed: a quantize job's calibration tokens.

Each job of a run draws its own tokens from the seed and the job's index,
so the same seed gives the same jobs, and the reference can draw the
tokens of a checked job again after the window.
"""
from __future__ import annotations

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent numpy stream per purpose, for any seed size."""
    return np.random.default_rng([int(seed), int(stream)])


def calibration_tokens(seed: int, job: int, batches: int, batch: int,
                       seq: int, vocab: int) -> np.ndarray:
    """(batches, batch, seq) int32 calibration ids of one quantize job."""
    return rng_for(seed, 1000 + job).integers(
        0, vocab, (batches, batch, seq), dtype=np.int32)
