"""Streams drawn from ``--seed``: any whole number, also past 32 bits."""
from __future__ import annotations

import numpy as np

WEIGHTS, TRAFFIC, SAMPLE, WARMUP = 1, 2, 3, 4


def sequence(seed: int, stream: int) -> np.random.SeedSequence:
    return np.random.SeedSequence([int(seed) % (1 << 128), stream])


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(sequence(seed, stream))


def key_words(seed: int, stream: int = WEIGHTS) -> np.ndarray:
    """Two uint32 words: the raw data of a threefry key."""
    return sequence(seed, stream).generate_state(2, dtype=np.uint32)
