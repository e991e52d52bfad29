"""Offsets drawn uniformly from ``[0, span)``."""
import numpy as np


def draw(rng: np.random.Generator, size: int, span: int) -> np.ndarray:
    return rng.integers(0, span, size, dtype=np.int64)
