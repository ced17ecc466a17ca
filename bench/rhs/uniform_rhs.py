"""A random right-hand side: ``b ~ U[0, 1)`` per unknown, scaled to
``scale`` amperes."""

import numpy as np


def draw(rng: np.random.Generator, a: np.ndarray, spec: dict, count: int) -> np.ndarray:
    return rng.uniform(0.0, 1.0, size=(count, a.shape[0])) * spec["scale"]
