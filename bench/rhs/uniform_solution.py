"""A chosen solution: ``x ~ U[lo, hi]`` volts per unknown, ``b = A x``."""

import numpy as np


def draw(rng: np.random.Generator, a: np.ndarray, spec: dict, count: int) -> np.ndarray:
    x = rng.uniform(spec["lo"], spec["hi"], size=(count, a.shape[0]))
    return x @ a.T
