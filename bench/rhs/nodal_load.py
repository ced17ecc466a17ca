"""PETSc KSP ex56's load: the same force ``load`` (one entry per
displacement component) on every free node, times a per-ticket
amplitude ``~ U[lo, hi]``, scaled so that the largest displacement at
amplitude 1 is ``x_max`` volts.  Expects the unknowns interleaved by
node, as ``bench/operators/elastic3d_q1.py`` orders them."""

import numpy as np


def draw(rng: np.random.Generator, a: np.ndarray, spec: dict, count: int) -> np.ndarray:
    load = np.asarray(spec["load"], dtype=np.float64)
    f = np.tile(load, a.shape[0] // load.size)
    f *= spec["x_max"] / np.abs(np.linalg.solve(a, f)).max()
    amp = rng.uniform(spec["lo"], spec["hi"], size=count)
    return amp[:, None] * f[None, :]
