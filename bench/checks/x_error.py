"""Largest relative error, in the max norm, of any answer of the window
against the float64 reference solve of its own system."""

import numpy as np

from bench import reference


def read(run, seed):
    answered = [t for t in run.window_tickets if t.x is not None]
    if not answered:
        return float("inf")
    worst = 0.0
    for system in run.systems:
        mine = [t for t in answered if t.system is system]
        if not mine:
            continue
        xs = np.stack([t.x for t in mine])
        ref = reference.solve_many(system.a, np.stack([t.b for t in mine]))
        err = np.max(np.abs(xs - ref), axis=1) / np.max(np.abs(ref), axis=1)
        worst = max(worst, float(np.max(err)))
    return worst
