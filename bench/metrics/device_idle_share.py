"""Share of the traced window in which no operation ran on the device,
averaged over the chips the cell uses."""

import numpy as np

from bench import trace as tr


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    busy = np.mean([tr.busy_ns(run.trace, d, lo, hi) for d in run.devices])
    if busy <= 0:
        return None
    return 100.0 * (1.0 - busy / (hi - lo))
