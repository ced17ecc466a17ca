"""Share of the time inside the program's ``core.settle`` spans (the
euler settle of a micro-batch: reassembly, DC solve, sweep) in which no
operation ran on the device, read from the trace on its own clock and
averaged over the chips the cell uses.  A program without the span
reads nothing."""

import numpy as np

from bench import trace as tr

SPAN = "core.settle"


def read(run):
    if run.trace is None:
        return None
    settle = tr.merged(tr.spans(run.trace, SPAN), *run.trace_window)
    total = sum(e - s for s, e in settle)
    if total <= 0:
        return None
    busy = np.mean([sum(tr.busy_ns(run.trace, d, s, e) for s, e in settle)
                    for d in run.devices])
    return 100.0 * (1.0 - busy / total)
