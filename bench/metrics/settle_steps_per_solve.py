"""Mean forward-Euler steps to settle per delivered ticket (the
service's ``info["settle_steps"]``): a count, so it tells fewer steps
from faster steps."""

import numpy as np


def read(run):
    steps = [t.settle_steps for t in run.tickets
             if t.settle_steps is not None and t.x is not None]
    return float(np.mean(steps)) if steps else None
