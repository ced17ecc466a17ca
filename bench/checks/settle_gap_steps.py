"""Largest gap in settle steps between the service's sweep and the
float64 sweep of the reference circuit (``bench.reference``), over a
sample of the window's tickets drawn from the seed.  A ticket without a
settle result counts as the whole sweep budget.  Cells whose traffic
does not settle have nothing to read here."""

import numpy as np

from bench import reference


def read(run, seed):
    if not run.cell.traffic.get("submit", {}).get("compute_settling"):
        return None
    hw = reference.Circuit(**run.config["circuit"])
    tickets = run.window_tickets
    if not tickets:
        return float(hw.max_steps)
    rng = np.random.default_rng([seed, 2])
    size = min(len(tickets), int(run.cell.traffic["settle_sample"]))
    sample = [tickets[k] for k in np.sort(rng.choice(len(tickets), size=size,
                                                     replace=False))]
    ref = reference.settle_steps([t.system.a for t in sample],
                                 [t.b for t in sample], hw)
    gaps = [abs(t.settle_steps - s) if t.settle_steps is not None and t.stable
            else hw.max_steps for t, s in zip(sample, ref)]
    return float(max(gaps))
