"""Share of the HBM roofline the settle sweep reaches.

The least time of the Euler steps the sweep took is their least bytes
(``bench.roofline.ell_step_bytes``: the operator's nonzero slots with a
4-byte index and a weight at the sweep dtype, plus the state and
constant vectors) over the chip's peak HBM bandwidth.  Each micro-batch
integrates all its slots for as many steps as its slowest system takes.
The shape (states, nonzeros per row) is that of each system's reference
circuit.  The bytes are those of a fused ELL step, the least any sweep
of the circuit needs; the served path today sweeps the dense operator
(one ``dense_step`` launch per step), which reads far more, so the
share is low until the served path sweeps a sparse operator.

That least time is divided by the device time of the sweep's window in
each drain: every operation from the start of the first sweep kernel
program to the end of the last, gathers and reductions included.
"""

import numpy as np

from bench import reference, roofline
from bench import trace as tr

# the programs that launch the sweep kernels (dense_step, dense_sweep,
# ell_step), by the names of their jitted wrappers in repro.kernels
SWEEP_PROGRAMS = ("transient_step_batched_pallas", "transient_sweep_pallas",
                  "ell_sweep_pallas", "ell_step_pallas")


def _is_sweep(name: str) -> bool:
    return any(k in name for k in SWEEP_PROGRAMS)


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    device_ns = 0
    for dev in run.devices:
        programs = run.trace.modules.get(dev, [])
        for d_lo, d_hi in tr.spans(run.trace, "bench.drain"):
            if d_lo < lo or d_hi > hi:
                continue
            sweep = [(s, e) for n, s, e in programs
                     if _is_sweep(n) and d_lo <= s < d_hi]
            if not sweep:
                continue
            w_lo, w_hi = min(s for s, _ in sweep), max(e for _, e in sweep)
            device_ns += tr.busy_ns(run.trace, dev, w_lo, w_hi)
    if device_ns <= 0:
        return None
    hw = reference.Circuit(**run.config["circuit"])
    slots = int(run.cell.traffic["batch_slots"])
    dtype = run.cell.traffic["submit"].get("sweep_dtype", "float32")
    peak = roofline.peaks(run.device_kind)["hbm_bytes_per_s"]
    shape = {}                      # system index -> (states, nonzeros per row)
    steps: dict[tuple, tuple] = {}  # micro-batch -> (system, slowest steps)
    for t in run.tickets:
        if t.settle_steps is None:
            continue
        if t.system.index not in shape:
            m, _ = reference.circuit(t.system.a, t.b, hw)
            shape[t.system.index] = (m.shape[0], int(np.diff(m.indptr).max()))
        _, most = steps.get(t.micro_batch, (None, 0))
        steps[t.micro_batch] = (t.system.index, max(most, t.settle_steps))
    least = sum(roofline.sweep_least_seconds(s, slots, *shape[k], dtype, peak)
                for k, s in steps.values())
    return 100.0 * least / (device_ns / 1e9) if least > 0 else None
