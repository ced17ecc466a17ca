"""Device time of one Euler step of the settle sweep: the summed device
time of the sweep's programs (each execution of a program that launches
the sweep kernels, as ``sweep_roofline`` names them) in the traced
window, over the growth of the service's ``settle_steps_swept`` counter
(the steps the device ran, per micro-batch up to its slowest system)
over the same rounds, in ms.  A chunk program's closing residual pass
is charged to its steps.  A program without the counter reads
nothing."""

from bench.metrics.sweep_roofline import SWEEP_PROGRAMS

COUNTER = "settle_steps_swept"


def read(run):
    if run.trace is None or COUNTER not in run.stats_after:
        return None
    steps = run.stats_after[COUNTER] - run.stats_before.get(COUNTER, 0)
    lo, hi = run.trace_window
    device_ns = sum(e - s for dev in run.devices
                    for name, s, e in run.trace.modules.get(dev, [])
                    if lo <= s and e <= hi
                    and any(p in name for p in SWEEP_PROGRAMS))
    if steps <= 0 or device_ns <= 0:
        return None
    return device_ns / 1e6 / steps
