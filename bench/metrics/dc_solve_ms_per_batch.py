"""Device time of the DC solve program (f32 LU and float64 refinement,
``_dc_solve_refined``) per execution in the traced window; each
execution solves one micro-batch."""

from bench import trace as tr

PROGRAM = "_dc_solve_refined"


def read(run):
    if run.trace is None:
        return None
    ns, count = tr.module_ns(run.trace, PROGRAM, *run.trace_window)
    return ns / 1e6 / count if count else None
