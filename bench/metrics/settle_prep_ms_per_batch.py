"""Host time of the settle path's preparation per micro-batch: the
growth of the service's ``core.settle_prep`` span (the host work
between the settle DC solve and the first sweep chunk: the step size,
the dt fold and float32 cast of the operator, its padding and upload)
over the traced window, over the growth of ``serve.dispatch``'s count,
in ms.  A program without the span reads nothing."""

from bench.metrics.netlist_ms_per_batch import span_ms_per_batch


def read(run):
    return span_ms_per_batch(run, "core.settle_prep")
