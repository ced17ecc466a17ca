"""Host time of the dense operator assembly per micro-batch: the growth
of the service's ``core.assemble`` span over the traced window, over
the growth of ``serve.dispatch``'s count, in ms.  Every call counts:
the DC cells assemble once per micro-batch, the euler settle cell
twice (the DC solve's assembly, then the settle path's own)."""

from bench.metrics.netlist_ms_per_batch import span_ms_per_batch


def read(run):
    return span_ms_per_batch(run, "core.assemble")
