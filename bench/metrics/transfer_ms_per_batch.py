"""Host time of the host-to-device copies of operators and states per
micro-batch: the growth of the service's ``core.transfer`` span over
the traced window, over the growth of ``serve.dispatch``'s count, in
ms.  The span times the host's ``device_put`` / ``jnp.asarray`` call
and adds no ``block_until_ready``.

On a TPU v5e the call does not wait for the copy: for the float64 DC
operator (1 GiB per micro-batch in ``hpcg27.dc``, 1.56 GiB in
``poisson5.dc``) it returns in under 2 ms, and the runtime then
relayouts the array on a worker thread (``Transpose::Execute`` in the
trace, about 0.75 s and 1.2 s) before the copy lands and the DC solve
can start.  That time is not in this metric: the chip waits for it
while the main thread is in its next span (``serve.harvest`` when
nothing else is left to build)."""

from bench.metrics.netlist_ms_per_batch import span_ms_per_batch


def read(run):
    return span_ms_per_batch(run, "core.transfer")
