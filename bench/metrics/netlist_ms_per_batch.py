"""Host time of the netlist build per micro-batch: the growth of the
service's ``core.build_nets`` span (the Sec-IV transform and its sync,
the numpy component extraction, the ``Netlist`` objects) over the
traced window, over the growth of ``serve.dispatch``'s count (one per
dispatched micro-batch), in ms.  A program without span totals in its
stats reads nothing."""


def span_ms_per_batch(run, name):
    """ms of span ``name`` per dispatched micro-batch between the two
    stats; None where the program keeps no span totals or never
    opened ``name``."""
    before = run.stats_before.get("spans")
    after = run.stats_after.get("spans")
    if before is None or after is None or name not in after:
        return None

    def grown(key, field):
        return (after.get(key, {}).get(field, 0)
                - before.get(key, {}).get(field, 0))

    batches = grown("serve.dispatch", "count")
    return grown(name, "s") * 1e3 / batches if batches > 0 else None


def read(run):
    return span_ms_per_batch(run, "core.build_nets")
