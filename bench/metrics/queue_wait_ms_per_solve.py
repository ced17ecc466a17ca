"""Queue wait per ticket: the growth of the service's ``queue_wait_s``
counter (each dispatched ticket's time from ``submit`` to the start of
the first ``serve.dispatch`` that carried it) over the traced window,
per ticket of the traced rounds, in ms.  A program without the counter
reads nothing."""


def read(run):
    if "queue_wait_s" not in run.stats_after or not run.tickets:
        return None
    waited = run.stats_after["queue_wait_s"] - run.stats_before["queue_wait_s"]
    return waited * 1e3 / len(run.tickets)
