"""Tickets delivered within the accuracy contract per second of the
window (first submit to the return of the last round's drain)."""


def read(run):
    return run.delivered / run.window_s
