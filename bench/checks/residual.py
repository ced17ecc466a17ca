"""Largest float64 relative residual ``||A x - b|| / ||b||`` of any
answer of the window; a ticket the service failed counts as infinite."""


def read(run, seed):
    return max((t.residual for t in run.window_tickets), default=float("inf"))
