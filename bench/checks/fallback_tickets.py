"""Answers the service delivered through its digital fallback instead of
the analog path.  Such an answer is right but did not come from the
timed path, so a DC solve that is broken underneath shows here."""


def read(run, seed):
    return float(sum(t.path == "fallback" for t in run.window_tickets))
