"""Set-up: process start to the first timed submit (imports, operator
and right-hand sides, service, compiles or cache reads, warm-up round)."""


def read(run):
    return run.setup_s
