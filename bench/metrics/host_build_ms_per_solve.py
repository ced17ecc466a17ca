"""Host build and dispatch per delivered ticket: the growth of the
service's ``host_build_s`` span over the traced window (netlist
transform, dense assembly, transfer and dispatch), in ms per ticket."""


def read(run):
    if not run.delivered:
        return None
    spent = run.stats_after["host_build_s"] - run.stats_before["host_build_s"]
    return spent * 1e3 / run.delivered
