"""Events committed ÷ rounds run over the traced stretch (a lane's own rounds,
summed over a fleet's lanes), from the totals on the program's chunk-log
rows: how many of a round's host columns the passes do work in. Nothing to
read where ``active_host_share`` has nothing."""

from benchmarks.layer_metrics.active_host_share import traced_stretch


def read(trace, counters, spans):
    stretch = traced_stretch(counters)
    if stretch is None:
        return None
    first, after = stretch
    rounds = after["rounds"] - first["rounds"]
    return (after["events"] - first["events"]) / rounds if rounds else None
