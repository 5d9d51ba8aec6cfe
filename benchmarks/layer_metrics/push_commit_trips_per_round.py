"""Trips of the rounds' push commits ÷ rounds run over the traced stretch (a
lane's own, summed over a fleet's lanes), from the ``push_commit_trips`` and
``rounds`` totals on the program's chunk-log rows: a round that staged no
event makes no trip, one whose busiest host staged more than the commit
writes a trip (``core/events.PUSH_RB``) makes several, and each trip sweeps
the event planes once. Exact for a seed. Nothing to read from rows without
the total (a program that writes each push into the planes where it is
made), nor where ``active_host_share`` has nothing."""

from benchmarks.layer_metrics.active_host_share import traced_stretch


def read(trace, counters, spans):
    stretch = traced_stretch(counters)
    if stretch is None or any("push_commit_trips" not in r for r in stretch):
        return None
    first, after = stretch
    rounds = after["rounds"] - first["rounds"]
    if not rounds:
        return None
    return (after["push_commit_trips"] - first["push_commit_trips"]) / rounds
