"""Packets sent ÷ outbox rows the window ends' route lookups read, in %, over
the traced stretch (summed over a fleet's lanes): a window end that runs
looks up every row of the outbox, ``outbox_cap × hosts`` a lane, filled or
not, and on a network past ``core/engine.MAX_VERTEX_RUNS`` /
``MAX_DENSE_VERTICES`` each lookup is an index of its own. What a lookup
made after the rows are compacted would be sized by. From the ``pkts_sent``
and ``route_rows`` totals on the program's chunk-log rows, through
``active_host_share.traced_stretch``; exact for a seed. Nothing to read from
rows without either total (a program that does not count its lookups), where
no window end ran in the stretch, nor where ``active_host_share`` has
nothing."""

from benchmarks.layer_metrics.active_host_share import traced_stretch

FIELDS = ("pkts_sent", "route_rows")


def read(trace, counters, spans):
    stretch = traced_stretch(counters)
    if stretch is None or any(k not in r for r in stretch for k in FIELDS):
        return None
    first, after = stretch
    rows = after["route_rows"] - first["route_rows"]
    if not rows:
        return None
    return 100.0 * (after["pkts_sent"] - first["pkts_sent"]) / rows
