"""TCP retransmission episodes (fast retransmits + RTOs) per 1,000 packets
sent over the traced stretch, summed over a fleet's lanes: how much of the
stretch's traffic is loss recovery. From the ``tcp_fast_rtx``, ``tcp_rto``
and ``pkts_sent`` totals on the program's chunk-log rows, through
``active_host_share.traced_stretch``; exact for a seed (a PR that moves it
has changed the simulation, not its speed). Nothing to read from rows
without those totals (a program before the log carried them), where no
packet was sent, nor where ``active_host_share`` has nothing."""

from benchmarks.layer_metrics.active_host_share import traced_stretch

FIELDS = ("tcp_fast_rtx", "tcp_rto", "pkts_sent")


def read(trace, counters, spans):
    stretch = traced_stretch(counters)
    if stretch is None or any(k not in r for r in stretch for k in FIELDS):
        return None
    first, after = stretch
    sent = after["pkts_sent"] - first["pkts_sent"]
    if not sent:
        return None
    resent = sum(after[k] - first[k] for k in ("tcp_fast_rtx", "tcp_rto"))
    return 1000.0 * resent / sent
