"""Device busy time per iteration of the round loop, in the traced chunks."""


def read(trace, counters, spans):
    return trace.busy_ns / 1e6 / counters["rounds"] if counters["rounds"] else None
