"""Device operations (leaf events of the trace) per iteration of the round
loop."""


def read(trace, counters, spans):
    return trace.n_ops / counters["rounds"] if counters["rounds"] else None
