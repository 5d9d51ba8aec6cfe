"""Median of the program's ``dispatch`` spans (the engine's run call
returning, once a chunk), in ms. Nothing to read in a capture without the
program's spans."""

import statistics


def read(trace, counters, spans):
    dispatch_ns = counters.get("dispatch_ns")
    return statistics.median(dispatch_ns) / 1e6 if dispatch_ns else None
