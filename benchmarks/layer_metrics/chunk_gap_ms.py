"""Median idle time on the device between one chunk's execution and the
next's, in ms. Nothing to read where the trace holds fewer than two."""

import statistics


def read(trace, counters, spans):
    gaps = trace.execution_gaps_ns
    return statistics.median(gaps) / 1e6 if gaps else None
