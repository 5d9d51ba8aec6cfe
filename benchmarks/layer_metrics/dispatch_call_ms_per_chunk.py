"""Median of the ``call`` sub-span of ``dispatch`` (the jitted call
returning), in ms, over the chunks ``chunk_turnaround_ms`` reads:
``dispatch_ms_per_chunk`` less this is ``args``, what the run call is handed
being made. Nothing to read from a program without the chunk log."""

import statistics

from benchmarks.layer_metrics.chunk_turnaround_ms import chunk_pairs


def read(trace, counters, spans):
    pairs = chunk_pairs(counters)
    if not pairs:
        return None
    rows = {r["seq"]: r for pair in pairs for r in pair}
    return statistics.median(r.get("call_ns", 0) for r in rows.values()) / 1e6
