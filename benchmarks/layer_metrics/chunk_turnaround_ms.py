"""Median time the host takes between a chunk's result being ready and the
next chunk's run call, in ms, from the program's chunk log (host clock, kept
in every run): with ``dispatch_ms_per_chunk`` the chunk boundary from the
host's side, in every cell. Nothing to read from a program without the log,
or from fewer than two chunks that follow one another."""

import statistics


def steady_pairs(rows, windows, traced_windows=0):
    """``(before, after)`` for every two rows of ``rows`` that are adjacent
    in the log (``seq``) and consecutive in simulated time, among the rows
    of ``windows`` windows of the engine that has most of those: the chunks
    of the measured cycles. A warm-up's row, a replay's and a cycle's first
    chunk continue no chunk of their size.

    A traced run starts the profiler before its traced stretch and stops it
    after, between two chunks: those two turnarounds are the profiler's, and
    are left out. The log says where the stretch is: the run replays it one
    window at a call afterwards, ``traced_windows`` rows of one window."""
    rows = [r for r in rows if r.get("first_window") is not None]
    count = {}
    for r in rows:
        if r["windows"] == windows:
            count[r["engine"]] = count.get(r["engine"], 0) + 1
    most = max(count, key=count.get, default=None)
    rows = [r for r in rows if r["engine"] == most]
    replay = [r["first_window"] for r in rows if r["windows"] == 1 != windows]
    t_from = min(replay, default=None)
    t_to = None if t_from is None else t_from + traced_windows
    rows = [r for r in rows if r["windows"] == windows]
    return [(a, b) for a, b in zip(rows, rows[1:])
            if a["seq"] + 1 == b["seq"]
            and a["first_window"] + a["windows"] == b["first_window"]
            and b["first_window"] not in (t_from, t_to)]


def chunk_pairs(counters):
    """The chunk log's pairs for the chunk size the counters give, or None
    where the program keeps no chunk log."""
    try:
        from shadow1_tpu.telemetry import chunk_log
    except ImportError:
        return None
    chunks = counters.get("chunks") or 0
    if not chunks:
        return None
    traced = counters.get("windows", 0)
    return steady_pairs(chunk_log().rows(), traced // chunks, traced)


def read(trace, counters, spans):
    pairs = chunk_pairs(counters)
    if not pairs:
        return None
    return statistics.median(b["enter_ns"] - a["ready_ns"] for a, b in pairs) / 1e6
