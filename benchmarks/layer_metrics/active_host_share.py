"""Hosts with an event eligible at a window's start ÷ hosts, in %, over the
traced stretch (summed over a fleet's lanes): what a compacted handler pass
would be sized by, where the passes run sweep every host. From the program's
chunk log, whose rows carry the running totals of their INPUT state: the
stretch's work is the totals of the row after its last chunk less those of
its first chunk's row. Nothing to read from a program without the log, from
rows without the totals, or where the cycle ends with the traced stretch."""


def traced_stretch(counters):
    """``(first, after)``: the chunk-log row of the traced stretch's first
    chunk and the row of the chunk that follows its last one, or None. The
    log says where the stretch is: a traced run replays it one window at a
    call afterwards (``chunk_turnaround_ms.steady_pairs``); the rows between
    the two are adjacent in the log and consecutive in simulated time."""
    try:
        from shadow1_tpu.telemetry import chunk_log
    except ImportError:
        return None
    chunks, traced = counters.get("chunks") or 0, counters.get("windows", 0)
    if not chunks or traced // chunks < 2:
        return None
    size = traced // chunks
    rows = [r for r in chunk_log().rows() if r.get("first_window") is not None]
    replay = [r for r in rows if r["windows"] == 1]
    if not replay:
        return None
    t_from = min(r["first_window"] for r in replay)
    mine = [r for r in rows if r["engine"] == replay[0]["engine"]
            and r["windows"] == size]
    found = None
    for i in range(len(mine) - chunks):
        run = mine[i:i + chunks + 1]
        if run[0]["first_window"] == t_from and all(
                a["seq"] + 1 == b["seq"]
                and a["first_window"] + size == b["first_window"]
                for a, b in zip(run, run[1:])):
            found = run[0], run[-1]
    if found is None or any(k not in r for r in found for k in
                            ("events", "rounds", "active_hosts", "hosts")):
        return None
    return found


def read(trace, counters, spans):
    stretch = traced_stretch(counters)
    if stretch is None or not stretch[0]["hosts"]:
        return None
    first, after = stretch
    return (100.0 * (after["active_hosts"] - first["active_hosts"])
            / (counters["windows"] * first["hosts"]))
