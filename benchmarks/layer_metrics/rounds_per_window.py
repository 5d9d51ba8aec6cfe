"""Iterations of the round loop per window in the traced chunks. A fleet's
loop is one ``while`` over all lanes and runs each window to that window's
slowest lane: per window the maximum over lanes, summed. Exact for a seed."""


def read(trace, counters, spans):
    return counters["rounds"] / counters["windows"] if counters["windows"] else None
