"""The share of the traced window in which no operation ran on the device."""


def read(trace, counters, spans):
    return 100.0 * trace.idle_share
