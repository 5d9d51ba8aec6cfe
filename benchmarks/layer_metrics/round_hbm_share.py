"""The share of a round's device time that one read and one write of the
live state would take at the chip's HBM bandwidth (bound: HBM). Low means
many passes over the state, or a round bound by kernel launches."""


def state_pass_bytes(counters):
    """One read and one write of every leaf of the simulation state."""
    return 2 * counters["state_bytes"]


def read(trace, counters, spans):
    if not counters["rounds"] or not counters["hbm_bytes_per_s"]:
        return None
    least_s = state_pass_bytes(counters) / counters["hbm_bytes_per_s"]
    return 100.0 * least_s / (trace.busy_ns / 1e9 / counters["rounds"])
