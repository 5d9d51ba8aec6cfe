"""The share of the traced window in which the device was idle inside an
execution of the window program (the idle between two executions is the
chunk runner's). Nothing to read where the trace has no module line."""


def read(trace, counters, spans):
    idle_ns = counters.get("exec_idle_ns")
    if idle_ns is None or not trace.window_ns:
        return None
    return 100.0 * idle_ns / trace.window_ns
