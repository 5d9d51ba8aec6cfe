"""The share of the device's busy time in ops that have no ``phase:`` scope
of their own nor on the control flow that contains them: how much of the
device's time the by-phase numbers do not cover."""


def read(trace, counters, spans):
    phase_s = counters.get("phase_s")
    if not phase_s or not counters.get("phase_busy_s"):
        return None
    return 100.0 * phase_s["unattributed"] / counters["phase_busy_s"]
