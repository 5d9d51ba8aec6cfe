"""Device time of the ops under ``phase:rounds`` / ``phase:pop`` per
iteration of the round loop, in ms."""


def read(trace, counters, spans):
    phase_s = counters.get("phase_s")
    if not phase_s or not counters["rounds"]:
        return None
    return 1e3 * phase_s["pop"] / counters["rounds"]
