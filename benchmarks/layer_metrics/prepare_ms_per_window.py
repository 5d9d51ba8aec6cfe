"""Device time of the ops under scope ``phase:prepare`` (the window's start:
arrivals taken from the links) per traced window, in ms."""


def read(trace, counters, spans):
    phase_s = counters.get("phase_s")
    if not phase_s or not counters["windows"]:
        return None
    return 1e3 * phase_s["prepare"] / counters["windows"]
