"""Device time of the window-end merge (scopes ``phase:route``,
``phase:exchange``, ``phase:deliver``) per traced window, in ms."""


def read(trace, counters, spans):
    phase_s = counters.get("phase_s")
    if not phase_s or not counters["windows"]:
        return None
    return 1e3 * phase_s["deliver"] / counters["windows"]
