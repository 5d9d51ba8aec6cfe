"""Device time of the round loop OUTSIDE its pops and handler passes (the
roll-up's ``rounds_other`` row: scope ``phase:rounds`` with no ``phase:pop``
and no ``phase:h_<kind>`` under it) per traced window, in ms. With a
``compact_cap`` in force that is the column mover — the bucket's columns out
of the state and back, scopes ``phase:compact_gather`` and
``phase:compact_scatter`` (``core/compact.py``), once a trip — plus the
loops' own bookkeeping; with none, the bookkeeping alone."""


def read(trace, counters, spans):
    phase_s = counters.get("phase_s")
    if not phase_s or not counters["windows"]:
        return None
    return 1e3 * phase_s["rounds_other"] / counters["windows"]
