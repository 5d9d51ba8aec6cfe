"""Device time of the K_TCP_TIMER handler pass (scope ``phase:h_timer``,
whatever runs inside it: the deadline check, an RTO's rewind, the flush that
resends) per iteration of the round loop, in ms: the ``h_timer`` sub-row of
the roll-up's ``handlers``, in ``handlers_ms_per_round``'s form. A row of
the roll-up exists only for a scope that ran an op in the traced stretch, and
the pass is guarded: where a TCP program (its ``h_deliver`` or ``h_txr`` row
is there) ran no op under ``h_timer`` — no timer was due in the stretch, as
in ``tor1k.seeds8``'s windows 20-25, whose first deadlines fall at 1.1 s —
the pass cost 0. Nothing to read without a roll-up, or from a program with
no TCP pass."""

TCP_ROWS = ("h_deliver", "h_txr")


def read(trace, counters, spans):
    phase_s = counters.get("phase_s")
    if not phase_s or not counters["rounds"]:
        return None
    if "h_timer" not in phase_s:
        return 0.0 if any(k in phase_s for k in TCP_ROWS) else None
    return 1e3 * phase_s["h_timer"] / counters["rounds"]
