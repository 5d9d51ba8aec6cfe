"""Mean over lanes of the handler passes that had an event of their kind ÷
the handler passes run (loop iterations × handler kinds) over the traced
stretch. Nothing to read where the model has one handler: its pass is not
guarded and not counted."""

import statistics


def read(trace, counters, spans):
    fires, kinds = counters.get("fires_by_lane"), counters.get("handler_kinds", 0)
    if not fires or kinds < 2 or not counters["rounds"]:
        return None
    return 100.0 * statistics.mean(fires) / (counters["rounds"] * kinds)
