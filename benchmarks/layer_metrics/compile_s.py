"""Seconds jax spent tracing, lowering and compiling (or loading from the
persistent cache) during set-up."""


def read(trace, counters, spans):
    return counters["compile_seconds"]
