"""Trips of the compacted round loop per window and lane over the traced
stretch: a window's rounds run on its active hosts a bucket of
``compact_cap`` columns a trip (``core/compact.py``), so 1.0 says every
traced window's active set fitted one bucket, more that some took several,
less that some had no event. From the ``buckets`` total on the program's
chunk-log rows, through ``active_host_share.traced_stretch``. Nothing to
read from a program that keeps no such total (no cap in force, or a program
before the trips were counted), nor where ``active_host_share`` has nothing."""

from benchmarks.layer_metrics.active_host_share import traced_stretch


def read(trace, counters, spans):
    stretch = traced_stretch(counters)
    lanes = counters.get("lanes") or 0
    if stretch is None or not lanes or not counters.get("windows"):
        return None
    first, after = stretch
    if "buckets" not in first or "buckets" not in after:
        return None
    return (after["buckets"] - first["buckets"]) / (counters["windows"] * lanes)
