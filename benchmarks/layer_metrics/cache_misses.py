"""Programs that set-up compiled and wrote to the persistent compilation
cache (what jax counts as a miss: a compile long enough to keep). 0 once a
checkout's first run has filled the cache, and always 0 where the cache is
pinned read-only: there the compile shows in the compile seconds alone."""


def read(trace, counters, spans):
    return counters["persistent_cache_misses"]
