"""What jax spent building programs, from jax's own monitoring events."""

from __future__ import annotations


class CompileMeter:
    """Seconds of tracing, lowering and backend compile (a persistent-cache
    hit counts its retrieval there), backend compiles, and the persistent
    cache's hits and misses, since construction. ``snapshot()`` lets a
    caller take the difference over a stretch of the run."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.backend_compiles = 0
        self.persistent_hits = 0
        self.persistent_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += secs
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compiles += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.persistent_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.persistent_misses += 1

    def close(self) -> None:
        """Stop listening: jax's listener lists are process-global."""
        import jax

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def snapshot(self) -> dict:
        return {"seconds": self.seconds,
                "backend_compiles": self.backend_compiles,
                "persistent_hits": self.persistent_hits,
                "persistent_misses": self.persistent_misses}
