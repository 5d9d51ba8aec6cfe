"""Host-side phase profiler — Chrome trace-event export.

The reference's heartbeat rows carry wall time next to sim time so the
sim/wall ratio and its phases are derivable from the log (SURVEY §5); the
batched rebuild's phases are coarser — compile, init, run-chunk, drain,
checkpoint — and the question a perf PR actually asks is "where did the
wall clock go between heartbeats?". This profiler answers it with near-zero
overhead: a ``with profiler.span("run-chunk"):`` records one complete
("ph": "X") trace event; ``write(path)`` emits Chrome trace-event JSON
that chrome://tracing and Perfetto (https://ui.perfetto.dev) load directly.

Not a replacement for ``--profile`` (the jax/XLA op-level profiler): this
is the cheap always-on layer above it, one event per phase rather than per
op, safe to leave enabled on production runs.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time

# Canonical phase names (docs/OBSERVABILITY.md) — free-form names are
# allowed, but the wired-in call sites use these.
PH_COMPILE = "compile"
PH_INIT = "init"
PH_RUN_CHUNK = "run-chunk"
PH_DRAIN = "drain"
PH_CHECKPOINT = "checkpoint"
# Device-trace span (the jax.profiler capture window — see device_trace).
PH_DEVICE_TRACE = "device-trace"


class PhaseProfiler:
    """Collects complete-span trace events; thread-safe, append-only."""

    def __init__(self, process_name: str = "shadow1_tpu"):
        self.t0 = time.perf_counter()
        self.process_name = process_name
        self.events: list[dict] = []
        self._lock = threading.Lock()

    def _now_us(self) -> float:
        return (time.perf_counter() - self.t0) * 1e6

    @contextlib.contextmanager
    def span(self, name: str, **args):
        """Time a phase: ``with prof.span("run-chunk", windows=128): ...``"""
        t_start = self._now_us()
        try:
            yield self
        finally:
            t_end = self._now_us()
            ev = {
                "name": name,
                "ph": "X",
                "ts": round(t_start, 1),
                "dur": round(t_end - t_start, 1),
                "pid": os.getpid(),
                "tid": threading.get_ident() & 0xFFFFFFFF,
            }
            if args:
                ev["args"] = args
            with self._lock:
                self.events.append(ev)

    def instant(self, name: str, **args) -> None:
        """Mark a point in time (``"ph": "i"`` instant event)."""
        ev = {
            "name": name,
            "ph": "i",
            "s": "p",  # process-scoped instant
            "ts": round(self._now_us(), 1),
            "pid": os.getpid(),
            "tid": threading.get_ident() & 0xFFFFFFFF,
        }
        if args:
            ev["args"] = args
        with self._lock:
            self.events.append(ev)

    def chrome_trace(self) -> dict:
        """The Chrome trace-event JSON object (dict form)."""
        meta = [{
            "name": "process_name",
            "ph": "M",
            "pid": os.getpid(),
            "tid": 0,
            "args": {"name": self.process_name},
        }]
        with self._lock:
            events = meta + list(self.events)
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def write(self, path: str) -> None:
        """Write the trace JSON (atomic: tmp + rename, like ckpt saves)."""
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.chrome_trace(), f)
        os.replace(tmp, path)

    def span_names(self) -> list[str]:
        with self._lock:
            return [e["name"] for e in self.events if e.get("ph") == "X"]


def maybe_span(profiler: PhaseProfiler | None, name: str, **args):
    """``profiler.span(...)`` or a nullcontext — call sites stay branchless."""
    if profiler is None:
        return contextlib.nullcontext()
    return profiler.span(name, **args)


class CompileMeter:
    """What jax spent building programs in this process, from jax's own
    monitoring events: trace + lowering + backend compile seconds (a
    persistent-cache hit counts its retrieval time there) and the
    persistent cache's hit/miss tally. Lets a result row report compile
    apart from run whether the run was one device execution or many
    chunks, and say whether the compile was warm."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def finish(self) -> dict:
        """Stop listening (jax's listener lists are process-global) and
        return the tally."""
        import jax

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)
        return {"seconds": round(self.seconds, 3),
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


@contextlib.contextmanager
def device_trace(log_dir: str, profiler: PhaseProfiler | None = None,
                 perfetto: bool = True):
    """The op-level zoom under the host-side phase spans: a ``jax.profiler``
    device trace scoped over the with-body, written to ``log_dir``.

    The engine's window program is annotated with
    ``jax.named_scope("phase:...")`` spans (core/engine.window_phases:
    prepare / rounds (pop, h_<kind>) / route / exchange / deliver / telem
    — plus ``phase:tcp_flush`` inside the TCP send path), so the captured
    trace shows exactly which window phase each device op belongs to.
    With ``perfetto=True`` jax also writes a ``*.perfetto-trace`` file
    under ``log_dir/plugins/profile/<run>/`` that https://ui.perfetto.dev
    loads directly (the TensorBoard profile plugin reads the same
    directory). A ``device-trace`` host span marks the capture window in
    the PhaseProfiler's own Chrome trace so the two zoom levels line up.

    Degrades gracefully: if the installed jax cannot start a profiler
    session (no profiler support, or a session already active), the body
    still runs and a warning names the reason — attribution tools must
    never fail a run over a missing trace backend."""
    import jax

    started = False
    try:
        try:
            jax.profiler.start_trace(log_dir,
                                     create_perfetto_trace=perfetto)
            started = True
        except Exception as e:  # profiler backend unavailable — not fatal
            import warnings

            warnings.warn(f"jax device trace unavailable ({e}); phases "
                          "still carry jax.named_scope annotations but no "
                          "device trace was captured")
        with maybe_span(profiler, PH_DEVICE_TRACE, log_dir=log_dir):
            yield
    finally:
        if started:
            jax.profiler.stop_trace()
