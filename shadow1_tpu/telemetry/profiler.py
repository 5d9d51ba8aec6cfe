"""Host-side phase profiler — one set of spans, two carriers.

The reference's heartbeat rows carry wall time next to sim time so the
sim/wall ratio and its phases are derivable from the log (SURVEY §5); the
batched rebuild's phases are coarser — compile, init, run-chunk (⊃ dispatch,
sync), commit, on-chunk (⊃ drain, checkpoint), retune — and the question a
perf PR actually asks is "where did the wall clock go between heartbeats?".

Every ``maybe_span(profiler, name)`` call site of the program is carried two
ways:

* as a ``jax.profiler.TraceAnnotation("shadow1:" + name)``, always — so any
  ``jax.profiler`` capture (``--profile DIR``, a benchmark's traced run)
  holds the program's spans on the *device trace's clock*, beside the device
  ops, whether or not a PhaseProfiler is attached. With no profiler session
  open an annotation is a flag check;
* as one complete (``"ph": "X"``) Chrome trace event of the attached
  ``PhaseProfiler`` (``--trace PATH``), on a ``time.perf_counter`` clock of
  its own: ``write(path)`` emits JSON that chrome://tracing and Perfetto
  (https://ui.perfetto.dev) load directly.

Spans of one chunk share the arguments ``done`` (the chunk's first window)
and ``windows``; a span's parent is the span that contains it on its thread.
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
PH_DISPATCH = "dispatch"    # engine.run / guard.run_guarded returning
PH_SYNC = "sync"            # block_until_ready, only under a PhaseProfiler
PH_COMMIT = "commit"        # txn.OverflowGuard.commit
PH_ON_CHUNK = "on-chunk"    # the chunk-boundary hook (heartbeat, snapshot)
PH_RETUNE = "retune"        # between-chunk cap adaptation
PH_DRAIN = "drain"
PH_CHECKPOINT = "checkpoint"
# Device-trace span (the jax.profiler capture window — see device_trace).
PH_DEVICE_TRACE = "device-trace"
# Every span of the program is a TraceAnnotation under this prefix.
ANNOTATION_PREFIX = "shadow1:"


def annotation(name: str, **args):
    """The span ``name`` on the profiler's clock alone."""
    import jax

    return jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name, **args)


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
            with annotation(name, **args):
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
    """``profiler.span(...)``, or the bare annotation where no PhaseProfiler
    is attached — call sites stay branchless, and every span is in any
    ``jax.profiler`` capture either way."""
    if profiler is None:
        return annotation(name, **args)
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
                 engine=None, st=None):
    """A ``jax.profiler`` capture scoped over the with-body, written to
    ``log_dir`` (the TensorBoard profile plugin reads that directory), with
    the program's own spans in it (``shadow1:<name>``, module docstring) and
    a ``device-trace`` span marking the capture window.

    The TPU trace does **not** carry the window program's
    ``jax.named_scope("phase:...")`` scopes: it names a device op by its HLO
    text. With ``engine`` (an ``Engine`` or ``FleetEngine``; ``st`` gives
    the state's shapes, default the initial state's) the phase of every
    captured op is joined on exit from the compiled program's text
    (telemetry/phases.py) and written to ``log_dir/phases.json``: device
    seconds by phase path, the fixed roll-up, busy time, ``unknown_ops``
    (0 when the captured program is ``engine``'s). That costs one more
    lowering and compile (a persistent-cache load where the cache holds the
    program), after the body, off its clock, and only where the capture
    holds device ops.

    Degrades gracefully: if the installed jax cannot start a profiler
    session (no profiler support, or a session already active), the body
    still runs and a warning names the reason — attribution tools must
    never fail a run over a missing trace backend, nor over a join that
    fails after it (a warning, no ``phases.json``). A capture with no
    device op (the CPU backend) writes no ``phases.json``."""
    import jax

    started = False
    try:
        try:
            jax.profiler.start_trace(log_dir)
            started = True
        except Exception as e:  # profiler backend unavailable — not fatal
            import warnings

            warnings.warn(f"jax device trace unavailable ({e}); the body "
                          "runs untraced")
        with maybe_span(profiler, PH_DEVICE_TRACE, log_dir=log_dir):
            yield
    finally:
        if started:
            jax.profiler.stop_trace()
    if started and engine is not None:
        from shadow1_tpu.telemetry import phases

        try:
            table = phases.attribute_capture(
                log_dir, lambda: engine.hlo_text(st))
        except Exception as e:  # the run is done: keep it and its capture
            import warnings

            warnings.warn(f"no phases.json: the join of the capture with "
                          f"the compiled program failed ({e!r})")
            table = None
        if table is not None:
            path = os.path.join(log_dir, "phases.json")
            with open(path + ".tmp", "w") as f:
                json.dump(table, f)
            os.replace(path + ".tmp", path)
