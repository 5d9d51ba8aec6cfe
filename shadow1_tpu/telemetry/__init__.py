"""Telemetry plane: on-device metrics ring, phase profiler, metrics registry.

Four coordinated observability pieces (see docs/OBSERVABILITY.md):

* ``telemetry.ring`` — per-window counter deltas recorded on device inside
  the jitted window loop, drained at chunk boundaries (the true time series
  the chunk-averaged heartbeat cannot provide);
* ``telemetry.profiler`` — host-side phase spans: ``shadow1:`` annotations
  in any ``jax.profiler`` capture, Chrome trace-event JSON
  (Perfetto-viewable) under a PhaseProfiler, and one row a chunk in the
  always-on ``chunk_log()`` (readiness, host health, the ``stall`` line);
* ``telemetry.phases`` — the window phase of every traced device op, joined
  from the compiled program's text (the TPU trace does not carry scopes);
* ``telemetry.registry`` — the one named-counter namespace shared by the
  tpu, sharded and cpu engines, with Prometheus text exposition and the
  JSONL record schema.

``registry`` is jax-free and safe for tools; ``ring`` pulls in jax — import
it lazily from host-only paths.
"""

from shadow1_tpu.telemetry.profiler import (  # noqa: F401
    ANNOTATION_PREFIX,
    PH_CHECKPOINT,
    PH_COMMIT,
    PH_COMPILE,
    PH_DEVICE_TRACE,
    PH_DISPATCH,
    PH_DRAIN,
    PH_INIT,
    PH_ON_CHUNK,
    PH_RETUNE,
    PH_RUN_CHUNK,
    PH_SYNC,
    CompileMeter,
    PhaseProfiler,
    chunk_log,
    device_trace,
    maybe_span,
)
from shadow1_tpu.telemetry.registry import (  # noqa: F401
    DROP_FIELDS,
    DROP_SPECS,
    METRIC_SPECS,
    RECORD_TYPES,
    RING_COUNTERS,
    RING_DIGESTS,
    RING_FIELDS,
    RING_GAUGES,
    RING_WORK,
    ExpositionServer,
    normalize,
    to_prometheus,
)
