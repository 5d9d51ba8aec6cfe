"""On-device per-window telemetry ring.

The reference's Tracker emits one counter row per heartbeat interval
(src/main/host/tracker.c, SURVEY §5); our chunked heartbeat only sees the
chunk-AVERAGED deltas, so a one-window occupancy spike — the exact datum
the rung-cap sizing debate needed (docs/R6_NOTES.md) — vanishes into the
mean. The ring fixes that without reintroducing mid-window host syncs:

* a device-resident ``[W, F]`` i64 buffer rides in ``SimState.telem``;
* at the end of every conservative window the engine writes one row —
  per-window DELTAS of the core counters plus the occupancy gauges
  (``registry.RING_FIELDS`` order) — at slot ``window % W``, entirely
  inside the jitted window loop (one dynamic_update_slice, no sync);
* at chunk boundaries the host drains the rows that accumulated since the
  last drain (``drain_ring``) and emits them as JSONL ``type: "ring"``
  records.

The ring therefore holds the last W windows; if a chunk spans more than W
windows the overwritten rows are gone — ``drain_ring`` reports the gap
explicitly (a ``ring_gap`` record) rather than pretending continuity.

Under sharding each shard computes its local row and the per-window
reduction (``telem_reduce`` in shard/engine.py) psums the counter columns
and max-reduces the fill gauge, so every shard carries the identical,
globally-correct ring — replicated like ``win_start``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from shadow1_tpu.consts import SEC
from shadow1_tpu.telemetry.registry import (
    REC_RING,
    REC_RING_GAP,
    RING_COUNTERS,
    RING_FIELDS,
)


class TelemetryRing(NamedTuple):
    """The device-resident ring: one i64 row per window, RING_FIELDS order."""

    buf: jnp.ndarray  # i64 [W, len(RING_FIELDS)]


def ring_init(n_windows: int) -> TelemetryRing | None:
    """A W-row ring, or None when the ring is disabled (W == 0).

    None keeps the SimState pytree leaf count identical to a ring-less
    build, so checkpoints and sharding specs are unaffected unless the
    ring is actually on."""
    if n_windows <= 0:
        return None
    return TelemetryRing(
        buf=jnp.zeros((int(n_windows), len(RING_FIELDS)), jnp.int64)
    )


def ring_record(ring: TelemetryRing, m0, m1, ev_fill,
                telem_reduce=None, digests=None) -> TelemetryRing:
    """Write one per-window row (traced; called at the end of window_step).

    ``m0``/``m1`` are the Metrics before/after the window; counter columns
    store ``m1 - m0``. ``ev_fill`` is the window-end event-slot fill the
    engine already computed for the ``ev_max_fill`` gauge. ``digests`` is
    the per-window state-digest vector (i64 [len(RING_DIGESTS)],
    core/digest.state_digests) or None — the digest columns then hold 0.
    ``telem_reduce(counters, gauges) -> (counters, gauges)`` globalizes the
    row under sharding (psum the counter deltas, elementwise-max the gauge
    vector); identity on a single device. The digest words are per-shard
    PARTIAL SUMS, so they ride the psum'd counter vector and come out as
    the exact single-device digests. ``x2x_max_fill`` is already replicated
    by the exchange's psum trick, so it bypasses the reduce."""
    from shadow1_tpu.telemetry.registry import RING_DIGESTS, RING_WORK

    w = ring.buf.shape[0]
    # The wasted-work columns (RING_WORK) are deltas of running-sum
    # counters like the rest — additive across shards, so they ride the
    # same psum'd vector and come out globally exact.
    counters = jnp.stack(
        [getattr(m1, f) - getattr(m0, f) for f in RING_COUNTERS + RING_WORK]
    )
    if digests is None:
        digests = jnp.zeros(len(RING_DIGESTS), jnp.int64)
    n_ctr = counters.shape[0]
    counters = jnp.concatenate([counters, digests])
    # RING_GAUGES order minus the trailing replicated x2x_max_fill.
    gauges = jnp.stack(
        [ev_fill, m1.ev_max_fill, m1.ob_max_fill, m1.compact_max_fill,
         m1.mq_max_fill, m1.push_stage_max]
    )
    if telem_reduce is not None:
        counters, gauges = telem_reduce(counters, gauges)
    counters, digests = counters[:n_ctr], counters[n_ctr:]
    row = jnp.concatenate(
        [counters, gauges, m1.x2x_max_fill[None], digests]
    ).astype(jnp.int64)
    # Slot = this window's global ordinal (the pre-increment counter).
    slot = (m0.windows % w).astype(jnp.int32)
    return ring._replace(
        buf=jax.lax.dynamic_update_slice(
            ring.buf, row[None, :], (slot, jnp.zeros((), jnp.int32))
        )
    )


def drain_ring(st, window_ns: int, start: int = 0) -> list[dict]:
    """Host-side drain: the ring rows for windows [start, windows_done).

    One device→host fetch per call (chunk boundary, never mid-window).
    Returns JSONL-ready dicts in window order; when more than W windows
    elapsed since ``start`` the overwritten head is reported as one
    ``ring_gap`` record instead of being silently skipped."""
    ring = getattr(st, "telem", None)
    if ring is None:
        return []
    buf = np.asarray(ring.buf)
    w = buf.shape[0]
    done = int(st.metrics.windows)
    lo = max(start, done - w)
    recs: list[dict] = []
    if lo > start:
        recs.append({
            "type": REC_RING_GAP,
            "windows_lost": lo - start,
            "first_window": start,
            "ring_slots": w,
        })
    for win in range(lo, done):
        row = buf[win % w]
        rec = {
            "type": REC_RING,
            "window": win,
            "sim_time_s": round((win + 1) * window_ns / SEC, 9),
        }
        rec.update({f: int(v) for f, v in zip(RING_FIELDS, row)})
        recs.append(rec)
    return recs
