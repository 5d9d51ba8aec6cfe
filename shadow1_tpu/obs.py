"""Observability — the heartbeat metrics stream.

The reference's Tracker logs per-host statistics at a configured interval
and its log records carry sim-time + wall-time so the sim/wall ratio is
derivable (src/main/host/tracker.c, SURVEY §5). The batched analogue: run
the window loop in chunks and emit one structured heartbeat per chunk with
the metric deltas — events/sec, packets, retransmits, overflow counters —
without ever synchronizing device→host inside a window.

Layered on top (round 6, docs/OBSERVABILITY.md): when the engine state
carries an on-device telemetry ring (EngineParams.metrics_ring), the
heartbeat also drains the ring's per-window rows at each chunk boundary —
the true per-window time series underneath the chunk averages — and a
telemetry.PhaseProfiler can be attached to time the compile / run-chunk /
drain / checkpoint phases into a Chrome trace.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import numpy as np

from shadow1_tpu.ckpt import run_chunked
from shadow1_tpu.consts import SEC
from shadow1_tpu.telemetry import (
    PH_CHECKPOINT,
    PH_COMPILE,
    PH_DRAIN,
    PH_INIT,
    chunk_log,
    maybe_span,
    normalize,
)


def _metrics_mapping(metrics) -> dict:
    """Engine metrics → plain int dict (Metrics NamedTuple or already a dict
    — alternate engines need not mimic the NamedTuple)."""
    d = metrics if isinstance(metrics, dict) else metrics._asdict()
    return {k: int(v) for k, v in d.items()}


class Heartbeat:
    """Collects per-chunk metric deltas; writes JSON lines to ``stream``.

    Metric dicts are normalized through the telemetry registry, so engines
    whose metrics lack canonical fields (cpu_engine, future models) reuse
    the heartbeat unchanged — missing counters read as 0, never KeyError.
    """

    def __init__(self, engine, stream=None, label: str = "heartbeat",
                 initial_state=None, profiler=None,
                 emit_heartbeat: bool = True, emit_ring: bool = True,
                 guard=None):
        self.engine = engine
        self.stream = stream if stream is not None else sys.stderr
        self.label = label
        self.profiler = profiler
        self.guard = guard  # txn.OverflowGuard — source of the retries block
        self.emit_heartbeat = emit_heartbeat
        self.emit_ring = emit_ring
        self.t_start = time.perf_counter()
        self.t_last = self.t_start
        # Seed the baseline from a resumed state so the first delta covers
        # only this invocation, not the checkpointed history.
        self.last: dict[str, int] = (
            normalize(_metrics_mapping(initial_state.metrics))
            if initial_state is not None else {}
        )
        # First ring window still undrained (resume-aware like ``last``).
        self._ring_next: int = self.last.get("windows", 0)
        # Same cursor for the flow-probe ring (telemetry/probes.py).
        self._probe_next: int = self.last.get("windows", 0)
        # And for the link accumulator (telemetry/links.py) — link records
        # are cumulative snapshots, so the cursor only suppresses re-drains
        # of already-emitted boundaries on resume.
        self._link_next: int = self.last.get("windows", 0)
        self.records: list[dict] = []
        self.ring_records: list[dict] = []
        self.flow_records: list[dict] = []
        self.link_records: list[dict] = []

    def _emit(self, rec: dict) -> None:
        if self.stream:
            print(json.dumps(rec), file=self.stream, flush=True)

    def __call__(self, st, done_windows: int) -> None:
        now = time.perf_counter()
        # The ONE device→host fetch of the chunk (never inside a window).
        with maybe_span(self.profiler, PH_DRAIN):
            m = normalize(_metrics_mapping(st.metrics))
            ring_recs = self._drain_ring(st)
            flow_recs = self._drain_probes(st)
            link_recs = self._drain_links(st)
        delta = {k: v - self.last.get(k, 0) for k, v in m.items()}
        dt = now - self.t_last
        sim_ns = int(st.win_start)  # the true sim clock (resume-aware)
        d_windows = delta.get("windows", 0)
        rec = {
            "type": self.label,
            "sim_time_s": round(sim_ns / SEC, 6),
            "wall_s": round(now - self.t_start, 3),
            "windows": done_windows,
            "events_per_sec": round(delta.get("events", 0) / dt, 1)
            if dt > 0 else None,
            "sim_per_wall": round(
                (getattr(self.engine, "window", 0) * d_windows / SEC) / dt, 4)
            if dt > 0 else None,
            # Occupancy: how many handler rounds the busiest host forced per
            # window this chunk (the per-window fixed-cost multiplier).
            "rounds_per_window": round(delta.get("rounds", 0) / d_windows, 2)
            if d_windows else None,
            "delta": delta,
        }
        # The model's run totals (bitcoin: total_seen / total_tx_rx /
        # total_msg_retries; registry.MODEL_TOTALS), absolutes like ``fill``.
        totals = getattr(self.engine, "model_totals", None)
        if totals is not None:
            rec["model"] = totals(st)
        # Drop accounting: the nine ways an event/packet can be discarded,
        # grouped under one structured block (with chunk deltas) instead of
        # scattered through ``delta`` — the shape heartbeat_report's
        # drop-reason table and alerting consume. Always present: an
        # all-zero block is the explicit "nothing dropped" signal.
        from shadow1_tpu.telemetry.registry import DROP_FIELDS

        drops = {f: delta.pop(f, 0) for f in DROP_FIELDS}
        rec["drops"] = {"total": sum(drops.values()), **drops}
        # Overflow-retry plane (txn.OverflowGuard): host-side counters, so
        # they never appear in engine deltas (normalize injects zeros —
        # dropped here); when the guard has retried, a ``retries`` block
        # carries the cumulative counters plus the live (grown) caps.
        from shadow1_tpu.telemetry.registry import HOST_FIELDS

        for f in HOST_FIELDS:
            delta.pop(f, None)
        if self.guard is not None and self.guard.chunk_retries:
            rec["retries"] = self.guard.report()
        # Fault plane: when churn/outage activity happened this chunk, a
        # ``faults`` block surfaces it directly (restart resets plus the
        # fault-induced rows of the drops table) — docs/OBSERVABILITY.md.
        restarts = delta.pop("host_restarts", 0)
        fault_drops = {k: drops[k] for k in
                       ("down_events", "down_pkts", "link_down_pkts")
                       if k in drops}
        if restarts or any(fault_drops.values()):
            rec["faults"] = {"host_restarts": restarts, **fault_drops}
        # Wasted-work accounting (performance attribution plane): the three
        # per-window boundary samples summed over this chunk, with the
        # denominators a consumer needs to turn them into utilization
        # fractions (n_hosts, the chunk's window count). Running sums, not
        # rates — they leave ``delta`` like the fill gauges and ride a
        # ``work`` block; tools/heartbeat_report.py's work-efficiency
        # section consumes it (and reads n_hosts from here for the
        # per-window ring fractions).
        work = {f: delta.pop(f, 0) for f in
                ("active_hosts", "elig_events", "outbox_hosts")}
        n_hosts = getattr(getattr(self.engine, "exp", None), "n_hosts", None)
        if any(work.values()):
            rec["work"] = dict(work)
            if n_hosts:
                rec["work"]["n_hosts"] = n_hosts
                if d_windows:
                    rec["work"]["active_frac"] = round(
                        work["active_hosts"] / (d_windows * n_hosts), 6)
        # Capacity occupancy: run-max fill gauges against their caps — the
        # data the cap controller and tools/captune.py size caps from.
        # High-water marks, not rates: they leave ``delta`` and ride a
        # ``fill`` block with the caps they are measured against.
        params = getattr(self.engine, "params", None)
        fill = {}
        for gauge, cap_field in (("ev_max_fill", "ev_cap"),
                                 ("ob_max_fill", "outbox_cap"),
                                 ("compact_max_fill", "compact_cap"),
                                 ("mq_max_fill", "msgq_pool"),
                                 # No knob: the rows a pass declares bound it
                                 # (core/engine.pass_rows).
                                 ("push_stage_max", None)):
            if delta.pop(gauge, 0) or m.get(gauge):
                fill[gauge] = m.get(gauge)
                if params is not None and cap_field:
                    fill[cap_field] = params.cap(cap_field)
        if fill:
            rec["fill"] = fill
        # Exchange occupancy (sharded engine): how close the busiest
        # all_to_all bucket has come to its cap — the datum that pins
        # x2x_cap rationally (a high-water near cap predicts overflow).
        cap = getattr(self.engine, "_x2x_cap", None)
        if cap:
            rec["x2x"] = {
                "max_fill": m.get("x2x_max_fill"),
                "cap": cap,
                "full_cap": getattr(self.engine, "_full_cap", None),
            }
            delta.pop("x2x_max_fill", None)  # a high-water mark, not a rate
        # The boundary of the chunk that just ran, as the chunk log has it
        # (the drain above has synced: its row is complete or a moment off).
        block = chunk_log().block()
        if block is not None:
            rec["chunk"] = block
        self.records.append(rec)
        if self.emit_heartbeat:
            self._emit(rec)
        for r in ring_recs:
            self.ring_records.append(r)
            if self.emit_ring:
                self._emit(r)
        for r in flow_recs:
            self.flow_records.append(r)
            if self.emit_ring:
                self._emit(r)
        for r in link_recs:
            self.link_records.append(r)
            if self.emit_ring:
                self._emit(r)
        self.t_last = now
        self.last = m

    def _drain_ring(self, st) -> list[dict]:
        """Per-window ring rows accumulated since the last chunk boundary."""
        if getattr(st, "telem", None) is None:
            return []
        from shadow1_tpu.telemetry.ring import drain_ring

        recs = drain_ring(st, self.engine.window, start=self._ring_next)
        self._ring_next = int(st.metrics.windows)
        return recs

    def _drain_probes(self, st) -> list[dict]:
        """Per-window flow-probe rows since the last chunk boundary (solo
        engines; the fleet engine's drain_rings handles its [E,...] ring)."""
        if getattr(st, "probes", None) is None:
            return []
        from shadow1_tpu.telemetry.probes import drain_probes

        probes = getattr(getattr(self.engine, "params", None), "probes", ())
        recs = drain_probes(st, self.engine.window, probes,
                            start=self._probe_next)
        self._probe_next = int(st.metrics.windows)
        return recs

    def _drain_links(self, st) -> list[dict]:
        """Cumulative per-edge link snapshot at this chunk boundary (solo
        engines; the fleet engine's drain_rings handles its [E,...]
        accumulator)."""
        if getattr(st, "links", None) is None:
            return []
        from shadow1_tpu.telemetry.links import drain_links

        recs = drain_links(st, self.engine.window, start=self._link_next)
        self._link_next = int(st.metrics.windows)
        return recs


def warm_up(engine, st=None, profiler=None):
    """``st`` (``engine.init_state()`` where None) with the program every
    chunk reuses compiled: ``n_windows`` is a traced argument, so a
    zero-window call builds it before any clock starts — the first
    heartbeat's events/sec folds no compile time in. Both runners
    (``run_with_heartbeat``, ``fleet.run.run_fleet``) start here."""
    if st is None:
        with maybe_span(profiler, PH_INIT):
            st = engine.init_state()
    with maybe_span(profiler, PH_COMPILE):
        try:
            jax.block_until_ready(engine.run(st, n_windows=0))
        except Exception as e:
            from shadow1_tpu import mem

            # OOM taxonomy: an exhaustion here is a COMPILE/allocation
            # failure, not a mid-run one — tag it so the CLI's memory
            # record reports the phase truthfully (mem.py).
            if mem.is_oom(e):
                e.shadow1_oom_phase = "compile"
            raise
    return st


def boundary_hook(beat, total, ckpt_path=None, ckpt_every_s=120.0,
                  ckpt_keep=3, drain=None, profiler=None, meta=None):
    """The ``on_chunk(st, done)`` both runners hand ``ckpt.run_chunked``:
    ``beat(st, done)`` (the heartbeat), the injection hooks, and with a
    ``ckpt_path`` the lineage snapshot and the ``.progress`` sidecar.

    The snapshot is throttled to ``ckpt_every_s`` of wall, and forced at
    the last chunk and by a pending ``drain`` request (the runner raises
    PreemptedExit right after this hook). ``meta()`` adds the caller's keys
    to the manifest entry (the fleet's ``lanes``, read when the snapshot is
    taken: they are the lanes of the state it holds). ``win_start`` is the
    max over a fleet's lanes, which a scalar's max is too."""
    from shadow1_tpu.lineage import Lineage, write_json_atomic
    from shadow1_tpu.preempt import run_injection_hooks

    lineage = Lineage(ckpt_path, keep=ckpt_keep) if ckpt_path else None
    last_save = time.perf_counter()
    seq = None

    def on_chunk(st, done):
        nonlocal last_save, seq
        beat(st, done)
        # The absolute sim clock — monotonic across respawned processes,
        # unlike the invocation-relative ``done``.
        sim_ns = int(np.asarray(st.win_start).max())
        # Fault/preemption/hang injection (tests, ci.sh, chaosprobe) — the
        # chunk-boundary contract of both runners, with or without a
        # checkpoint path; inert without the env vars.
        run_injection_hooks(sim_ns)
        if lineage is None:
            return
        now = time.perf_counter()
        saved = (done >= total or now - last_save > ckpt_every_s
                 or (drain is not None and drain.requested))
        if saved:
            entry = {"win_start": sim_ns, "done_windows": done}
            if meta is not None:
                entry.update(meta())
            with maybe_span(profiler, PH_CHECKPOINT):
                seq = lineage.save(st, entry)
            last_save = now
        # The progress sidecar is written at EVERY chunk boundary — it is
        # the watchdog's liveness signal, so it must tick even between
        # throttled saves. Atomic like save_state: a wedge mid-write must
        # not leave a truncated sidecar that makes the supervisor abandon a
        # perfectly resumable snapshot.
        write_json_atomic(ckpt_path + ".progress",
                          {"done_windows": done, "total": total,
                           "win_start": sim_ns, "seq": seq})
        # Fault injection (SURVEY §5 failure-detection analogue): die
        # like a wedged device process at an exact sim time, once — a
        # respawned resume starts past it. Gated on a save having
        # happened; inert without the env var.
        crash_at = os.environ.get("SHADOW1_OBS_CRASH_AT_NS")
        if saved and crash_at is not None and sim_ns == int(crash_at):
            os._exit(41)

    return on_chunk


def run_with_heartbeat(engine, st=None, n_windows=None, every_windows=None,
                       stream=None, ckpt_path=None, ckpt_every_s=120.0,
                       profiler=None, emit_heartbeat=True, emit_ring=True,
                       controller=None, guard=None, selfcheck=False,
                       ckpt_keep=3, drain=None):
    """Run the engine emitting a heartbeat every ``every_windows`` windows.

    With ``ckpt_path``, engine state is snapshotted there at heartbeat
    boundaries (throttled to ~``ckpt_every_s`` of wall) plus a ``.progress``
    sidecar with the completed window count — so a device fault mid-run
    (which can wedge the whole process)
    loses at most the windows since the last save, and a supervisor can
    respawn a fresh process that resumes from the snapshot (cli.py --ckpt).
    Determinism makes the resumed run bit-identical to an uninterrupted one.
    Snapshots rotate through a ``ckpt_keep``-deep generation set
    (lineage.Lineage, CLI --ckpt-keep) so a corrupt newest snapshot costs
    one generation of progress, not the run; the ``.progress`` sidecar is
    refreshed at EVERY chunk boundary (write-then-rename atomic) — it is
    the liveness signal the supervisor's watchdog reads, so it must tick
    even between throttled snapshot saves.

    ``drain`` (preempt.DrainHandler — the signal plane): a pending
    SIGTERM/SIGINT drain request forces the snapshot at the next chunk
    boundary regardless of the wall throttle, then the chunk runner raises
    preempt.PreemptedExit (docs/SEMANTICS.md "Preemption contract").

    With ``profiler`` (telemetry.PhaseProfiler), the compile warmup, every
    run-chunk, every chunk-boundary drain and every checkpoint save are
    recorded as Chrome-trace spans (CLI --trace).

    With ``controller`` (tune.CapController — CLI --auto-caps), buffer caps
    adapt between chunks: the controller may swap in an engine re-jitted at
    new static capacities with the state migrated bit-exactly; subsequent
    heartbeats report the live engine's caps.

    With ``guard`` (txn.OverflowGuard — CLI --on-overflow retry|halt),
    chunks are transactional: overflowing chunks are discarded and replayed
    at grown caps (or the run halts with a structured error), heartbeats
    and checkpoints only ever see committed overflow-free states, and
    heartbeat records carry a ``retries`` block once a retry happened.
    ``selfcheck`` verifies the drop-accounting identity at every committed
    boundary (txn.check_boundary_identity).

    Returns (final_state, heartbeat) — heartbeat.records holds the stream,
    heartbeat.ring_records the drained per-window telemetry rows.
    """
    total = n_windows if n_windows is not None else engine.n_windows
    if every_windows is None:
        every_windows = max(total // 10, 1)
    st = warm_up(engine, st, profiler)
    hb = Heartbeat(engine, stream=stream, initial_state=st, profiler=profiler,
                   emit_heartbeat=emit_heartbeat, emit_ring=emit_ring,
                   guard=guard)
    retune = None
    if controller is not None:
        def retune(eng_cur, s):
            eng_new, s = controller(eng_cur, s)
            hb.engine = eng_new  # heartbeat caps track the live engine
            return eng_new, s
    if guard is not None:
        # Retry-driven cap grows swap engines too — heartbeat fill blocks
        # must report the caps of the engine that actually ran the chunk.
        guard.on_engine_swap = lambda eng_new: setattr(hb, "engine", eng_new)
    on_chunk = boundary_hook(hb, total, ckpt_path, ckpt_every_s, ckpt_keep,
                             drain, profiler)
    st = run_chunked(engine, st, n_windows=total, chunk=every_windows,
                     on_chunk=on_chunk, profiler=profiler, retune=retune,
                     guard=guard, selfcheck=selfcheck, drain=drain)
    return st, hb
