"""Checkpoint / resume — snapshot the engine state pytree, continue later.

The reference has NO checkpointing (SURVEY §5: impossible with real process
memory in v1.x). Here engine state is a pytree of arrays, so a snapshot is
just the flattened tree serialized to one .npz file; resume loads it back
into the treedef of a freshly-initialized state and continues the window
loop. Determinism makes this exact: a run that checkpoints and resumes
produces bit-identical results to an uninterrupted run (tested in
tests/test_ckpt_obs.py).
"""

from __future__ import annotations

import numpy as np
import jax

# Snapshot format version. Bump whenever the SimState pytree's leaf order,
# count, or layout changes so stale snapshots fail with a clear message
# instead of an opaque shape/KeyError (round-3 advisor finding).
#   1: round 2-3 host-major layout
#   2: round 4 host-minor layout ([C,H]/[S,H]/[NP,C,H] tensors)
#   3: round 5 adds Metrics.x2x_max_fill (exchange occupancy high-water)
#   4: round 5 i32 round path — EventBuf gains t32/epoch, tb splits into
#      (tb_hi, tb_lo) i32 planes (core/events.py)
#   5: round 6 telemetry — SimState gains the optional ``telem`` ring leaf
#      (present only when EngineParams.metrics_ring > 0; a ring-less state
#      keeps the v4 leaf layout, but the format is bumped so a ring/ring-less
#      mismatch fails as a version error, not a confusing leaf-count one)
#   6: capacity autotuning — Metrics gains the ev_max_fill / ob_max_fill /
#      compact_max_fill gauges, and load_state learns CAP MIGRATION: a
#      snapshot whose ev_cap/outbox_cap differs from the engine's restores
#      via tune/resize.py instead of failing the shape check (--auto-caps
#      runs checkpoint at whatever cap they had grown to)
#   7: determinism flight recorder — the telemetry ring row widens by the
#      RING_DIGESTS state-digest columns (telemetry/registry.py), so any
#      snapshot carrying a ring leaf changes shape. No digest STATE rides
#      the snapshot beyond that: digest words are pure functions of the
#      engine state, which is why a resumed run's digest stream continues
#      bit-identically to the uninterrupted one with no extra bookkeeping.
#   8: fault plane — Metrics gains link_down_pkts / host_restarts, the ring
#      row widens by the matching counter columns, and every snapshot now
#      carries an ``integrity`` splitmix64 digest over all leaves:
#      load_state rejects truncated or bit-flipped snapshots with
#      CorruptCheckpointError instead of resuming from garbage, and the
#      supervisor (cli._supervise) discards a corrupt checkpoint like a
#      stale one rather than crash-looping on it.
#   9: fleet mode (shadow1_tpu/fleet/) — a snapshot may now hold a FLEET
#      state: every leaf carries a leading [E] experiment axis (event
#      buffers [E,C,H], metrics [E], rings [E,W,F]). Solo snapshots are
#      unchanged in layout, but the format is bumped so a fleet/solo
#      mixup fails as a version/shape error with this history to point at
#      rather than a confusing leaf-shape one. Per-experiment resume
#      slicing (fleet.engine.slice_experiment) re-saves one lane as a
#      plain solo snapshot.
#  10: performance attribution plane — Metrics gains the wasted-work
#      running sums active_hosts / elig_events / outbox_hosts, and any
#      snapshot carrying a telemetry ring widens its row by the matching
#      RING_WORK delta columns (telemetry/registry.py). Like the digest
#      columns, no extra state rides the snapshot beyond the new leaves:
#      the per-window values are pure boundary samples, so a resumed run's
#      work-gauge stream continues bit-identically.
#  11: flow-probe plane — SimState gains the optional ``probes`` ring leaf
#      ([W,K,F] i64, telemetry/probes.py; fleet: [E,W,K,F]), present only
#      when EngineParams.probes names watched entities AND metrics_ring > 0.
#      A probe-less state keeps the v10 leaf layout; the bump makes a
#      probes-on/probes-off mismatch fail as a version error. Probe rows
#      are pure window-boundary samples, so a resumed run's flow stream
#      continues bit-identically (same rule as the digest/work columns).
#  12: link-telemetry plane — SimState gains the optional ``links``
#      accumulator leaf ([V,V,F] i64, telemetry/links.py; fleet:
#      [E,V,V,F]), present only when EngineParams.link_telem is on. The
#      accumulator holds cumulative per-edge counters and drains as pure
#      running-total snapshots, so a resumed run's link stream continues
#      bit-identically with no baseline bookkeeping. A telemetry-off
#      state keeps the v11 leaf layout; the bump makes an on/off mismatch
#      fail as a version error.
#  13: Metrics gains deliver_ranks (the window-end merge's fill-loop trips
#      times its rank block, core/events.deliver_batch): one more i64 leaf
#      in every snapshot. A running sum like the other counters, so a
#      resumed run continues it bit-identically.
#  14: Metrics gains runs_pkt/deliver/timer/txr/app (rounds in which the
#      program ran each handler pass: the guard predicate reduced over a
#      fleet's lanes, core/engine.any_host): five more i64 leaves in every
#      snapshot. Running sums; a lane sliced out of a fleet carries the
#      fleet's count so far and continues it solo as fires_* would.
#  15: Metrics gains runs_window_end (windows in which the program ran the
#      window end, core/engine.deliver_window's guard): one more i64 leaf in
#      every snapshot. A running sum like runs_*.
#  16: SimState gains the optional ``compact_buckets`` leaf (i64 scalar;
#      fleet: [E]), the compacted round loop's trips, present only where a
#      compact_cap is in force (core/compact.py). A snapshot of a run
#      without a cap is leaf for leaf a v15 one.
#  17: the message-boundary queue is one pool a host: the TCP dict's
#      mq_valid / mq_end / mq_meta [MQ, S, H] leaves become mq_sock /
#      mq_end / mq_meta [P, H] (tcp/tcp.py; P = EngineParams.mq_pool), and
#      Metrics gains mq_max_fill / mq_overflow (the ring row their two
#      columns). load_state's cap migration covers the pool as it covers
#      ev_cap (tune/resize.resize_mq_pool). No older snapshot loads.
#  18: Metrics gains push_commit_trips / push_stage_max (the round's one
#      commit of its staged pushes, core/events.push_commit; the ring row the
#      gauge's column): two more i64 leaves in every snapshot. The stage
#      itself is None between rounds and in no snapshot.
#  19: Metrics gains route_rows (outbox rows the executed window ends'
#      route_outbox looked up, core/engine.deliver_window): one more i64
#      leaf in every snapshot. A running sum like runs_window_end.
CKPT_FORMAT = 19


class CorruptCheckpointError(ValueError):
    """The snapshot file is damaged (truncated zip, undecodable member, or
    integrity-digest mismatch) — as opposed to a well-formed snapshot of
    the wrong config, which stays a plain ValueError."""


_IM64 = (1 << 64) - 1
_IK = 0x2545F4914F6CDD1D           # the digest fold multiplier (core/digest)
_ISEED = 0xC6A4A7935BD1E995        # distinct seed: file integrity domain


def _integrity_digest(leaves) -> int:
    """Position-sensitive splitmix64 digest of the snapshot payload.

    Per leaf: the raw bytes (u64-padded) are each mixed with their word
    position and xor-reduced; leaf hashes then fold in order with the byte
    length, so any single flipped bit, swapped word, or truncated tail
    changes the digest. numpy-only — the supervisor verifies checkpoints
    host-side without touching an accelerator, so nothing under core/ may
    be imported here (its modules create jax arrays at import, which
    initialises a backend and takes the chip the child needs)."""
    from shadow1_tpu.rng import _mix_np

    def _mix_int(z: int) -> int:
        # core.digest._mix_int's value, via the numpy twin on one word.
        with np.errstate(over="ignore"):
            return int(_mix_np(np.uint64(z)))

    z = _ISEED
    for i, a in enumerate(leaves):
        a = np.ascontiguousarray(np.asarray(a))
        b = a.tobytes()
        pad = (-len(b)) % 8
        u = np.frombuffer(b + b"\0" * pad, np.uint64)
        if u.size:
            with np.errstate(over="ignore"):
                pos = np.arange(u.size, dtype=np.uint64)
                w = _mix_np(u + _mix_np(pos * np.uint64(_IK)
                                        + np.uint64(i + 1)))
            h = int(np.bitwise_xor.reduce(w))
        else:
            h = 0
        z = _mix_int((z * _IK + h) & _IM64)
        z = (z * _IK + len(b)) & _IM64
    return _mix_int(z)


def _flatten(st):
    leaves, treedef = jax.tree_util.tree_flatten(st)
    return leaves, treedef


def save_state(st, path: str) -> None:
    """Snapshot a SimState pytree to ``path`` (.npz).

    Write-then-rename: the fault-tolerant runners save while the device may
    be about to wedge the process; a crash mid-write must leave the previous
    snapshot intact, never a truncated zip."""
    import os

    leaves, _ = _flatten(st)
    arrays = {f"leaf_{i}": np.asarray(x) for i, x in enumerate(leaves)}
    arrays["format"] = np.asarray([CKPT_FORMAT, len(leaves)], np.int64)
    arrays["integrity"] = np.asarray(
        [_integrity_digest(arrays[f"leaf_{i}"] for i in range(len(leaves)))],
        np.uint64,
    )
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        np.savez_compressed(f, **arrays)
    os.replace(tmp, path)


def load_state(template, path: str, migrate_caps: bool = True):
    """Load a snapshot into the structure of ``template`` (a SimState from
    ``engine.init_state()``) — shapes/dtypes must match the engine config.

    One sanctioned mismatch: with ``migrate_caps`` (default), a snapshot
    saved at a different ``ev_cap``/``outbox_cap`` is migrated to the
    template's caps via tune/resize.py (bit-exact — pop order lives in the
    (time, tb) keys, not slot indices). This is how an ``--auto-caps`` run's
    checkpoints — saved at whatever cap the controller had grown to —
    restore into an engine built from the config's static caps. Every other
    shape/dtype difference still fails as a config mismatch."""
    tleaves, treedef = _flatten(template)
    try:
        with np.load(path) as data:
            fmt = (data["format"] if "format" in data.files
                   else np.asarray([1, -1]))
            n_saved = int(fmt[1])
            saved = [data[f"leaf_{i}"] for i in range(max(n_saved, 0))
                     if f"leaf_{i}" in data.files]
            stored = (int(data["integrity"][0])
                      if "integrity" in data.files else None)
    except Exception as e:  # truncated zip / undecodable member / bad header
        raise CorruptCheckpointError(
            f"checkpoint {path} is unreadable ({type(e).__name__}: {e}) — "
            f"truncated or damaged snapshot; discard it and re-run"
        ) from e
    if int(fmt[0]) != CKPT_FORMAT:
        raise ValueError(
            f"checkpoint {path} has format v{int(fmt[0])}, this build "
            f"reads v{CKPT_FORMAT} — snapshot from an incompatible "
            f"framework version; re-run from scratch"
        )
    if stored is None or len(saved) != n_saved:
        raise CorruptCheckpointError(
            f"checkpoint {path} is missing state members "
            f"({len(saved)}/{n_saved} leaves, integrity "
            f"{'present' if stored is not None else 'absent'}) — truncated "
            f"snapshot; discard it and re-run"
        )
    if _integrity_digest(saved) != stored:
        raise CorruptCheckpointError(
            f"checkpoint {path} fails its integrity digest — the snapshot "
            f"was bit-corrupted after writing; discard it and re-run"
        )
    if n_saved != len(tleaves):
        raise ValueError(
            f"checkpoint {path} holds {n_saved} state leaves, engine "
            f"expects {len(tleaves)} — engine config mismatch"
        )
    leaves = saved
    if migrate_caps:
        # Structure (leaf count) already matched, so the saved leaves
        # unflatten into a SimState whose planes carry the SAVED caps;
        # migrate the event buffer / outbox onto the template's caps before
        # the strict per-leaf validation below.
        st = jax.tree_util.tree_unflatten(treedef, leaves)
        from shadow1_tpu.tune.resize import mq_pool_of, resize_state

        ev_cap = np.asarray(template.evbuf.kind).shape[-2]
        ob_cap = np.asarray(template.outbox.dst).shape[-2]
        mq_pool = mq_pool_of(template)
        if (np.asarray(st.evbuf.kind).shape[-2] != ev_cap
                or np.asarray(st.outbox.dst).shape[-2] != ob_cap
                or mq_pool_of(st) != mq_pool):
            try:
                st = resize_state(st, ev_cap=ev_cap, outbox_cap=ob_cap,
                                  msgq_pool=mq_pool)
            except ValueError as e:
                raise ValueError(
                    f"checkpoint {path} cannot migrate onto this engine's "
                    f"caps ({e}) — rebuild the engine at the snapshot's caps "
                    f"(ckpt.snapshot_caps) or resume with --auto-caps, which "
                    f"does this automatically"
                ) from e
            leaves = jax.tree_util.tree_leaves(st)
    for i, (have, want) in enumerate(zip(leaves, tleaves)):
        have = np.asarray(have)
        w = np.asarray(want)
        if have.shape != w.shape or have.dtype != w.dtype:
            raise ValueError(
                f"checkpoint leaf {i}: {have.shape}/{have.dtype} != "
                f"engine state {w.shape}/{w.dtype} — config mismatch"
            )
    return jax.tree_util.tree_unflatten(treedef, leaves)


def verify_file(path: str) -> tuple[bool, str | None]:
    """Host-side snapshot health check: (ok, reason-if-not).

    Reads the file with numpy only (no engine, no accelerator) and checks
    the member set plus the integrity digest — the supervisor runs this
    BEFORE spawning a child on a leftover checkpoint, so a bit-corrupted
    snapshot is discarded like a stale one instead of crash-looping the
    respawn budget away (cli._supervise)."""
    try:
        with np.load(path) as data:
            if "format" not in data.files:
                return False, "no format member"
            n = int(data["format"][1])
            if "integrity" not in data.files:
                return False, "no integrity digest (pre-v8 or truncated)"
            stored = int(data["integrity"][0])
            leaves = []
            for i in range(n):
                if f"leaf_{i}" not in data.files:
                    return False, f"missing leaf_{i} of {n}"
                leaves.append(data[f"leaf_{i}"])
    except Exception as e:
        return False, f"unreadable ({type(e).__name__}: {e})"
    if _integrity_digest(leaves) != stored:
        return False, "integrity digest mismatch (bit corruption)"
    return True, None


def snapshot_caps(template, path: str) -> tuple[int, int] | None:
    """(ev_cap, outbox_cap) a snapshot was SAVED at, read off its leaf
    shapes without loading the full state. An ``--auto-caps`` run
    checkpoints at whatever cap the controller had grown to — possibly
    holding more events per host than the config's static cap can — so a
    supervised respawn must rebuild its engine at the snapshot's caps
    before resuming (cli.py does this; a shrink-on-load that would drop
    events refuses instead). Returns None when the snapshot's leaf layout
    doesn't match ``template`` (the format checks in load_state will say
    why)."""
    leaves = jax.tree_util.tree_leaves(template)

    def idx(leaf):
        for i, l in enumerate(leaves):
            if l is leaf:
                return i
        return None

    i_ev = idx(template.evbuf.kind)
    i_ob = idx(template.outbox.dst)
    try:
        with np.load(path) as data:
            for i in (i_ev, i_ob):
                if i is None or f"leaf_{i}" not in data.files:
                    return None
            ev, ob = data[f"leaf_{i_ev}"].shape, data[f"leaf_{i_ob}"].shape
    except Exception as e:  # truncated zip / undecodable member
        raise CorruptCheckpointError(
            f"checkpoint {path} is unreadable ({type(e).__name__}: {e}) — "
            f"truncated or damaged snapshot; discard it and re-run"
        ) from e
    # Slot axis is axis=-2 on solo ([C, H]) and fleet ([E, C, H]) planes
    # alike (the tune/resize.py convention).
    if len(ev) < 2 or len(ob) < 2:
        return None
    return int(ev[-2]), int(ob[-2])


def run_chunked(engine, st=None, n_windows: int | None = None,
                chunk: int = 0, on_chunk=None, profiler=None, retune=None,
                guard=None, selfcheck: bool = False, drain=None):
    """Run in fixed-size window chunks, invoking ``on_chunk(st, done)`` after
    each (for checkpoints/heartbeats). One compiled program is reused for
    every full chunk. Returns the final state.

    THE chunk loop: the benchmark calls it bare, ``obs.run_with_heartbeat``
    and ``fleet.run.run_fleet`` hand it hooks, nothing else dispatches a
    chunk. One order at every boundary: ``commit`` -> self-check -> drain
    latch -> ``on_chunk`` (heartbeat, injection hooks, snapshot,
    ``.progress``: obs.boundary_hook) -> PreemptedExit -> ``retune``.

    Every chunk is spanned (telemetry/profiler.py): ``run-chunk`` ⊃
    ``dispatch`` (the run call returning, ⊃ ``args``, ``call``; + ``sync``
    under a profiler), ``wait`` on the chunk log's waiter thread (the
    result ready), then ``commit`` (guard), ``on-chunk``, ``retune``, each
    with the chunk's first window as ``done``. The spans are ``shadow1:``
    annotations in any ``jax.profiler`` capture and one row of
    ``telemetry.chunk_log()``, always; ``profiler``
    (telemetry.PhaseProfiler) also records them for ``--trace`` and makes
    ``run-chunk`` cover execution.

    ``retune(engine, st) -> (engine, st)`` is the between-chunk adaptation
    hook (tune/autocap.CapController; the fleet's lane finalize): it may
    hand back a DIFFERENT engine (re-jitted at new static capacities, or
    with fewer lanes) with the state migrated to match.
    Called after ``on_chunk`` so heartbeats/checkpoints see the state that
    actually ran the chunk; never called after the final chunk.

    ``guard`` (txn.OverflowGuard — CLI ``--on-overflow retry|halt``; or the
    fleet's commit hook of the same surface, ``bind`` / ``run_guarded`` /
    ``commit``) makes chunk execution TRANSACTIONAL: the chunk-start state
    is kept as the rollback point, and the commit either accepts the chunk
    (no fresh overflow), discards it and replays it inside the commit (at
    grown caps; the fleet's also with a failing lane quarantined, so with
    fewer lanes — this loop learns nothing about lanes), or raises a
    structured CapacityExceededError. Commit runs BEFORE ``on_chunk``, so
    heartbeats and checkpoints only ever see committed (overflow-free)
    states — a checkpoint can never capture a tainted chunk. Without a
    guard (the default ``drop`` policy) no state is retained and no extra
    host sync is paid.

    ``selfcheck`` (CLI ``--selfcheck``) verifies the drop-accounting
    identity on every committed chunk boundary (txn.SelfCheckError on
    violation) — churnprobe's probe-only invariant, guarding every run.

    ``drain`` (preempt.DrainHandler) is the signal plane: when a
    SIGTERM/SIGINT has requested a drain, the loop finishes the in-flight
    chunk, commits it, lets ``on_chunk`` run (which forces the final
    snapshot when the run carries a checkpoint path) and raises
    preempt.PreemptedExit — checked only at chunk boundaries, never inside
    a window (a window is the atomic unit of the determinism contract)."""
    from shadow1_tpu.telemetry import (
        PH_COMMIT,
        PH_DISPATCH,
        PH_INIT,
        PH_ON_CHUNK,
        PH_RETUNE,
        PH_SYNC,
        chunk_log,
        maybe_span,
    )

    chunks = chunk_log()
    if st is None:
        with maybe_span(profiler, PH_INIT):
            st = engine.init_state()
    if guard is not None:
        guard.bind(engine, st)
    total = n_windows if n_windows is not None else engine.n_windows
    if chunk <= 0:
        chunk = total
    done = 0
    while done < total:
        step = min(chunk, total - done)
        # What every span of this chunk carries: its first window and size.
        ids = {"done": done, "windows": step}
        # Rollback point: jax states are immutable and run() never donates,
        # so holding the reference is free until the commit drops it.
        st0 = st if guard is not None else None
        with chunks.chunk(profiler, engine, st, **ids) as ch:
            # Under a guard the sharded engine's eager x2x safety net
            # stands down (guard.run_guarded passes check_x2x=False) — the
            # commit below owns the overflow response.
            with maybe_span(profiler, PH_DISPATCH, **ids):
                st = (guard.run_guarded(engine, st, step)
                      if guard is not None
                      else engine.run(st, n_windows=step))
            # Readiness is the log's waiter's to take: no sync here.
            ch.watch(st)
            if profiler is not None:
                # Only under a PhaseProfiler: make the span cover execution,
                # not just async dispatch. Chunk boundary — never inside a
                # window.
                with maybe_span(profiler, PH_SYNC, **ids):
                    jax.block_until_ready(st)
        if guard is not None:
            with maybe_span(profiler, PH_COMMIT, **ids):
                engine, st = guard.commit(engine, st0, st, done, step)
        done += step
        if selfcheck:
            from shadow1_tpu.txn import check_boundary_identity

            check_boundary_identity(
                type(engine).metrics_dict(st),
                where=f"chunk boundary, window {int(st.metrics.windows)}")
        # Sample the drain latch BEFORE on_chunk: on_chunk's forced-save
        # check can only see the latch as MORE set than this sample, so
        # whenever we raise below, the final snapshot was already forced —
        # a signal landing mid-on_chunk is honored one boundary later,
        # never honored without its snapshot.
        draining = drain is not None and drain.requested and done < total
        if on_chunk is not None:
            with maybe_span(profiler, PH_ON_CHUNK, **ids):
                on_chunk(st, done)
        if draining:
            from shadow1_tpu.preempt import PreemptedExit

            raise PreemptedExit(
                st=st, signame=drain.signame, done_windows=done,
                win_start=int(np.asarray(st.win_start).max()))
        if retune is not None and done < total:
            with maybe_span(profiler, PH_RETUNE, **ids):
                engine, st = retune(engine, st)
            if guard is not None:
                guard.engine = engine
    return st
