"""Command-line runner: ``python -m shadow1_tpu <config.yaml> [options]``.

The analogue of the reference's ``shadow [options] shadow.config.xml`` entry
point (src/main/main.c + core/support/options.c): one experiment file, an
engine selector, and end-of-run metrics. The ``--engine`` flag overrides the
config's ``engine.scheduler`` the way the reference's CLI flags override its
config values.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time


MAX_RESPAWNS = 8

# The CLI exit-code taxonomy lives in consts.py (jax-free) — 0 ok, 2 config,
# 4 capacity halt, 5 preempted drain, 6 watchdog-classified hang; duplicated
# as literals nowhere (docs/SEMANTICS.md "Preemption contract", README).
from shadow1_tpu.consts import (  # noqa: E402 (jax-free module)
    EXIT_CAPACITY,
    EXIT_CODES,
    EXIT_CONFIG,
    EXIT_HUNG,
    EXIT_MEMORY,
    EXIT_OK,
    EXIT_PREEMPTED,
)


def _config_fingerprint(config_path: str) -> str:
    """Identity of the experiment a --ckpt snapshot belongs to. Snapshot
    leaf shapes alone cannot distinguish two configs that differ only in
    scalars (seed, stop_time), so resume safety needs the config bytes."""
    import hashlib

    with open(config_path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _emit_resume_record(ckpt_path, resolved, win_start, lineage=None) -> None:
    """One parseable ``resume`` record on stderr per lineage resume (schema
    in docs/OBSERVABILITY.md): which generation the run continued from,
    how many corrupt newer generations were skipped, and the lineage depth
    on disk — the rows heartbeat_report's lineage section summarizes."""
    rec = {"type": "resume", "ckpt": ckpt_path,
           "generation": resolved.seq, "win_start": int(win_start),
           "fallback_skipped": len(resolved.skipped)}
    if resolved.skipped:
        rec["discarded"] = [s["file"] for s in resolved.skipped]
    if lineage is not None:
        rec["generations_kept"] = len(lineage.generations())
    print(json.dumps(rec), file=sys.stderr, flush=True)


def _resolve_ckpt_lineage(args, log, what="checkpoint"):
    """Child-side resume resolution, shared by the solo and fleet paths:
    walk the --ckpt lineage to the newest VALID generation (deleting
    corrupt newer ones), warn when nothing verifies, and fall back to an
    explicit --resume. Returns (resolved, lineage, resume_path)."""
    if not args.ckpt:
        return None, None, args.resume
    from shadow1_tpu.lineage import Lineage

    lineage = Lineage(args.ckpt, keep=args.ckpt_keep)
    r = lineage.resolve(discard_invalid=True)
    resolved = r if (r is not None and r.path is not None) else None
    if r is not None and resolved is None:
        log.warning(f"discarding corrupt {what}", path=args.ckpt,
                    reason=(r.skipped[0]["reason"] if r.skipped
                            else "no valid generation"))
    return resolved, lineage, (resolved.path if resolved else args.resume)


def _supervise(child_argv, ckpt_path, config_path,
               watchdog_s: float = 0.0) -> int:
    """Parent side of ``--ckpt`` fault tolerance: run the CLI in a child
    process; when it dies with a checkpoint showing forward progress,
    respawn a fresh child that resumes from the snapshot — a wedged-runtime
    fault never survives into the next attempt because the next attempt is
    a new process.

    Failure handling beyond the bare respawn loop:

    * **checkpoint lineage** (lineage.Lineage): the generation set is
      resolved host-side before every spawn — a corrupt HEAD with a valid
      older generation behind it is announced and left for the child to
      fall back on (one generation of progress lost, not the run); only
      when NO generation verifies is the whole set discarded and the run
      restarted from scratch;
    * **preemption** (rc == EXIT_PREEMPTED): a SIGTERM/SIGINT drain is a
      clean-resume exit, not a crash — no backoff, no crash accounting,
      checkpoint kept; the supervisor exits EXIT_PREEMPTED itself so the
      operator (or the next scheduler slot) reruns the same command to
      continue. The supervisor forwards its own SIGTERM/SIGINT to the
      child so signaling either process drains the run.
    * **watchdog** (``--watchdog-s`` / env SHADOW1_WATCHDOG_S): a child
      whose ``.progress`` sidecar mtime goes stale past the deadline is
      killed and classified **hung** — distinct from crashed, with its own
      backoff lane; two consecutive hangs without forward progress abort
      with EXIT_HUNG and point at tools/faultprobe — an unresponsive
      device costs a bounded delay, never an unbounded one;
    * **exponential backoff**: the respawn delay doubles on consecutive
      no-progress failures (per lane) and resets when an attempt makes
      forward progress (env SHADOW1_SUPERVISE_BACKOFF_S tunes the base;
      tests set 0);
    * **failure classification**: two consecutive crashes at the same
      ``win_start`` mean the fault is deterministic at that sim time — a
      third identical attempt would burn the respawn budget for nothing,
      so the supervisor aborts with a diagnosis instead.
    * **memory classification** (rc == EXIT_MEMORY, or belt-and-braces: a
      raw ``RESOURCE_EXHAUSTED`` on the child's stderr even when the
      structured taxonomy was bypassed — a crash deep in backend init, an
      allocator abort): device memory exhaustion is a deterministic
      config-vs-device condition, so the supervisor never respawns into
      the same wall; it points at the estimator's advice instead. The
      child's stderr is teed through a scanning thread so heartbeats
      still flow to the parent's stderr unchanged.
    """
    import os
    import signal
    import subprocess
    import threading
    import time as _time

    from shadow1_tpu.lineage import Lineage, write_json_atomic
    from shadow1_tpu.preempt import FORCE_GRACE_S

    sidecar = ckpt_path + ".progress"
    meta_path = ckpt_path + ".meta"
    lineage = Lineage(ckpt_path)

    def _emit_lineage(event: str, **fields) -> None:
        # Parseable lineage records on stderr, beside the [supervise] prose
        # — tools/heartbeat_report.py's "lineage" section reads these.
        print(json.dumps({"type": "lineage", "event": event, **fields}),
              file=sys.stderr, flush=True)

    # A snapshot left by an earlier interrupted run of a DIFFERENT config
    # must not silently hijack this run (same leaf shapes would pass
    # load_state's checks): fingerprint-mismatched leftovers are deleted.
    fp = _config_fingerprint(config_path)
    stale = False
    # ANY lineage candidate counts — a kill between head-rotation and
    # install leaves rotated generations with no head, and those must not
    # hijack a different config's run any more than a head would.
    if any(os.path.exists(p) for p in lineage.sidecar_paths()):
        try:
            with open(meta_path) as f:
                stale = json.load(f).get("config_sha256") != fp
        except (OSError, ValueError):
            stale = True
    if stale:
        print(f"[supervise] discarding stale checkpoint {ckpt_path} "
              f"(different or unknown config)", file=sys.stderr, flush=True)
        lineage.remove_all()
        for p in (sidecar, meta_path):
            if os.path.exists(p):
                os.remove(p)
    write_json_atomic(meta_path, {"config_sha256": fp})
    backoff_base = float(os.environ.get("SHADOW1_SUPERVISE_BACKOFF_S", "1.0"))
    last_progress = -1
    no_progress = 0       # consecutive crashes without forward progress
    no_progress_hung = 0  # consecutive watchdog kills without progress
    rc = 1

    # Signal plane, parent side: forward the first SIGTERM/SIGINT to the
    # child (so signaling only the supervisor still drains the run — group
    # delivery handles the common case, and the child debounces the
    # duplicate); a second one kills the child hard and re-raises.
    proc_box: list = [None]
    sig_seen: list = []

    def _forward(signum, frame):
        now = _time.monotonic()
        child = proc_box[0]
        # Same escalation window as the child's DrainHandler — parent and
        # child must agree on what counts as a duplicate delivery.
        if sig_seen and now - sig_seen[0] >= FORCE_GRACE_S:
            if child is not None and child.poll() is None:
                child.kill()
            signal.signal(signum, signal.SIG_DFL)
            os.kill(os.getpid(), signum)
            return
        if not sig_seen:
            sig_seen.append(now)
            print(f"[supervise] {signal.Signals(signum).name} received — "
                  f"forwarding drain request to the child",
                  file=sys.stderr, flush=True)
            if child is not None and child.poll() is None:
                child.send_signal(signal.SIGTERM)

    prev_handlers = {s: signal.signal(s, _forward)
                     for s in (signal.SIGTERM, signal.SIGINT)}
    try:
        for attempt in range(MAX_RESPAWNS + 1):
            if sig_seen:
                # The drain request landed while no child was alive (e.g.
                # during a backoff sleep, or the child died un-gracefully
                # right after the forward): honor it here — never respawn
                # past a preemption notice.
                print(f"[supervise] preemption requested — not "
                      f"respawning; checkpoint kept; rerun the same "
                      f"command to resume", file=sys.stderr, flush=True)
                _emit_lineage("preempted", rc=EXIT_PREEMPTED)
                return EXIT_PREEMPTED
            res = lineage.resolve()
            if res is not None and res.path is None:
                # Candidates existed but EVERY generation is damaged: same
                # policy as a stale snapshot — restart from scratch. The
                # progress baseline resets with it; the next child
                # legitimately re-earns its first windows.
                why = res.skipped[0]["reason"] if res.skipped else "?"
                print(f"[supervise] discarding corrupt checkpoint "
                      f"{ckpt_path} ({why}; no valid generation of "
                      f"{len(res.skipped)}); restarting from scratch",
                      file=sys.stderr, flush=True)
                _emit_lineage("discard_all", reason=why,
                              generations=len(res.skipped))
                lineage.remove_all()
                if os.path.exists(sidecar):
                    os.remove(sidecar)
                last_progress = -1
            elif res is not None and res.skipped:
                # Corrupt head, valid generation behind it: announce; the
                # child's own resolve falls back (and prunes the damage).
                print(f"[supervise] checkpoint head "
                      f"{res.skipped[0]['file']} is corrupt "
                      f"({res.skipped[0]['reason']}); resume will fall "
                      f"back to generation {res.seq}",
                      file=sys.stderr, flush=True)
                _emit_lineage("corrupt_head", fallback_seq=res.seq,
                              skipped=len(res.skipped),
                              reason=res.skipped[0]["reason"])
            cmd = [sys.executable, "-m", "shadow1_tpu", *child_argv,
                   "--supervised-child"]
            # stdout inherited: final JSON flows. stderr is TEED through a
            # scanning thread (heartbeats still reach the parent's stderr
            # line-for-line) so a raw RESOURCE_EXHAUSTED crash — one that
            # bypassed the structured EXIT_MEMORY taxonomy entirely — is
            # still classified as deterministic memory exhaustion below
            # instead of crash-looping through the backoff ladder. Popen
            # (not run) so the watchdog can poll the progress sidecar
            # while waiting.
            proc = subprocess.Popen(cmd, stderr=subprocess.PIPE, text=True,
                                    errors="replace")
            # [line_count, line index of the last RESOURCE_EXHAUSTED] — the
            # classifier below requires the marker NEAR death, so a
            # non-fatal allocator warning early in a long run (GPU
            # autotuners log these and continue) cannot misclassify an
            # unrelated later crash as memory exhaustion.
            oom_seen = [0, None]

            def _tee(pipe=proc.stderr, seen=oom_seen):
                for line in iter(pipe.readline, ""):
                    seen[0] += 1
                    if "RESOURCE_EXHAUSTED" in line:
                        seen[1] = seen[0]
                    sys.stderr.write(line)
                sys.stderr.flush()
                pipe.close()

            tee = threading.Thread(target=_tee, daemon=True)
            tee.start()
            proc_box[0] = proc
            if sig_seen and proc.poll() is None:
                # A drain request that landed between the top-of-loop check
                # and this assignment had no child to forward to — deliver
                # it now rather than letting the child run to completion
                # past a preemption notice.
                proc.send_signal(signal.SIGTERM)
            spawn_wall = _time.time()
            hung = False
            hung_stale = 0.0
            poll_s = (max(0.1, min(1.0, watchdog_s / 5))
                      if watchdog_s > 0 else 1.0)
            while True:
                try:
                    rc = proc.wait(timeout=poll_s)
                    break
                except subprocess.TimeoutExpired:
                    pass
                if watchdog_s <= 0:
                    continue
                try:
                    beat = os.path.getmtime(sidecar)
                except OSError:
                    beat = None
                beaten = beat is not None and beat > spawn_wall
                ref = beat if beaten else spawn_wall
                # Before THIS attempt's first beat the child is importing
                # and compiling (no sidecar ticks yet) — allow 3× the
                # deadline for startup; after a beat, the configured S.
                deadline = watchdog_s if beaten else 3 * watchdog_s
                stale_s = _time.time() - ref
                if stale_s > deadline:
                    print(f"[supervise] child hung: progress sidecar "
                          f"stale {stale_s:.1f}s > watchdog "
                          f"{deadline:.1f}s — killing pid {proc.pid}",
                          file=sys.stderr, flush=True)
                    proc.kill()
                    rc = proc.wait()
                    hung = True
                    hung_stale = stale_s
                    break
            proc_box[0] = None
            tee.join(timeout=10.0)  # drain the scan before classifying
            # Raw-marker classification is deliberately narrow: a plain
            # crash exit (rc > 0, not a structured taxonomy code, not a
            # signal death, not a watchdog kill) whose stderr ENDED with
            # the marker — the dying traceback — within the last 50
            # lines. Everything else falls through to the PR 4/7
            # crash/hung classifiers and keeps its recovery path.
            raw_oom = (rc > 0 and rc not in EXIT_CODES and not hung
                       and oom_seen[1] is not None
                       and oom_seen[0] - oom_seen[1] <= 50)
            if rc == EXIT_MEMORY or raw_oom:
                # Memory exhaustion (structured pre-flight/runtime exit,
                # or — belt and braces — a raw RESOURCE_EXHAUSTED crash
                # the taxonomy never saw): deterministic config-vs-device
                # condition; a respawn replays the identical allocation
                # and burns the budget for nothing.
                how = ("rc=EXIT_MEMORY" if rc == EXIT_MEMORY
                       else f"rc={rc}, raw RESOURCE_EXHAUSTED on stderr")
                print(f"[supervise] child exhausted device memory ({how}) "
                      f"— deterministic config-vs-device condition; not "
                      f"respawning. Apply the memory advice above, rerun "
                      f"with --on-oom downshift, or probe the feasible "
                      f"envelope: python -m shadow1_tpu.tools.memprobe "
                      f"{config_path} --maxfit",
                      file=sys.stderr, flush=True)
                return EXIT_MEMORY
            if rc == EXIT_CAPACITY:
                # Capacity halt (--on-overflow halt →
                # CapacityExceededError): a deterministic config condition,
                # not a device fault — a respawn would replay the identical
                # overflow and burn the budget. The child already printed
                # the structured advice.
                print(f"[supervise] child halted on a capacity policy "
                      f"(rc={rc}, CapacityExceededError) — deterministic "
                      f"config condition; not respawning. Apply the "
                      f"engine: cap advice above, or rerun with "
                      f"--on-overflow retry.", file=sys.stderr, flush=True)
                return rc
            if rc == EXIT_PREEMPTED:
                # Clean-resume classification: the child committed its
                # in-flight chunk and wrote a final snapshot before
                # exiting. No backoff, no crash accounting, checkpoint
                # KEPT — rerunning the same command resumes bit-exactly.
                print(f"[supervise] child drained after a preemption "
                      f"signal (rc={rc}) — checkpoint kept; rerun the "
                      f"same command to resume", file=sys.stderr, flush=True)
                _emit_lineage("preempted", rc=rc)
                return EXIT_PREEMPTED
            if rc == EXIT_OK:
                # A finished run's snapshot must not silently resume a
                # later invocation of the same command into a no-op.
                lineage.remove_all()
                for p in (sidecar, meta_path):
                    if os.path.exists(p):
                        os.remove(p)
                return EXIT_OK
            progress = -1
            if os.path.exists(sidecar):
                try:
                    with open(sidecar) as f:
                        progress = json.load(f).get("win_start", -1)
                except (OSError, ValueError):
                    progress = -1
            if hung:
                # Every kill gets its record (including one that triggers
                # the EXIT_HUNG classification below), with the OBSERVED
                # staleness, not the configured deadline.
                _emit_lineage("watchdog_kill", stale_s=round(hung_stale, 1),
                              sim_ns=max(progress, 0), attempt=attempt)
            if progress > last_progress:
                no_progress = 0
                no_progress_hung = 0
                last_progress = progress
            elif hung:
                no_progress_hung += 1
                if no_progress_hung >= 2:
                    print(
                        f"[supervise] two consecutive watchdog kills with "
                        f"no forward progress at sim_ns={max(progress, 0)} "
                        f"— the hang is deterministic at that point "
                        f"(wedged dispatch, unresponsive device), further "
                        f"respawns would repeat it. Bisect with "
                        f"`python -m shadow1_tpu.tools.faultprobe` "
                        f"(does the device answer at all, and which "
                        f"program stops it), then `python -m shadow1_tpu.tools."
                        f"paritytrace {config_path} tpu cpu` once the "
                        f"device answers.", file=sys.stderr, flush=True)
                    return EXIT_HUNG
            else:
                no_progress += 1
                if no_progress >= 2:
                    print(
                        f"[supervise] two consecutive crashes (rc={rc}) "
                        f"with no forward progress at "
                        f"sim_ns={max(progress, 0)} — the fault is "
                        f"deterministic at that point, further respawns "
                        f"would repeat it. Diagnose with "
                        f"`python -m shadow1_tpu.tools.faultprobe` "
                        f"(device/kernel faults) or `python -m shadow1_tpu."
                        f"tools.paritytrace {config_path} tpu cpu` (state "
                        f"divergence).", file=sys.stderr, flush=True)
                    return rc
            if attempt == MAX_RESPAWNS:
                return rc
            # Base delay after an attempt that made progress, doubled per
            # consecutive no-progress failure IN ITS LANE (hangs and
            # crashes back off independently — an unresponsive device and
            # a crashing kernel are different pathologies); the classifiers
            # above bound the exponent, not this formula.
            delay = backoff_base * (2 ** (no_progress_hung if hung
                                          else no_progress))
            kind = "hung (watchdog kill)" if hung else f"died rc={rc}"
            print(f"[supervise] child {kind} at sim_ns={progress}; "
                  f"respawning ({attempt + 1}/{MAX_RESPAWNS}) "
                  f"after {delay:.1f}s backoff",
                  file=sys.stderr, flush=True)
            if delay > 0:
                _time.sleep(delay)
    finally:
        for s, h in prev_handlers.items():
            signal.signal(s, h)
    return rc


@contextlib.contextmanager
def _run_traced(args, engine=None):
    """The run under ``--trace PATH`` / ``--profile DIR``: yields the
    PhaseProfiler to hand to the chunk runner (None where neither flag is
    set). ``--profile`` scopes a ``telemetry.device_trace`` over the body;
    with ``engine`` it leaves ``DIR/phases.json`` (device time by window
    phase, joined against that engine's program) beside
    ``DIR/phases.trace.json``. Files are written on a clean exit only."""
    if not (args.trace or args.profile):
        yield None
        return
    from shadow1_tpu.telemetry import PhaseProfiler, device_trace

    phases = PhaseProfiler()
    with (device_trace(args.profile, phases, engine=engine) if args.profile
          else contextlib.nullcontext()):
        yield phases
    if args.trace:
        phases.write(args.trace)
    if args.profile:
        os.makedirs(args.profile, exist_ok=True)
        phases.write(os.path.join(args.profile, "phases.trace.json"))


def _chunks_block(out: dict) -> None:
    """The final JSON's ``chunks`` block where the run went through a chunk
    loop: the chunk log's summary (how many chunks, the boundary from the
    host's side in ms a chunk and as a share of the chunks' wall, stalls —
    docs/OBSERVABILITY.md "Chunk log")."""
    from shadow1_tpu.telemetry import chunk_log

    chunks = chunk_log().summary()
    if chunks["count"]:
        out["chunks"] = chunks


def _fleet_main(args, params, plan, log, t0, capacity_exit,
                preempted_exit, memory_exit=None, sub_batch=None,
                auto_caps=False, pre_downshift_retry=False) -> int:
    """The --fleet execution path: one FleetEngine run over the expanded
    sweep, per-experiment final records + a fleet summary on stdout
    (docs/OBSERVABILITY.md §"Fleet records"). ``sub_batch`` (set by the
    --on-oom downshift planner) routes to the sequential sub-batched
    runner instead; ``memory_exit`` maps a runtime RESOURCE_EXHAUSTED to
    the structured EXIT_MEMORY taxonomy. The recovery plane (retry /
    quarantine / lane finalize / auto-caps) is driven by ``params`` inside
    fleet.run.run_fleet; this layer resolves resume state — including a
    lineage generation whose ``lanes`` meta says the sweep had already
    quarantined/finalized lanes (rebuild exactly that sub-fleet) and
    snapshots carrying retry/auto-caps-grown caps (rebuild at the
    snapshot's caps, mirroring the solo path)."""
    import os as _os

    import jax
    import numpy as np

    from shadow1_tpu import mem
    from shadow1_tpu.fleet.engine import FleetEngine
    from shadow1_tpu.fleet.run import final_records, run_fleet
    from shadow1_tpu.preempt import DrainHandler, PreemptedExit
    from shadow1_tpu.txn import CapacityExceededError

    if sub_batch and sub_batch < len(plan.exps):
        # Spans only: phases.json is one engine's, and each batch has its own.
        with _run_traced(args) as phases:
            return _fleet_subbatched(args, params, plan, log, t0,
                                     capacity_exit, preempted_exit,
                                     memory_exit, sub_batch, profiler=phases)
    # Resume resolution FIRST: a lineage generation carries the surviving
    # lane ids (``lanes`` manifest meta) when the sweep had already
    # quarantined or finalized lanes — the engine must be built for
    # exactly that sub-fleet or the [E', ...] snapshot cannot load.
    resolved, ckpt_lineage, resume_path = _resolve_ckpt_lineage(
        args, log, what="fleet checkpoint")
    exps, labels, max_rounds = plan.exps, plan.labels, plan.max_rounds
    meta_lanes = (resolved.meta or {}).get("lanes") if resolved else None
    sub_applied = False
    if meta_lanes is not None and \
            list(meta_lanes) != [l["exp"] for l in plan.labels]:
        sub = plan.subset(meta_lanes)
        exps, labels, max_rounds = sub.exps, sub.labels, sub.max_rounds
        sub_applied = True
        log.info("resuming partially-recovered sweep",
                 lanes=list(meta_lanes), of=len(plan.exps))

    def _build(p):
        eng = FleetEngine(exps, p, max_rounds)
        eng.exp_ids = [l["exp"] for l in labels]
        return eng

    try:
        eng = _build(params)
    except Exception as e:
        if memory_exit is not None and mem.is_oom(e):
            return memory_exit(e, phase="init")
        raise
    log.info("fleet expanded", experiments=eng.n_exp,
             hosts=eng.exp.n_hosts, window_ns=eng.window)
    st = None
    metrics0_by_gid = None
    if resume_path:
        from shadow1_tpu.ckpt import (
            CorruptCheckpointError,
            load_state,
            snapshot_caps,
        )

        params0, eng0 = params, eng
        try:
            template = eng.init_state()
            if (auto_caps or params.on_overflow == "retry"
                    or pre_downshift_retry):
                # Retry/auto-caps runs checkpoint at whatever cap they had
                # grown to — rebuild the fleet engine at the snapshot's
                # caps before loading (the solo-path recipe; a shrink-on-
                # load that would drop events refuses otherwise).
                snap = snapshot_caps(template, resume_path)
                if snap and snap != (params.ev_cap, params.outbox_cap):
                    import dataclasses

                    params = dataclasses.replace(
                        params, ev_cap=snap[0], outbox_cap=snap[1])
                    eng = _build(params)
                    template = eng.init_state()
            st = load_state(template, resume_path)
        except CorruptCheckpointError as e:
            # Same policy as the solo path: a supervised child must not
            # crash-loop the respawn budget on a snapshot corrupted after
            # the parent's pre-spawn verification — fall back to a fresh
            # start. An explicit --resume keeps failing loudly. A fresh
            # start means the FULL sweep: the discarded generation's
            # ``lanes`` subset (and its quarantine ledger) dies with it —
            # every lane re-earns its fate from window 0.
            if resolved is None:
                raise
            log.warning("discarding corrupt fleet checkpoint",
                        path=resume_path, reason=str(e))
            st, resume_path, resolved = None, None, None
            params = params0
            if sub_applied:
                exps, labels, max_rounds = (plan.exps, plan.labels,
                                            plan.max_rounds)
                eng = _build(params)
            else:
                eng = eng0
        else:
            metrics0_by_gid = {l["exp"]: m for l, m in
                               zip(labels, eng.metrics_per_exp(st))}
            done = int(np.asarray(st.win_start).max()) // eng.window
            if resolved is not None:
                _emit_resume_record(args.ckpt, resolved,
                                    int(np.asarray(st.win_start).max()),
                                    ckpt_lineage)
            if args.windows is None:
                args.windows = max(eng.n_windows - done, 0)
            elif resolved is not None:
                # Supervised respawn: --windows is the TOTAL for the whole
                # supervised run, not N more on top of the snapshot.
                args.windows = max(args.windows - done, 0)
    ring_w = params.metrics_ring
    drain = DrainHandler().install()
    # Quarantined-lane snapshots land beside the fleet checkpoint; a
    # checkpoint-less run keeps them beside the config (full path kept —
    # never the process cwd).
    qbase = args.ckpt or _os.path.splitext(args.config)[0] + ".lane"
    hb = None
    try:
        if _os.environ.get("SHADOW1_MEM_INJECT_OOM") == "run":
            raise RuntimeError("RESOURCE_EXHAUSTED: injected (test hook)")
        with _run_traced(args, eng) as phases:
            st, hb = run_fleet(
                eng, st, n_windows=args.windows,
                every_windows=args.heartbeat or (ring_w or None),
                stream=None if (args.heartbeat or ring_w
                                or params.link_telem) else False,
                ckpt_path=args.ckpt, ckpt_every_s=args.ckpt_every_s,
                emit_heartbeat=bool(args.heartbeat),
                emit_ring=bool(ring_w or params.link_telem),
                selfcheck=bool(params.selfcheck),
                labels=labels,
                ckpt_keep=args.ckpt_keep,
                drain=drain,
                auto_caps=auto_caps,
                quarantine_base=qbase,
                recovery_seed=({"quarantined":
                                (resolved.meta or {}).get("quarantined", []),
                                "finished":
                                (resolved.meta or {}).get("finished", [])}
                               if resolved is not None and st is not None
                               else None),
                # Quarantine / early-finalize records print to stdout the
                # moment the lane leaves the fleet — its fleet_exp would
                # otherwise never appear.
                emit_record=lambda rec: print(json.dumps(rec), flush=True),
                profiler=phases,
            )
            jax.block_until_ready(st)
    except CapacityExceededError as e:
        return capacity_exit(e)
    except PreemptedExit as e:
        return preempted_exit(e, resumed=bool(resume_path))
    except Exception as e:
        if memory_exit is not None and mem.is_oom(e):
            return memory_exit(e)
        raise
    if args.save_state:
        from shadow1_tpu.ckpt import save_state

        save_state(st, args.save_state)
    wall = time.perf_counter() - t0
    n_windows = args.windows if args.windows is not None else eng.n_windows
    # The live fleet shape (lanes may have left mid-sweep) is on the
    # heartbeat; rate baselines re-align by global id.
    metrics0 = ([metrics0_by_gid.get(l["exp"], {}) for l in hb.labels]
                if metrics0_by_gid is not None else None)
    recs, summary = final_records(hb.engine, st, hb.labels, n_windows, wall,
                                  resumed=bool(resume_path),
                                  metrics0=metrics0,
                                  recovery=hb.recovery)
    for r in recs:
        print(json.dumps(r))
    _chunks_block(summary)
    print(json.dumps(summary))
    return 0


def _fleet_subbatched(args, params, plan, log, t0, capacity_exit,
                      preempted_exit, memory_exit, sub: int,
                      profiler=None) -> int:
    """Memory-downshifted fleet: the sweep's E lanes run as SEQUENTIAL
    sub-batches of ≤ ``sub`` lanes, each its own vmapped FleetEngine run
    (cli --on-oom downshift; mem.downshift sized ``sub`` so one batch fits
    the device budget).

    Bit-exactness: lanes are independent — counter-based RNG keyed per
    (seed, host, ctr), per-lane fault tables, per-lane selects in the
    batched while_loop — so lane e's digest stream and metrics are
    IDENTICAL whether it runs beside 2 or 200 other lanes
    (tools/memprobe.py --subbatch is the per-invocation proof, the fleet
    contract's fleetprobe idiom). Each batch prints its fleet_exp records
    with SWEEP-GLOBAL experiment ids (FleetEngine.exp_base) as it
    finishes; one merged fleet_summary closes the run.

    ``--ckpt`` composes: each batch checkpoints ITS OWN [k, ...] state,
    with the sub-batch cursor riding the lineage manifest entry
    (``batch`` + ``batch_summaries`` — completed batches' summaries, so
    the merged fleet_summary survives a crash) beside the batch's
    ``lanes``. A resume rebuilds the engine for the recorded batch, loads
    its snapshot, finishes it and continues the remaining batches —
    completed batches never re-run. (--resume/--save-state still refuse
    at the downshift planner: an explicit snapshot path has no cursor.)
    A drain request stops the sweep at a batch/chunk boundary — finished
    lanes keep their records."""
    import os as _os

    import numpy as np
    import jax

    from shadow1_tpu import mem
    from shadow1_tpu.fleet.engine import FleetEngine
    from shadow1_tpu.fleet.run import final_records, run_fleet
    from shadow1_tpu.preempt import DrainHandler, PreemptedExit
    from shadow1_tpu.telemetry.registry import gauge_names
    from shadow1_tpu.txn import CapacityExceededError

    E = len(plan.exps)
    n_batches = -(-E // sub)
    log.info("fleet sub-batched for memory", experiments=E,
             lanes_per_batch=sub, batches=n_batches)
    drain = DrainHandler().install()
    ring_w = params.metrics_ring
    summaries: list[dict] = []
    windows_done = args.windows
    lanes_run = 0
    # Per-batch resume: the newest lineage generation names the batch it
    # snapshots and carries the summaries of every COMPLETED batch.
    resolved, ckpt_lineage, resume_path = _resolve_ckpt_lineage(
        args, log, what="sub-batched fleet checkpoint")
    start_batch = 0
    if resume_path and resolved is not None and resolved.meta:
        start_batch = int(resolved.meta.get("batch", 0))
        summaries = list(resolved.meta.get("batch_summaries", []))
        lanes_run = start_batch * sub
    for bi, i in enumerate(range(0, E, sub)):
        if bi < start_batch:
            continue  # completed pre-crash; its summary rode the manifest
        exps = plan.exps[i:i + sub]
        labels = plan.labels[i:i + sub]
        max_rounds = plan.max_rounds[i:i + sub]
        recovery_seed = None
        st = None
        batch_resumed = False
        resuming_here = resume_path and bi == start_batch
        if resuming_here and resolved is not None and resolved.meta:
            # The generation may snapshot a batch that already
            # quarantined/finalized lanes — rebuild exactly that
            # sub-batch or the [k', ...] snapshot cannot load
            # (the _fleet_main lanes-meta recipe, per batch).
            meta_lanes = resolved.meta.get("lanes")
            if meta_lanes is not None and \
                    list(meta_lanes) != [l["exp"] for l in labels]:
                by_gid = {l["exp"]: j for j, l in enumerate(labels)}
                keep = [by_gid[g] for g in meta_lanes if g in by_gid]
                exps = [exps[j] for j in keep]
                max_rounds = [max_rounds[j] for j in keep]
                labels = [labels[j] for j in keep]
            recovery_seed = {
                "quarantined": resolved.meta.get("quarantined", []),
                "finished": resolved.meta.get("finished", [])}
        try:
            eng = FleetEngine(exps, params, max_rounds)
            eng.exp_base = i
            eng.exp_ids = [l["exp"] for l in labels]
            n_windows = (args.windows if args.windows is not None
                         else eng.n_windows)
            remaining = n_windows
            if resuming_here:
                from shadow1_tpu.ckpt import (
                    CorruptCheckpointError,
                    load_state,
                )

                try:
                    st = load_state(eng.init_state(), resume_path)
                except CorruptCheckpointError as e:
                    if resolved is None:
                        raise
                    log.warning("discarding corrupt sub-batch checkpoint",
                                path=resume_path, reason=str(e))
                    st = None
                    recovery_seed = None
                    # Fresh restart of THIS batch = the full batch again.
                    exps = plan.exps[i:i + sub]
                    labels = plan.labels[i:i + sub]
                    eng = FleetEngine(exps, params,
                                      plan.max_rounds[i:i + sub])
                    eng.exp_base = i
                else:
                    batch_resumed = True
                    done = (int(np.asarray(st.win_start).max())
                            // eng.window)
                    remaining = max(n_windows - done, 0)
                    _emit_resume_record(args.ckpt, resolved,
                                        int(np.asarray(st.win_start).max()),
                                        ckpt_lineage)
            st, hb = run_fleet(
                eng, st, n_windows=remaining,
                every_windows=args.heartbeat or (ring_w or None),
                stream=None if (args.heartbeat or ring_w
                                or params.link_telem) else False,
                ckpt_path=args.ckpt, ckpt_every_s=args.ckpt_every_s,
                emit_heartbeat=bool(args.heartbeat),
                emit_ring=bool(ring_w or params.link_telem),
                selfcheck=bool(params.selfcheck),
                labels=labels,
                ckpt_keep=args.ckpt_keep,
                drain=drain,
                quarantine_base=(args.ckpt or _os.path.splitext(
                    args.config)[0] + ".lane"),
                emit_record=lambda rec: print(json.dumps(rec), flush=True),
                resume_meta={"batch": bi, "batch_summaries": summaries},
                recovery_seed=recovery_seed,
                profiler=profiler,
            )
            jax.block_until_ready(st)
        except CapacityExceededError as e:
            return capacity_exit(e)
        except PreemptedExit as e:
            return preempted_exit(e, resumed=batch_resumed)
        except Exception as e:
            if memory_exit is not None and mem.is_oom(e):
                return memory_exit(e)
            raise
        windows_done = n_windows
        # The LIVE batch shape: quarantine/finalize policies ride params
        # into run_fleet and may have shrunk the batch mid-run.
        recs, summary = final_records(hb.engine, st, hb.labels, n_windows,
                                      time.perf_counter() - t0,
                                      resumed=batch_resumed,
                                      recovery=hb.recovery)
        for r in recs:
            print(json.dumps(r))
        summaries.append(summary)
        lanes_run += len(exps)
        if drain.requested and lanes_run < E:
            # done_windows keeps its documented unit (windows committed
            # this invocation — preempt.py), matching the full-fleet
            # drain for the same run; finished lanes already printed
            # their fleet_exp records above.
            return preempted_exit(PreemptedExit(
                st=None, signame=drain.signame,
                done_windows=n_windows,
                win_start=int(summary.get("sim_seconds", 0) * 1e9)),
                resumed=batch_resumed)
    # Merged fleet_summary: counters sum, gauges (and the lockstep
    # windows/rounds) max across batches — the same aggregation rule as
    # FleetEngine.metrics_dict, applied one level up.
    maxed = set(gauge_names()) | {"windows", "rounds"}
    agg: dict[str, int] = {}
    for s in summaries:
        for k, v in s["metrics"].items():
            agg[k] = (max(agg.get(k, 0), int(v)) if k in maxed
                      else agg.get(k, 0) + int(v))
    wall = time.perf_counter() - t0
    s0 = summaries[0]
    sim_s = s0["sim_seconds"]
    merged = {
        "type": "fleet_summary",
        "engine": "fleet",
        "experiments": E,
        "hosts": s0["hosts"],
        "window_ns": s0["window_ns"],
        "windows": windows_done,
        "sim_seconds": sim_s,
        "wall_seconds": round(wall, 3),
        "sim_per_wall": round(sim_s / wall, 3) if wall > 0 else None,
        "events_per_sec": (round(agg.get("events", 0) / wall, 1)
                           if wall > 0 else None),
        "events_per_exp": [e for s in summaries
                           for e in s["events_per_exp"]],
        "resumed": False,
        "caps": s0["caps"],
        "metrics": agg,
        # The downshift audit: how the sweep was split for memory.
        "sub_batches": n_batches,
        "lanes_per_batch": sub,
    }
    _chunks_block(merged)
    print(json.dumps(merged))
    return 0


def main(argv=None) -> int:
    # Serve-plane subcommands (shadow1_tpu/serve/): `serve` starts the
    # persistent multi-tenant daemon, `submit` is its client. Dispatched
    # before the solo argparse so the solo surface (positional config +
    # flags) stays byte-compatible for every existing caller.
    argv = list(sys.argv[1:]) if argv is None else list(argv)
    if argv and argv[0] == "serve":
        from shadow1_tpu.serve.daemon import main as serve_main

        return serve_main(argv[1:])
    if argv and argv[0] == "submit":
        from shadow1_tpu.platform import assert_backend_untouched
        from shadow1_tpu.serve.client import main as submit_main

        rc = submit_main(argv[1:])
        assert_backend_untouched("submit")
        return rc
    ap = argparse.ArgumentParser(
        prog="shadow1_tpu",
        description="TPU-native discrete-event network simulator",
    )
    ap.add_argument("config", help="YAML experiment file")
    ap.add_argument("--engine", choices=["cpu", "tpu", "sharded"], default=None,
                    help="override engine.scheduler from the config")
    ap.add_argument("--windows", type=int, default=None,
                    help="run only this many conservative windows")
    ap.add_argument("--summary", action="store_true",
                    help="also print per-host model summary totals")
    ap.add_argument("--heartbeat", type=int, default=None, metavar="W",
                    help="emit a heartbeat line to stderr every W windows")
    ap.add_argument("--save-state", default=None, metavar="PATH",
                    help="snapshot final engine state to PATH (.npz)")
    ap.add_argument("--ckpt", default=None, metavar="PATH",
                    help="fault-tolerant run: snapshot state to PATH at "
                         "heartbeat boundaries and supervise the run in a "
                         "child process — on a device fault the child is "
                         "respawned resuming from PATH (a device fault "
                         "can wedge the whole process, so recovery is a "
                         "fresh process)")
    ap.add_argument("--ckpt-every-s", type=float, default=120.0,
                    metavar="S", help="throttle --ckpt snapshots to ~S "
                                      "seconds of wall (saves cost host "
                                      "transfer + npz write)")
    ap.add_argument("--ckpt-keep", type=int, default=3, metavar="K",
                    help="checkpoint lineage depth: keep the newest K "
                         "snapshot generations (the newest at the --ckpt "
                         "path, older rotated to PATH.gNNNNNN with a "
                         "PATH.lineage manifest); resume uses the newest "
                         "generation that passes its integrity digest, so "
                         "a torn/bit-flipped head costs one generation of "
                         "progress instead of the whole run")
    ap.add_argument("--watchdog-s", type=float, default=None, metavar="S",
                    help="supervisor watchdog: kill a child whose "
                         ".progress sidecar has not been refreshed for S "
                         "seconds of wall and classify the attempt as "
                         "'hung' (distinct backoff lane from crashes; two "
                         "consecutive no-progress hangs abort with the "
                         "dedicated exit code). Default: env "
                         "SHADOW1_WATCHDOG_S, else off. Size it above one "
                         "chunk's wall; startup (imports + compile, before "
                         "an attempt's first beat) gets 3x the deadline")
    ap.add_argument("--supervised-child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--resume", default=None, metavar="PATH",
                    help="resume from a state snapshot (batched engines)")
    ap.add_argument("--tracker", default=None, metavar="PATH",
                    help="write final per-host tracker records (JSON lines)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the run into DIR "
                         "(open with TensorBoard). The capture holds the "
                         "program's own spans as 'shadow1:<name>' on the "
                         "device trace's clock. Also writes "
                         "DIR/phases.trace.json (the --trace spans) and "
                         "DIR/phases.json: device seconds by window phase "
                         "(prepare / rounds/pop / rounds/h_<kind> / deliver "
                         "/ telem), joined from the compiled program's text "
                         "because a TPU trace does not carry scopes. Works "
                         "under --fleet")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome trace-event JSON of the host-side "
                         "spans (init, compile, run-chunk > dispatch + sync, "
                         "commit, on-chunk > drain + checkpoint, retune; "
                         "each with its chunk's first window as 'done') to "
                         "PATH, on a clock of its own — load in Perfetto or "
                         "chrome://tracing. Works under --fleet")
    ap.add_argument("--auto-caps", action="store_true",
                    help="occupancy-driven capacity autotuning: at chunk "
                         "boundaries, grow ev_cap before overflow and shrink "
                         "it after sustained low occupancy (measured via the "
                         "on-device fill gauges), migrating state bit-exactly "
                         "and re-jitting at ladder-quantized caps "
                         "(shadow1_tpu/tune/; overrides engine.auto_caps)")
    ap.add_argument("--metrics-ring", type=int, default=None, metavar="W",
                    help="keep a W-window on-device telemetry ring and emit "
                         "one per-window JSONL record to stderr per window "
                         "(drained at chunk boundaries; overrides "
                         "engine.metrics_ring from the config)")
    ap.add_argument("--watch", action="append", default=None,
                    metavar="HOST[:SOCK]",
                    help="watch a flow or host (repeatable): sample its "
                         "state columns (TCP cwnd/ssthresh/srtt/rto/"
                         "inflight..., NIC backlog/bytes, pending events) "
                         "at every window boundary into an on-device probe "
                         "ring, drained as per-window 'flow' JSONL records "
                         "on stderr (telemetry/probes.py; a ring is enabled "
                         "automatically on the batched engines). HOST is a "
                         "config host name (group[i] or group-i for "
                         "members) or a numeric id; omit :SOCK for the "
                         "host-level view. Merges with the config's "
                         "'probes:' section. Render with tools/flowreport.py")
    ap.add_argument("--state-digest", choices=["on", "off"], default=None,
                    metavar="on|off",
                    help="determinism flight recorder (core/digest.py): "
                         "compute per-window order-independent state digests "
                         "(evbuf/outbox/tcp/nic/rng words) inside the window "
                         "loop and carry them as telemetry-ring columns "
                         "(batched engines; a ring is enabled automatically) "
                         "or as per-window 'digest' JSONL records on stderr "
                         "(cpu oracle). off (default) traces zero digest "
                         "ops. Compare streams with tools/paritytrace.py")
    ap.add_argument("--link-telem", choices=["on", "off"], default=None,
                    metavar="on|off",
                    help="per-link telemetry plane (telemetry/links.py): "
                         "accumulate per-edge packet/byte/drop/queued "
                         "counters in a device-resident [V,V] tensor inside "
                         "the window loop, drained at chunk boundaries as "
                         "cumulative 'link' JSONL records on stderr (the cpu "
                         "oracle mirrors them bit-exactly). off (default) "
                         "traces zero link ops. Render with "
                         "tools/netreport.py")
    ap.add_argument("--on-overflow", choices=["drop", "retry", "halt"],
                    default=None, metavar="drop|retry|halt",
                    help="overflow policy at chunk boundaries "
                         "(shadow1_tpu/txn.py; overrides engine.on_overflow). "
                         "drop (default) = counted-but-lossy; retry = "
                         "TRANSACTIONAL chunks: discard the tainted chunk, "
                         "grow the offending cap one ladder step (bit-exact "
                         "migration + re-jit) and replay it — the digest "
                         "stream bit-matches a straight run at the final "
                         "caps; halt = raise CapacityExceededError with "
                         "paste-ready cap advice (exit code 4)")
    ap.add_argument("--on-oom", choices=["halt", "downshift"],
                    default="halt", metavar="halt|downshift",
                    help="memory-budget policy (shadow1_tpu/mem.py): the "
                         "pre-flight byte estimator compares the engine "
                         "state planes + known transient peaks against the "
                         "device's reported memory (env SHADOW1_MEM_BYTES "
                         "overrides) BEFORE compiling. halt (default) = "
                         "reject an oversubscribed config with a "
                         "structured MemoryBudgetError (per-plane bytes + "
                         "paste-ready advice, exit code 7); downshift = "
                         "degrade gracefully in bit-exactness-preserving "
                         "order: drop the txn rollback copy (retry demotes "
                         "to halt), shrink the telemetry ring, split a "
                         "fleet into sequential sub-batches (per-lane "
                         "digest streams stay bit-identical)")
    ap.add_argument("--on-lane-fail", choices=["halt", "quarantine"],
                    default=None, metavar="halt|quarantine",
                    help="fleet lane-failure policy (shadow1_tpu/fleet/"
                         "run.py; overrides engine.on_lane_fail). halt "
                         "(default) = a deterministically failing lane "
                         "(capacity halt / retry-ladder exhaustion / "
                         "per-lane selfcheck violation) kills the whole "
                         "sweep with the solo exit taxonomy; quarantine = "
                         "slice the lane out of the chunk-start state into "
                         "a solo-resumable checkpoint + a fleet_quarantine "
                         "record, repack the survivors (bit-exact streams) "
                         "and finish the sweep at E-k/E")
    ap.add_argument("--lane-finalize", action="store_true",
                    help="fleet mid-sweep lane lifecycle: lanes whose "
                         "event buffer fully drains (per-lane stop "
                         "horizon passed, nothing can ever fire again) "
                         "emit their fleet_exp record immediately and are "
                         "sliced out at chunk boundaries, shrinking the "
                         "device program to the lanes still doing work "
                         "(overrides engine.lane_finalize)")
    ap.add_argument("--selfcheck", action="store_true",
                    help="verify the drop-accounting identity (every sent "
                         "packet reaches exactly one counted fate) at every "
                         "chunk/window boundary; violation = structured "
                         "SelfCheckError naming the non-closing counters")
    ap.add_argument("--faults", choices=["on", "off"], default="on",
                    metavar="on|off",
                    help="fault plane (config `faults:` section — host "
                         "down/up cycles, link outage windows, timed loss "
                         "ramps; docs/SEMANTICS.md §'Fault plane'). "
                         "`off` runs the same experiment with the schedule "
                         "stripped (the healthy-world A/B); the legacy "
                         "per-group stop_time churn is unaffected")
    ap.add_argument("--fleet", action="store_true",
                    help="batched experiment sweep (shadow1_tpu/fleet/): "
                         "expand the config's `sweep:` section into E "
                         "experiment variants (seeds, loss rates, fault "
                         "schedules — one topology shape class) and run "
                         "them as ONE vmapped device program. Emits one "
                         "fleet_exp JSON record per experiment plus a "
                         "fleet_summary line; per-experiment digest "
                         "streams are bit-identical to solo runs "
                         "(docs/SEMANTICS.md §'Fleet contract')")
    ap.add_argument("--log-level", default="message",
                    choices=["error", "warning", "message", "info", "debug"],
                    help="stderr log verbosity (reference --log-level analogue)")
    args = ap.parse_args(argv)

    import shadow1_tpu  # noqa: F401  (x64 before jax arrays)
    from shadow1_tpu.config.experiment import WatchlistError, load_experiment

    try:
        exp, params, scheduler = load_experiment(args.config)
    except WatchlistError as e:
        # Structured config rejection (EXIT_CONFIG via argparse), not a
        # traced-shape crash deep in the engine: a typo'd probe target is
        # a config error like any other.
        ap.error(str(e))
    if args.faults == "off":
        exp.faults = None
    if args.watch:
        # CLI watch targets resolve through the SAME path as the config's
        # 'probes:' section (names via exp.dns), then merge with it —
        # duplicates collapse, config entries keep first-seen order.
        import dataclasses

        from shadow1_tpu.config.experiment import resolve_watchlist

        try:
            extra = resolve_watchlist(list(args.watch), exp.dns,
                                      params.sockets_per_host)
        except WatchlistError as e:
            ap.error(str(e))
        merged = list(params.probes)
        merged += [p for p in extra if p not in merged]
        params = dataclasses.replace(params, probes=tuple(merged))
    if args.metrics_ring is not None:
        import dataclasses

        params = dataclasses.replace(params, metrics_ring=args.metrics_ring)
    engine_kind = args.engine or scheduler
    if args.state_digest is not None:
        import dataclasses

        params = dataclasses.replace(
            params, state_digest=int(args.state_digest == "on"))
    if args.link_telem is not None:
        import dataclasses

        params = dataclasses.replace(
            params, link_telem=int(args.link_telem == "on"))
    if (params.state_digest and params.metrics_ring <= 0
            and args.metrics_ring is None and engine_kind != "cpu"):
        # The digest words are ring columns on the batched engines; give the
        # stream a transport when neither config nor flags did (depth = the
        # heartbeat chunk keeps the drain gap-free). An EXPLICIT
        # --metrics-ring 0 is honored and fails loudly in the engine's
        # state_digest-needs-a-ring check instead.
        import dataclasses

        params = dataclasses.replace(
            params, metrics_ring=args.heartbeat or 64)
    if (params.probes and params.metrics_ring <= 0
            and args.metrics_ring is None and engine_kind != "cpu"):
        # Probe rows ride their own [W, K, F] ring but reuse the telemetry
        # ring's depth knob — same auto-enable rule as --state-digest
        # (explicit --metrics-ring 0 fails loudly in check_probe_params).
        import dataclasses

        params = dataclasses.replace(
            params, metrics_ring=args.heartbeat or 64)
    if args.on_overflow is not None:
        import dataclasses

        params = dataclasses.replace(params, on_overflow=args.on_overflow)
    if args.selfcheck:
        import dataclasses

        params = dataclasses.replace(params, selfcheck=1)
    if args.on_lane_fail is not None or args.lane_finalize:
        import dataclasses

        if not args.fleet:
            ap.error("--on-lane-fail/--lane-finalize are fleet lane "
                     "policies; they need --fleet")
        repl = {}
        if args.on_lane_fail is not None:
            repl["on_lane_fail"] = args.on_lane_fail
        if args.lane_finalize:
            repl["lane_finalize"] = 1
        params = dataclasses.replace(params, **repl)
    auto_caps = bool(args.auto_caps or params.auto_caps)
    if engine_kind == "cpu" and (args.save_state or args.resume
                                 or args.heartbeat or args.tracker
                                 or args.profile or args.ckpt
                                 or args.trace or args.metrics_ring
                                 or args.auto_caps
                                 or args.on_overflow == "retry"
                                 or args.on_oom == "downshift"):
        ap.error("--save-state/--resume/--heartbeat/--tracker/--profile/"
                 "--ckpt/--trace/--metrics-ring/--auto-caps/"
                 "--on-overflow retry/--on-oom downshift require a batched "
                 "engine (tpu or sharded)")
    if args.fleet:
        bad = [f for f, v in (("--tracker", args.tracker),
                              ("--summary", args.summary)) if v]
        if bad:
            ap.error(f"--fleet does not support {', '.join(bad)}: "
                     f"per-experiment tracker/summary are a follow-up; use "
                     f"the fleet_exp records and --metrics-ring "
                     f"(per-experiment rows)")
        from shadow1_tpu.fleet.expand import FleetConfigError

        def _fleet_config_exit(e: FleetConfigError) -> int:
            """Structured fleet rejection: message on stderr, one
            parseable JSON record on stdout, config exit code."""
            print(f"FleetConfigError: {e}", file=sys.stderr, flush=True)
            print(json.dumps({"error": "fleet_config", "kind": e.kind,
                              "knob": e.knob, "message": str(e)}))
            return EXIT_CONFIG
        if engine_kind != "tpu":
            return _fleet_config_exit(FleetConfigError(
                f"--fleet batches the single-device tpu engine; "
                f"engine={engine_kind!r} is not composable with the "
                f"experiment axis yet (run the sweep's experiments solo "
                f"on that engine, or drop --engine)", kind="mode",
                knob="engine"))
        # --auto-caps and --on-overflow retry were kind="mode" rejections
        # through PR 12 — both now run fleet-wide (the [E, ...] pytree is
        # the transaction unit; docs/SEMANTICS.md §"Fleet recovery
        # contract"), so the only remaining mode rejection is the engine
        # selector above.
        # Validate the sweep BEFORE any supervision/backend work: a
        # malformed sweep must fail once in the parent, not crash-loop
        # supervised children.
        from shadow1_tpu.fleet.expand import load_sweep

        try:
            fleet_plan = load_sweep(args.config)
        except FleetConfigError as e:
            return _fleet_config_exit(e)
        if args.faults == "off":
            # Healthy-world A/B, fleet-shaped: strip every experiment's
            # fault schedule (including ones a vary[] entry added) exactly
            # like the solo path strips exp.faults; legacy per-group
            # stop_time churn stays, same as solo.
            for fexp in fleet_plan.exps:
                fexp.faults = None
    if args.ckpt and args.resume and args.windows is not None:
        # Under supervision --windows is the TOTAL for the whole run; under
        # --resume it means N MORE windows. Combining all three makes a
        # respawned child's remaining-window arithmetic ambiguous — refuse.
        ap.error("--ckpt with both --resume and --windows is ambiguous "
                 "(total or N-more?); drop one of them")
    if args.ckpt_keep < 1:
        ap.error("--ckpt-keep must be >= 1")
    if args.ckpt and not args.supervised_child:
        # Parent side of fault tolerance: never init the accelerator here —
        # all device work happens in supervised children.
        import os as _os

        watchdog_s = (args.watchdog_s if args.watchdog_s is not None
                      else float(_os.environ.get("SHADOW1_WATCHDOG_S", "0")))
        rc = _supervise(argv, args.ckpt, args.config, watchdog_s=watchdog_s)
        from shadow1_tpu.platform import assert_backend_untouched

        assert_backend_untouched("the --ckpt supervisor")
        return rc
    # The batched engines run on whatever platform jax picks
    # (JAX_PLATFORMS, else the accelerator); a backend that fails to come
    # up ends the process. The CPU oracle needs jax too (it mirrors the
    # RNG streams) but must never take the accelerator.
    from shadow1_tpu.platform import describe, force_cpu

    if engine_kind == "cpu":
        force_cpu(1)
    from shadow1_tpu.telemetry import CompileMeter

    compiles = CompileMeter()
    from shadow1_tpu.log import SimLogger

    log = SimLogger(level=args.log_level)
    log.info("experiment loaded", hosts=exp.n_hosts, engine=engine_kind,
             window_ns=exp.window)
    t0 = time.perf_counter()
    metrics0: dict[str, int] = {}
    resume_path = None
    controller = None
    guard = None

    from shadow1_tpu import mem
    from shadow1_tpu.txn import CapacityExceededError

    def _memory_exit(e: mem.MemoryBudgetError) -> int:
        """Pre-flight budget rejection: full advice on stderr, one
        parseable JSON error record on stdout, the dedicated exit code the
        supervisor classifies as deterministic (no respawn) — the exact
        shape of the capacity-halt taxonomy (docs/SEMANTICS.md)."""
        print(f"MemoryBudgetError: {e}", file=sys.stderr, flush=True)
        print(json.dumps({
            "error": "memory_budget",
            "estimated": e.estimated,
            "budget": e.budget,
            "budget_source": e.budget_source,
            "planes": e.planes,
            "peaks": e.peaks,
            "advice": e.advice,
        }))
        return EXIT_MEMORY

    def _memory_exit_runtime(e, phase: str = "run") -> int:
        """Runtime OOM taxonomy: a RESOURCE_EXHAUSTED that slipped past
        the estimate (transients beyond the model, concurrent tenants)
        still exits structured — phase-tagged record on stdout, pointer at
        the estimator's advice on stderr, EXIT_MEMORY for the supervisor's
        deterministic classification."""
        phase = getattr(e, "shadow1_oom_phase", phase)
        print(f"[mem] device memory exhausted during {phase}: "
              f"{str(e)[:2000]}", file=sys.stderr, flush=True)
        print(f"[mem] deterministic config-vs-device condition — rerun "
              f"with --on-oom downshift, shrink the dominant plane (the "
              f"mem record above attributes bytes per plane), or probe "
              f"the feasible envelope: python -m "
              f"shadow1_tpu.tools.memprobe {args.config} --maxfit",
              file=sys.stderr, flush=True)
        print(json.dumps({
            "error": "memory_exhausted",
            "phase": phase,
            "message": str(e)[:500],
        }))
        return EXIT_MEMORY

    # ---- pre-flight memory budget (shadow1_tpu/mem.py) -------------------
    # Estimate the full device-byte footprint from the config ALONE (an
    # abstract trace — no state-sized allocation) and compare it against
    # the backend's reported memory before any compile is attempted. The
    # one parseable ``mem`` record per run feeds heartbeat_report's memory
    # section; the estimate failing soft (warning) can never block a
    # runnable config.
    sub_batch = None
    pre_downshift_retry = False  # retry demoted by a memory downshift
    if engine_kind != "cpu":
        import os as _osm

        n_exp = len(fleet_plan.exps) if args.fleet else 1
        n_dev = 1
        if engine_kind == "sharded":
            import jax as _jaxm

            n_dev = len(_jaxm.devices())
        est_exp = fleet_plan.exps[0] if args.fleet else exp
        budget, budget_src = mem.device_budget()
        mem_est = None
        try:
            mem_est = mem.estimate(est_exp, params, n_exp=n_exp,
                                   n_dev=n_dev)
        except Exception as est_err:  # noqa: BLE001 — estimator fails soft
            log.warning("memory estimate unavailable", error=repr(est_err))
        if mem_est is not None:
            print(json.dumps(mem_est.record(budget, budget_src,
                                            engine=engine_kind)),
                  file=sys.stderr, flush=True)
            if budget is not None and mem_est.peak_bytes > budget:
                if args.on_oom == "downshift":
                    try:
                        pre_downshift_retry = params.on_overflow == "retry"
                        # --save-state gates like --ckpt/--resume: a
                        # shrunk ring would write a snapshot no
                        # same-config engine could load back. Sub-batching
                        # DOES compose with --ckpt (per-batch snapshots +
                        # the batch cursor in the lineage manifest) but
                        # not with an explicit --resume/--save-state path,
                        # which has no cursor and no all-lane state.
                        params, sub_batch, ds_actions = mem.downshift(
                            est_exp, params, n_exp, budget, n_dev=n_dev,
                            resumable=bool(args.ckpt or args.resume
                                           or args.save_state),
                            subbatch_resumable=bool(
                                args.ckpt and not args.resume
                                and not args.save_state))
                    except mem.MemoryBudgetError as e:
                        return _memory_exit(e)
                    ds_est = mem.estimate(est_exp, params,
                                          n_exp=sub_batch or n_exp,
                                          n_dev=n_dev)
                    print(json.dumps(mem.downshift_record(
                        ds_actions, ds_est.peak_bytes, budget)),
                        file=sys.stderr, flush=True)
                    for a in ds_actions:
                        log.warning("memory downshift", **a)
                else:
                    try:
                        mem.check_budget(mem_est, budget, budget_src)
                    except mem.MemoryBudgetError as e:
                        return _memory_exit(e)
        if _osm.environ.get("SHADOW1_MEM_INJECT_OOM") == "raw":
            # Test hook for the supervisor's belt-and-braces stderr scan:
            # die like a crash the structured taxonomy never saw — raw
            # marker on stderr, generic nonzero exit.
            print("FATAL: RESOURCE_EXHAUSTED: injected raw OOM (test hook)",
                  file=sys.stderr, flush=True)
            raise SystemExit(1)

    def _capacity_exit(e: CapacityExceededError) -> int:
        """Structured halt: full advice on stderr, one parseable JSON error
        record on stdout, the dedicated exit code the supervisor classifies
        as deterministic (no respawn)."""
        print(f"CapacityExceededError: {e}", file=sys.stderr, flush=True)
        print(json.dumps({
            "error": "capacity_exceeded",
            "knob": e.knob,
            "counter": e.counter,
            "cap": e.cap,
            "overflow": e.overflow,
            "windows": list(e.window_range),
            "recommended": e.recommended,
        }))
        return EXIT_CAPACITY

    def _preempted_exit(e, resumed=False) -> int:
        """Graceful-drain exit: the in-flight chunk was committed (and the
        final snapshot written when the run carries --ckpt) before this
        point — print the parseable stdout record and exit the dedicated
        code the supervisor classifies as clean-resume (no backoff, no
        crash accounting; the preemption contract, docs/SEMANTICS.md)."""
        e.ckpt = args.ckpt  # the chunk runner below this layer doesn't know
        print(f"[preempt] drain complete after {e.signame}: "
              f"{e.done_windows} window(s) committed, "
              f"sim_ns={e.win_start}"
              + (f", snapshot {args.ckpt}" if args.ckpt
                 else ", no checkpoint path"),
              file=sys.stderr, flush=True)
        print(json.dumps({
            "preempted": True,
            "signal": e.signame,
            "windows_done": e.done_windows,
            "win_start": e.win_start,
            "ckpt": args.ckpt,
            "resumed": bool(resumed),
        }))
        return EXIT_PREEMPTED

    if args.fleet:
        from shadow1_tpu.fleet.expand import FleetConfigError

        try:
            return _fleet_main(args, params, fleet_plan, log, t0,
                               _capacity_exit, _preempted_exit,
                               _memory_exit_runtime, sub_batch=sub_batch,
                               auto_caps=auto_caps,
                               pre_downshift_retry=pre_downshift_retry)
        except FleetConfigError as e:
            # Late rejections (FleetEngine construction) use the same
            # structured exit as the early validation block above.
            return _fleet_config_exit(e)

    if engine_kind == "cpu":
        from shadow1_tpu.cpu_engine import CpuEngine

        if auto_caps:
            # Config-level engine.auto_caps follows the engine.metrics_ring
            # precedent — inert on the oracle (so a shared config still runs
            # under --engine cpu) — but say so; the explicit --auto-caps
            # flag errors above like every other batched-only flag.
            log.warning("engine.auto_caps ignored: the cpu oracle runs "
                        "eagerly per event, there is no chunked window loop "
                        "to retune")
        if params.on_overflow == "retry":
            # Same precedent: config-level retry stays inert on the oracle
            # (it cannot re-run a window); the explicit flag errors above.
            log.warning("engine.on_overflow=retry ignored: the eager cpu "
                        "oracle cannot replay a window; halt and "
                        "--selfcheck apply as boundary checks")
        eng = CpuEngine(exp, params)
        try:
            metrics = eng.run(n_windows=args.windows)
        except CapacityExceededError as e:
            return _capacity_exit(e)
        summary = eng.summary()
        n_windows = args.windows if args.windows is not None else eng.n_windows
        if params.state_digest:
            # The oracle's per-window digest stream (REC_DIGEST rows) — the
            # comparand for the batched engines' ring dg_* columns.
            for rec in eng.digest_rows:
                print(json.dumps(rec), file=sys.stderr)
        if eng.work_rows:
            # The oracle's per-window wasted-work stream (REC_WORK rows) —
            # the comparand for the batched engines' RING_WORK columns.
            # Enabled by a config-level engine.metrics_ring (the --metrics-
            # ring FLAG stays batched-only: there is no on-device ring
            # here, only its per-window mirror).
            for rec in eng.work_rows:
                print(json.dumps(rec), file=sys.stderr)
        if eng.probe_rows:
            # The oracle's per-window flow-probe stream (REC_FLOW rows) —
            # the comparand for the batched engines' probe-ring records
            # (--watch works here directly: no ring needed, the oracle
            # samples the boundary state straight into rows).
            for rec in eng.probe_rows:
                print(json.dumps(rec), file=sys.stderr)
        if eng.link_rows:
            # The oracle's cumulative per-edge link stream (REC_LINK rows)
            # — the comparand for the batched engines' link records.
            for rec in eng.link_rows:
                print(json.dumps(rec), file=sys.stderr)
    else:
        import jax

        if engine_kind == "sharded":
            from shadow1_tpu.shard.engine import ShardedEngine as Eng
        else:
            from shadow1_tpu.core.engine import Engine as Eng
        try:
            eng = Eng(exp, params)
        except Exception as e:
            # Engine construction allocates the ctx constants (and the
            # restart capture) — an exhaustion here maps to the memory
            # taxonomy like everything later.
            if mem.is_oom(e):
                return _memory_exit_runtime(e, phase="init")
            raise
        st = None
        # A --ckpt snapshot on disk wins over --resume: it is the newer
        # state a supervised respawn must continue from. The lineage
        # resolve walks head → older generations and lands on the newest
        # one that passes its integrity digest (discarding corrupt newer
        # ones so they can never rotate back into the set).
        import os

        resolved, ckpt_lineage, resume_path = _resolve_ckpt_lineage(args, log)
        if resume_path:
            from shadow1_tpu.ckpt import (
                CorruptCheckpointError,
                load_state,
                snapshot_caps,
            )

            params0, eng0 = params, eng
            try:
                template = eng.init_state()
                if (auto_caps or params.on_overflow == "retry"
                        or pre_downshift_retry):
                    # pre_downshift_retry: snapshots from BEFORE a memory
                    # downshift demoted retry→halt may still carry
                    # retry-grown caps — keep the cap-migration path
                    # alive across the demotion.
                    # An --auto-caps run checkpoints at whatever cap it had
                    # grown to — and so does an --on-overflow retry run
                    # (retry-driven grows stick); a host may hold more
                    # events than the config's static cap, so the respawned
                    # engine must START at the snapshot's caps (the
                    # controller re-shrinks later if the occupancy allows)
                    # — otherwise every respawn would die in the
                    # shrink-refuses-to-drop-events check.
                    snap = snapshot_caps(template, resume_path)
                    if snap and snap != (params.ev_cap, params.outbox_cap):
                        import dataclasses

                        params = dataclasses.replace(
                            params, ev_cap=snap[0], outbox_cap=snap[1])
                        eng = Eng(exp, params)
                        template = eng.init_state()
                st = load_state(template, resume_path)
            except CorruptCheckpointError as e:
                # Supervised child: a damaged snapshot must not crash-loop
                # the respawn budget — fall back to a fresh start (the
                # supervisor pre-verifies too; this covers corruption in
                # between, at no extra hashing on the healthy path). An
                # explicit --resume keeps failing loudly instead.
                if resolved is None:
                    raise
                log.warning("discarding corrupt checkpoint",
                            path=resume_path, reason=str(e))
                st, params, eng = None, params0, eng0
                resume_path, resolved = None, None
            else:
                metrics0 = Eng.metrics_dict(st)
                done = int(st.win_start) // exp.window
                if resolved is not None:
                    _emit_resume_record(args.ckpt, resolved,
                                        int(st.win_start), ckpt_lineage)
                if args.windows is None:
                    # Complete the configured run: only the windows
                    # remaining after the checkpoint, not n_windows again
                    # on top of it.
                    args.windows = max(eng.n_windows - done, 0)
                elif resolved is not None:
                    # Supervised respawn: --windows is the TOTAL for the
                    # whole supervised run, not N more on top of the
                    # snapshot.
                    args.windows = max(args.windows - done, 0)
        ring_w = params.metrics_ring
        if auto_caps:
            from shadow1_tpu.tune import CapController

            controller = CapController(eng, lambda p: Eng(exp, p),
                                       log=log.info, initial_state=st)
        if params.on_overflow in ("retry", "halt"):
            from shadow1_tpu.txn import OverflowGuard

            # Shares the controller's engine cache when --auto-caps is on,
            # and reports retry-driven grows to its lossless floor — the
            # two planes never double-grow or oscillate (tune/autocap.py).
            guard = OverflowGuard(eng, make_engine=lambda p: Eng(exp, p),
                                  mode=params.on_overflow,
                                  controller=controller, log=log.info)
        from shadow1_tpu.preempt import DrainHandler, PreemptedExit

        try:
            with _run_traced(args, eng) as phases:
                if os.environ.get("SHADOW1_MEM_INJECT_OOM") == "run":
                    raise RuntimeError(
                        "RESOURCE_EXHAUSTED: injected (test hook)")
                # phases covers --profile too: its phases.trace.json must
                # carry real spans, so any profiled run routes through the
                # instrumented chunk runner. --auto-caps needs the chunked
                # path too (resizes happen at chunk boundaries), as do the
                # overflow policy and --selfcheck (both are chunk-boundary
                # checks).
                if (args.heartbeat or args.ckpt or ring_w
                        or params.link_telem
                        or phases is not None or controller is not None
                        or guard is not None or params.selfcheck):
                    from shadow1_tpu.obs import run_with_heartbeat

                    # Signal plane: SIGTERM/SIGINT request a graceful
                    # drain, honored at the next chunk boundary (only the
                    # chunked path has boundaries to drain at — a plain
                    # eng.run keeps the default die-on-signal behavior).
                    drain = DrainHandler().install()
                    st, _hb = run_with_heartbeat(
                        eng, st, n_windows=args.windows,
                        # Ring-only runs chunk at the ring depth so the
                        # drain keeps up with the overwrites: gap-free
                        # per-window records without --heartbeat.
                        every_windows=args.heartbeat or (ring_w or None),
                        # --ckpt/--trace without --heartbeat chunk the run
                        # but emit no heartbeat lines; ring records always
                        # flow when the ring is on.
                        stream=None if (args.heartbeat or ring_w
                                        or params.link_telem) else False,
                        ckpt_path=args.ckpt, ckpt_every_s=args.ckpt_every_s,
                        profiler=phases,
                        emit_heartbeat=bool(args.heartbeat),
                        emit_ring=bool(ring_w or params.link_telem),
                        controller=controller,
                        guard=guard,
                        selfcheck=bool(params.selfcheck),
                        ckpt_keep=args.ckpt_keep,
                        drain=drain,
                    )
                else:
                    st = eng.run(st, n_windows=args.windows)
                jax.block_until_ready(st)
        except CapacityExceededError as e:
            return _capacity_exit(e)
        except PreemptedExit as e:
            return _preempted_exit(e, resumed=bool(resume_path))
        except Exception as e:
            # Runtime OOM taxonomy: the compile warmup tags its own phase
            # (obs.py); anything later is a run-time allocation failure.
            if mem.is_oom(e):
                return _memory_exit_runtime(e)
            raise
        if args.save_state:
            from shadow1_tpu.ckpt import save_state

            save_state(st, args.save_state)
        # Close the memory loop: when the backend reports its measured
        # allocation high-water (TPU/GPU memory_stats), one final mem
        # record pairs it with the pre-flight estimate —
        # heartbeat_report's memory section prints estimated vs reported.
        peak_in_use = mem.device_peak_in_use()
        if peak_in_use is not None:
            print(json.dumps({
                "type": "mem", "event": "final",
                "peak_in_use": peak_in_use,
                "estimated_peak": (mem_est.peak_bytes
                                   if mem_est is not None else None),
            }), file=sys.stderr, flush=True)
        metrics = Eng.metrics_dict(st)
        summary = eng.model_summary(st)
        n_windows = args.windows if args.windows is not None else eng.n_windows
        if args.tracker:
            from shadow1_tpu.log import tracker_records

            with open(args.tracker, "w") as f:
                for rec in tracker_records(eng, st):
                    f.write(json.dumps(rec) + "\n")

    wall = time.perf_counter() - t0
    sim_s = n_windows * exp.window / 1e9
    if guard is not None:
        # The retry plane's host-side counters ride the same metrics
        # namespace (telemetry.registry HOST_FIELDS).
        metrics = {**metrics, "chunk_retries": guard.chunk_retries,
                   "retry_windows_rerun": guard.retry_windows_rerun}
    # Rates cover THIS invocation: under --resume, cumulative checkpointed
    # metrics are baselined out.
    ev_run = metrics["events"] - metrics0.get("events", 0)
    out = {
        "engine": engine_kind,
        # Where it ran, as jax reports it: "engine" names the code path,
        # these three name the device (a "tpu" engine on a cpu platform is
        # a CPU run).
        **describe(),
        "hosts": exp.n_hosts,
        "window_ns": exp.window,
        "windows": n_windows,
        "sim_seconds": round(sim_s, 6),
        "wall_seconds": round(wall, 3),
        # Of wall_seconds, what jax spent tracing/lowering/compiling (or
        # fetching from the persistent cache); the rest is init + run.
        "compile": compiles.finish(),
        "sim_per_wall": round(sim_s / wall, 3) if wall > 0 else None,
        "events_per_sec": round(ev_run / wall, 1) if wall > 0 else None,
        "resumed": bool(resume_path),
        # The caps the run STARTED at — with metrics.ev_max_fill etc. this
        # is what tools/captune.py computes over-provisioning factors from.
        "caps": {
            "ev_cap": params.ev_cap,
            "outbox_cap": params.outbox_cap,
            "compact_cap": params.compact_cap,
            "msgq_pool": params.mq_pool,
        },
        "metrics": {k: int(v) for k, v in metrics.items()},
    }
    # Drop accounting, grouped by reason (telemetry.registry.DROP_FIELDS) —
    # the same structured block heartbeat records carry, with run totals.
    from shadow1_tpu.telemetry.registry import DROP_FIELDS

    drops = {f: int(metrics.get(f, 0)) for f in DROP_FIELDS}
    out["drops"] = {"total": sum(drops.values()), **drops}
    # Wasted-work accounting run totals (performance attribution plane):
    # the per-window boundary samples summed over the run, with the
    # denominators for utilization fractions — the heartbeat ``work`` block
    # with run scope (tools/heartbeat_report.py reads n_hosts from here).
    work = {f: int(metrics.get(f, 0))
            for f in ("active_hosts", "elig_events", "outbox_hosts")}
    n_win_total = int(metrics.get("windows", 0))
    if any(work.values()):
        out["work"] = {**work, "n_hosts": exp.n_hosts}
        if n_win_total:
            out["work"]["active_frac"] = round(
                work["active_hosts"] / (n_win_total * exp.n_hosts), 6)
    # Fault plane run totals (schema mirrors the heartbeat ``faults`` block).
    restarts = int(metrics.get("host_restarts", 0))
    fault_drops = {k: drops[k] for k in
                   ("down_events", "down_pkts", "link_down_pkts")}
    if restarts or any(fault_drops.values()):
        out["faults"] = {"host_restarts": restarts, **fault_drops}
    if guard is not None:
        # Run totals of the transactional plane: always present under a
        # non-drop policy (chunk_retries == 0 is the explicit "no chunk
        # was ever tainted" signal), with the per-retry audit log.
        out["retries"] = {**guard.report(), "resizes": guard.resizes}
    if controller is not None:
        out["auto_caps"] = {
            "resizes": controller.resizes,
            "final": controller.final_caps or {"ev_cap": params.ev_cap,
                                               "outbox_cap": params.outbox_cap},
        }
    if args.summary:
        out["summary"] = {
            k: int(v) for k, v in summary.items()
            if getattr(v, "ndim", 1) == 0 or isinstance(v, (int, float))
        }
    _chunks_block(out)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
