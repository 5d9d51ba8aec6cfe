"""Run the five-rung BASELINE benchmark ladder and record the results.

    python bench_ladder.py [rung ...] [--windows N] [--budget-s S] [--json PATH]

For each rung config (configs/rung*.yaml): run the batched engine on the
platform jax picks (the row names it) with chunked timing — compile excluded,
overflow counters recorded (the parity contract requires them to be 0; a
nonzero count means the rung's capacity knobs need retuning, and the row
says so) — and the sequential CPU oracle on a bounded slice of the same
experiment for the events/sec comparison (the oracle is O(events) Python;
its slice and the extrapolation basis are recorded in the row).

Fault tolerance: a device fault can wedge the whole process (after a
fault, even fresh small programs fail until re-init). So every rung runs
in a CHILD process that checkpoints engine state to disk after each chunk;
on a fault the child exits and the parent respawns a fresh child that
resumes from the checkpoint — determinism makes the resumed run identical
to an uninterrupted one (docs/SEMANTICS.md; tests/test_ckpt_obs.py). Timed
walls accumulate across children; every child's compile time is excluded
and reported separately. ``--budget-s`` bounds each rung's *timed* wall:
the rung stops at a chunk boundary once exceeded and the row records how
many of the configured windows were measured (status "budget").

Output: one JSON line per rung on stdout (plus a human table on stderr),
and with ``--json`` the rows are also written to a file. BASELINE.md's
results table is generated from these rows.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

# rung -> (config, initial chunk). Heavy net rungs start with small chunks:
# tor/bitcoin windows are orders of magnitude heavier than phold/tgen ones,
# and a chunk is what a fault costs to re-run. (The earlier chip path also
# faulted on long single executions; the current one ran 200 rung-3
# windows in one — CHANGES.md PR 22, ROADMAP Speed 7.)
RUNGS = {
    "rung1": ("configs/rung1_filexfer.yaml", 100),
    "rung2": ("configs/rung2_tgen100.yaml", 100),
    "rung3": ("configs/rung3_tor1k.yaml", 20),
    "rung4": ("configs/rung4_tor10k.yaml", 5),
    "rung5": ("configs/rung5_bitcoin5k.yaml", 10),
    # Not a SURVEY rung: the dense-scale crossover exhibit (50k-host tgen
    # mesh, ~5e5 events/window). Run SLICED (--windows 100): the full 20 s
    # sim is hours of eager-engine wall; throughput is the metric.
    "dense": ("configs/dense_tgen50k.yaml", 10),
}
ORACLE_EVENT_BUDGET = 200_000  # stop the oracle slice near this many events
SAVE_EVERY_S = 300.0           # checkpoint throttle (timed-wall seconds):
                               # a rung-4 save moves >1 GB of state to the
                               # host; 300 s bounds that overhead while
                               # risking ≤5 min of re-execution per fault.
MAX_RESPAWNS = 8               # fresh-process resumes per rung (each pays
                               # a full recompile; the budget bounds only
                               # the timed wall)
RC_FAULT = 3                   # child: device fault, checkpoint is resumable


# --------------------------------------------------------------------------
# Child: run one rung (possibly resuming), checkpoint each chunk, report.
# --------------------------------------------------------------------------
def child_main(name: str, path: str, state_path: str, report_path: str,
               total_override: int | None, chunk0: int, budget_s: float,
               engine_spec: str | None = None) -> int:
    import shadow1_tpu  # noqa: F401
    import jax

    from shadow1_tpu import ckpt
    from shadow1_tpu.platform import describe
    from shadow1_tpu.config.experiment import load_experiment
    from shadow1_tpu.core.engine import Engine

    from shadow1_tpu.config.experiment import apply_engine_overrides

    exp, params, _scheduler = load_experiment(path)
    params = apply_engine_overrides(params, engine_spec)
    eng = Engine(exp, params)
    total = total_override or eng.n_windows

    # n_windows is traced, so a zero-window call compiles the exact program
    # every chunk reuses — compile never rides a long device execution.
    t0 = time.perf_counter()
    jax.block_until_ready(eng.run(eng.init_state(), n_windows=0))
    compile_wall = time.perf_counter() - t0

    st = eng.init_state()
    if os.path.exists(state_path):
        st = ckpt.load_state(st, state_path)
    done = int(st.win_start) // exp.window
    status, chunk, faults = "done", chunk0, 0

    def snapshot(s) -> dict:
        """Host-side metrics/summary stash — taken at every checkpoint so a
        fault report never reads from a wedged device."""
        return {
            "metrics": Engine.metrics_dict(s),
            "summary": {
                k: int(v) for k, v in eng.model_summary(s).items()
                if getattr(v, "ndim", 1) == 0
            },
        }

    snap = snapshot(st)

    def report(timed_wall: float, ckpt_wall: float) -> None:
        rec = {
            "status": status, "done": done, "ckpt_done": ckpt_done,
            "total": total,
            "wall_s": timed_wall, "ckpt_s": ckpt_wall,
            "compile_s": compile_wall,
            "chunk_final": chunk, "faults_recovered": faults,
            **describe(), **snap,
        }
        with open(report_path, "w") as f:
            json.dump(rec, f)

    # Timed wall covers ONLY the device execution; checkpoint saves (host
    # transfer + npz write, fault-tolerance overhead) are metered separately
    # in ckpt_s so throughput numbers stay comparable to an unchunked run.
    # Saves are throttled (~every SAVE_EVERY_S of timed wall): at rung-4
    # scale the state is >1 GB and a per-chunk save would dwarf the run.
    # On a fault, up to SAVE_EVERY_S of windows re-execute
    # from the last save — deterministically identical, wall double-counted
    # (events are not), so throughput errs toward underreporting.
    timed = ckpt_s = last_save = 0.0
    ckpt_done = done
    while done < total:
        step = min(chunk, total - done)
        try:
            t0 = time.perf_counter()
            nxt = eng.run(st, n_windows=step)
            jax.block_until_ready(nxt)
            timed += time.perf_counter() - t0
            st, done = nxt, done + step
            if done >= total or timed - last_save > SAVE_EVERY_S:
                t0 = time.perf_counter()
                ckpt.save_state(st, state_path)
                ckpt_s += time.perf_counter() - t0
                last_save = timed
                ckpt_done = done
                snap = snapshot(st)
        except Exception:  # noqa: BLE001 — jax runtime faults
            faults += 1
            if chunk <= 5 or faults > 4:
                # Process may be wedged: report resumable and bail out.
                # Windows/metrics roll back to the last checkpoint (what the
                # resume will continue from); the wall spent past it stays
                # counted, erring toward underreported throughput.
                status = "fault"
                done = ckpt_done
                report(timed, ckpt_s)
                return RC_FAULT
            chunk = max(5, chunk // 4)
            continue
        if timed > budget_s and done < total:
            status = "budget"
            if ckpt_done < done:
                t0 = time.perf_counter()
                ckpt.save_state(st, state_path)
                ckpt_s += time.perf_counter() - t0
                ckpt_done = done
                snap = snapshot(st)
            break
    report(timed, ckpt_s)
    return 0


# --------------------------------------------------------------------------
# Parent: respawn children across faults, aggregate walls, add the oracle.
# --------------------------------------------------------------------------
def run_rung(name: str, path: str, windows_override: int | None,
             chunk0: int, budget_s: float, workdir: str, rep: int = 0,
             engine_spec: str | None = None) -> dict:
    state_path = os.path.join(workdir, f"{name}.r{rep}.state.npz")
    report_path = os.path.join(workdir, f"{name}.r{rep}.report.json")
    wall = compile_total = ckpt_total = 0.0
    faults_total = respawns = 0
    rec = None
    last_done = -1
    for attempt in range(MAX_RESPAWNS + 1):
        # Each child gets only the budget remaining after its predecessors,
        # so a faulting rung's AGGREGATE timed wall honors --budget-s: once
        # it is spent, no further child runs (round-3 advisor: the old 30 s
        # floor let a repeatedly-faulting rung overshoot the budget by up to
        # (MAX_RESPAWNS+1)*30 s).
        remaining = budget_s - wall
        if remaining <= 0:
            if rec is None:
                raise RuntimeError(
                    f"--budget-s {budget_s} leaves no time for any child run"
                )
            # Only a faulted child re-enters this loop, so rec["status"] is
            # "fault" here; keep it — the run ended on an unrecovered fault.
            break
        cmd = [sys.executable, __file__, "--child", name,
               "--state", state_path, "--report", report_path,
               "--chunk", str(chunk0),
               "--budget-s", str(remaining)]
        if windows_override:
            cmd += ["--windows", str(windows_override)]
        if engine_spec:
            cmd += ["--engine", engine_spec]
        r = subprocess.run(cmd, capture_output=True, text=True)
        if not os.path.exists(report_path):
            raise RuntimeError(
                f"child died without a report (rc={r.returncode}): "
                f"{r.stderr[-800:]}"
            )
        with open(report_path) as f:
            rec = json.load(f)
        os.remove(report_path)
        wall += rec["wall_s"]
        compile_total += rec["compile_s"]
        ckpt_total += rec["ckpt_s"]
        faults_total += rec["faults_recovered"]
        if rec["status"] != "fault":
            break
        if rec["done"] <= last_done:
            # No forward progress across a whole process: stop grinding.
            break
        last_done = rec["done"]
        if attempt == MAX_RESPAWNS:
            break
        respawns += 1
        print(f"[{name}] device fault at {rec['done']}/{rec['total']} "
              f"windows — respawning ({respawns})", file=sys.stderr, flush=True)
    if rec["status"] == "fault":
        # The rung ENDED on a fault: its last counted fault was terminal,
        # not recovered — subtract it so the row is honest (r3 advisor).
        # Faults inside children that a later respawn resumed past stay
        # counted as recovered.
        faults_total = max(faults_total - 1, 0)
    if rec["status"] in ("fault", "budget"):
        # Keep the checkpoint: it is the only resumable artifact — a rerun
        # against a recovered device (fault) or with a deeper budget
        # (budget; round-3 advisor) continues from it instead of starting
        # over.
        print(f"[{name}] status={rec['status']}; resumable checkpoint kept "
              f"at {state_path}", file=sys.stderr, flush=True)
    elif os.path.exists(state_path):
        os.remove(state_path)

    from shadow1_tpu.config.experiment import load_experiment
    from shadow1_tpu.consts import SEC

    exp, _params, _ = load_experiment(path)
    m = rec["metrics"]
    done = rec["done"]
    sim_s = done * exp.window / SEC
    row = {
        "rung": name,
        "config": path,
        "commit": _git_head(),
        "engine_overrides": engine_spec,
        "status": rec["status"],
        "n_hosts": exp.n_hosts,
        "windows": done,
        "windows_configured": rec["total"],
        "sim_s": round(sim_s, 3),
        "platform": rec["platform"],
        "device_kind": rec["device_kind"],
        "n_devices": rec["n_devices"],
        "engine": "tpu-batched",
        "events": m["events"],
        "events_per_sec": round(m["events"] / wall, 1) if wall else None,
        "sim_per_wall": round(sim_s / wall, 4) if wall else None,
        "wall_s": round(wall, 2),
        "ckpt_s": round(ckpt_total, 2),
        "compile_s": round(compile_total, 2),
        "ev_overflow": m["ev_overflow"],
        "ob_overflow": m["ob_overflow"],
        "round_cap_hits": m["round_cap_hits"],
        "rounds_per_window": round(m["rounds"] / max(m["windows"], 1), 2),
        "device_faults_recovered": faults_total,
        "process_respawns": respawns,
    }
    if rec["status"] in ("fault", "budget"):
        row["resume_checkpoint"] = state_path
    for k in ("total_flows_done", "total_streams_done", "clients_done",
              "total_cells_fwd", "total_rx_bytes", "total_seen"):
        if k in rec["summary"]:
            row[k] = rec["summary"][k]
    return row


def _git_head() -> str:
    """Commit the measurement ran at — recorded in each row so renders never
    misattribute numbers to a later HEAD."""
    r = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                       capture_output=True, text=True,
                       cwd=os.path.dirname(os.path.abspath(__file__)))
    return r.stdout.strip() or "?"


def run_cpp_comparator(name: str, path: str, tpu_row: dict,
                       engine_spec: str | None = None) -> dict:
    """The honest thread-per-core C++ baseline on the same rung, same window
    count — its counters bit-match both engines (tests/test_native_
    comparator.py), so its wall clock is the denominator of the north-star
    claim (BASELINE.json; SURVEY §7.3.5)."""
    import os as _os

    from shadow1_tpu import native
    from shadow1_tpu.config.experiment import apply_engine_overrides, load_experiment

    exp, params, _ = load_experiment(path)
    params = apply_engine_overrides(params, engine_spec)
    windows = tpu_row["windows"]
    if not windows:
        return {"cpp_skipped": "no measured windows"}
    try:
        r = native.run_net(exp, params, windows,
                           n_threads=_os.cpu_count() or 1)
    except native.NativeUnavailable as e:
        return {"cpp_skipped": str(e)[:200]}
    out = {
        "cpp_events": r["events"],
        "cpp_wall_s": round(r["wall_s"], 3),
        "cpp_events_per_sec": r["events_per_sec"],
        "cpp_threads": r["n_threads"],
        # Cross-validation: same windows -> the counters must bit-match the
        # batched engine's row (strong evidence nothing drifted in prod).
        "cpp_events_match": r["events"] == tpu_row["events"],
    }
    if tpu_row.get("events_per_sec") and r["events_per_sec"]:
        out["vs_cpp"] = round(
            tpu_row["events_per_sec"] / r["events_per_sec"], 3
        )
        sim_s = tpu_row["sim_s"]
        if r["wall_s"] > 0:
            out["cpp_sim_per_wall"] = round(sim_s / r["wall_s"], 4)
    return out


def run_oracle_slice(name: str, path: str, tpu_row: dict,
                     engine_spec: str | None = None) -> dict:
    """Bounded oracle run: whole windows until the event budget is hit."""
    from shadow1_tpu.config.experiment import apply_engine_overrides, load_experiment
    from shadow1_tpu.cpu_engine import CpuEngine

    exp, params, _ = load_experiment(path)
    params = apply_engine_overrides(params, engine_spec)
    if exp.n_hosts * params.sockets_per_host > 500_000:
        # The eager oracle allocates one Python object per socket; at rung-4
        # scale that is >1M objects — skip rather than swap the box.
        return {"oracle_skipped": f"{exp.n_hosts} hosts x "
                                  f"{params.sockets_per_host} sockets"}
    cpu = CpuEngine(exp, params)
    t0 = time.perf_counter()
    done = 0
    cm = {"events": 0}
    while done < tpu_row["windows"]:
        step = max(1, tpu_row["windows"] // 50)
        cm = cpu.run(n_windows=done + step)
        done += step
        if cm["events"] >= ORACLE_EVENT_BUDGET or time.perf_counter() - t0 > 120:
            break
    wall = time.perf_counter() - t0
    return {
        "oracle_windows": done,
        "oracle_events": cm["events"],
        "oracle_wall_s": round(wall, 2),
        "oracle_events_per_sec": round(cm["events"] / wall, 1) if wall else None,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("rungs", nargs="*", default=None)
    ap.add_argument("--windows", type=int, default=None)
    ap.add_argument("--budget-s", type=float, default=900.0,
                    help="per-rung timed-wall budget (chunk-boundary stop)")
    ap.add_argument("--json", default=None)
    ap.add_argument("--no-oracle", action="store_true")
    ap.add_argument("--engine", default=None,
                    help="EngineParams overrides, e.g. "
                         "'compact_cap=384,pop_extract=gather' (A/B knob)")
    ap.add_argument("--repeats", type=int, default=1,
                    help="measure each rung N times (fresh state per rep); "
                         "the row reports the median-throughput rep plus "
                         "min/median/max across reps; single-run rows "
                         "are labeled n=1")
    # child-mode flags (internal)
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--state", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--report", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--chunk", type=int, default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.child:
        path, _chunk0 = RUNGS[args.child]
        sys.exit(child_main(args.child, path, args.state, args.report,
                            args.windows, args.chunk, args.budget_s,
                            engine_spec=args.engine))

    import shadow1_tpu  # noqa: F401
    from shadow1_tpu.platform import force_cpu

    # The parent runs only the oracle slice and the C++ comparator between
    # children; it must not hold the chip its children need.
    force_cpu(1)

    # "dense" is opt-in (sliced runs only — see RUNGS comment).
    names = args.rungs or [n for n in RUNGS if n != "dense"]
    rows = []
    workdir = tempfile.mkdtemp(prefix="ladder_")
    for name in names:
        path, chunk0 = RUNGS[name]
        try:
            reps = []
            for rep in range(max(args.repeats, 1)):
                r = run_rung(name, path, args.windows, chunk0,
                             args.budget_s, workdir, rep=rep,
                             engine_spec=args.engine)
                reps.append(r)
                if args.repeats > 1:
                    eps_s = (f"{r['events_per_sec']:,.0f} ev/s"
                             if r["events_per_sec"] is not None else "(no wall)")
                    print(f"[{name}] rep {rep + 1}/{args.repeats}: {eps_s}",
                          file=sys.stderr, flush=True)
            # Median-throughput rep is the headline row (lower middle for
            # even N — the conservative pick under wall variance); the
            # spread fields record what variance did to the rest.
            scored = sorted(reps, key=lambda r: r["events_per_sec"] or 0)
            row = scored[(len(scored) - 1) // 2]
            row["repeats"] = len(reps)
            if len(reps) > 1:
                eps = [r["events_per_sec"] for r in reps]
                spw = [r["sim_per_wall"] for r in reps]
                row["events_per_sec_reps"] = eps
                row["sim_per_wall_reps"] = spw
            if not args.no_oracle:
                row.update(run_oracle_slice(name, path, row,
                                            engine_spec=args.engine))
                if row.get("oracle_events_per_sec") and row["events_per_sec"]:
                    row["vs_oracle"] = round(
                        row["events_per_sec"] / row["oracle_events_per_sec"], 2
                    )
            row.update(run_cpp_comparator(name, path, row,
                                          engine_spec=args.engine))
        except Exception as e:  # noqa: BLE001 — record the failure, keep going
            import traceback

            row = {"rung": name, "config": path, "error": repr(e)[:400],
                   "traceback": traceback.format_exc()[-1500:]}
        rows.append(row)
        print(json.dumps(row), flush=True)
        if "error" in row:
            line = f"FAILED: {row['error']}"
        else:
            eps = row["events_per_sec"]
            spw = row["sim_per_wall"]
            line = (
                f"{eps:>12,.0f} ev/s  " if eps is not None else "  (no wall)  "
            ) + (
                f"sim/wall {spw:.3f}  " if spw is not None else ""
            ) + (
                f"wall {row['wall_s']}s  "
                f"windows {row['windows']}/{row['windows_configured']}  "
                f"overflow {row['ev_overflow']}+{row['ob_overflow']}  "
                f"respawns {row['process_respawns']}  status {row['status']}"
            )
        print(f"[{name}] {line}", file=sys.stderr, flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
