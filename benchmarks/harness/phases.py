"""Device time by window phase, the program's own spans, and the idle gaps
named by both: what a traced run can say once the program provides the join.

The program (``shadow1_tpu/telemetry/phases.py``) turns the optimized HLO
text of its window program into a table, instruction name → phase path, and
attributes a device's op line to it; its chunk loops put their spans into
any ``jax.profiler`` capture as ``shadow1:<name>``. This file is the
benchmark's side: attribute a capture to the table (``phase_report``), name
each of the longest idle gaps by program span, bracketing ops and their
phases (``gap_report``), and compute the per-layer quantities
(``layer_values``).

Nothing in ``loop.py`` calls this yet: a PR that is not of the benchmark
kind may not edit the harness's files, and ``loop.main`` drops the raw trace
before the readers run (PERF.md §7a3 names the edits that remain). Until it
is wired in, the same reading is a command, on the chip:

    python benchmarks/harness/phases.py --workload <cell> --seed <n>

which sets a cell up as ``run.py`` does, runs its traced stretch once
untraced and once under the profiler, and prints the phase table, the gaps
and the quantities as JSON lines.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import sys
import time

if __name__ == "__main__":      # run as a script from the root of a checkout
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmarks.harness import trace as tr  # noqa: E402

# The program's spans are TraceAnnotations under this prefix.
PROGRAM_PREFIX = "shadow1:"
FIRES = ("fires_pkt", "fires_deliver", "fires_timer", "fires_txr", "fires_app")


def _program():
    """The program's ``telemetry.phases`` (imported late: the command sets
    the compile cache's place before ``shadow1_tpu`` is imported)."""
    from shadow1_tpu.telemetry import phases

    return phases


def read_capture(log_dir: str) -> dict:
    """``trace.read_xplane`` with the program's spans kept beside the
    harness's. It keeps a host event by ``startswith(SPAN_PREFIX)``, which
    takes a tuple as well; this goes when ``read_xplane`` keeps both itself."""
    from unittest import mock

    with mock.patch.object(tr, "SPAN_PREFIX", (tr.SPAN_PREFIX, PROGRAM_PREFIX)):
        return tr.read_xplane(log_dir)


def program_spans(trace: dict) -> list[tuple[str, int, int]]:
    """The program's host spans as ``(name, start_ns, end_ns)``, prefix
    removed, in time order."""
    out = []
    for p in trace["planes"]:
        if p["name"].startswith(tr.DEVICE_PLANE):
            continue
        for ln in p["lines"]:
            out += [(n[len(PROGRAM_PREFIX):], s, s + d)
                    for n, s, d in ln["events"] if n.startswith(PROGRAM_PREFIX)]
    return sorted(out, key=lambda x: x[1])


def main_executions(plane: dict) -> list[tuple[int, int]]:
    """Runs of the window program on a device: the module that took most of
    the time (as ``trace.reduce`` picks it)."""
    mods = tr._line(plane, tr.MODULES_LINE)
    total: dict[str, int] = {}
    for n, _, d in mods:
        total[n] = total.get(n, 0) + d
    if not total:
        return []
    main = max(total, key=total.get)
    return sorted((s, s + d) for n, s, d in mods if n == main)


def device_ops(plane: dict) -> list:
    """The ops of a device's op line by the program's rule
    (``telemetry.phases.ops``): control flow is told by the instruction's
    kind. ``trace.leaves`` takes any event for a container whose successor
    starts before it ends, so a fusion that a zero-length op (an async
    start, a ``ConcatBitcast``) shares its start timestamp with leaves
    ``busy`` and shows as an idle gap inside the execution (PERF.md §3)."""
    return _program().ops(tr._line(plane, tr.OPS_LINE))


def phase_report(trace: dict, table: dict) -> dict:
    """``attribute`` of the first device's op line against ``table``: rows
    by phase path, the roll-up, busy seconds, ``unknown_ops``."""
    plane = tr.device_planes(trace)[0]
    runs = main_executions(plane)
    return _program().attribute(tr._line(plane, tr.OPS_LINE), table,
                                runs or None)


def execution_idle_ns(plane: dict) -> int | None:
    """Idle time inside executions of the window program: of each run, its
    duration less the time a leaf op was running in it."""
    runs = main_executions(plane)
    if not runs:
        return None
    busy = tr.union([(s, s + d) for _, s, d in device_ops(plane)])
    idle = 0
    for r0, r1 in runs:
        inside = sum(min(e, r1) - max(s, r0) for s, e in busy
                     if s < r1 and e > r0)
        idle += (r1 - r0) - inside
    return idle


def gap_report(trace: dict, table: dict, n: int = 5) -> dict:
    """The ``n`` longest idle gaps of the first device's op line, each as
    ``{span, before, after, phase_before, phase_after, seconds,
    inside_execution, other_lines}``: the innermost program span open at its
    middle (else the harness's), the ops on either side by instruction name
    and phase path, and what every other line of the device plane had open
    during it. Also the names of every line the plane has, and under
    ``idle_after`` the ``n`` instructions that idle time follows most:
    ``[instruction, phase, instances, instances followed by idle, seconds]``
    (a gap that recurs is a property of the op before it)."""
    plane = tr.device_planes(trace)[0]
    runs = main_executions(plane)
    prog, harness = program_spans(trace), tr.spans(trace)
    name = _program().instruction_name
    gaps, covered, last = [], None, None
    follows: dict[str, list] = {}    # instruction -> [instances, gaps, ns]
    for ev in device_ops(plane):
        if covered is not None and ev[1] > covered:
            gaps.append((ev[1] - covered, covered, last, ev))
            row = follows[name(last[0])]
            row[1] += 1
            row[2] += ev[1] - covered
        if covered is None or ev[1] + ev[2] > covered:
            covered, last = ev[1] + ev[2], ev
        follows.setdefault(name(ev[0]), [0, 0, 0])[0] += 1
    out = []
    for dur, at, before, after in sorted(gaps, key=lambda g: -g[0])[:n]:
        mid = at + dur // 2
        span = tr.covering_span(prog, mid)
        if span == tr.NO_SPAN:
            span = tr.covering_span(harness, mid)
        others = {}
        for ln in plane["lines"]:
            if ln["name"] in (tr.OPS_LINE, tr.MODULES_LINE):
                continue
            open_ = [name(e[0]) for e in ln["events"]
                     if e[1] < at + dur and e[1] + e[2] > at]
            others[ln["name"]] = {"open": len(open_), "first": open_[:3]}
        b, a = name(before[0]), name(after[0])
        out.append({
            "span": span, "before": b, "after": a,
            "phase_before": table.get(b, ""),
            "phase_after": table.get(a, ""),
            "seconds": dur / 1e9,
            "inside_execution": any(s <= mid < e for s, e in runs),
            "other_lines": others})
    idle_after = [[k, table.get(k, ""), v[0], v[1], v[2] / 1e9]
                  for k, v in sorted(follows.items(), key=lambda kv: -kv[1][2])[:n]]
    return {"lines": [ln["name"] for ln in plane["lines"]], "gaps": out,
            "idle_after": idle_after}


def handler_kinds(table: dict) -> int:
    """How many handler kinds the program has: the distinct ``h_<kind>``
    scopes of its phase table."""
    kinds = {part for path in table.values()
             for part in path.split("/") if part.startswith("h_")}
    return len(kinds)


def useful_pass_share(fires_by_lane: list[int], rounds: int,
                      kinds: int) -> float | None:
    """Mean over lanes of the handler passes that had an event of their kind
    ÷ the handler passes run (loop iterations × kinds), in %. None where the
    model has one handler (its pass is not guarded and not counted)."""
    if kinds < 2 or not rounds or not fires_by_lane:
        return None
    return 100.0 * statistics.mean(fires_by_lane) / (rounds * kinds)


def layer_values(report: dict, trace: dict, red, counters: dict) -> dict:
    """The per-layer quantities this reading gives, under the names ISSUE 25
    gives them. ``counters`` holds ``rounds`` and ``windows`` of the traced
    stretch (as the harness's), and where known ``fires_by_lane`` and
    ``handler_kinds``. A quantity with nothing to read is left out."""
    vals: dict[str, float] = {}
    rounds, windows = counters.get("rounds"), counters.get("windows")
    roll = report["rollup"]
    if windows:
        vals["prepare_ms_per_window"] = 1e3 * roll["prepare"] / windows
        vals["deliver_ms_per_window"] = 1e3 * roll["deliver"] / windows
    if rounds:
        vals["pop_ms_per_round"] = 1e3 * roll["pop"] / rounds
        vals["handlers_ms_per_round"] = 1e3 * roll["handlers"] / rounds
    if report["busy_s"]:
        vals["phase_unattributed_share"] = (
            100.0 * roll["unattributed"] / report["busy_s"])
    idle = execution_idle_ns(tr.device_planes(trace)[0])
    if idle is not None and red.window_ns:
        vals["exec_idle_share"] = 100.0 * idle / red.window_ns
    useful = useful_pass_share(counters.get("fires_by_lane") or [], rounds,
                               counters.get("handler_kinds", 0))
    if useful is not None:
        vals["handler_pass_useful_share"] = useful
    dispatch = [e - s for n, s, e in program_spans(trace) if n == "dispatch"]
    if dispatch:
        vals["dispatch_ms_per_chunk"] = statistics.median(dispatch) / 1e6
    return vals


# ---- the command -----------------------------------------------------------

def _say(**kw) -> None:
    print(json.dumps(kw), flush=True)


def _lane_sums(metrics, names) -> list[int]:
    import numpy as np

    return [int(x) for x in sum(np.asarray(getattr(metrics, n)).reshape(-1)
                                for n in names)]


def _keep(path: str, raw: dict, table: dict, counters: dict) -> None:
    """The capture as a plain dict (an op named by the head of its HLO
    text), and beside it the table's rows for the instructions it holds."""
    import gzip

    name = _program().instruction_name
    for p in raw["planes"]:
        for ln in p["lines"]:
            ln["events"] = [[n[:tr.NAME_CHARS], s, d] for n, s, d in ln["events"]]
    with gzip.open(path, "wt") as f:
        json.dump(raw, f)
    seen = {name(e[0]) for p in tr.device_planes(raw)
            for ln in p["lines"] for e in ln["events"]}
    with open(path.removesuffix(".json.gz") + ".phase_table.json", "w") as f:
        json.dump({"counters": counters, "table": {
            k: v for k, v in sorted(table.items()) if k in seen}}, f, indent=0)


def main(argv, root: str) -> int:
    from benchmarks.harness import loop

    ap = argparse.ArgumentParser(prog="benchmarks/harness/phases.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--root", default=root,
                    help="the directory of the BENCHMARK.json to read "
                         "(benchmarks/tests/rehearsal for a small cell)")
    ap.add_argument("--keep-trace", default=None,
                    help="write the capture, reduced to a plain dict, to "
                         "this .json.gz, and the phase table beside it")
    args = ap.parse_args(argv)
    args.control, root = None, os.path.abspath(args.root)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(root, ".jax_cache"))
    c = loop._load_cell(root, args)

    import jax
    import shadow1_tpu  # noqa: F401  (x64 on, before any jax array)

    from benchmarks.harness import sim as simmod

    device = loop._device(int(c["cell"]["chips"]), True)
    if device is None:
        return loop.EXIT_NO_CHIP
    _say(run=c["cell"]["name"], seed=args.seed, **device)
    doc, base_dir = simmod.experiment_doc(c["cfg_path"], c["meta"], c["traffic"])
    sim = simmod.build(doc, base_dir, c["meta"]["engine"],
                       simmod.lane_seeds(c["traffic"], args.seed))
    jax.block_until_ready(
        loop.run_chunk(sim, sim.engine.init_state(), c["chunk"]))
    t_from, t_to = c["traced"]
    trace_dir = os.path.join(root, ".bench_trace")

    def stretch(traced: bool):
        """Windows 0..t_to from a fresh state, the stretch t_from..t_to on
        the clock and, if ``traced``, under the profiler."""
        st = sim.engine.init_state()
        jax.block_until_ready(st)
        for done in range(0, t_to, c["chunk"]):
            if done == t_from:
                at_from = jax.device_get(st.metrics)
                if traced:
                    shutil.rmtree(trace_dir, ignore_errors=True)
                    jax.profiler.start_trace(trace_dir)
                t0 = time.perf_counter()
            st = loop.run_chunk(sim, st, c["chunk"])
        wall = time.perf_counter() - t0
        if traced:
            jax.profiler.stop_trace()       # writes the capture: seconds
        return (at_from, jax.device_get(st.metrics), wall,
                time.perf_counter() - t0 - wall)

    _, _, wall_off, _ = stretch(False)
    at_from, at_to, wall_on, stop_s = stretch(True)
    t0 = time.perf_counter()
    raw = read_capture(trace_dir)
    read_s = time.perf_counter() - t0
    shutil.rmtree(trace_dir, ignore_errors=True)
    t0 = time.perf_counter()
    table = _program().phase_table(sim.engine.hlo_text())
    hlo_s = time.perf_counter() - t0

    per_window = loop._replay_rounds(sim, c, at_to)
    events = (sum(_lane_sums(at_to, ["events"]))
              - sum(_lane_sums(at_from, ["events"])))
    fires = [b - a for a, b in zip(_lane_sums(at_from, FIRES),
                                   _lane_sums(at_to, FIRES))]
    counters = {"rounds": loop.loop_rounds(per_window),
                "windows": len(per_window), "fires_by_lane": fires,
                "handler_kinds": handler_kinds(table)}
    if args.keep_trace:
        _keep(args.keep_trace, raw, table, counters)
    _say(traced_windows=[t_from, t_to], table_instructions=len(table),
         program_spans=sorted({n for n, _, _ in program_spans(raw)}),
         **counters)
    _say(tracing_overhead={
        "events_in_stretch": events,
        "stretch_wall_s_trace_off": wall_off, "stretch_wall_s_trace_on": wall_on,
        "events_per_s_trace_off": events / wall_off,
        "events_per_s_trace_on": events / wall_on,
        "after_the_stretch_s": {"stop_trace": stop_s, "read_capture": read_s,
                                "hlo_text": hlo_s}})
    red = tr.reduce(raw)
    report = phase_report(raw, table)
    _say(busy_s=red.busy_ns / 1e9, window_s=red.window_ns / 1e9,
         executions=red.executions, phases=report["rows"],
         rollup=report["rollup"], phases_busy_s=report["busy_s"],
         unknown_ops=report["unknown_ops"], inherited_s=report["inherited_s"])
    _say(**gap_report(raw, table))
    _say(layer_values=layer_values(report, raw, red, counters),
         breakdown_phases=[[p, r["seconds"]]
                           for p, r in list(report["rows"].items())[:10]])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))))
