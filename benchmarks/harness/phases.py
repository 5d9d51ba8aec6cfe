"""Device time by window phase, the program's own spans, and the idle gaps
named by both: what a traced run says once the program provides the join.

The program runs every stage of its window under
``jax.named_scope("phase:<name>")``. A TPU trace does not carry those
scopes: it names a device op by its HLO text. They are in the compiled
program, whose every instruction has ``metadata={op_name=".../phase:rounds/
while/body/phase:pop/add"}``; so the phase of a traced op is a join,
instruction name → ``op_name``, made here from the text the engine's
``hlo_text()`` returns (``phase_table``), and ``attribute`` sums a device's
op line by it. Both are this benchmark's own copies of the arithmetic in
``shadow1_tpu/telemetry/phases.py`` (the yardstick lives under the
benchmark's paths; ``tests/test_phase_reading.py`` holds the two to the same
answer on a capture recorded on the chip). The program's chunk loops put
their spans into any ``jax.profiler`` capture as ``shadow1:<name>``.

``loop.main`` calls ``counters_of`` under ``--trace 1`` and hands what it
returns to the per-layer readers as further keys of ``counters``; the full
phase table and the named gaps (``gap_report``) go on an earlier line of the
run's output. The same run is a command of its own, for a capture to keep:

    python benchmarks/harness/phases.py --workload <cell> --seed <n>
        [--root benchmarks/tests/rehearsal] [--keep-trace f.json.gz]
"""

from __future__ import annotations

import json
import os
import re
import sys
import time

if __name__ == "__main__":      # run as a script from the root of a checkout
    _STARTED = time.perf_counter()
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmarks.harness import trace as tr  # noqa: E402

# Per lane: rounds in which a handler pass had an event of its kind.
FIRES = ("fires_pkt", "fires_deliver", "fires_timer", "fires_txr", "fires_app")

PHASE = re.compile(r"phase:(\w+)")
# `  ROOT %fusion.172 = s32[...] fusion(...), ..., metadata={op_name="..."}`
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')

# The fixed roll-up (every row of `attribute` lands in exactly one; `h_<kind>`
# rows repeat their part of `handlers`).
PREPARE, POP, HANDLERS, DELIVER, TELEM = ("prepare", "pop", "handlers",
                                          "deliver", "telem")
ROUNDS_OTHER = "rounds_other"   # the round loop outside pop and handler passes
OTHER = "other"                 # a phase: scope this roll-up does not know
UNATTRIBUTED = "unattributed"   # no phase: on the op nor on what contains it
OTHER_PROGRAMS = "other_programs"   # ops of another module than the table's
_DELIVER_PARTS = ("route", "exchange", "deliver")


def phase_path(op_name: str) -> str:
    """The ``phase:`` components of an HLO ``op_name`` in order, joined by
    ``/``; ``""`` where there is none. ``vmap`` wraps a scope
    (``vmap(phase:rounds)``), so the components are searched for, not split."""
    return "/".join(PHASE.findall(op_name))


def phase_table(hlo_text: str) -> dict[str, str]:
    """Instruction name → phase path for every instruction of every
    computation of an optimized HLO module. An instruction with no metadata
    maps to ``""``."""
    table: dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        op = _OP_NAME.search(line)
        table[m.group(1)] = phase_path(op.group(1)) if op else ""
    return table


def rollup_key(path: str) -> tuple[str, str | None]:
    """The roll-up row of a phase path, and its ``h_<kind>`` sub-row."""
    if not path:
        return UNATTRIBUTED, None
    parts = path.split("/")
    if parts[0] == "rounds":
        kinds = [p for p in parts[1:] if p.startswith("h_")]
        if kinds:
            return HANDLERS, kinds[0]
        return (POP, None) if "pop" in parts[1:] else (ROUNDS_OTHER, None)
    if parts[0] in _DELIVER_PARTS:
        return DELIVER, None
    if parts[0] in (PREPARE, TELEM):
        return parts[0], None
    return OTHER, None


def attribute(events: list, table: dict[str, str],
              executions: list[tuple[int, int]] | None = None) -> dict:
    """Device time by phase. ``events`` is one device's op line, control
    flow included; ``table`` is ``phase_table`` of the program that ran;
    ``executions`` are the ``(start, end)`` of that program's runs where the
    trace holds other programs too (their ops go to ``other_programs``:
    instruction names are unique only inside a module).

    An op (``trace.leaves``' rule) takes the phase path of its instruction;
    one with no ``phase:`` of its own (a layout copy, a fusion merged across
    a boundary) inherits the path of the innermost control-flow event that
    contains it and has one. Ops run one after another on a device's line,
    so the rows sum to busy exactly, in integer ns (``overlap_ns`` says by
    how much the ops' intervals overlap: 0).

    Returns ``{"rows": {path: {"seconds", "ops", "instances"}},
    "rollup": {row: seconds}, "busy_s", "busy_ns", "unknown_ops",
    "inherited_s", "overlap_ns"}``; ``unknown_ops`` counts the distinct
    instructions of the program that ran and the table does not hold (0
    when the table is of the program that ran)."""
    evs = tr.in_order(events)
    rows: dict[str, dict] = {}
    unknown: set[str] = set()
    stack: list[tuple[int, str]] = []    # (end_ns, own or inherited path)
    busy, inherited, covered, overlap = 0, 0, 0, 0
    for i, (name, start, dur) in enumerate(evs):
        end = start + dur
        while stack and stack[-1][0] <= start:
            stack.pop()
        instr = tr.instruction_name(name)
        ours = executions is None or any(s <= start < e for s, e in executions)
        path = table.get(instr, "") if ours else ""
        own = bool(path)
        if not path and stack:
            path = stack[-1][1]
        if tr.contains_next(evs, i):
            stack.append((end, path))
            continue
        overlap += max(0, min(end, covered) - start)
        covered = max(covered, end)
        if not ours:
            path = OTHER_PROGRAMS
        elif instr not in table:
            unknown.add(instr)
        elif not own and path:
            inherited += dur
        row = rows.setdefault(path, {"ns": 0, "names": set(), "instances": 0})
        row["ns"] += dur
        row["names"].add(instr)
        row["instances"] += 1
        busy += dur
    rollup = {k: 0 for k in (PREPARE, POP, HANDLERS, ROUNDS_OTHER, DELIVER,
                             TELEM, OTHER, UNATTRIBUTED, OTHER_PROGRAMS)}
    for path, row in rows.items():
        key, kind = ((OTHER_PROGRAMS, None) if path == OTHER_PROGRAMS
                     else rollup_key(path))
        rollup[key] += row["ns"]
        if kind:
            rollup[kind] = rollup.get(kind, 0) + row["ns"]
    return {
        "rows": {p: {"seconds": r["ns"] / 1e9, "ops": len(r["names"]),
                     "instances": r["instances"]}
                 for p, r in sorted(rows.items(), key=lambda kv: -kv[1]["ns"])},
        "rollup": {k: v / 1e9 for k, v in rollup.items()},
        "busy_s": busy / 1e9,
        "busy_ns": busy,
        "unknown_ops": len(unknown),
        "inherited_s": inherited / 1e9,
        "overlap_ns": overlap,
    }


def phase_report(trace: dict, table: dict) -> dict:
    """``attribute`` of the first device's op line against ``table``: rows
    by phase path, the roll-up, busy seconds, ``unknown_ops``."""
    plane = tr.device_planes(trace)[0]
    return attribute(tr._line(plane, tr.OPS_LINE), table,
                     tr.main_executions(plane) or None)


def execution_idle_ns(plane: dict) -> int | None:
    """Idle time inside executions of the window program: of each run, its
    duration less the time an op was running in it."""
    runs = tr.main_executions(plane)
    if not runs:
        return None
    busy = tr.union([(s, s + d)
                     for _, s, d in tr.leaves(tr._line(plane, tr.OPS_LINE))])
    idle = 0
    for r0, r1 in runs:
        inside = sum(min(e, r1) - max(s, r0) for s, e in busy
                     if s < r1 and e > r0)
        idle += (r1 - r0) - inside
    return idle


def gap_report(trace: dict, table: dict, n: int = 5) -> dict:
    """The ``n`` longest idle gaps of the first device's op line, each as
    ``{span, before, after, phase_before, phase_after, seconds,
    inside_execution, other_lines}``: the innermost span of the program or
    the harness open at its middle, the ops on either side by instruction name
    and phase path, and what every other line of the device plane had open
    during it. Also the names of every line the plane has, and under
    ``idle_after`` the ``n`` instructions that idle time follows most:
    ``[instruction, phase, instances, instances followed by idle, seconds]``
    (a gap that recurs is a property of the op before it)."""
    plane = tr.device_planes(trace)[0]
    runs = tr.main_executions(plane)
    on_host = tr.host_spans(trace)
    name = tr.instruction_name
    gaps, covered, last = [], None, None
    follows: dict[str, list] = {}    # instruction -> [instances, gaps, ns]
    for ev in tr.leaves(tr._line(plane, tr.OPS_LINE)):
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
        span = tr.covering_span(on_host, mid)
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


def lane_sums(metrics, names) -> list[int]:
    """Per lane, the sum of the counters ``names`` of a fetched ``Metrics``."""
    import numpy as np

    return [int(x) for x in sum(np.asarray(getattr(metrics, n)).reshape(-1)
                                for n in names)]


def counters_of(trace: dict, report: dict, table: dict,
                at_from, at_to) -> dict:
    """What the per-layer readers are handed of this reading, as further
    keys of ``counters``: ``phase_s`` (the roll-up, seconds by row),
    ``phase_busy_s``, ``unknown_ops``, ``exec_idle_ns`` (None where the
    trace has no module line), ``dispatch_ns`` (every ``dispatch`` span of
    the program), ``handler_kinds`` and ``fires_by_lane`` (per lane, the
    handler passes that had an event of their kind between the two fetched
    ``Metrics``, at the traced stretch's start and end)."""
    return {
        "phase_s": report["rollup"], "phase_busy_s": report["busy_s"],
        "unknown_ops": report["unknown_ops"],
        "exec_idle_ns": execution_idle_ns(tr.device_planes(trace)[0]),
        "dispatch_ns": [e - s for n, s, e in tr.program_spans(trace)
                        if n == "dispatch"],
        "handler_kinds": handler_kinds(table),
        "fires_by_lane": [b - a for a, b in zip(lane_sums(at_from, FIRES),
                                                lane_sums(at_to, FIRES))],
    }


def keep(path: str, raw: dict, table: dict, counters: dict) -> None:
    """The capture as a plain dict (an op named by the head of its HLO
    text), and beside it the table's rows for the instructions it holds
    and the stretch's counters: how ``tests/data/`` was recorded."""
    import gzip

    for p in raw["planes"]:
        for ln in p["lines"]:
            ln["events"] = [[n[:tr.NAME_CHARS], s, d] for n, s, d in ln["events"]]
    with gzip.open(path, "wt") as f:
        json.dump(raw, f)
    seen = {tr.instruction_name(e[0]) for p in tr.device_planes(raw)
            for ln in p["lines"] for e in ln["events"]}
    with open(path.removesuffix(".json.gz") + ".phase_table.json", "w") as f:
        json.dump({"counters": counters, "table": {
            k: v for k, v in sorted(table.items()) if k in seen}}, f, indent=0)


def main(argv, root: str, started: float) -> int:
    """One traced run of a cell, as ``run.py --trace 1`` makes it."""
    import argparse

    from benchmarks.harness import loop

    ap = argparse.ArgumentParser(prog="benchmarks/harness/phases.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True)
    ap.add_argument("--root", default=root,
                    help="the directory of the BENCHMARK.json to read "
                         "(benchmarks/tests/rehearsal for a small cell)")
    ap.add_argument("--keep-trace", default=None,
                    help="write the capture, reduced to a plain dict, to "
                         "this .json.gz, and the phase table beside it")
    args = ap.parse_args(argv)
    more = ["--keep-trace", args.keep_trace] if args.keep_trace else []
    return loop.main(["--workload", args.workload, "--seed", args.seed,
                      "--seconds", "0", "--trace", "1", *more],
                     os.path.abspath(args.root), started)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), _STARTED))
