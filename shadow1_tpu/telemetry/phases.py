"""Window phases on the device trace — the join the TPU trace lacks.

The window program runs every stage under ``jax.named_scope("phase:<name>")``
(core/engine.window_phases: ``prepare`` / ``rounds`` ⊃ ``pop``, ``h_<kind>``
/ ``route`` / ``exchange`` / ``deliver`` / ``telem``; ``tcp_flush`` in
tcp/tcp.py). A TPU trace does **not** carry those scopes: it names a device
op by its HLO text (``%fusion.172 = s32[3670016,15]{…} fusion(…``) and an
event's stats hold only offsets. The scopes are in the *compiled program*:
every instruction of the optimized HLO module has
``metadata={op_name="jit(run)/phase:rounds/while/body/phase:pop/add"}``. So
the phase of a traced op is a join, instruction name → ``op_name``, made
here from the text ``Engine.hlo_text`` / ``FleetEngine.hlo_text`` return.

Everything but ``read_device_ops`` is arithmetic on strings and plain lists
(jax-free): ``phase_table`` (text → ``{instruction: phase path}``),
``attribute`` (events ``[name, start_ns, dur_ns]`` + table → seconds by
phase path, with a fixed roll-up). ``telemetry.device_trace`` writes the
result as ``phases.json``; the benchmark's readers divide the same rows.
"""

from __future__ import annotations

import glob
import os
import re

PHASE = re.compile(r"phase:(\w+)")
# `  ROOT %fusion.172 = s32[...] fusion(...), ..., metadata={op_name="..."}`
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")

# The fixed roll-up (every row of `attribute` lands in exactly one; `h_<kind>`
# rows repeat their part of `handlers`).
PREPARE, POP, HANDLERS, DELIVER, TELEM = ("prepare", "pop", "handlers",
                                          "deliver", "telem")
ROUNDS_OTHER = "rounds_other"   # the round loop outside pop and handler passes
OTHER = "other"                 # a phase: scope this roll-up does not know
UNATTRIBUTED = "unattributed"   # no phase: on the op nor on what contains it
OTHER_PROGRAMS = "other_programs"   # ops of another module than the table's
_DELIVER_PARTS = ("route", "exchange", "deliver")

# The instructions that contain others on a device's op line. Nothing else
# does: a zero-length op (an async start, a ConcatBitcast) can carry the same
# start timestamp as the fusion after it, and must not make that fusion look
# like a container.
CONTROL_FLOW = ("while", "conditional", "call")

DEVICE_PLANE = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def phase_path(op_name: str) -> str:
    """The ``phase:`` components of an HLO ``op_name`` in order, joined by
    ``/``; ``""`` where there is none. ``vmap`` wraps a scope
    (``vmap(phase:rounds)``), so the components are searched for, not split."""
    return "/".join(PHASE.findall(op_name))


def instruction_name(event_name: str) -> str:
    """The instruction name at the head of a TPU trace event's name (the
    event is named by the op's HLO text, cut anywhere after the name)."""
    head = event_name.split(" = ", 1)[0].split("=", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def is_control_flow(instruction: str) -> bool:
    """Whether an instruction (by its name, ``while.12``) is one that
    contains the ops it runs on a device's op line."""
    return instruction.split(".", 1)[0] in CONTROL_FLOW


def module_name(hlo_text: str) -> str:
    """``jit_run`` of ``HloModule jit_run, ...``: what the trace's module
    line calls an execution of this program (``jit_run(<fingerprint>)``)."""
    m = _MODULE.match(hlo_text)
    return m.group(1) if m else ""


def phase_table(hlo_text: str) -> dict[str, str]:
    """Instruction name → phase path for every instruction of every
    computation of an optimized HLO module: fusions, and the leaves XLA
    does not fuse (custom-call, sort, copy, copy-start/-done,
    dynamic-update-slice, while, conditional). An instruction with no
    metadata maps to ``""``."""
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


def _contains_next(evs: list, i: int) -> bool:
    """Whether event ``i`` of an op line sorted by (start, -duration) is
    control flow that contains the event after it."""
    name, start, dur = evs[i]
    return (is_control_flow(instruction_name(name)) and i + 1 < len(evs)
            and evs[i + 1][1] < start + dur)


def ops(events: list) -> list:
    """The ops of a device's op line, in time order: every event but the
    control flow that contains others."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    return [ev for i, ev in enumerate(evs) if not _contains_next(evs, i)]


def _inside(intervals: list[tuple[int, int]], at: int) -> bool:
    return any(s <= at < e for s, e in intervals)


def attribute(events: list, table: dict[str, str],
              executions: list[tuple[int, int]] | None = None) -> dict:
    """Device time by phase. ``events`` is one device's op line,
    ``[name, start_ns, dur_ns]``, control flow included (a ``while`` or a
    ``conditional`` is an event that contains the ops it runs); ``table``
    is ``phase_table`` of the program that ran; ``executions`` are the
    ``(start, end)`` of that program's runs where the trace holds other
    programs too (their ops go to ``other_programs``: instruction names are
    unique only inside a module).

    An op (every event but a ``while`` / ``conditional`` / ``call`` that
    contains the next) takes the phase path of its instruction; one with no
    ``phase:`` of its own (a layout copy, a fusion merged across a boundary)
    inherits the path of the innermost control-flow event that contains it
    and has one. Ops run one after another on a device's line, so
    ``sum(rows) == busy`` exactly, in integer ns (``overlap_ns`` says by how
    much the ops' intervals overlap: 0).

    Returns ``{"rows": {path: {"seconds", "ops", "instances"}},
    "rollup": {row: seconds}, "busy_s", "unknown_ops", "inherited_s",
    "overlap_ns"}``; ``unknown_ops`` counts the distinct instructions of
    the program that ran and the table does not hold (0 when the table is
    of the program that ran)."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    rows: dict[str, dict] = {}
    unknown: set[str] = set()
    stack: list[tuple[int, str]] = []    # (end_ns, own or inherited path)
    busy, inherited, covered, overlap = 0, 0, 0, 0
    for i, (name, start, dur) in enumerate(evs):
        end = start + dur
        while stack and stack[-1][0] <= start:
            stack.pop()
        instr = instruction_name(name)
        ours = executions is None or _inside(executions, start)
        path = table.get(instr, "") if ours else ""
        own = bool(path)
        if not path and stack:
            path = stack[-1][1]
        if _contains_next(evs, i):
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


def executions_of(module_events: list, module: str) -> list[tuple[int, int]]:
    """The ``(start, end)`` of every run of ``module`` on a trace's module
    line (events are named ``<module>(<fingerprint>)``)."""
    return sorted((s, s + d) for n, s, d in module_events
                  if n.split("(", 1)[0] == module)


def read_device_ops(log_dir: str) -> tuple[list, list]:
    """The op line and the module line of the first device that ran XLA ops,
    from the newest ``.xplane.pb`` under ``log_dir`` — the one helper here
    that needs jax. ``([], [])`` where the capture holds no device op (a
    CPU capture has a device plane of another shape)."""
    import jax

    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        return [], []
    for plane in jax.profiler.ProfileData.from_file(files[-1]).planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        lines = {ln.name: [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                           for ev in ln.events] for ln in plane.lines
                 if ln.name in (OPS_LINE, MODULES_LINE)}
        if lines.get(OPS_LINE):
            return lines[OPS_LINE], lines.get(MODULES_LINE, [])
    return [], []


def attribute_capture(log_dir: str, text_of) -> dict | None:
    """``attribute`` of the capture under ``log_dir`` against the program
    whose optimized HLO text ``text_of()`` returns; None, and no call,
    where no device op was captured."""
    ops, modules = read_device_ops(log_dir)
    if not ops:
        return None
    hlo_text = text_of()
    runs = executions_of(modules, module_name(hlo_text)) if modules else None
    out = attribute(ops, phase_table(hlo_text), runs or None)
    out["executions"] = len(runs) if runs else None
    return out
