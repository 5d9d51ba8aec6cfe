"""From a profiler trace to numbers.

``read_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain dict (the only part that needs jax); everything else here is arithmetic
on that dict, checked in ``benchmarks/tests`` against a small trace recorded
on the chip:

    {"planes": [{"name": ..., "lines": [{"name": ..., "events": [[name, start_ns, dur_ns], ...]}]}]}

A TPU's device plane carries a line of XLA ops in which control flow (a
``while``, a ``conditional``, a ``call``) is an event that *contains* the ops
it runs. Busy time is therefore taken over leaf events only: every event but
the control flow that contains another. Control flow is told by the
instruction's kind, never by overlap alone: a zero-length op (an async start,
a ``ConcatBitcast``) can carry the start timestamp of the fusion after it
and sort behind it, and that fusion is an op, not a container.
"""

from __future__ import annotations

import dataclasses
import glob
import os

DEVICE_PLANE = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# The harness's own host spans are TraceAnnotations under this prefix, the
# program's (its chunk loops') under the other.
SPAN_PREFIX = "bench:"
PROGRAM_PREFIX = "shadow1:"
# The instructions that contain others on a device's op line.
CONTROL_FLOW = ("while", "conditional", "call")
# Where no harness span is open the host is in the loop between two chunks.
NO_SPAN = "between-chunks"
# The TPU names an op by its whole HLO text; the breakdown keeps its head.
NAME_CHARS = 96


class TraceError(RuntimeError):
    pass


def read_xplane(log_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``log_dir`` as a plain dict. Of host
    planes only the harness's spans and the program's are kept: the rest is
    large and unread."""
    import jax

    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise TraceError(f"the profiler wrote no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    planes = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PLANE)
        lines = []
        for line in plane.lines:
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                      for ev in line.events
                      if device or ev.name.startswith((SPAN_PREFIX,
                                                       PROGRAM_PREFIX))]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace: dict) -> list[dict]:
    """The planes of devices that ran at least one XLA op."""
    return [p for p in trace["planes"]
            if p["name"].startswith(DEVICE_PLANE)
            and any(ln["name"] == OPS_LINE for ln in p["lines"])]


def _line(plane: dict, name: str) -> list:
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln["events"]
    return []


def instruction_name(event_name: str) -> str:
    """The instruction name at the head of a TPU trace event's name (the
    event is named by the op's HLO text, cut anywhere after the name)."""
    head = event_name.split(" = ", 1)[0].split("=", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def is_control_flow(instruction: str) -> bool:
    """Whether an instruction (by its name, ``while.12``) is one that
    contains the ops it runs on a device's op line."""
    return instruction.split(".", 1)[0] in CONTROL_FLOW


def contains_next(evs: list, i: int) -> bool:
    """Whether event ``i`` of an op line sorted by (start, -duration) is
    control flow that contains the event after it."""
    name, start, dur = evs[i]
    return (is_control_flow(instruction_name(name)) and i + 1 < len(evs)
            and evs[i + 1][1] < start + dur)


def in_order(events: list) -> list:
    """An op line sorted so that a container comes before what it contains."""
    return sorted(events, key=lambda e: (e[1], -e[2]))


def leaves(events: list) -> list:
    """The ops of a device's op line, in time order: every event but the
    control flow that contains the next."""
    evs = in_order(events)
    return [ev for i, ev in enumerate(evs) if not contains_next(evs, i)]


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Merge ``(start, end)`` intervals that touch or overlap."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def spans(trace: dict, prefix: str = SPAN_PREFIX) -> list[tuple[str, int, int]]:
    """The host spans under ``prefix`` (the harness's own by default) as
    ``(name, start_ns, end_ns)``, prefix removed, in time order."""
    out = []
    for p in trace["planes"]:
        if p["name"].startswith(DEVICE_PLANE):
            continue
        for ln in p["lines"]:
            out += [(n[len(prefix):], s, s + d) for n, s, d in ln["events"]
                    if n.startswith(prefix)]
    return sorted(out, key=lambda x: x[1])


def host_spans(trace: dict) -> list[tuple[str, int, int]]:
    """The harness's spans and the program's together: what the host was
    doing, as far as either says."""
    return sorted(spans(trace) + program_spans(trace), key=lambda x: x[1])


def covering_span(host_spans: list, at_ns: int) -> str:
    """The innermost of ``host_spans`` open at ``at_ns``."""
    best = None
    for name, s, e in host_spans:
        if s <= at_ns < e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else NO_SPAN


def program_spans(trace: dict) -> list[tuple[str, int, int]]:
    """The program's host spans, as ``spans`` gives the harness's."""
    return spans(trace, PROGRAM_PREFIX)


def main_executions(plane: dict) -> list[tuple[int, int]]:
    """Runs of the window program on a device, ``(start_ns, end_ns)`` in time
    order: the module that took most of the time."""
    mods = _line(plane, MODULES_LINE)
    total: dict[str, int] = {}
    for n, _, d in mods:
        total[n] = total.get(n, 0) + d
    if not total:
        return []
    main = max(total, key=total.get)
    return sorted((s, s + d) for n, s, d in mods if n == main)


def names_seen(trace: dict, times: int) -> int:
    """How many distinct leaf-op names the first device ran exactly
    ``times`` times: the trace's own witness of a loop's iteration count."""
    counts: dict[str, int] = {}
    for n, _, _ in leaves(_line(device_planes(trace)[0], OPS_LINE)):
        counts[n] = counts.get(n, 0) + 1
    return sum(1 for v in counts.values() if v == times)


@dataclasses.dataclass
class Reduction:
    """What the layer metrics read of one traced window (one device: the
    mean over devices where there are several)."""
    window_ns: float          # first op start to last op end
    busy_ns: float            # union of leaf-op intervals
    n_ops: float              # leaf-op events
    executions: float         # runs of the window program
    execution_gaps_ns: list   # idle between one run's end and the next's start
    device_ops: list          # [[name, seconds]] by total time, ten
    idle_gaps: list           # [[host span, seconds]] longest first, five
    n_devices: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.window_ns


def _reduce_plane(plane: dict, host_spans: list) -> Reduction:
    ops = leaves(_line(plane, OPS_LINE))
    if not ops:
        raise TraceError(f"{plane['name']}: no op ran on the device")
    busy = union([(s, s + d) for _, s, d in ops])
    t0, t1 = busy[0][0], busy[-1][1]
    by_name: dict[str, int] = {}
    for n, _, d in ops:
        by_name[n] = by_name.get(n, 0) + d
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(((b[0] - a[1], a[1]) for a, b in zip(busy, busy[1:])),
                  reverse=True)[:5]
    runs = main_executions(plane)
    run_gaps = [b[0] - a[1] for a, b in zip(runs, runs[1:])]
    return Reduction(
        window_ns=float(t1 - t0),
        busy_ns=float(sum(e - s for s, e in busy)),
        n_ops=float(len(ops)),
        executions=float(len(runs)),
        execution_gaps_ns=run_gaps,
        device_ops=[[n[:NAME_CHARS], d / 1e9] for n, d in top],
        idle_gaps=[[covering_span(host_spans, at + g // 2), g / 1e9]
                   for g, at in gaps],
        n_devices=1)


def reduce(trace: dict) -> Reduction:
    """Reduce every device plane and average over them."""
    planes = device_planes(trace)
    if not planes:
        have = {p["name"]: [ln["name"] for ln in p["lines"]]
                for p in trace["planes"]}
        raise TraceError(f"no device plane with an {OPS_LINE!r} line in the "
                         f"trace; it has {have}")
    on_host = host_spans(trace)
    rs = [_reduce_plane(p, on_host) for p in planes]
    n = len(rs)
    first = rs[0]
    return Reduction(
        window_ns=sum(r.window_ns for r in rs) / n,
        busy_ns=sum(r.busy_ns for r in rs) / n,
        n_ops=sum(r.n_ops for r in rs) / n,
        executions=sum(r.executions for r in rs) / n,
        execution_gaps_ns=first.execution_gaps_ns,
        device_ops=first.device_ops,
        idle_gaps=first.idle_gaps,
        n_devices=n)
