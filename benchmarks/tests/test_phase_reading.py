"""The benchmark's reading of phases, program spans and idle gaps
(harness/phases.py): on a trace small enough to work out by hand, and on a
capture recorded on the chip that holds the program's ``shadow1:`` spans,
with the phase table of the program that ran beside it (data/). Also: what
the recorded PR 24 trace reduces to, pinned, so that a later edit of the
reduction shows as one; and the benchmark's copies of the join against the
program's own."""

import gzip
import json
import os
import types

import pytest

from benchmarks.harness import manifest as mf
from benchmarks.harness import phases as ph
from benchmarks.harness import trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")

PHASE_METRICS = ["prepare_ms_per_window", "pop_ms_per_round", "handlers_ms_per_round",
                 "deliver_ms_per_window", "phase_unattributed_share", "exec_idle_share",
                 "dispatch_ms_per_chunk", "handler_pass_useful_share"]
TABLE = {"while.0": "", "fusion.p": "prepare", "while.1": "rounds",
         "fusion.pop": "rounds/pop", "fusion.t": "rounds/h_timer/tcp_flush",
         "fusion.a": "rounds/h_app", "copy.x": "", "fusion.d": "deliver/route",
         "copy-start.1": ""}


def ev(name, start, dur):
    return [f"%{name} = s32[8]{{0}} op(...)", start, dur]


@pytest.fixture()
def by_hand():
    """Two runs of the window program on one device. Run 1 (100..300):
    prepare 100..120, the round loop 120..250 (pop 120..160, a copy with no
    scope 160..170, idle 170..200, a timer pass 200..250), route 260..300
    (idle 250..260). Run 2 (400..450): pop 400..420, an app pass 420..450.
    A copy between memory spaces is open 165..205 on the async line."""
    ops = [ev("while.0", 100, 200), ev("fusion.p", 100, 20),
           ev("while.1", 120, 130), ev("fusion.pop", 120, 40),
           ev("copy.x", 160, 10), ev("fusion.t", 200, 50),
           ev("fusion.d", 260, 40),
           ev("while.0", 400, 50), ev("while.1", 400, 50),
           ev("fusion.pop", 400, 20), ev("fusion.a", 420, 30)]
    mods = [["jit_run(1)", 100, 200], ["jit_run(1)", 400, 50],
            ["jit_tiny(2)", 350, 1]]
    host = [[tr.SPAN_PREFIX + "run-chunk", 90, 20], [tr.SPAN_PREFIX + "block", 110, 195],
            [tr.SPAN_PREFIX + "run-chunk", 380, 25], [tr.SPAN_PREFIX + "block", 405, 50],
            [tr.PROGRAM_PREFIX + "run-chunk", 91, 18], [tr.PROGRAM_PREFIX + "dispatch", 92, 10],
            [tr.PROGRAM_PREFIX + "run-chunk", 381, 23], [tr.PROGRAM_PREFIX + "dispatch", 382, 14],
            ["something else", 0, 1000]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": tr.OPS_LINE, "events": ops},
            {"name": tr.MODULES_LINE, "events": mods},
            {"name": "Async XLA Ops", "events": [ev("copy-start.1", 165, 40)]}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]}]}


def test_program_spans_and_the_harness_s_are_read_apart(by_hand):
    assert [s[0] for s in tr.program_spans(by_hand)] == ["run-chunk", "dispatch"] * 2
    assert [s[0] for s in tr.spans(by_hand)] == ["run-chunk", "block"] * 2
    assert tr.main_executions(by_hand["planes"][0]) == [(100, 300), (400, 450)]
    # A moment is named by the innermost span of either kind open at it.
    on_host = tr.host_spans(by_hand)
    assert [tr.covering_span(on_host, at) for at in (95, 105, 150, 350)] == [
        "dispatch", "run-chunk", "block", tr.NO_SPAN]


def test_read_xplane_keeps_the_harness_s_spans_and_the_program_s(tmp_path):
    import jax

    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(tr.SPAN_PREFIX + "run-chunk"):
            with jax.profiler.TraceAnnotation(tr.PROGRAM_PREFIX + "dispatch"):
                with jax.profiler.TraceAnnotation("someone else's"):
                    jax.block_until_ready(jax.numpy.arange(8) + 1)
    both = tr.read_xplane(str(tmp_path))
    assert [s[0] for s in tr.program_spans(both)] == ["dispatch"]
    assert [s[0] for s in tr.spans(both)] == ["run-chunk"]
    assert (tr.SPAN_PREFIX, tr.PROGRAM_PREFIX) == ("bench:", "shadow1:")
    assert not [e for p in both["planes"] if not p["name"].startswith(tr.DEVICE_PLANE)
                for ln in p["lines"] for e in ln["events"]
                if not e[0].startswith((tr.SPAN_PREFIX, tr.PROGRAM_PREFIX))]


def test_phase_report_by_hand(by_hand):
    rep = ph.phase_report(by_hand, TABLE)
    red = tr.reduce(by_hand)
    assert rep["busy_ns"] == red.busy_ns == 20 + 40 + 10 + 50 + 40 + 20 + 30
    rows = {p: round(r["seconds"] * 1e9) for p, r in rep["rows"].items()}
    # The copy has no scope of its own: it inherits the round loop's.
    assert rows == {"prepare": 20, "rounds/pop": 60, "rounds": 10,
                    "rounds/h_timer/tcp_flush": 50, "deliver/route": 40,
                    "rounds/h_app": 30}
    assert sum(rows.values()) == rep["busy_ns"] and rep["unknown_ops"] == 0
    roll = {k: round(v * 1e9) for k, v in rep["rollup"].items() if v}
    assert roll == {"prepare": 20, "pop": 60, "rounds_other": 10, "handlers": 80,
                    "h_timer": 50, "h_app": 30, "deliver": 40}


def test_gap_report_names_a_gap_by_span_ops_phases_and_other_lines(by_hand):
    rep = ph.gap_report(by_hand, TABLE)
    assert rep["lines"] == [tr.OPS_LINE, tr.MODULES_LINE, "Async XLA Ops"]
    gaps = rep["gaps"]
    assert [g["seconds"] for g in gaps] == [100e-9, 30e-9, 10e-9]
    between, in_loop, before_route = gaps
    # 300..400: no span of the program nor of the harness is open at 350.
    assert between["span"] == tr.NO_SPAN and not between["inside_execution"]
    assert (between["before"], between["after"]) == ("fusion.d", "fusion.pop")
    assert (between["phase_before"], between["phase_after"]) == ("deliver/route", "rounds/pop")
    # 170..200, inside run 1: the program's spans are closed (dispatch has
    # returned), the harness is in `block`; a copy is open on the async line.
    assert in_loop["span"] == "block" and in_loop["inside_execution"]
    assert (in_loop["before"], in_loop["after"]) == ("copy.x", "fusion.t")
    assert (in_loop["phase_before"], in_loop["phase_after"]) == ("", "rounds/h_timer/tcp_flush")
    assert in_loop["other_lines"] == {"Async XLA Ops": {"open": 1, "first": ["copy-start.1"]}}
    assert before_route["other_lines"]["Async XLA Ops"]["open"] == 0
    # Which instructions idle time follows: [name, phase, instances, gaps, s].
    assert rep["idle_after"][:3] == [
        ["fusion.d", "deliver/route", 1, 1, 100e-9],
        ["copy.x", "", 1, 1, 30e-9],
        ["fusion.t", "rounds/h_timer/tcp_flush", 1, 1, 10e-9]]


def _fires(*by_lane):
    """A fetched ``Metrics`` as far as the reading looks at one: every
    handler pass's counter, the lanes' useful passes all under the first."""
    zero = [0] * len(by_lane)
    return types.SimpleNamespace(**{k: zero for k in ph.FIRES[1:]},
                                 **{ph.FIRES[0]: list(by_lane)})


def _phase_values(trace, table, counters, at_from, at_to):
    """The phase metrics through their readers, as a traced run computes
    them."""
    m = mf.load(ROOT)
    counters = {**counters, **ph.counters_of(
        trace, ph.phase_report(trace, table), table, at_from, at_to)}
    red = tr.reduce(trace)
    return {name: mf.reader(ROOT, m, "layer_metrics", name)(red, counters, {})
            for name in PHASE_METRICS}


def test_the_phase_readers_by_hand(by_hand):
    assert ph.handler_kinds(TABLE) == 2
    vals = _phase_values(by_hand, TABLE, {"rounds": 4, "windows": 2},
                         _fires(10, 20), _fires(16, 22))
    assert vals == {
        "prepare_ms_per_window": pytest.approx(20 / 1e6 / 2),
        "deliver_ms_per_window": pytest.approx(40 / 1e6 / 2),
        "pop_ms_per_round": pytest.approx(60 / 1e6 / 4),
        "handlers_ms_per_round": pytest.approx(80 / 1e6 / 4),
        "phase_unattributed_share": 0.0,
        # Idle inside the two runs: 170..200 and 250..260, of 100..450.
        "exec_idle_share": pytest.approx(100 * 40 / 350),
        # Lanes saw 6 and 2 useful passes of 4 rounds x 2 kinds.
        "handler_pass_useful_share": pytest.approx(100 * 4 / 8),
        "dispatch_ms_per_chunk": pytest.approx(12 / 1e6),
    }
    assert ph.execution_idle_ns(by_hand["planes"][0]) == 40
    # One handler: its pass is not guarded, so there is nothing to read.
    one = {k: v for k, v in TABLE.items() if "h_app" not in v}
    assert _phase_values(by_hand, one, {"rounds": 4, "windows": 2},
                         _fires(0, 0), _fires(3, 3))["handler_pass_useful_share"] is None


def test_an_op_the_old_leaf_rule_dropped_is_an_op(by_hand):
    """A zero-length op that shares the start timestamp of the fusion after
    it: by overlap alone the fusion would be a container and its time an
    idle gap. Busy, the phases and the gaps all keep it."""
    plane = by_hand["planes"][0]
    before = tr.reduce(by_hand)
    plane["lines"][0]["events"] += [ev("fusion.big", 300, 60), ev("custom-call.0", 300, 0)]
    names = [tr.instruction_name(e[0]) for e in tr.leaves(plane["lines"][0]["events"])]
    assert "fusion.big" in names and "while.1" not in names
    rep = ph.phase_report(by_hand, {**TABLE, "fusion.big": "telem"})
    assert rep["busy_ns"] == tr.reduce(by_hand).busy_ns == before.busy_ns + 60
    # 300..400 was the longest gap; now it is 360..400, for both readings.
    assert ph.gap_report(by_hand, TABLE)["gaps"][0]["seconds"] == 40e-9
    assert tr.reduce(by_hand).idle_gaps[0][1] == 40e-9


# ---- what the recorded traces read --------------------------------------------

def _load(name):
    with gzip.open(os.path.join(DATA, name), "rt") as f:
        return json.load(f)


def test_the_pr24_trace_reduces_to_what_it_reduced_to():
    """Every field of ``Reduction`` and every layer metric of PR 24 on the
    trace PR 24 recorded: the numbers of 2364b10, to the digit (its ten
    containers are all control flow, so telling them by kind moves nothing
    here; at 65,536 hosts it does: PERF.md section 3)."""
    r = tr.reduce(_load("trace_phold32_v5e.json.gz"))
    assert (r.window_ns, r.busy_ns, r.n_ops, r.executions, r.n_devices) == (
        6770065.0, 550312.0, 2444.0, 2.0, 1)
    assert r.execution_gaps_ns == [5722916]
    assert r.idle_gaps == [["run-chunk", 0.005723838], ["run-chunk", 8.009e-06],
                           ["run-chunk", 8.006e-06], ["run-chunk", 6.67e-06],
                           ["block", 6.595e-06]]
    assert r.device_ops[0][0].startswith("%fusion.171 = (s32[32]{0:T(128)}")
    assert [s for _, s in r.device_ops[:3]] == [3.9546e-05, 3.5986e-05, 3.0384e-05]
    m = mf.load(ROOT)
    counters = {"rounds": 7, "windows": 2, "lanes": 1, "chunks": 2,
                "state_bytes": 819, "hbm_bytes_per_s": 819e9,
                "compile_seconds": 1.5, "persistent_cache_misses": 1,
                "persistent_cache_hits": 0}
    spans = {"imports": 2.0, "backend": 1.0, "build": 3.0, "warmup": 4.0}
    old = {"build_s": 6.0, "compile_s": 1.5, "cache_misses": 1,
           "chunk_gap_ms": 5.722916, "ms_per_round": 0.078616,
           "rounds_per_window": 3.5, "ops_per_round": 2444 / 7,
           "round_hbm_share": 0.0025440113971710596,
           "device_idle_share": 91.8713926675741}
    for name, want in old.items():
        got = mf.reader(ROOT, m, "layer_metrics", name)(r, counters, spans)
        assert got == pytest.approx(want, rel=1e-12), name


@pytest.fixture(scope="module")
def recorded():
    """Two 2-window chunks of PHOLD at 32 hosts, captured on one TPU v5e by
    ``harness/phases.py --keep-trace`` (op names cut to 96 characters), and
    beside it the phase table of the program that ran, with the stretch's
    counters."""
    with open(os.path.join(DATA, "trace_phold32_spans_v5e.phase_table.json")) as f:
        side = json.load(f)
    return _load("trace_phold32_spans_v5e.json.gz"), side["table"], side["counters"]


def test_the_recorded_capture_holds_the_program_s_spans(recorded):
    trace, _, _ = recorded
    spans = tr.program_spans(trace)
    assert [s[0] for s in spans] == ["run-chunk", "dispatch"] * 2
    for chunk, dispatch in zip(spans[::2], spans[1::2]):
        assert chunk[1] <= dispatch[1] and dispatch[2] <= chunk[2]
    # The harness's own spans are there as before, on the same clock.
    assert [s[0] for s in tr.spans(trace)] == ["run-chunk", "block"] * 2
    assert {"XLA Modules", "XLA Ops"} <= set(ph.gap_report(trace, {})["lines"])


def test_every_recorded_op_is_in_the_table_and_the_phases_sum_to_busy(recorded):
    trace, table, counters = recorded
    rep = ph.phase_report(trace, table)
    red = tr.reduce(trace)
    assert rep["unknown_ops"] == 0 and rep["overlap_ns"] == 0
    assert rep["busy_ns"] == red.busy_ns
    assert sum(r["seconds"] for r in rep["rows"].values()) == pytest.approx(
        rep["busy_ns"] / 1e9, rel=1e-9)
    roll = rep["rollup"]
    assert roll["pop"] > 0 and roll["handlers"] > 0 and roll["deliver"] > 0
    assert roll["h_phold"] == roll["handlers"]
    # A 32-host program is 0.55 ms of device time, a third of it the copies
    # between memory spaces at an execution's start, which no scope covers
    # (at 65,536 hosts the same rows are 1 % of busy).
    assert roll["unattributed"] / rep["busy_s"] < 0.5
    at = types.SimpleNamespace(**{k: [0] for k in ph.FIRES})
    vals = _phase_values(trace, table, counters, at, at)
    assert vals.pop("handler_pass_useful_share") is None    # PHOLD: one handler
    assert len(vals) == 7 and all(v is not None and v >= 0 for v in vals.values())
    # The four times and what the roll-up keeps beside them are busy.
    times = (vals["prepare_ms_per_window"] + vals["deliver_ms_per_window"]) * counters["windows"] \
        + (vals["pop_ms_per_round"] + vals["handlers_ms_per_round"]) * counters["rounds"]
    rest = roll["rounds_other"] + roll["telem"] + roll["other"] + roll["unattributed"] \
        + roll["other_programs"]
    assert times / 1e3 + rest == pytest.approx(rep["busy_s"], rel=1e-9)
    gaps = ph.gap_report(trace, table)["gaps"]
    assert len(gaps) == 5 and gaps[0]["seconds"] >= gaps[-1]["seconds"]
    assert all(g["before"] in table and g["after"] in table for g in gaps)


# ---- the benchmark's copies of the join against the program's own -------------

def test_the_benchmark_s_join_and_the_program_s_give_the_same_answers(recorded, by_hand):
    """``trace.leaves``, ``phases.phase_table`` and ``phases.attribute`` are
    copies of ``shadow1_tpu/telemetry/phases.py``'s arithmetic, kept here so
    that no edit of the program moves the yardstick: the same op list for the
    same line, the same table for the same text, the same report."""
    from shadow1_tpu.telemetry import phases as program

    hand = {**by_hand}
    hand["planes"][0]["lines"][0]["events"] += [
        ev("fusion.big", 300, 60), ev("custom-call.0", 300, 0)]
    for trace, table in ((recorded[0], recorded[1]), (hand, TABLE)):
        plane = tr.device_planes(trace)[0]
        line = tr._line(plane, tr.OPS_LINE)
        assert tr.leaves(line) == program.ops(line)
        runs = tr.main_executions(plane)
        assert ph.attribute(line, table, runs) == program.attribute(line, table, runs)
        assert ph.attribute(line, table) == program.attribute(line, table)
    text = """HloModule jit_run, entry_computation_layout={()->s32[]}

%body (p: s32[8]) -> s32[8] {
  %p = s32[8]{0} parameter(0)
  %fusion.1 = s32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(run)/vmap(phase:rounds)/while/body/phase:pop/add"}
  ROOT %copy.2 = s32[8]{0} copy(%fusion.1)
}

ENTRY %main () -> s32[] {
  %while.3 = s32[8]{0} while(%x), condition=%c, body=%body, metadata={op_name="jit(run)/phase:rounds/while"}
  ROOT %custom-call.4 = s32[] custom-call(), metadata={op_name="jit(run)/phase:deliver/phase:route/x"}
}
"""
    assert ph.phase_table(text) == program.phase_table(text) == {
        "p": "", "fusion.1": "rounds/pop", "copy.2": "", "while.3": "rounds",
        "custom-call.4": "deliver/route"}
