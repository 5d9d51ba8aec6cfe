"""The reduction from a profiler trace to numbers: on a trace small enough to
work out by hand, and on one recorded on the chip (data/)."""

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


PR24_METRICS = ["build_s", "compile_s", "cache_misses", "chunk_gap_ms", "ms_per_round",
                "rounds_per_window", "ops_per_round", "round_hbm_share", "device_idle_share"]
# What a traced run reads of the phase join (PR 32).
PHASE_METRICS = ["prepare_ms_per_window", "pop_ms_per_round", "handlers_ms_per_round",
                 "deliver_ms_per_window", "phase_unattributed_share", "exec_idle_share",
                 "dispatch_ms_per_chunk", "handler_pass_useful_share"]


def ev(name, start, dur):
    return [name, start, dur]


@pytest.fixture()
def by_hand():
    """Two runs of a program on one device. Each is a `while` that contains
    its ops; run 1 is busy 100..160 and 170..200, run 2 is busy 400..450."""
    ops = [ev("while.1", 100, 100), ev("fusion.a", 100, 60), ev("copy.b", 170, 30),
           ev("while.1", 400, 50), ev("fusion.a", 400, 20), ev("fusion.a", 420, 30)]
    mods = [ev("jit_run(1)", 100, 100), ev("jit_run(1)", 400, 50),
            ev("jit_tiny(2)", 300, 1)]
    host = [ev(tr.SPAN_PREFIX + "run-chunk", 90, 20), ev(tr.SPAN_PREFIX + "block", 110, 95),
            ev(tr.SPAN_PREFIX + "run-chunk", 380, 25), ev(tr.SPAN_PREFIX + "block", 405, 50),
            ev(tr.PROGRAM_PREFIX + "dispatch", 92, 10),
            ev(tr.PROGRAM_PREFIX + "dispatch", 382, 14),
            ev("something else", 0, 1000)]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": tr.OPS_LINE, "events": ops},
                                             {"name": tr.MODULES_LINE, "events": mods},
                                             {"name": "Steps", "events": [ev("0", 100, 350)]}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]}]}


def test_leaves_drop_the_events_that_contain_others(by_hand):
    ops = by_hand["planes"][0]["lines"][0]["events"]
    assert [e[0] for e in tr.leaves(ops)] == ["fusion.a", "copy.b", "fusion.a", "fusion.a"]


def test_a_zero_length_op_at_a_fusion_s_start_does_not_make_it_a_container(by_hand):
    """A `ConcatBitcast` custom-call or an async start often carries the start
    timestamp of the fusion after it and sorts behind it. The fusion is an op
    and stays in busy; a `while` that contains ops is still a container."""
    ops = by_hand["planes"][0]["lines"][0]["events"]
    before = tr.reduce(by_hand)
    ops += [ev("%fusion.big = s32[8]{0} fusion(...)", 200, 150),
            ev("%custom-call.7 = s32[8]{0} custom-call(...)", 200, 0)]
    names = [tr.instruction_name(e[0]) for e in tr.leaves(ops)]
    assert names == ["fusion.a", "copy.b", "fusion.big", "custom-call.7",
                     "fusion.a", "fusion.a"]
    r = tr.reduce(by_hand)
    assert r.busy_ns == before.busy_ns + 150 and r.n_ops == before.n_ops + 2
    # 200..400 was the longest gap; what is left of it is 350..400.
    assert before.idle_gaps[0][1] == 200 / 1e9 and r.idle_gaps[0][1] == 50 / 1e9
    # Control flow that contains nothing (it ran no op) is an op's worth of
    # time on the line, not a container.
    assert tr.leaves([ev("while.9", 0, 5), ev("fusion.z", 5, 1)]) == [
        ev("while.9", 0, 5), ev("fusion.z", 5, 1)]
    assert [tr.is_control_flow(n) for n in (
        "while.12", "conditional", "call.3", "fusion.1", "while_thing")] == [
        True, True, True, False, False]
    assert tr.instruction_name("%while.12 = (s32[]) while(%x), condition=") == "while.12"
    assert tr.instruction_name("%fusion.3 = s32[8]{0:T(128)} fus") == "fusion.3"


def test_names_seen_counts_the_op_names_that_ran_so_often(by_hand):
    assert tr.names_seen(by_hand, 3) == 1     # fusion.a
    assert tr.names_seen(by_hand, 1) == 1     # copy.b
    assert tr.names_seen(by_hand, 2) == 0


def test_union_merges_touching_and_overlapping_intervals():
    assert tr.union([(5, 7), (0, 2), (2, 3), (6, 9), (20, 21)]) == [(0, 3), (5, 9), (20, 21)]


def test_busy_idle_executions_and_gaps_by_hand(by_hand):
    r = tr.reduce(by_hand)
    assert r.n_devices == 1
    assert r.window_ns == 350            # 100 .. 450
    assert r.busy_ns == 60 + 30 + 50     # the two touching ops of run 2 merge
    assert r.idle_share == pytest.approx(1 - 140 / 350)
    assert r.n_ops == 4
    assert r.executions == 2 and r.execution_gaps_ns == [200]
    assert r.device_ops == [["fusion.a", 110 / 1e9], ["copy.b", 30 / 1e9]]
    # The longest gap (200..400) is named by the span open at its middle: none,
    # the host is between two chunks; the short one (160..170) falls in `block`.
    assert r.idle_gaps == [["between-chunks", 200 / 1e9], ["block", 10 / 1e9]]


def test_a_trace_in_which_nothing_ran_on_the_device_is_an_error(by_hand):
    by_hand["planes"][0]["lines"][0]["events"] = []
    with pytest.raises(tr.TraceError):
        tr.reduce(by_hand)
    with pytest.raises(tr.TraceError):
        tr.reduce({"planes": by_hand["planes"][1:]})


def test_every_layer_metric_reads_the_hand_trace(by_hand):
    m = mf.load(ROOT)
    counters = {"rounds": 7, "windows": 2, "lanes": 1, "chunks": 2,
                "state_bytes": 819, "hbm_bytes_per_s": 819e9,
                "compile_seconds": 1.5, "persistent_cache_misses": 1,
                "persistent_cache_hits": 0}
    spans = {"imports": 2.0, "backend": 1.0, "build": 3.0, "warmup": 4.0}
    red = tr.reduce(by_hand)
    # What a traced run adds to the counters: the phases of a program with
    # one handler kind (PHOLD's shape), and no handler pass counted.
    table = {"while.1": "", "fusion.a": "rounds/pop", "copy.b": "deliver/route"}
    fires = types.SimpleNamespace(**{k: [0] for k in ph.FIRES})
    counters.update(ph.counters_of(by_hand, ph.phase_report(by_hand, table),
                                   table, fires, fires))
    got = {e["name"]: mf.reader(ROOT, m, "layer_metrics", e["name"])(red, counters, spans)
           for e in m["per_layer"]}
    # A quantity split by what it moves (<quantity>.<suffix>) has one reader.
    for name in [n for n in got if "." in n]:
        assert got.pop(name) == got[name.split(".")[0]]
    # (A metric a later PR adds is that PR's to test.)
    assert {k: got[k] for k in PR24_METRICS + PHASE_METRICS} == {
        "build_s": 6.0, "compile_s": 1.5, "cache_misses": 1,
        "chunk_gap_ms": 200 / 1e6,
        "ms_per_round": 140 / 1e6 / 7,
        "rounds_per_window": 3.5,
        "ops_per_round": 4 / 7,
        "round_hbm_share": pytest.approx(100 * (2 * 819 / 819e9) / (140e-9 / 7)),
        "device_idle_share": pytest.approx(60.0),
        "prepare_ms_per_window": 0.0,
        "pop_ms_per_round": pytest.approx(110 / 1e6 / 7),
        "handlers_ms_per_round": 0.0,
        "deliver_ms_per_window": pytest.approx(30 / 1e6 / 2),
        "phase_unattributed_share": 0.0,
        # Idle inside the two runs: 160..170 of 100..200, of a window 100..450.
        "exec_idle_share": pytest.approx(100 * 10 / 350),
        "dispatch_ms_per_chunk": pytest.approx(12 / 1e6),
        "handler_pass_useful_share": None,      # one handler: nothing to read
    }
    # Nothing to read: no rounds advanced, one execution only, a capture
    # with no module line and none of the program's spans.
    counters.update(rounds=0, windows=0, exec_idle_ns=None, dispatch_ns=[],
                    phase_busy_s=0.0)
    red.execution_gaps_ns = []
    for name in ("ms_per_round", "rounds_per_window", "ops_per_round",
                 "round_hbm_share", "chunk_gap_ms", "prepare_ms_per_window",
                 "pop_ms_per_round", "handlers_ms_per_round",
                 "deliver_ms_per_window", "phase_unattributed_share",
                 "exec_idle_share", "dispatch_ms_per_chunk"):
        assert mf.reader(ROOT, m, "layer_metrics", name)(red, counters, spans) is None
    # A run that made no phase reading at all (the keys are not there).
    for k in ("phase_s", "phase_busy_s", "exec_idle_ns", "dispatch_ns",
              "fires_by_lane", "handler_kinds"):
        del counters[k]
    for name in PHASE_METRICS:
        assert mf.reader(ROOT, m, "layer_metrics", name)(red, counters, spans) is None


@pytest.fixture()
def recorded():
    """Two 2-window chunks of PHOLD at 32 hosts, traced on one TPU v5e by
    `run.py --trace 1 --keep-trace` (op names cut to 96 characters)."""
    with gzip.open(os.path.join(HERE, "data", "trace_phold32_v5e.json.gz"), "rt") as f:
        return json.load(f)


def test_the_recorded_trace_reduces_to_what_a_sweep_over_its_events_gives(recorded):
    r = tr.reduce(recorded)
    plane = tr.device_planes(recorded)[0]
    ops = [e for ln in plane["lines"] if ln["name"] == tr.OPS_LINE for e in ln["events"]]
    # Control flow contains its ops: 3 `while`s a run and their bodies.
    parents = [e for e in ops if e not in tr.leaves(ops)]
    assert len(ops) == 2454 and len(parents) == 10
    assert {e[0].split(" = ")[0].rstrip(".0123456789") for e in parents} <= {
        "%while", "%conditional", "%call"}
    # Busy is the time of every event that is not control flow, no more and
    # no less: ops run one after another on a device's line.
    assert r.busy_ns == sum(e[2] for e in ops if not tr.is_control_flow(
        tr.instruction_name(e[0])))
    # Busy time again, by sweeping the sorted end points of every leaf.
    points = sorted([(s, 1) for _, s, d in tr.leaves(ops)]
                    + [(s + d, -1) for _, s, d in tr.leaves(ops)])
    open_, busy, last = 0, 0, None
    for at, step in points:
        if open_ > 0:
            busy += at - last
        open_, last = open_ + step, at
    assert r.busy_ns == busy == 550312
    assert r.window_ns == 6770065 and r.n_ops == 2444
    assert r.idle_share == pytest.approx(0.918714, abs=1e-6)
    # Two runs of the window program (two tiny programs run besides), and
    # the device idle between them while the host dispatches the next chunk.
    assert r.executions == 2 and r.execution_gaps_ns == [5722916]
    assert r.idle_gaps[0][0] == "run-chunk" and r.idle_gaps[0][1] == pytest.approx(5.72e-3, rel=0.01)
    assert [s[0] for s in tr.spans(recorded)] == ["run-chunk", "block"] * 2
    assert len(r.device_ops) == 10 and all(len(n) <= tr.NAME_CHARS for n, _ in r.device_ops)
    assert r.device_ops == sorted(r.device_ops, key=lambda x: -x[1])
