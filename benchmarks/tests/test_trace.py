"""The reduction from a profiler trace to numbers: on a trace small enough to
work out by hand, and on one recorded on the chip (data/)."""

import gzip
import json
import os

import pytest

from benchmarks.harness import manifest as mf
from benchmarks.harness import trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


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
            ev("something else", 0, 1000)]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": tr.OPS_LINE, "events": ops},
                                             {"name": tr.MODULES_LINE, "events": mods},
                                             {"name": "Steps", "events": [ev("0", 100, 350)]}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]}]}


def test_leaves_drop_the_events_that_contain_others(by_hand):
    ops = by_hand["planes"][0]["lines"][0]["events"]
    assert [e[0] for e in tr.leaves(ops)] == ["fusion.a", "copy.b", "fusion.a", "fusion.a"]


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
    got = {e["name"]: mf.reader(ROOT, m, "layer_metrics", e["name"])(red, counters, spans)
           for e in m["per_layer"]}
    # A quantity split by what it moves (<quantity>.<suffix>) has one reader.
    for name in [n for n in got if "." in n]:
        assert got.pop(name) == got[name.split(".")[0]]
    assert got == {
        "build_s": 6.0, "compile_s": 1.5, "cache_misses": 1,
        "chunk_gap_ms": 200 / 1e6,
        "ms_per_round": 140 / 1e6 / 7,
        "rounds_per_window": 3.5,
        "ops_per_round": 4 / 7,
        "round_hbm_share": pytest.approx(100 * (2 * 819 / 819e9) / (140e-9 / 7)),
        "device_idle_share": pytest.approx(60.0),
    }
    # Nothing to read: no rounds advanced, one execution only.
    counters.update(rounds=0, windows=0)
    red.execution_gaps_ns = []
    for name in ("ms_per_round", "rounds_per_window", "ops_per_round",
                 "round_hbm_share", "chunk_gap_ms"):
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
