"""The chunk log (telemetry/profiler.py ChunkLog): the third, always-on
carrier of the chunk runner's spans — one row a chunk of the one chunk loop,
whichever runner hands it hooks, readiness taken by the waiter thread, the host's health over a chunk,
the ``stall`` line of a chunk far slower than the chunks it repeats, the
heartbeat's ``chunk`` block and the final JSON's ``chunks`` block."""

import json
import subprocess
import sys
import threading
import time
import types

import jax
import numpy as np
import pytest

from shadow1_tpu.ckpt import run_chunked
from shadow1_tpu.config.compiled import single_vertex_experiment
from shadow1_tpu.consts import MS, EngineParams
from shadow1_tpu.core.engine import Engine
from shadow1_tpu.fleet.engine import FleetEngine
from shadow1_tpu.fleet.run import run_fleet
from shadow1_tpu.obs import run_with_heartbeat
from shadow1_tpu.telemetry import PhaseProfiler, chunk_log, profiler, registry
from shadow1_tpu.telemetry.profiler import (
    PH_ARGS,
    PH_CALL,
    PH_CHECKPOINT,
    PH_DRAIN,
    maybe_span,
    run_span,
)
from tests.test_phases import _captured_spans

PARAMS = EngineParams(ev_cap=32, outbox_cap=16)
# Of the two blocks, the keys every chunk loop gives; registry.CHUNK_BOUNDARY
# are there where the loop ran those spans.
# registry.CHUNK_CAP_TOTALS where the program has a compact_cap in force.
SOMETIMES = set(registry.CHUNK_BOUNDARY) | set(registry.CHUNK_CAP_TOTALS)
REQUIRED_CHUNKS = set(registry.CHUNKS_BLOCK) - SOMETIMES
REQUIRED_CHUNK = set(registry.CHUNK_BLOCK) - SOMETIMES
PREFIX = profiler.ANNOTATION_PREFIX


def phold(seed=7):
    return single_vertex_experiment(
        n_hosts=16, seed=seed, end_time=80 * MS, latency_ns=10 * MS,
        model="phold", model_cfg={"mean_delay_ns": 20 * MS, "init_events": 2})


@pytest.fixture(autouse=True)
def log():
    """The process's log, emptied: other tests of this worker ran chunks."""
    lg = chunk_log()
    lg.clear()
    lg.enabled = True
    yield lg
    lg.enabled = True
    lg.clear()


# ---- rows of real runs -----------------------------------------------------

def _solo(n, chunk):
    eng = Engine(phold(), PARAMS)
    return eng, run_chunked(eng, n_windows=n, chunk=chunk)


def _solo_heartbeat(n, chunk):
    eng = Engine(phold(), PARAMS)
    st, _hb = run_with_heartbeat(eng, n_windows=n, every_windows=chunk,
                                 stream=False)
    return eng, st


def _fleet(n, chunk):
    eng = FleetEngine([phold(7), phold(8)], PARAMS)
    st, _hb = run_fleet(eng, n_windows=n, every_windows=chunk, stream=False)
    return eng, st


@pytest.mark.parametrize("loop", [_solo, _solo_heartbeat, _fleet])
def test_every_chunk_loop_leaves_one_row_a_chunk(log, loop):
    eng, st = loop(6, 2)
    jax.block_until_ready(st)
    rows = log.rows()
    assert [(r["first_window"], r["windows"], r["done"]) for r in rows] == [
        (0, 2, 0), (2, 2, 2), (4, 2, 4)]
    assert [r["seq"] for r in rows] == sorted(r["seq"] for r in rows)
    for r in rows:
        assert r["engine"] == eng._chunk_log_no > 0
        assert r["enter_ns"] <= r["dispatched_ns"] <= r["ready_ns"]
        # dispatch ⊃ args, call: the engine's own run method spans both.
        assert 0 < r["args_ns"] + r["call_ns"] <= r["dispatch_ns"]
        assert r["dispatch_ns"] <= r["dispatched_ns"] - r["enter_ns"]
        assert set(r["health"]) <= set(registry.CHUNK_HEALTH)
        assert {"cpu_s", "nivcsw", "nvcsw", "majflt", "inblock", "oublock",
                "load1"} <= set(r["health"])
        assert r["wall_ns"] > 0
    # A chunk that continues the one before it has the turnaround between.
    assert "turnaround_ns" not in rows[0]
    assert all("turnaround_ns" in r for r in rows[1:])
    # The spans a loop runs between two chunks are on the row of the chunk
    # that follows them; a loop's first follows none.
    between = {"on_chunk_ns", "drain_ns"} if loop is not _solo else set()
    assert not set(rows[0]) & set(profiler._BOUNDARY_SPANS.values())
    for r in rows[1:]:
        assert set(r) & set(profiler._BOUNDARY_SPANS.values()) == between
        assert all(r[k] > 0 for k in between)
    s = log.summary()
    assert REQUIRED_CHUNKS | {k[:-2] + "ms" for k in between} == set(s)
    assert set(s) <= set(registry.CHUNKS_BLOCK)
    assert (s["count"], s["rows"], s["windows"], s["stalls"]) == (3, 3, 2, 0)
    assert s["boundary_ms"] == pytest.approx(
        s["dispatch_ms"] + s["turnaround_ms"], abs=1e-3)
    # Of a chunk's wall plus turnaround where the loop waits for each chunk
    # (a heartbeat syncs); a loop that runs ahead hides its boundary.
    assert s["boundary_share"] > 0
    if loop is not _solo:
        assert s["boundary_share"] < 1


def test_the_final_metrics_are_the_same_with_the_log_on_and_off(log):
    eng = Engine(phold(), PARAMS)
    on = Engine.metrics_dict(run_chunked(eng, n_windows=6, chunk=2))
    assert log.settle(5.0) and log.count == 3
    log.enabled = False
    off = Engine.metrics_dict(run_chunked(eng, n_windows=6, chunk=2))
    assert on == off and on["windows"] == 6
    # Off, nothing is kept, and no chunk is any thread's.
    assert log.count == 3 and log.block() is None


def test_once_ready_the_log_holds_no_device_array(log):
    eng = Engine(phold(), PARAMS)
    jax.block_until_ready(run_chunked(eng, n_windows=4, chunk=2))
    assert log.settle(5.0)

    def walk(x):
        if isinstance(x, dict):
            for v in x.values():
                yield from walk(v)
        elif isinstance(x, (list, tuple)):
            for v in x:
                yield from walk(v)
        else:
            yield x

    leaves = list(walk(list(log._rows)))
    assert leaves and all(
        x is None or type(x) in (int, float, str) for x in leaves), leaves
    # Nor is the chunk this thread ran last still the thread's.
    assert profiler._THREAD.chunk is None


def test_the_log_keeps_the_last_chunks_only():
    small = profiler.ChunkLog(keep=4)
    eng = FakeEngine(FakeClock())
    for i in range(10):
        with small.chunk(None, eng, state(2 * i), done=2 * i, windows=2) as ch:
            ch.watch(state(2 * i + 2))
    assert small.settle(5.0)
    assert [r["first_window"] for r in small.rows()] == [12, 14, 16, 18]
    assert small.count == 10 and profiler.ChunkLog.KEEP == 512
    small._stop()
    small._waiter.join(5.0)
    assert not small._waiter.is_alive()


def test_without_the_kernel_s_pressure_files_the_keys_are_absent(log, monkeypatch):
    real = open

    def no_pressure(path, *a, **kw):
        if str(path).startswith("/proc/pressure/"):
            raise PermissionError(path)
        return real(path, *a, **kw)

    monkeypatch.setattr(profiler, "open", no_pressure, raising=False)
    eng = Engine(phold(), PARAMS)
    jax.block_until_ready(run_chunked(eng, n_windows=4, chunk=2))
    rows = log.rows()
    assert len(rows) == 2 and all("error" not in r for r in rows)
    for r in rows:
        assert not [k for k in r["health"] if k.startswith("psi_")]
        assert "cpu_s" in r["health"]


# ---- the spans on the other two carriers -------------------------------------

def test_a_capture_holds_wait_args_and_call_with_the_chunk_s_arguments(log, tmp_path):
    eng = Engine(phold(), PARAMS)
    st = eng.init_state()
    jax.block_until_ready(eng.run(st, n_windows=0))     # compile outside
    with jax.profiler.trace(str(tmp_path / "cap")):
        st = run_chunked(eng, st, n_windows=4, chunk=2)
        jax.block_until_ready(st)
        assert log.settle(5.0)      # the waiter closes its span in the capture
    spans = _captured_spans(str(tmp_path / "cap"))
    by_name = {}
    for name, start, end, stats in spans:
        by_name.setdefault(name[len(PREFIX):], []).append((start, end, stats))
    for name in ("wait", "args", "call", "dispatch", "run-chunk"):
        assert [(s["done"], s["windows"]) for _, _, s in by_name[name]] == [
            (0, 2), (2, 2)], name
    # dispatch ⊃ args, call, in that order; wait opens once dispatch is over.
    for d, a, c, w in zip(*(by_name[n] for n in ("dispatch", "args", "call", "wait"))):
        assert d[0] <= a[0] <= a[1] <= c[0] <= c[1] <= d[1]
        assert w[0] >= c[1]


def test_under_a_phase_profiler_wait_joins_the_chrome_trace_and_sync_stays(log):
    prof = PhaseProfiler()
    eng = Engine(phold(), PARAMS)
    run_chunked(eng, n_windows=4, chunk=2, profiler=prof)
    assert log.settle(5.0)
    names = prof.span_names()
    for name in ("run-chunk", "dispatch", "args", "call", "wait", "sync"):
        assert names.count(name) == 2, name
    waits = [e for e in prof.events if e["name"] == "wait"]
    assert [e["args"] for e in waits] == [{"done": 0, "windows": 2},
                                          {"done": 2, "windows": 2}]
    # The waiter's thread, not the loop's.
    loop_tid = {e["tid"] for e in prof.events if e["name"] == "dispatch"}
    assert {e["tid"] for e in waits}.isdisjoint(loop_tid)


def test_a_quarantine_replays_inside_commit_and_its_boundary_has_one_on_chunk(
        log, tmp_path):
    """The fleet's recovery plane is the runner's commit hook: the chunk
    whose lane fails is dispatched once by the loop, the quarantine and
    the survivors' replay lie inside that chunk's one ``commit`` (as
    OverflowGuard's grown replay does), and the boundary is handed to
    ``on-chunk`` once, with the survivors."""
    import dataclasses

    from tests.test_fleet_recover import UNDER, mk

    params = dataclasses.replace(UNDER, on_overflow="halt",
                                 on_lane_fail="quarantine")
    eng = FleetEngine([mk(5, loss=0.5), mk(6), mk(7, loss=0.5)], params)
    prof = PhaseProfiler()
    _st, hb = run_fleet(eng, n_windows=20, every_windows=5, stream=False,
                        profiler=prof, quarantine_base=str(tmp_path / "lane"))
    (q,) = hb.recovery["quarantined"]
    done = q["window"]              # a fresh run: the loop's count is the window
    of_chunk = [e for e in prof.events
                if e.get("args", {}).get("done") == done]
    names = [e["name"] for e in of_chunk]
    for once in ("run-chunk", "dispatch", "sync", "commit", "on-chunk"):
        assert names.count(once) == 1, (once, names)
    (commit,) = (e for e in of_chunk if e["name"] == "commit")
    drains = [e for e in of_chunk if e["name"] == "drain"]
    # The refused attempt's fetch and the replay's, both inside the commit.
    assert len(drains) == 2
    for d in drains:
        assert commit["ts"] <= d["ts"]
        assert d["ts"] + d["dur"] <= commit["ts"] + commit["dur"] + 0.2
    # Every other boundary committed at its first attempt.
    assert prof.span_names().count("drain") == 4 + 1
    assert [r["exps"] for r in (h["fleet"] for h in hb.records)] == (
        [[0, 1, 2]] * (done // 5) + [[0, 2]] * (4 - done // 5))
    # A replay opens no chunk of its own: one row a boundary, and the
    # chunks after the quarantine are another engine's.
    assert log.settle(5.0)
    rows = log.rows()
    assert [r["done"] for r in rows] == [0, 5, 10, 15]
    # On a row the commit is less the fetches it holds: the boundary's
    # parts sum to its turnaround (to the waiter's stamp, under a sync).
    ours = [profiler.ChunkLog._parts(r) for r in rows
            if "turnaround_ns" in r]        # the same engine's as before it
    assert len(ours) == 2
    for parts in ours:
        assert {"commit", "drain", "turnaround"} <= set(parts)
        assert parts["turnaround"] > -1e6, parts
    held = next(r for r in rows if r["done"] == done + 5)
    assert held["commit_ns"] + held["drain_ns"] == pytest.approx(
        commit["dur"] * 1e3, rel=0.02)
    engines = [r["engine"] for r in rows]
    split = [r["done"] for r in rows].index(done) + 1
    assert len(set(engines[:split])) == 1 and engines[0] == eng._chunk_log_no
    assert all(n != engines[0] for n in engines[split:])


def test_outside_a_chunk_loop_the_run_call_s_spans_are_bare_annotations(log):
    eng = Engine(phold(), PARAMS)
    st = run_chunked(eng, n_windows=2, chunk=2)
    before = log.rows()
    # The loop is over: a run call is no part of the chunk it ran last.
    jax.block_until_ready(eng.run(st, n_windows=2))
    assert not isinstance(run_span(PH_CALL), profiler._Timed)
    # Nor is a span of a boundary: it joins no row that is in the log, and
    # not the first chunk of the next loop either.
    with maybe_span(None, PH_DRAIN):
        pass
    assert log.rows() == before and len(before) == 1
    run_chunked(eng, st, n_windows=4, chunk=2)
    assert ["drain_ns" in r for r in log.rows()] == [False] * 3
    # A heartbeat's block is of a chunk once: the loop is over.
    assert log.block(5.0) is not None and log.block() is None


# ---- the stall line, on a clock the test holds --------------------------------

class FakeClock:
    """``perf_counter_ns`` for the profiler module: time passes only where
    a fake engine says so."""

    def __init__(self):
        self.now = 1_000_000_000
        self._lock = threading.Lock()

    def ns(self):
        return self.now

    def pass_ms(self, ms):
        with self._lock:
            self.now += int(ms * 1e6)


class Leaf:
    """A state's ``metrics.windows`` that is ready ``late_ms`` after it is
    asked for."""

    def __init__(self, value, clock=None, late_ms=0.0, gate=None):
        self.value, self.clock, self.late_ms = value, clock, late_ms
        self.gate = gate

    def block_until_ready(self):
        if self.gate is not None:
            assert self.gate.wait(10.0)
        if self.late_ms:
            self.clock.pass_ms(self.late_ms)
        return self

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.value)


def state(windows, clock=None, late_ms=0.0, gate=None):
    return types.SimpleNamespace(metrics=types.SimpleNamespace(
        windows=Leaf(windows, clock, late_ms, gate)))


class FakeEngine:
    """``run`` takes ``call_ms`` and its result ``late_ms`` more to be
    ready; ``slow`` maps a call's number to the ``(call_ms, late_ms)`` of
    that call alone."""

    n_windows = 6

    def __init__(self, clock, call_ms=10.0, late_ms=10.0, slow=None,
                 gate=None):
        self.clock, self.call_ms, self.late_ms = clock, call_ms, late_ms
        self.slow, self.calls, self.gate = slow or {}, 0, gate

    def run(self, st, n_windows=None):
        call_ms, late_ms = self.slow.get(self.calls, (self.call_ms, self.late_ms))
        self.calls += 1
        with run_span(PH_ARGS):
            self.clock.pass_ms(0.5)
        with run_span(PH_CALL):
            self.clock.pass_ms(call_ms)
        return state(int(np.asarray(st.metrics.windows)) + n_windows,
                     self.clock, late_ms, self.gate)


@pytest.fixture()
def clock(monkeypatch):
    c = FakeClock()
    monkeypatch.setattr(profiler, "time", types.SimpleNamespace(
        perf_counter_ns=c.ns, perf_counter=time.perf_counter,
        process_time=time.process_time, sleep=time.sleep))
    return c


def cycles(log, eng, n_cycles, chunks=3, size=2):
    """The harness's shape: the same windows again and again, one call of
    the chunk runner a chunk, each waited for."""
    for _ in range(n_cycles):
        st = state(0)
        for _ in range(chunks):
            st = run_chunked(eng, st, n_windows=size, chunk=size)
            assert log.settle(5.0)
            eng.clock.pass_ms(0.05)     # the harness between two chunks


def stall_lines(capsys):
    cap = capsys.readouterr()
    assert cap.out == ""            # stderr, never stdout
    lines = [json.loads(ln) for ln in cap.err.strip().splitlines() if ln]
    assert all(ln["type"] == registry.REC_STALL for ln in lines)
    return lines


@pytest.mark.parametrize("slow,where", [((2000.0, 10.0), "call"),
                                        ((10.0, 2000.0), "wait")])
def test_a_stalled_chunk_earns_one_line_that_names_where(log, clock, capsys,
                                                         slow, where):
    # The 14th call: the second chunk of the fifth cycle.
    eng = FakeEngine(clock, slow={13: slow})
    cycles(log, eng, 6)
    (line,) = stall_lines(capsys)
    assert line["level"] == "warning" and line["msg"]
    assert (line["first_window"], line["windows"], line["against"]) == (2, 2, "twins")
    assert line["rows"] == 4 and line["engine"] == eng._chunk_log_no
    assert line["wall_ms"] == pytest.approx(2010.5, abs=0.01)
    assert line["median_ms"] == pytest.approx(20.5, abs=0.01)
    assert line["ratio"] == pytest.approx(2010.5 / 20.5, abs=0.01)
    assert line["where"] == where
    assert set(line["ms"]) == set(line["median_of_ms"]) == {
        "args", "call", "wait", "turnaround"} < set(registry.STALL_PARTS)
    assert line["ms"][where] == pytest.approx(2000.0, abs=0.01)
    assert line["median_of_ms"]["turnaround"] == pytest.approx(0.05, abs=0.01)
    assert set(line["health"]) == set(line["median_of_health"])
    row = [r for r in log.rows() if "stall" in r]
    assert len(row) == 1 and row[0]["seq"] == line["chunk"]
    assert log.summary()["stalls"] == 1


def test_cycles_that_repeat_the_first_earn_no_line(log, clock, capsys):
    """The warm-up compiles (every chunk of the first cycle is 50x slower):
    it is never flagged itself, and as one twin among several it does not
    move the median. Nor is a chunk 1.4x its twins a stall, nor one that is
    twice its twins by a few milliseconds."""
    eng = FakeEngine(clock, slow={0: (1000.0, 10.0), 1: (1000.0, 10.0),
                                  2: (1000.0, 10.0), 13: (18.0, 10.0)})
    cycles(log, eng, 6)
    quick = FakeEngine(clock, call_ms=1.0, late_ms=1.0, slow={13: (4.0, 1.0)})
    cycles(log, quick, 6)
    assert stall_lines(capsys) == [] and log.stalls == 0
    assert log.count == 36


def test_an_engine_is_no_twin_of_one_that_was_collected_before_it(log, clock, capsys):
    """``id()`` of a collected engine comes back on the next one made: an
    engine is known by a number of its own, so a slower engine that takes
    a dead one's address is not held to its walls."""
    import gc

    eng = FakeEngine(clock)
    cycles(log, eng, 4)
    no = eng._chunk_log_no
    del eng
    gc.collect()
    slow = FakeEngine(clock, call_ms=200.0)
    cycles(log, slow, 2)
    assert slow._chunk_log_no > no      # whatever id(slow) is
    assert stall_lines(capsys) == [] and log.stalls == 0
    assert {r["engine"] for r in log.rows()} == {no, slow._chunk_log_no}


def test_with_no_twin_a_chunk_is_held_to_its_neighbours_at_three_times(log, clock, capsys):
    """A run from the CLI never repeats a window: chunks are compared with
    the chunks before them, and a simulation that turns twice as slow is
    no stall."""
    eng = FakeEngine(clock, slow={5: (40.0, 10.0), 6: (10.0, 80.0)})
    eng.n_windows = 16
    run_chunked(eng, state(0), n_windows=16, chunk=2,
                on_chunk=lambda st, done: log.settle(5.0))
    (line,) = stall_lines(capsys)
    assert (line["against"], line["first_window"], line["where"]) == (
        "neighbours", 12, "wait")
    assert line["wall_ms"] == pytest.approx(90.5, abs=0.01)
    assert line["median_ms"] == pytest.approx(20.5, abs=0.01)


def test_every_stalled_chunk_earns_its_own_line_judged_by_its_own_twins(
        log, clock, capsys):
    slow = (10.0, 500.0)
    eng = FakeEngine(clock, slow={12: slow, 13: slow, 14: slow, 17: slow})
    cycles(log, eng, 6)
    lines = stall_lines(capsys)
    assert [ln["first_window"] for ln in lines] == [0, 2, 4, 4]
    # The last one's twins hold a stalled chunk: the median does not move,
    # and the line's medians are of the very rows it was judged by.
    assert [ln["rows"] for ln in lines] == [4, 4, 4, 5]
    assert all(ln["median_ms"] == pytest.approx(20.5, abs=0.01)
               and ln["median_of_ms"]["wait"] == pytest.approx(10.0, abs=0.01)
               for ln in lines)
    assert log.stalls == 4


def test_a_slow_span_of_the_boundary_before_a_stalled_chunk_is_named(
        log, clock, capsys):
    """A loop with a checkpoint between its chunks: the chunk whose result
    is 80 ms late after a checkpoint that took 400 ms more than its twins'
    names the checkpoint; the line's parts overlap nowhere."""
    eng = FakeEngine(clock, slow={13: (10.0, 90.0)})

    def on_chunk(st, done):
        assert log.settle(5.0)
        with maybe_span(None, PH_CHECKPOINT, done=done):
            clock.pass_ms(405.0 if eng.calls == 13 and done == 2 else 5.0)
        clock.pass_ms(1.0)

    for _ in range(6):
        run_chunked(eng, state(0), n_windows=6, chunk=2, on_chunk=on_chunk)
    assert log.settle(5.0)
    (line,) = stall_lines(capsys)
    assert (line["first_window"], line["against"], line["where"]) == (
        2, "twins", "checkpoint")
    assert line["ms"] == {"args": 0.5, "call": 10.0, "wait": 90.0,
                          "checkpoint": 405.0, "turnaround": 1.0}
    assert line["median_of_ms"] == {"args": 0.5, "call": 10.0, "wait": 10.0,
                                    "checkpoint": 5.0, "turnaround": 1.0}
    rows = log.rows()
    assert [r.get("checkpoint_ns") for r in rows[:3]] == [None, 5e6, 5e6]
    s = log.summary()
    # on-chunk began with the wait for the result (10 ms on this clock).
    assert (s["checkpoint_ms"], s["on_chunk_ms"]) == (5.0, 16.0)
    assert "drain_ms" not in s and s["turnaround_ms"] == 6.0


def test_a_drain_inside_a_commit_is_one_part_of_the_turnaround_not_two(
        log, clock, capsys):
    """The fleet's commit hook holds its ``drain`` fetch (fleet/run.py): in
    the trace the one span lies inside the other, on a row ``commit`` is
    the commit's own time, so the parts still split the turnaround and the
    slow fetch is named, not the commit around it."""
    eng = FakeEngine(clock, late_ms=0.0, slow={13: (10.0, 90.0)})

    def commit(engine, st0, st, done, step):
        assert log.settle(5.0)
        clock.pass_ms(3.0)
        with maybe_span(None, PH_DRAIN, done=done):
            clock.pass_ms(407.0 if eng.calls == 13 else 7.0)
        return engine, st

    guard = types.SimpleNamespace(
        bind=lambda engine, st: None, commit=commit,
        run_guarded=lambda engine, st, n: engine.run(st, n_windows=n))
    for _ in range(6):
        run_chunked(eng, state(0), n_windows=6, chunk=2, guard=guard,
                    on_chunk=lambda st, done: clock.pass_ms(1.0))
    assert log.settle(5.0)
    (line,) = stall_lines(capsys)
    assert (line["first_window"], line["where"]) == (2, "drain")
    assert line["ms"] == {"args": 0.5, "call": 10.0, "wait": 90.0,
                          "commit": 3.0, "drain": 407.0, "turnaround": 1.0}
    assert line["median_of_ms"] == {"args": 0.5, "call": 10.0, "wait": 0.0,
                                    "commit": 3.0, "drain": 7.0,
                                    "turnaround": 1.0}
    s = log.summary()
    assert (s["commit_ms"], s["drain_ms"], s["turnaround_ms"]) == (
        3.0, 7.0, 11.0)


def test_a_loop_that_runs_ahead_of_the_device_is_not_a_run_of_stalls(log, clock, capsys):
    """With no sync between chunks the queue before a chunk is not the
    chunk's: its wall starts where the chunk before it was ready."""
    gate = threading.Event()        # the device is busy until all are sent
    eng = FakeEngine(clock, call_ms=1.0, late_ms=30.0, gate=gate)
    eng.n_windows = 40
    run_chunked(eng, state(0), n_windows=40, chunk=2)
    gate.set()
    assert log.settle(5.0)
    rows = log.rows()
    assert len(rows) == 20 and stall_lines(capsys) == []
    assert [r["wall_ns"] for r in rows[1:]] == [30_000_000] * 19
    assert rows[-1]["ready_ns"] - rows[-1]["enter_ns"] > 19 * 30_000_000
    assert all(r["turnaround_ns"] < 0 for r in rows[1:])


def test_a_result_that_fails_ends_neither_the_waiter_nor_the_run(log, clock):
    class Broken(Leaf):
        def block_until_ready(self):
            raise RuntimeError("RESOURCE_EXHAUSTED: injected")

    eng = FakeEngine(clock)
    with log.chunk(None, eng, state(0), done=0, windows=2) as ch:
        ch.watch(types.SimpleNamespace(
            metrics=types.SimpleNamespace(windows=Broken(2))))
    cycles(log, eng, 1)
    rows = log.rows()
    assert len(rows) == 4 and "RESOURCE_EXHAUSTED" in rows[0]["error"]
    assert all("error" not in r for r in rows[1:])


def test_chunk_loops_on_many_threads_share_the_one_log(log):
    """More loops than cores, the interpreter switching threads as often as
    it can: every chunk gets its row, whole, and the rows of one loop keep
    their order."""
    n_threads, n_chunks = 16, 40
    clock = FakeClock()
    errors = []

    engines = [FakeEngine(clock, call_ms=0.0, late_ms=0.0)
               for _ in range(n_threads)]

    def loop(k):
        try:
            run_chunked(engines[k], state(0), n_windows=2 * n_chunks, chunk=2)
        except Exception as e:     # pragma: no cover - reported below
            errors.append(repr(e))

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=loop, args=(k,)) for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60.0)
        assert not any(t.is_alive() for t in threads) and not errors, errors
        assert log.settle(30.0)
    finally:
        sys.setswitchinterval(was)
    assert log.count == n_threads * n_chunks
    rows = log.rows()
    assert len(rows) == 512 and len({r["seq"] for r in rows}) == 512
    assert all("wall_ns" in r and "health" in r and "error" not in r for r in rows)
    by_engine = {}
    for r in rows:
        by_engine.setdefault(r["engine"], []).append(r["first_window"])
    assert all(ws == sorted(ws) for ws in by_engine.values())


# ---- who reads it: heartbeats and the CLI's last line ---------------------------

@pytest.mark.parametrize("fleet", [False, True])
def test_the_cli_s_heartbeats_and_last_line_carry_the_documented_blocks(tmp_path, fleet):
    cfg = tmp_path / "run.yaml"
    cfg.write_text(
        "general: {seed: 7, stop_time: 80 ms}\n"
        "engine: {scheduler: tpu, ev_cap: 32, outbox_cap: 16}\n"
        "network: {single_vertex: {latency: 10 ms}}\n"
        "hosts: [{name: h, count: 8}]\n"
        "app: {model: phold, params: {mean_delay_ns: 2.0e7, "
        "init_events: 2}}\n" + ("sweep: {seeds: [7, 8]}\n" if fleet else ""))
    out = subprocess.run(
        [sys.executable, "-m", "shadow1_tpu", str(cfg), "--heartbeat", "2",
         *(["--fleet"] if fleet else [])], capture_output=True, text=True)
    assert out.returncode == 0, out.stderr[-800:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last.get("type") == ("fleet_summary" if fleet else None)
    assert set(last["chunks"]) == REQUIRED_CHUNKS | {"on_chunk_ms", "drain_ms"}
    assert (last["chunks"]["count"], last["chunks"]["windows"]) == (4, 2)
    assert last["chunks"]["boundary_ms"] > 0
    beats = [json.loads(ln) for ln in out.stderr.splitlines()
             if ln.startswith('{"type": "heartbeat"')]
    assert len(beats) == 4
    for i, hb in enumerate(beats):
        block = hb["chunk"]
        # The boundary before a chunk, and what the loop ran in it: the
        # heartbeat of the chunk before (its drain inside it, or beside).
        want = REQUIRED_CHUNK | {"on_chunk_ms", "drain_ms"}
        if i == 0:
            want = ({"dispatch_ms", "wait_ms"} | set(registry.CHUNK_TOTALS)
                    | set(registry.CHUNK_LOSS_TOTALS)
                    | set(registry.CHUNK_PUSH_TOTALS)
                    | set(registry.CHUNK_ROUTE_TOTALS))
        assert want <= set(block) <= set(registry.CHUNK_BLOCK + registry.CHUNK_HEALTH)
        assert want == set(block) - set(registry.CHUNK_HEALTH)
        assert block["dispatch_ms"] > 0 and block["wait_ms"] >= 0
        assert "cpu_s" in block and "load1" in block
    # Nothing of the log on stdout: the last line is still the result.
    assert all('"stall"' not in ln for ln in out.stdout.splitlines())
    # The totals: a heartbeat's are of its chunk's START (none before the
    # first chunk; they only grow), the last line's what the chunks did that
    # a kept row continues: all but the last one's.
    hosts = 8 * (2 if fleet else 1)
    assert [hb["chunk"]["hosts"] for hb in beats] == [hosts] * 4
    assert [beats[0]["chunk"][k] for k in registry.CHUNK_TOTALS[:-1]] == [0] * 4
    for k in ("events", "rounds", "elig_events"):
        at = [hb["chunk"][k] for hb in beats]
        assert at == sorted(at) and at[-1] > 0, (k, at)
        assert last["chunks"][k] == at[-1]
    assert last["chunks"]["hosts"] == hosts
    assert 0 < last["chunks"]["active_hosts"] <= 6 * hosts


# ---- what a chunk did: the totals of its input state ---------------------------

@pytest.mark.parametrize("loop", [_solo, _fleet])
def test_a_row_carries_the_totals_of_its_input_state_and_the_host_count(log, loop):
    """Read with ``first_window`` from the state a chunk is handed: zeros on
    the first row, and on the last what a run of the windows before it ends
    on (summed over a fleet's lanes); two rows that follow one another give
    the first one's work, and the last row, which nothing follows, none."""
    eng, st = loop(6, 2)
    jax.block_until_ready(st)
    rows = log.rows()
    lanes = getattr(eng, "n_exp", 1)
    assert [r["hosts"] for r in rows] == [16 * lanes] * 3
    assert [rows[0][k] for k in profiler._TOTALS] == [0, 0, 0, 0]
    at4 = eng.run(n_windows=4).metrics
    assert {k: rows[2][k] for k in profiler._TOTALS} == {
        k: int(np.sum(np.asarray(getattr(at4, k)))) for k in profiler._TOTALS}
    did = [profiler.work_between(a, b) for a, b in zip(rows, rows[1:])]
    assert all(d["events"] > 0 and d["rounds"] > 0 and d["elig_events"] > 0
               and 0 < d["active_hosts"] <= 2 * 16 * lanes for d in did)
    assert {k: sum(d[k] for d in did) for k in profiler._TOTALS} == {
        k: rows[2][k] for k in profiler._TOTALS}
    assert profiler.work_between(rows[2], None) is None
    # Not adjacent, or not the window the first ended on: no work either.
    assert profiler.work_between(rows[0], rows[2]) is None
    assert profiler.work_between(rows[0], {**rows[1], "first_window": 0}) is None
    assert profiler.work_between(rows[0], {**rows[1], "engine": -1}) is None
    s = log.summary()
    assert {k: s[k] for k in registry.CHUNK_TOTALS} == {
        **{k: rows[2][k] for k in profiler._TOTALS}, "hosts": 16 * lanes}


def _fleet_capped(n, chunk):
    eng = FleetEngine([phold(7), phold(8)],
                      EngineParams(ev_cap=32, outbox_cap=16, compact_cap=8))
    st, _hb = run_fleet(eng, n_windows=n, every_windows=chunk, stream=False)
    return eng, st


def test_a_row_carries_the_trips_where_a_compact_cap_is_in_force(log):
    """``buckets``: the input state's ``compact_buckets`` summed over the
    lanes, beside the other totals; two rows that follow one another give a
    chunk's trips, the summary their sum. A program without a cap keeps no
    such total and its rows, work and summary lack the key."""
    eng, st = _fleet_capped(6, 2)
    jax.block_until_ready(st)
    rows = log.rows()
    assert [r["buckets"] for r in rows[:1]] == [0]
    at4 = eng.run(n_windows=4)
    assert rows[2]["buckets"] == int(np.sum(np.asarray(at4.compact_buckets)))
    assert rows[2]["rounds"] == int(np.sum(np.asarray(at4.metrics.rounds)))
    did = [profiler.work_between(a, b) for a, b in zip(rows, rows[1:])]
    # 16 hosts, most of them active, 8 columns a trip: two trips a window
    # and lane where every host is, one round a trip at the least.
    assert all(2 * 2 <= d["buckets"] <= min(2 * 2 * 2, d["rounds"]) for d in did)
    assert sum(d["buckets"] for d in did) == rows[2]["buckets"] \
        == log.summary()["buckets"]
    log.clear()
    _, st = _fleet(6, 2)
    jax.block_until_ready(st)
    rows = log.rows()
    did = [profiler.work_between(a, b) for a, b in zip(rows, rows[1:])]
    assert all(d is not None and "buckets" not in d for d in did)
    assert not any("buckets" in r for r in rows)
    assert "buckets" not in log.summary()


def counted(windows, clock=None, late_ms=0.0, **totals):
    st = state(windows, clock, late_ms)
    for k, v in totals.items():
        setattr(st.metrics, k, Leaf(v))
    return st


class CountingEngine(FakeEngine):
    """A fake engine whose states count: a call adds ``(rounds, events)``
    from ``work`` (by the call's number) or ``(10, 100)`` to every lane."""

    exp = types.SimpleNamespace(n_hosts=8)
    n_exp = 2

    def __init__(self, clock, work=None, **kw):
        super().__init__(clock, **kw)
        self.work = work or {}

    def run(self, st, n_windows=None):
        rounds, events = self.work.get(self.calls, (10, 100))
        out = super().run(st, n_windows)
        m = st.metrics
        for k, add in (("rounds", rounds), ("events", events),
                       ("active_hosts", 3), ("elig_events", events)):
            setattr(out.metrics, k, Leaf(np.asarray(getattr(m, k)) + add))
        return out


def zeros(lanes=2):
    return counted(0, **{k: np.zeros(lanes, np.int64) for k in profiler._TOTALS})


def test_rows_of_fake_states_without_totals_carry_none(log, clock):
    cycles(log, FakeEngine(clock), 1)
    rows = log.rows()
    assert len(rows) == 3
    assert not any(set(r) & set(registry.CHUNK_TOTALS) for r in rows)
    assert all(profiler.work_between(a, b) is None for a, b in zip(rows, rows[1:]))
    assert not set(log.summary()) & set(registry.CHUNK_TOTALS)


def test_the_stall_line_prints_the_chunk_s_rounds_and_events_beside_its_wall(
        log, clock, capsys):
    """A loop whose chunks follow one another: the chunk that took 60x its
    neighbours' wall did 50x their rounds, and the line says so; the loop's
    last chunk stalls too, nothing continues it, and its line has no work."""
    eng = CountingEngine(clock, slow={5: (10.0, 1200.0), 7: (10.0, 900.0)},
                         work={5: (500, 4000)})
    eng.n_windows = 16
    run_chunked(eng, zeros(), n_windows=16, chunk=2,
                on_chunk=lambda st, done: log.settle(5.0) or time.sleep(0.08)
                if done == 16 else None)
    assert log.settle(5.0)
    busy, last = stall_lines(capsys)
    assert (busy["first_window"], busy["against"]) == (10, "neighbours")
    assert set(registry.STALL_WORK) <= set(busy)
    # Summed over the two lanes.
    assert (busy["rounds"], busy["events"]) == (1000, 8000)
    assert (busy["median_of_rounds"], busy["median_of_events"]) == (20, 200)
    assert last["first_window"] == 14 and not set(registry.STALL_WORK) & set(last)
    rows = log.rows()
    assert [r["hosts"] for r in rows] == [16] * 8
    assert [r["rounds"] for r in rows] == [0, 20, 40, 60, 80, 100, 1100, 1120]
    assert log.summary()["rounds"] == 1120


class LossyEngine(CountingEngine):
    """A counting engine whose states keep the loss plane too: a call adds
    ``loss`` (by the call's number; default 50 sent, 1 lost, nothing resent)
    to every lane's ``pkts_sent``, ``pkts_lost``, ``tcp_fast_rtx``,
    ``tcp_rto`` and ``tcp_ooo_drops``."""

    def __init__(self, clock, loss=None, **kw):
        super().__init__(clock, **kw)
        self.loss = loss or {}

    def run(self, st, n_windows=None):
        add = self.loss.get(self.calls, (50, 1, 0, 0, 0))
        out = super().run(st, n_windows)
        for k, more in zip(registry.CHUNK_LOSS_TOTALS, add):
            setattr(out.metrics, k,
                    Leaf(np.asarray(getattr(st.metrics, k)) + more))
        return out


def lossy_zeros(lanes=2):
    return counted(0, **{k: np.zeros(lanes, np.int64)
                         for k in (*profiler._TOTALS, *registry.CHUNK_LOSS_TOTALS)})


def test_a_row_carries_the_five_totals_of_the_loss_plane(log, clock):
    """Read with the other totals of the chunk's INPUT state, summed over the
    lanes: zeros on the first row, running totals after; the heartbeat's
    block and the summary carry them, and two adjacent rows give the first
    one's packets sent, lost, resent and dropped out of order."""
    eng = LossyEngine(clock, loss={2: (400, 9, 2, 3, 11)})
    eng.n_windows = 8
    run_chunked(eng, lossy_zeros(), n_windows=8, chunk=2)
    assert log.settle(5.0)
    rows = log.rows()
    assert len(rows) == 4
    assert all(set(registry.CHUNK_LOSS_TOTALS) <= set(r) for r in rows)
    assert [[r[k] for k in registry.CHUNK_LOSS_TOTALS] for r in rows] == [
        [0, 0, 0, 0, 0], [100, 2, 0, 0, 0], [200, 4, 0, 0, 0],
        [1000, 22, 4, 6, 22]]
    did = profiler.work_between(rows[2], rows[3])
    assert {k: did[k] for k in registry.CHUNK_LOSS_TOTALS} == {
        "pkts_sent": 800, "pkts_lost": 18, "tcp_fast_rtx": 4, "tcp_rto": 6,
        "tcp_ooo_drops": 22}
    assert (did["rounds"], did["events"]) == (20, 200)
    # The block of the chunk this thread ran last (a heartbeat's): the
    # totals at that chunk's start; the summary: what the chunks that a
    # kept row continues did, all but the last one's.
    block = log.block()
    assert {k: block[k] for k in registry.CHUNK_LOSS_TOTALS} == {
        k: rows[3][k] for k in registry.CHUNK_LOSS_TOTALS}
    assert set(block) <= set(registry.CHUNK_BLOCK + registry.CHUNK_HEALTH)
    s = log.summary()
    assert {k: s[k] for k in registry.CHUNK_LOSS_TOTALS} == {
        k: rows[3][k] for k in registry.CHUNK_LOSS_TOTALS}
    assert set(s) <= set(registry.CHUNKS_BLOCK)


def test_rows_of_states_without_the_loss_plane_keep_their_work(log, clock):
    """PR 43's fake states (events, rounds, hosts, no packet counters): the
    rows, the work between two of them and the summary are what they were,
    with none of the five."""
    eng = CountingEngine(clock)
    eng.n_windows = 6
    run_chunked(eng, zeros(), n_windows=6, chunk=2)
    assert log.settle(5.0)
    rows = log.rows()
    assert not any(set(r) & set(registry.CHUNK_LOSS_TOTALS) for r in rows)
    did = profiler.work_between(rows[0], rows[1])
    assert did == {"events": 200, "rounds": 20, "active_hosts": 6,
                   "elig_events": 200}
    assert not set(log.summary()) & set(registry.CHUNK_LOSS_TOTALS)


def test_the_stall_line_prints_what_the_chunk_resent_beside_its_rounds(
        log, clock, capsys):
    """The chunk that took 60x its neighbours' wall also recovered from 40x
    their losses: the line says how many episodes it started (fast
    retransmits + RTOs, summed over the lanes), how many packets it lost,
    and the median of the rows it was judged by. Rows without the loss plane
    earn a line without those keys (the test above this file's other stall
    tests: ``CountingEngine``)."""
    eng = LossyEngine(clock, slow={5: (10.0, 1200.0)}, work={5: (500, 4000)},
                      loss={5: (900, 40, 7, 13, 60), 2: (50, 1, 1, 0, 0)})
    eng.n_windows = 16
    run_chunked(eng, lossy_zeros(), n_windows=16, chunk=2)
    assert log.settle(5.0)
    (busy,) = stall_lines(capsys)
    assert busy["first_window"] == 10
    assert set(registry.STALL_WORK + registry.STALL_LOSS_WORK) <= set(busy)
    assert (busy["rounds"], busy["retransmits"], busy["pkts_lost"]) == (
        1000, 40, 80)
    # Its neighbours: one started 2 episodes (both lanes), the others none.
    assert busy["median_of_retransmits"] == 0


def test_the_input_s_scalars_are_read_when_a_chunk_is_handed_over_not_when_it_is_ready(
        log, clock):
    """Every fetch of a state's scalar happens before the waiter blocks on
    the result (the caller is then about to wait); none after the result
    turned ready, when the caller runs."""
    order = []

    class Noting(Leaf):
        def __init__(self, value, name):
            super().__init__(value)
            self.name = name

        def __array__(self, dtype=None, copy=None):
            order.append("read " + self.name)
            return super().__array__(dtype, copy)

        def block_until_ready(self):
            order.append("block " + self.name)
            return self

    def st(windows):
        s = state(windows)
        s.metrics.windows = Noting(windows, "windows")
        for k in profiler._TOTALS:
            setattr(s.metrics, k, Noting(windows * 7, k))
        return s

    eng = FakeEngine(clock)
    for i in range(2):
        with log.chunk(None, eng, st(2 * i), done=2 * i, windows=2) as ch:
            ch.watch(st(2 * i + 2))
    assert log.settle(5.0)
    reads = ["read " + k for k in ("windows", *profiler._TOTALS)]
    assert order == (reads + ["block windows"]) * 2
    assert [r["events"] for r in log.rows()] == [0, 14]
