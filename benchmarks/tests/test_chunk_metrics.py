"""The two readers of the program's chunk log (``chunk_turnaround_ms``,
``dispatch_call_ms_per_chunk``): which rows they take, by hand, and that a
traced run of a rehearsal cell prints both."""

import gzip
import json
import os
import time

import pytest

from benchmarks.harness import loop
from benchmarks.harness import manifest as mf

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REHEARSAL = os.path.join(HERE, "rehearsal")
MS = 1_000_000
NAMES = ("chunk_turnaround_ms", "dispatch_call_ms_per_chunk")


def reader(name):
    return mf.reader(ROOT, mf.load(ROOT), "layer_metrics", name)


def row(seq, engine, first_window, windows, enter_ms, ready_ms, call_ms=3.0):
    return {"seq": seq, "engine": engine, "first_window": first_window,
            "windows": windows, "enter_ns": int(enter_ms * MS),
            "ready_ns": int(ready_ms * MS), "call_ns": int(call_ms * MS)}


def a_run_s_rows(traced=True):
    """What the log holds after a run of a cell with 2-window chunks and
    cycles of 8 windows: another engine's priming chunk, the warm-up, one
    cycle; after a traced run (the stretch: windows 2 to 6) also the replay
    (the stretch's start in one chunk, then window by window)."""
    rows = [row(0, "other", 0, 2, 0, 90, call_ms=80),
            row(1, "sim", 0, 2, 100, 190, call_ms=70)]           # warm-up
    # The cycle: turnarounds of 0.5, 0.1 and 0.3 ms; calls of 3, 4, 5, 6 ms.
    at, seq = 200.0, 2
    for k, turn in enumerate((None, 0.5, 0.1, 0.3)):
        at += turn or 0.0
        rows.append(row(seq, "sim", 2 * k, 2, at, at + 20, call_ms=3 + k))
        at, seq = at + 20, seq + 1
    if traced:
        rows.append(row(seq, "sim", 0, 2, at + 50, at + 70, call_ms=9))
        rows += [row(seq + 1 + i, "sim", 2 + i, 1, at + 80 + 10 * i,
                     at + 85 + 10 * i, call_ms=1) for i in range(4)]
    return rows


@pytest.fixture()
def logged(monkeypatch):
    """Put rows where the readers look: the program's chunk log."""
    from shadow1_tpu.telemetry import chunk_log

    def put(rows):
        monkeypatch.setattr(chunk_log(), "rows", lambda wait_s=1.0: list(rows))
    return put


COUNTERS = {"windows": 4, "chunks": 2}      # the traced stretch: 2-window chunks


def read_both(counters=COUNTERS):
    return (reader("chunk_turnaround_ms")(None, counters, {}),
            reader("dispatch_call_ms_per_chunk")(None, counters, {}))


def test_the_turnaround_is_read_between_chunks_that_follow_one_another(logged):
    logged(a_run_s_rows(traced=False))
    # Three pairs inside the cycle. Not the warm-up into the cycle's first
    # chunk (both start at window 0), no row of the other engine. The call
    # over the four rows those pairs hold: 3, 4, 5, 6 ms.
    assert read_both() == (pytest.approx(0.3), pytest.approx(4.5))


def test_a_traced_run_s_profiler_and_replay_are_left_out(logged):
    logged(a_run_s_rows(traced=True))
    # The profiler starts before the chunk at window 2 and stops after the
    # one that ends at window 6: those turnarounds are its own. The replay
    # (a 2-window chunk from window 0, then 1-window rows) says where the
    # stretch is, and pairs with nothing. Left: 2 -> 4, calls of 4 and 5 ms.
    assert read_both() == (pytest.approx(0.1), pytest.approx(4.5))


def test_rows_that_are_not_adjacent_in_the_log_make_no_pair(logged):
    rows = a_run_s_rows(traced=False)
    del rows[3]         # the cycle's second chunk fell out of the log
    logged(rows)
    # Left: (4 -> 6) alone; windows 0 and 4 are neither adjacent nor consecutive.
    assert read_both() == (pytest.approx(0.3), pytest.approx(5.5))


def test_the_engine_with_most_rows_is_the_one_read(logged):
    # A second engine with fewer chunks of the same size, slower in all.
    more = [row(100 + k, "few", 2 * k, 2, 1000 + 30 * k, 1025 + 30 * k, call_ms=20)
            for k in range(3)]
    logged(a_run_s_rows(traced=False) + more)
    assert read_both() == (pytest.approx(0.3), pytest.approx(4.5))


@pytest.mark.parametrize("rows,counters", [
    ([], COUNTERS),                                 # no program ran
    (a_run_s_rows()[:3], COUNTERS),                 # warm-up + one chunk
    (a_run_s_rows(), {"windows": 4, "chunks": 0}),  # no chunk size
    (a_run_s_rows(), {"windows": 6, "chunks": 2}),  # no row of that size
    (a_run_s_rows(), {}),
])
def test_with_under_two_such_rows_there_is_nothing_to_read(logged, rows, counters):
    logged(rows)
    assert read_both(counters) == (None, None)


def test_a_program_without_a_chunk_log_gives_nothing_and_does_not_raise(monkeypatch):
    """The parent commit's program, under this benchmark's files."""
    import shadow1_tpu.telemetry as telemetry

    monkeypatch.delattr(telemetry, "chunk_log")
    assert read_both() == (None, None)


@pytest.mark.parametrize("cell", ["phold32.dense4", "tor20.seeds2"])
def test_a_traced_run_of_a_rehearsal_cell_prints_both(capsys, monkeypatch, cell):
    """``--trace 1`` on the CPU with a capture recorded on the chip in the
    profiler's place (as test_rehearsal does): the chunk log is the run's
    own, so both readers find the cycle's chunks. The rehearsal manifest is
    an accepted benchmark file, not this PR's to edit, so the run is handed
    it with the real manifest's two entries appended, as a benchmark PR
    would leave it."""
    from benchmarks.harness import phases
    from benchmarks.harness import trace as tr
    from shadow1_tpu.telemetry import chunk_log

    data = os.path.join(HERE, "data")
    with gzip.open(os.path.join(data, "trace_phold32_spans_v5e.json.gz"), "rt") as f:
        capture = json.load(f)
    table = mf.read_json(os.path.join(
        data, "trace_phold32_spans_v5e.phase_table.json"))["table"]
    monkeypatch.setattr(tr, "read_xplane", lambda log_dir: capture)
    monkeypatch.setattr(phases, "phase_table", lambda text: (
        table if "HloModule" in text else {}))
    man = mf.load(REHEARSAL)
    have = {e["name"] for e in man["per_layer"]}
    man["per_layer"] += [e for e in mf.load(ROOT)["per_layer"]
                         if e["name"] in NAMES and e["name"] not in have]
    monkeypatch.setattr(mf, "load", lambda root: man)
    chunk_log().clear()
    rc = loop.main(["--workload", cell, "--seed", "7", "--seconds", "0.3",
                    "--trace", "1"], REHEARSAL, time.perf_counter(),
                   require_chip=False)
    out = capsys.readouterr().out.strip().splitlines()
    res = json.loads(out[-1])
    assert rc == 0 and res["correct"] is True
    for name in NAMES:
        assert res["metrics"][name]["unit"] == "ms"
        assert isinstance(res["metrics"][name]["value"], float)
    assert res["metrics"]["dispatch_call_ms_per_chunk"]["value"] > 0
    # The harness is back in the chunk runner within a few ms of a result.
    assert abs(res["metrics"]["chunk_turnaround_ms"]["value"]) < 50
    # Nothing of the log on stdout: every line there is still the harness's.
    assert all('"stall"' not in ln for ln in out)
