"""BASELINE's config 4 as a supported deployment: ``tor10k`` (rung 4, one
10,000-host Tor network) and its cell ``tor10k.join``.

The real width is held by its files and by ``eval_shape`` only; what RUNS
here is rung 4's shape in miniature (``tests/rehearsal_tor_join``: the three
relay classes at rung 4's weights, 2 authorities, 24 clients that join 45 ms
apart — so that in one window one client fetches the directory, another
builds a circuit, a third streams — 2 circuits x 3 streams) as ONE lane of
the fleet engine, held to the C++ reference counter for counter and to the
solo engine leaf for leaf, then run through the benchmark's own harness with
its controls and its two new per-layer readers.
"""

import collections
import contextlib
import dataclasses
import functools
import io
import json
import os
import re
import time
import types
import warnings

import jax
import numpy as np
import pytest
import yaml

from shadow1_tpu.core.engine import Engine, compact_cap_of
from shadow1_tpu.fleet.engine import (
    FleetEngine,
    fleet_metrics_per_exp,
    slice_experiment,
)
from shadow1_tpu.fleet.expand import expand_sweep
from shadow1_tpu.telemetry import chunk_log
from shadow1_tpu.telemetry.phases import phase_path
from tests.parity import lane_metrics, unlike_but_trips, unlike_leaves

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REHEARSAL = os.path.join(ROOT, "tests", "rehearsal_tor_join")
RUNG4 = os.path.join(ROOT, "configs", "rung4_tor10k.yaml")
BENCH = os.path.join(ROOT, "benchmarks")
CELL = "tor33.join24"
SEED = 600000004000             # the cell's pool of one; past 2**32
N_WINDOWS, MIDWAY = 60, 40
MUST_BE_ZERO = ["ev_overflow", "ob_overflow", "round_cap_hits",
                "total_ct_overflow"]


def doc33():
    with open(os.path.join(REHEARSAL, "configs", "tor33.yaml")) as f:
        doc = yaml.safe_load(f)
    doc["sweep"] = {"seeds": [SEED]}
    return doc


@pytest.fixture(scope="module")
def plan():
    return expand_sweep(doc33())


@pytest.fixture(scope="module")
def fleet(plan):
    """The fleet of one under the file's parameters (``compact_cap`` 8 of 33
    columns in force: several trips a window), its state after 40 windows
    and after all 60."""
    eng = FleetEngine(plan.exps, plan.params, plan.max_rounds)
    midway = eng.run(n_windows=MIDWAY)
    return eng, midway, eng.run(midway, n_windows=N_WINDOWS - MIDWAY)


def lane_counters(eng, st):
    return {**eng.model_totals(st)[0], **fleet_metrics_per_exp(st)[0]}


# ---- (a) the miniature has rung 4's shape --------------------------------------

def test_the_miniature_keeps_rung_4_s_classes_weights_and_arrivals():
    with open(RUNG4) as f:
        real = yaml.safe_load(f)
    small = doc33()
    assert small["app"]["groups"].keys() == real["app"]["groups"].keys()
    for name in ("guard", "middle", "exit", "dirauth"):
        assert small["app"]["groups"][name] == real["app"]["groups"][name]
    a, b = small["app"]["groups"]["client"], real["app"]["groups"]["client"]
    assert (a["n_circuits"], a["n_streams"]) == (b["n_circuits"], b["n_streams"]) == (2, 3)
    assert set(a["start_time"]) == set(b["start_time"]) == {"start", "interval"}
    assert small["app"]["defaults"]["mean_think_ns"] == real["app"]["defaults"]["mean_think_ns"]
    counts = {g["name"]: g["count"] for g in small["hosts"]}
    assert counts == {"guard": 2, "middle": 3, "exit": 2, "dirauth": 2, "client": 24}
    assert small["engine"]["compact_cap"] and real["engine"]["compact_cap"] == 1280


def test_in_one_window_clients_are_in_every_phase(fleet, plan):
    """At window 40 of the miniature: a client that has not got its
    directory yet, one that has and has finished no stream (it builds or
    streams), and one with a stream behind it — what ``tor1k.seeds8``, whose
    clients all wake in window 3, never shows."""
    eng, midway, end = fleet
    client = np.asarray(plan.exps[0].model_cfg["role"]) == 1
    s = eng.model_summary(midway, 0)
    boot = np.asarray(s["bootstrap_time"])[client]
    streams = np.asarray(s["streams_done"])[client]
    assert client.sum() == 24
    assert (boot == 0).any()
    assert ((boot > 0) & (streams == 0)).sum() >= 5
    assert (streams > 0).sum() >= 3
    # They join one every 45 ms: the directory arrives in that order, over
    # more than a simulated second.
    boot_end = np.asarray(eng.model_summary(end, 0)["bootstrap_time"])[client]
    assert (boot_end > 0).all() and (np.diff(boot_end) > 0).all()
    assert boot_end[-1] - boot_end[0] > 1_000_000_000
    m = fleet_metrics_per_exp(end)[0]
    # Few hosts have an eligible event in a window: the regime of the cell.
    assert 0 < m["active_hosts"] < 0.4 * N_WINDOWS * 33
    assert m["compact_max_fill"] < 33


# ---- (b) one lane of the fleet = the reference = the solo engine ---------------

def test_the_lane_equals_the_reference_counter_for_counter(fleet, plan):
    from benchmarks.reference import comparator

    eng, _, st = fleet
    ref = comparator.counters(plan.exps[0], eng.params, SEED, N_WINDOWS)
    have = lane_counters(eng, st)
    compared = {k: (have.get(k), v) for k, v in ref.items()
                if k not in comparator.NOT_COUNTERS}
    assert len(compared) >= 18, sorted(compared)
    assert {"total_streams_done", "total_cells_rx", "total_cells_fwd",
            "total_ct_overflow", "clients_done", "tcp_rto",
            "tcp_ooo_drops"} <= set(compared)
    assert all(a == b for a, b in compared.values()), compared
    assert have["total_streams_done"] >= 10 and have["events"] > 3000
    assert all(have[k] == 0 for k in (*MUST_BE_ZERO, "mq_overflow"))


def test_the_lane_equals_the_solo_engine_leaf_for_leaf(fleet, plan):
    eng, _, st = fleet
    solo = Engine(plan.exps[0], eng.params)
    want = solo.run(n_windows=N_WINDOWS)
    assert not unlike_leaves(slice_experiment(st, 0), want)
    assert lane_metrics(fleet_metrics_per_exp(st)[0]) \
        == lane_metrics(Engine.metrics_dict(want))
    assert eng.model_totals(st)[0] == solo.model_totals(want)
    # One lane: the program ran a pass exactly where the lane had the kind.
    m = fleet_metrics_per_exp(st)[0]
    assert all(m["runs_" + k] == m["fires_" + k]
               for k in ("deliver", "timer", "txr", "app"))


# ---- (b2) the cap is a width, not a path: 8, 32 and no cap are one simulation ---

def _fleet_at(plan, cap):
    eng = FleetEngine(plan.exps, dataclasses.replace(plan.params, compact_cap=cap),
                      plan.max_rounds)
    return eng, eng.run(n_windows=N_WINDOWS)


@pytest.fixture(scope="module")
def full_width(plan):
    return _fleet_at(plan, 0)


@pytest.fixture(scope="module")
def one_trip(plan):
    return _fleet_at(plan, 32)


def test_a_lane_of_several_trips_a_window_equals_the_full_width_lane(
        fleet, full_width):
    """``compact_cap`` 8: the busiest window has more than 8 active hosts, so
    it takes several trips — every leaf but the round loop's counts of
    itself is the cap-0 lane's (whose counters the reference test above
    holds to the C++ reference through this one)."""
    (_, _, st), (_, want) = fleet, full_width
    assert unlike_but_trips(st, want) == []
    m, w = fleet_metrics_per_exp(st)[0], fleet_metrics_per_exp(want)[0]
    assert m["compact_max_fill"] == w["compact_max_fill"] > 8
    assert m["rounds"] > w["rounds"]
    assert N_WINDOWS < int(st.compact_buckets[0]) <= m["rounds"]


def test_a_lane_of_one_trip_a_window_equals_the_full_width_lane_rounds_too(
        one_trip, full_width):
    """``compact_cap`` 32 of 33: no window has 33 active hosts, so every
    window with an event takes exactly one trip, and then ``rounds``,
    ``fires_*`` and ``runs_*`` are the full-width counts too: every leaf
    but ``compact_buckets``, which counts the windows that had an event."""
    (_, st), (_, want) = one_trip, full_width
    assert not unlike_leaves(st._replace(compact_buckets=None), want)
    m = fleet_metrics_per_exp(st)[0]
    assert m["compact_max_fill"] <= 32
    # One trip a window that had an event, none in the few that had none.
    assert N_WINDOWS - 6 <= int(st.compact_buckets[0]) < N_WINDOWS


def _boundaries(tcp, lane=None):
    """Per host, the pool's boundaries as a sorted list (socket, end, meta)."""
    sock, end, meta = (np.asarray(tcp[k] if lane is None else tcp[k][lane])
                       for k in ("mq_sock", "mq_end", "mq_meta"))
    return [sorted(zip(sock[sock[:, h] >= 0, h].tolist(),
                       end[sock[:, h] >= 0, h].tolist(),
                       meta[sock[:, h] >= 0, h].tolist()))
            for h in range(sock.shape[1])]


def test_the_boundary_pool_moves_with_its_columns_under_a_cap(
        fleet, full_width, plan):
    """The pool's three planes keep H minor, so the column mover carries
    them like any leaf: after 40 windows (boundaries pending on 9 hosts) and
    after 60 the compacted lane's pool is the full-width lane's, slot for
    slot, and the gauge with it."""
    (eng, midway, st), (weng, _) = fleet, full_width
    assert eng.params.mq_pool == 128 and weng.params.compact_cap == 0
    want_mid = weng.run(n_windows=MIDWAY)
    for got, want in ((midway, want_mid),
                      (st, weng.run(want_mid, n_windows=N_WINDOWS - MIDWAY))):
        for k in ("mq_sock", "mq_end", "mq_meta"):
            assert np.array_equal(np.asarray(got.model.tcp[k]),
                                  np.asarray(want.model.tcp[k])), k
        m, w = fleet_metrics_per_exp(got)[0], fleet_metrics_per_exp(want)[0]
        assert m["mq_max_fill"] == w["mq_max_fill"] > 0
        assert m["mq_overflow"] == w["mq_overflow"] == 0
    assert sum(bool(b) for b in _boundaries(midway.model.tcp, 0)) >= 3


def test_the_boundary_pool_sharded_three_ways_is_the_solo_engine_s(plan):
    """33 hosts on three devices of eleven: each shard holds its hosts'
    columns of the pool; boundaries, gauge and counters are the solo
    engine's."""
    from shadow1_tpu.shard.engine import ShardedEngine

    params = dataclasses.replace(plan.params, compact_cap=0)
    solo = Engine(plan.exps[0], params)
    want = solo.run(n_windows=MIDWAY)
    sh = ShardedEngine(plan.exps[0], params, devices=jax.devices()[:3])
    got = sh.run(n_windows=MIDWAY)
    assert _boundaries(got.model.tcp) == _boundaries(want.model.tcp)
    assert sum(bool(b) for b in _boundaries(want.model.tcp)) >= 3
    m, w = ShardedEngine.metrics_dict(got), Engine.metrics_dict(want)
    assert m["mq_max_fill"] == w["mq_max_fill"] > 0
    assert all(m[k] == w[k] for k in ("events", "pkts_sent", "pkts_delivered",
                                      "mq_overflow", "x2x_overflow"))


# ---- (b3) what the compiled program of a fleet with a cap holds ------------------
# Read off the compiled program's text, where every instruction carries the
# scopes it was traced under (telemetry/phases.py): the scopes are relative
# to the trace, so nothing an earlier test traced can show up in them.

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\(.*?\)|\S+)\s+'
                    r'([\w\-]+)\(.*?op_name="([^"]*)"')
_H_WIDE = re.compile(r"[\[,]33[\],]")      # a dimension of 33 hosts


@functools.cache
def _hlo_lines(eng):
    """A fleet's compiled window program, a line an instruction; compiled
    once an engine (two tests and two parsers read each)."""
    return eng.hlo_text().splitlines()


def _instructions(eng):
    """(result shape, opcode, op_name) of every instruction of a fleet's
    compiled window program that has an ``op_name``."""
    return [m.groups() for m in map(_INSTR.match, _hlo_lines(eng)) if m]


def _round_loops(instrs):
    """The ``while`` instructions of the rounds phase outside the handler
    passes and the round's push commit (its own loop of trips, PR 49: one
    ``while`` under ``phase:push_commit``'s guard, inside the round loop),
    by how deep they sit: ``.../phase:rounds)/while`` is depth 1."""
    tails = [op.split("phase:rounds)", 1)[1] for _, opc, op in instrs
             if opc == "while" and "phase:rounds)" in op and "phase:h_" not in op]
    commits = [t for t in tails if "phase:push_commit" in t]
    assert len(commits) == 1 and commits[0].endswith("/while") \
        and "phase:push_commit/cond/" in commits[0]
    tails.remove(commits[0])
    return sorted(t.count("while") for t in tails)


_GATHER = re.compile(r'=\s*\w+\[([\d,]*)\]\S*\s+gather\(.*?slice_sizes=\{([\d,]*)\}'
                     r'.*?op_name="([^"]*)"')


def _gathers(eng):
    """(op_name, index entries) of every ``gather`` of a fleet's compiled
    window program: the result's elements over a slice's. A row-uniform
    gather (one index for a whole column of rows) has as many entries as
    columns it reads, an element-wise one as many as elements."""
    def size(dims):
        return int(np.prod([int(d) for d in dims.split(",") if d] or [1]))

    return [(m[3], size(m[1]) // size(m[2]))
            for m in map(_GATHER.search, _hlo_lines(eng)) if m]


def _pass_lookups(instrs):
    """The gathers and scatters of the pops and handler passes, by phase."""
    return collections.Counter(
        (phase_path(op), opc) for _, opc, op in instrs
        if opc in ("gather", "scatter") and ("phase:pop" in op or "phase:h_" in op))


def test_a_fleet_program_with_a_cap_holds_one_round_loop_bucket_wide(
        fleet, full_width):
    """The census of ISSUEs 44 and 45: the round loop is in the program
    once, under the trip loop; no instruction of a pop or a handler pass is
    33 columns wide (they are 8 wide), and the passes look up what the
    full-width program's do and no more; under ``phase:compact_*`` the
    columns move by ROW-UNIFORM gathers — ``cap`` (8) index entries a leaf
    on the way out, ``H`` (33) on the way back, never one an element — with
    no ``dot``, ``convolution`` or ``scatter``."""
    eng, _, st = fleet
    instrs = _instructions(eng)
    assert _round_loops(instrs) == [1, 2]        # the trips ⊃ the rounds
    passes = [(sh, op) for sh, _, op in instrs
              if "phase:pop" in op or "phase:h_" in op]
    assert len(passes) > 5000
    assert not [x for x in passes if _H_WIDE.search(x[0])][:3]
    assert any(re.search(r"[\[,]8[\],]", sh) for sh, _ in passes)
    assert _pass_lookups(instrs) == _pass_lookups(_instructions(full_width[0]))
    mover = collections.Counter(
        opc for _, opc, op in instrs if "phase:compact_" in op)
    assert not {"dot", "convolution", "scatter"} & set(mover), mover
    host_leaves = sum(
        x.shape[-1] == 33
        for x in jax.tree.leaves((st.evbuf, st.outbox, st.model, st.cpu_busy)))
    entries = collections.Counter(
        (phase_path(op), n) for op, n in _gathers(eng) if "phase:compact_" in op)
    assert mover["gather"] == sum(entries.values())
    # Out: a gather a leaf (and one a Ctx table the compiler could not
    # fold), 8 entries each; back: a gather a leaf, 33 entries each.
    assert set(entries) == {("rounds/compact_gather", 8),
                            ("rounds/compact_scatter", 33)}, entries
    assert entries["rounds/compact_scatter", 33] == host_leaves > 50
    assert host_leaves <= entries["rounds/compact_gather", 8] <= host_leaves + 13
    # Both scopes are there, inside the rounds phase and outside every pass
    # (a fusion merged from two ops names its scope twice).
    paths = {phase_path(op) for _, _, op in instrs if "phase:compact_" in op}
    assert {"rounds/compact_gather", "rounds/compact_scatter"} <= paths
    assert all(set(p.split("/")) <= {"rounds", "compact_gather",
                                     "compact_scatter"} for p in paths), paths


def test_a_fleet_program_without_a_cap_is_the_program_it_was(full_width):
    """No cap: no leaf for the trips in the state, nothing under a
    ``phase:compact_*`` scope, one round loop, 33 columns wide, straight
    under ``phase:rounds`` — the program the parent lowered (CHANGES.md,
    PR 44, has the four cells' hashes)."""
    eng, st = full_width
    assert st.compact_buckets is None
    assert len(jax.tree.leaves(st)) + 1 == len(
        jax.tree.leaves(jax.eval_shape(FleetEngine(
            eng.exps, dataclasses.replace(eng.params, compact_cap=8)).init_state)))
    instrs = _instructions(eng)
    assert _round_loops(instrs) == [1]
    assert not [op for _, _, op in instrs if "phase:compact_" in op]
    assert any(_H_WIDE.search(sh) for sh, _, op in instrs if "phase:h_" in op)


# ---- (c) the cell in miniature through the benchmark's harness -----------------

def _bench(seed, *more):
    from benchmarks.harness import loop

    out = io.StringIO()
    with contextlib.redirect_stdout(out), warnings.catch_warnings():
        warnings.simplefilter("ignore", UserWarning)
        rc = loop.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                        "0.2", "--trace", "0", *more], REHEARSAL,
                       time.perf_counter(), require_chip=False)
    lines = [json.loads(ln) for ln in out.getvalue().strip().splitlines()]
    return rc, lines[-1], [ln for ln in lines if "engine_vs_reference" in ln]


@pytest.mark.parametrize("seed", [7, 3_000_000_019])
def test_the_cell_in_miniature_is_correct_and_every_run_does_the_same_work(
        fleet, seed):
    rc, res, lanes = _bench(seed)
    assert rc == 0 and res["correct"] is True
    assert (res["attempted"], res["failed"]) == (1, 0)
    (ln,) = lanes
    assert ln["seed"] == ln["reference_seed"] == SEED and ln["ok"]
    assert ln["windows"] == N_WINDOWS and not ln["must_be_zero"]
    # A pool of one: whatever --seed, the counters are the fixture's.
    have = lane_counters(fleet[0], fleet[2])
    assert all(a == b == have[k] for k, (a, b) in ln["engine_vs_reference"].items())
    assert all(res["compared"][k + ".must_be_zero"] == [0, 0] for k in MUST_BE_ZERO)
    assert set(res["metrics"]) == {"events_per_s", "peak_hbm_mb", "setup_s"}


@pytest.mark.parametrize("control", ["wrong_seed", "small_caps"])
def test_the_cell_in_miniature_under_a_control_is_not_correct(control):
    """Tor draws at run time, so the reference under the next seed differs;
    with ``ev_cap`` 20 the relays drop events (``ev_max_fill`` is 83)."""
    rc, res, (ln,) = _bench(11, "--control", control)
    assert rc == 0 and res["correct"] is False and res["failed"] == 1
    assert "events" in ln["differ"] and not ln["ok"]
    if control == "wrong_seed":
        assert ln["reference_seed"] == SEED + 1 and not ln["must_be_zero"]
    else:
        assert ln["must_be_zero"].get("ev_overflow")
        assert res["compared"]["ev_overflow.must_be_zero"][0] > 0


# ---- (d) the two new readers, on the rows of a traced run's shape --------------

@pytest.fixture()
def traced_rows(fleet, plan):
    """The chunk log after what a traced run of the miniature leaves in it:
    a warm-up chunk, the cycle's twelve, the replay of windows 0-35 (one row
    of 30 windows, five of one). Gives the metrics and the trips
    (``compact_buckets``) at windows 30 and 35."""
    from benchmarks.harness import loop
    from benchmarks.harness import sim as simmod

    eng = fleet[0]
    sim = simmod.Sim(eng, plan.exps, plan.params, True)
    log = chunk_log()
    log.clear()
    log.enabled = True
    loop.run_chunk(sim, eng.init_state(), 5)
    st, at = eng.init_state(), {}
    for done in range(0, N_WINDOWS, 5):
        at[done] = jax.device_get((st.metrics, st.compact_buckets))
        st = loop.run_chunk(sim, st, 5)
    loop._replay_rounds(sim, {"traced": (30, 35)}, at[35][0])
    yield types.SimpleNamespace(
        m30=at[30][0], m35=at[35][0],
        trips=int(np.sum(at[35][1]) - np.sum(at[30][1])))
    log.clear()


def _readers():
    from benchmarks.harness import manifest as mf

    m = mf.load(REHEARSAL)
    names = [e["name"] for e in mf.metrics_of(m, "per_layer", CELL)]
    assert names[-4:] == ["active_host_share", "events_per_round",
                          "buckets_per_window", "rounds_other_ms_per_window"]
    return [mf.reader(REHEARSAL, m, "layer_metrics", n) for n in names[-4:-1]]


def test_the_new_readers_read_the_traced_chunk_s_work_off_the_chunk_log(traced_rows):
    m30, m35 = traced_rows.m30, traced_rows.m35
    share, per_round, buckets = _readers()
    counters = {"chunks": 1, "windows": 5, "rounds": 1, "lanes": 1}

    def delta(k):
        return int(np.sum(getattr(m35, k)) - np.sum(getattr(m30, k)))

    assert share(None, counters, {}) == pytest.approx(
        100.0 * delta("active_hosts") / (5 * 33))
    assert 0 < share(None, counters, {}) < 100
    assert per_round(None, counters, {}) == pytest.approx(
        delta("events") / delta("rounds"))
    assert per_round(None, counters, {}) > 1
    # PR 44's reader: the trips of windows 30-35 a window and lane, against
    # the state's own count (cap 8: more than one trip in some window).
    assert buckets(None, counters, {}) == pytest.approx(traced_rows.trips / 5)
    assert buckets(None, counters, {}) > 1
    assert buckets(None, {**counters, "lanes": 0}, {}) is None
    # No traced chunk in the counters, or a stretch this log has no rows of.
    assert share(None, {"chunks": 0, "windows": 0}, {}) is None
    assert per_round(None, {"chunks": 2, "windows": 20}, {}) is None
    assert buckets(None, {"chunks": 2, "windows": 20, "lanes": 1}, {}) is None


def test_on_rows_without_the_totals_the_new_readers_return_none(traced_rows,
                                                                monkeypatch):
    """The parent's rows (PR 39's): ``first_window`` and the clock stamps,
    no totals and no ``hosts``. And a log without the replay's rows (an
    untraced run) says nothing of where the stretch is."""
    from shadow1_tpu.telemetry.registry import CHUNK_CAP_TOTALS, CHUNK_TOTALS

    share, per_round, buckets = _readers()
    counters = {"chunks": 1, "windows": 5, "rounds": 1, "lanes": 1}
    log = chunk_log()
    rows = log.rows()
    totals = set(CHUNK_TOTALS + CHUNK_CAP_TOTALS)
    assert all(totals <= set(r) for r in rows) and len(rows) == 19
    # PR 43's rows (and a program with no cap in force): no ``buckets``.
    monkeypatch.setattr(log, "rows", lambda wait_s=1.0: [
        {k: v for k, v in r.items() if k not in CHUNK_CAP_TOTALS} for r in rows])
    assert share(None, counters, {}) is not None
    assert buckets(None, counters, {}) is None
    monkeypatch.setattr(log, "rows", lambda wait_s=1.0: [
        {k: v for k, v in r.items() if k not in totals} for r in rows])
    assert share(None, counters, {}) is None
    assert per_round(None, counters, {}) is None
    assert buckets(None, counters, {}) is None
    monkeypatch.setattr(log, "rows", lambda wait_s=1.0: [
        r for r in rows if r["windows"] == 5])
    assert share(None, counters, {}) is None
    assert per_round(None, counters, {}) is None
    assert buckets(None, counters, {}) is None


# ---- (e) the real cell's data files ---------------------------------------------

def test_the_benchmark_s_experiment_file_is_rung_4_byte_for_byte():
    with open(RUNG4, "rb") as a, \
            open(os.path.join(BENCH, "configs", "tor10k.yaml"), "rb") as b:
        assert a.read() == b.read()


def test_the_cell_s_files_state_what_the_issue_fixed():
    with open(os.path.join(BENCH, "configs", "tor10k.json")) as f:
        meta = json.load(f)
    with open(os.path.join(BENCH, "traffic", "join.json")) as f:
        mix = json.load(f)
    assert (meta["experiment"], meta["engine"]) == ("tor10k.yaml", "fleet")
    assert meta["architecture"] is None
    assert meta["source"].startswith("BASELINE.json config 4, '10k-host Tor "
                                     "network at consensus scale")
    assert "configs/rung4_tor10k.yaml" in meta["source"] and len(meta["source"]) <= 200
    assert meta["reduced"] == ["stop_time"] == list(meta["reduced_why"])
    assert "No width is cut" in meta["reduced_why"]["stop_time"]
    assert meta["must_be_zero"] == MUST_BE_ZERO and len(meta["guarantees"]) >= 3
    fallback = mix["cycle_windows"] == 80
    assert {k: mix[k] for k in ("lanes", "seed_pool_first", "overrides",
                                "chunk_windows", "cycle_windows",
                                "trace_from_window", "trace_chunks")} == {
        "lanes": 1, "seed_pool_first": SEED, "overrides": {},
        "chunk_windows": 5, "cycle_windows": 80 if fallback else 120,
        "trace_from_window": 40 if fallback else 60, "trace_chunks": 1}
    # Every value the source does not fix is stated with its reason, and the
    # finding that rung 4 cannot reach its own end is where the cap is.
    assert {"why", "hosts", "relay_mix", "clients", "bandwidth_up/down",
            "network.single_vertex.latency", "sockets_per_host", "msgq_cap",
            "ct_cap", "cells_max", "ev_cap", "outbox_cap", "max_rounds",
            "compact_cap", "lanes", "cycle", "walls"} <= set(meta["assumed"])
    assert all(isinstance(v, str) and len(v) > 20 for v in meta["assumed"].values())
    assert "590" in meta["assumed"]["ct_cap"] and "1,564" in meta["assumed"]["ct_cap"]
    assert "recollection" in meta["assumed"]["why"]
    # The rehearsal is the cell's own mix at the miniature's cycle.
    with open(os.path.join(REHEARSAL, "traffic", "join24.json")) as f:
        small = json.load(f)
    assert {**mix, "cycle_windows": 60, "trace_from_window": 30, "what": None} \
        == {**small, "what": None}


def test_rung_4_itself_builds_a_fleet_of_one_at_full_width():
    """The real file under the cell's seed, shapes only (no state is made):
    565.0 MB in one lane (1,271.6 while the message boundaries were two
    planes [1, 64, 128, 10000] and a ``pred`` one: a pool [1, 256, 10000]
    since PR 48, and the event payload plane is the largest leaf); its
    ``compact_cap`` 1,280 is in force — no warning, not one parameter
    moved — so the lane's rounds run 1,280 of 10,000 columns a trip, and the
    state has one leaf more, the trips' count. (The name is PR 43's: until
    PR 44 the fleet dropped the cap and ran full width.)"""
    with open(RUNG4) as f:
        doc = yaml.safe_load(f)
    doc["sweep"] = {"seeds": [SEED]}
    plan = expand_sweep(doc, base_dir=os.path.dirname(RUNG4))
    assert plan.params.compact_cap == 1280
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        eng = FleetEngine(plan.exps, plan.params, plan.max_rounds)
    assert eng.params == plan.params
    assert compact_cap_of(eng.params, plan.exps[0].n_hosts) == 1280
    assert (eng.n_exp, plan.exps[0].n_hosts) == (1, 10000)
    assert (eng.params.ev_cap, eng.params.sockets_per_host,
            eng.params.msgq_cap, eng.params.max_rounds) == (256, 128, 64, 1024)
    cfg = plan.exps[0].model_cfg
    assert (int(cfg["ct_cap"]), int(cfg["cells_max"])) == (1024, 120)
    role = np.asarray(cfg["role"])
    assert [int((role == r).sum()) for r in (0, 1, 2)] == [1000, 8990, 10]
    st = jax.eval_shape(eng.init_state)
    assert st.compact_buckets.shape == (1,)
    leaves = jax.tree.leaves(st)
    sizes = sorted(((x.size * x.dtype.itemsize, x.shape) for x in leaves),
                   reverse=True)
    # Metrics.mq_max_fill, mq_overflow (PR 48); push_commit_trips,
    # push_stage_max (PR 49: the stage itself is no leaf between rounds);
    # route_rows (PR 50).
    assert len(leaves) == 136
    assert sum(b for b, _ in sizes) == 565_040_396 + 24
    assert sizes[0] == (102_400_000, (1, 10, 256, 10000))
    assert {st.model.tcp[k].shape for k in ("mq_sock", "mq_end", "mq_meta")} \
        == {(1, 256, 10000)}
    assert not [shape for _, shape in sizes if shape[-3:-1] == (64, 128)]


# ---- (e) the mover's yardstick: the round loop outside pops and passes ----------

def test_rounds_other_ms_per_window_reads_the_mover_and_the_loop_s_bookkeeping():
    """PR 45's reader on a device line worked out by hand: two windows, each
    one trip of the compacted round loop — columns out (30 ns), a pop, a
    handler pass, columns back (50 ns) — and 10 ns of the loop's own
    bookkeeping. Both manifests' entries without a ``workloads`` list end on
    its entry, every cell reads it, and it reads nothing without a phase
    table."""
    from benchmarks.harness import manifest as mf
    from benchmarks.harness import phases as ph
    from benchmarks.harness import trace as tr

    for root, cell in ((REHEARSAL, CELL), (ROOT, "tor10k.join"),
                       (ROOT, "phold65k.dense")):
        m = mf.load(root)
        # The last of the entries every cell reads (PR 47 appended two that
        # list their cells).
        everywhere = [e for e in m["per_layer"] if "workloads" not in e]
        assert everywhere[-1] == {
            "name": "rounds_other_ms_per_window", "unit": "ms",
            "better": "lower", "source": "device_trace",
            "layer": "window program", "moves": "events_per_s"}
        assert [e for e in mf.metrics_of(m, "per_layer", cell)
                if "workloads" not in e][-1] == everywhere[-1]
        read = mf.reader(root, m, "layer_metrics", "rounds_other_ms_per_window")
    table = {"while.0": "", "while.1": "rounds", "while.2": "rounds",
             "gather.1": "rounds/compact_gather", "fusion.pop": "rounds/pop",
             "fusion.h": "rounds/h_deliver/tcp_flush", "copy.1": "",
             "gather.2": "rounds/compact_scatter", "fusion.p": "prepare",
             "fusion.d": "deliver/deliver"}
    assert {ph.rollup_key(v)[0] for k, v in table.items()
            if k.startswith("gather")} == {ph.ROUNDS_OTHER}

    def ev(name, start, dur):
        return [f"%{name} = s32[8]{{0}} op(...)", start, dur]

    ops = []
    for t in (0, 1000):
        ops += [ev("while.0", t, 400), ev("fusion.p", t, 20),
                ev("while.1", t + 20, 300), ev("gather.1", t + 20, 30),
                ev("while.2", t + 50, 200), ev("fusion.pop", t + 50, 40),
                ev("fusion.h", t + 90, 150), ev("copy.1", t + 240, 10),
                ev("gather.2", t + 250, 50), ev("fusion.d", t + 340, 60)]
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": tr.OPS_LINE, "events": ops},
        {"name": tr.MODULES_LINE, "events": [["jit_run(1)", 0, 400],
                                             ["jit_run(1)", 1000, 400]]}]}]}
    rep = ph.phase_report(trace, table)
    assert rep["unknown_ops"] == 0 and rep["busy_ns"] == 2 * 360
    counters = {"phase_s": rep["rollup"], "windows": 2}
    assert read(None, counters, {}) == pytest.approx(1e3 * (30 + 10 + 50) * 1e-9)
    assert read(None, {"windows": 2}, {}) is None
    assert read(None, {"phase_s": rep["rollup"], "windows": 0}, {}) is None
