"""A Tor seed study on the fleet engine, held to the solo engine and to the
C++ reference.

Tor draws at run time (relay choice, stream lengths, think times), so the
seed is data: every lane of one vmapped program must equal a solo run leaf
for leaf, and the benchmark's reference counter for counter, under ITS seed.
All at 20 hosts (2 guards, 3 middles, 2 exits, 1 dirauth, 12 clients), 3
lanes, 40 windows (``tests/rehearsal_tor20``: the benchmark cell
``tor1k.seeds8`` in miniature — its pool's first seeds, its cycle, and like
``configs/rung3_tor1k.yaml`` a ``compact_cap``, in force on the fleet as on
the solo engine: 8 of 20 columns a trip, several trips a window — run
through the benchmark's own harness at the end), plus the data files of the
real cell and the phase scopes of ``apps/tor.py``.
"""

import contextlib
import dataclasses
import io
import json
import os
import subprocess
import sys
import time
import warnings

import jax
import jax.numpy as jnp
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
from shadow1_tpu.telemetry import phases
from shadow1_tpu.telemetry.registry import LANE_PROGRAM_FIELDS, MODEL_TOTALS
from tests.parity import (
    assert_runs_contract,
    lane_metrics,
    unlike_but_trips,
    unlike_leaves,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REHEARSAL = os.path.join(ROOT, "tests", "rehearsal_tor20")
RUNG3 = os.path.join(ROOT, "configs", "rung3_tor1k.yaml")
BENCH = os.path.join(ROOT, "benchmarks")
CELL = "tor20.seeds3"
N_WINDOWS = 40
SEEDS = [600000003000 + i for i in range(3)]    # the cell's pool; past 2**32
TOR_TOTALS = ("total_streams_done", "total_cells_rx", "total_cells_fwd",
              "total_ct_overflow", "total_cell_retries", "clients_done")
TOR_SCOPES = {"tor_dir", "tor_build", "tor_relay", "tor_stream"}


def doc20(seeds=None):
    with open(os.path.join(REHEARSAL, "configs", "tor20.yaml")) as f:
        doc = yaml.safe_load(f)
    if seeds is not None:
        doc["sweep"] = {"seeds": list(seeds)}
    return doc


@pytest.fixture(scope="module")
def plan():
    return expand_sweep(doc20(SEEDS))


@pytest.fixture(scope="module")
def fleet(plan):
    eng = FleetEngine(plan.exps, plan.params, plan.max_rounds)
    return eng, eng.run(n_windows=N_WINDOWS)


@pytest.fixture(scope="module")
def solos(plan, fleet):
    """Each lane's experiment alone on the solo engine, under the fleet's
    parameters (the file's ``compact_cap`` 8 in force): (engine, end
    state)."""
    out = []
    for exp in plan.exps:
        eng = Engine(exp, fleet[0].params)
        out.append((eng, eng.run(n_windows=N_WINDOWS)))
    return out


def lane_counters(eng, st, lane):
    return {**eng.model_totals(st)[lane], **fleet_metrics_per_exp(st)[lane]}


# ---- (a) every lane is the solo engine's run under that lane's seed ----------

@pytest.mark.parametrize("lane", range(len(SEEDS)))
def test_a_lane_equals_the_solo_engine_leaf_for_leaf(fleet, solos, lane):
    eng, st = fleet
    solo, want = solos[lane]
    assert not unlike_leaves(slice_experiment(st, lane), want)
    assert lane_metrics(fleet_metrics_per_exp(st)[lane]) \
        == lane_metrics(Engine.metrics_dict(want))
    totals = solo.model_totals(want)
    assert eng.model_totals(st)[lane] == totals
    assert set(totals) == set(TOR_TOTALS) <= set(MODEL_TOTALS)
    summary = solo.model_summary(want)
    assert totals["total_cell_retries"] == int(summary["cell_retries"].sum())
    assert totals["clients_done"] == int((summary["done_time"] > 0).sum())
    assert totals["total_streams_done"] > 0 and totals["total_cells_fwd"] > 0


def test_a_lane_that_finishes_first_rides_padding_trips_unchanged(fleet, solos):
    """The trip loop runs while ANY lane has an active host left
    (``any_lane``), so a lane whose own active set is used up rides the
    other lanes' further trips with an all-padding bucket. The lanes'
    active sets differ in size (their trips differ), so some lane did ride
    such trips; it counts only its own, and its state is its solo run's —
    ``compact_buckets`` and ``rounds`` included (test (a) compares every
    leaf): a padding trip pops nothing, writes nothing, counts nothing."""
    _, st = fleet
    trips = [int(t) for t in np.asarray(st.compact_buckets)]
    assert len(set(trips)) > 1, trips
    lanes = fleet_metrics_per_exp(st)
    for lane, (_, want) in enumerate(solos):
        assert trips[lane] == int(want.compact_buckets)
        assert lanes[lane]["rounds"] == Engine.metrics_dict(want)["rounds"]
    # A program of three lanes made at least the slowest lane's trips; the
    # lane with the fewest rode the difference as padding.
    assert max(trips) - min(trips) >= 1


def test_the_guards_engage_and_runs_count_the_program(fleet, solos):
    """What ``any_host`` brings: a pass no lane has an event for is skipped
    (``runs_app`` below the loop's iterations), a pass some OTHER lane has one
    for runs in this lane too (``runs_*`` above its ``fires_*``: the lanes of
    test (a) were equal to their solo runs through such rounds), and
    ``runs_*`` is one number in every lane."""
    _, st = fleet
    lanes = fleet_metrics_per_exp(st)
    assert_runs_contract(lanes, [Engine.metrics_dict(s) for _, s in solos])
    for ln in lanes:
        assert ln["runs_app"] > ln["fires_app"] > 0, ln
        assert ln["runs_deliver"] > ln["fires_deliver"] > 0, ln
    # The loop runs each window to its slowest lane, so its iterations are
    # at least any one lane's rounds.
    iterations_at_least = max(ln["rounds"] for ln in lanes)
    assert lanes[0]["runs_app"] < iterations_at_least


# ---- (b) every lane is the C++ reference's run under that lane's seed --------

@pytest.mark.parametrize("lane", range(len(SEEDS)))
def test_a_lane_equals_the_reference_counter_for_counter(fleet, plan, lane):
    from benchmarks.reference import comparator

    eng, st = fleet
    ref = comparator.counters(plan.exps[lane], eng.params, SEEDS[lane],
                              N_WINDOWS)
    have = lane_counters(eng, st, lane)
    compared = {k: (have.get(k), v) for k, v in ref.items()
                if k not in comparator.NOT_COUNTERS}
    assert len(compared) >= 18, sorted(compared)
    assert {"total_streams_done", "total_cells_rx", "total_cells_fwd",
            "total_ct_overflow", "clients_done"} <= set(compared)
    assert all(a == b for a, b in compared.values()), compared
    assert have["total_cells_rx"] > 0
    assert have["ev_overflow"] == have["ob_overflow"] == 0
    assert have["round_cap_hits"] == have["total_ct_overflow"] == 0


# ---- (c) the seed acts through the handlers' draws ----------------------------

def test_the_lanes_differ_and_another_set_of_seeds_differs_from_them(fleet):
    eng, st = fleet
    lanes = [lane_counters(eng, st, e) for e in range(len(SEEDS))]
    assert len({json.dumps(ln, sort_keys=True) for ln in lanes}) == len(SEEDS)
    other = expand_sweep(doc20([7, 8, 9]))
    old = (eng.exps, eng.max_rounds)
    traces = eng._run_jit._cache_size()
    try:
        eng.rebind(other.exps, other.max_rounds)
        st2 = eng.run(n_windows=N_WINDOWS)
        assert eng._run_jit._cache_size() == traces     # the seed is data
        lanes2 = [lane_counters(eng, st2, e) for e in range(3)]
    finally:
        eng.rebind(*old)
    assert all(a != b for a in lanes for b in lanes2)
    assert all(ln["ev_overflow"] == 0 and ln["events"] > 1000 for ln in lanes2)


# ---- (d) the scopes of onion routing ------------------------------------------

@pytest.mark.parametrize("which", ["solo", "fleet"])
def test_the_onion_scopes_are_in_the_program_s_phase_table(fleet, solos, which):
    """Every ``phase:tor_*`` scope reaches the compiled program, under the
    handler pass that runs it; an op line made of the program's own
    instructions is attributed with nothing unknown and sums to busy."""
    eng = fleet[0] if which == "fleet" else solos[0][0]
    table = phases.phase_table(eng.hlo_text())
    paths = set(table.values())
    parts = {p for path in paths for p in path.split("/")}
    assert TOR_SCOPES <= parts, sorted(parts)
    for pass_, scope in (("h_app", "tor_dir"), ("h_app", "tor_relay"),
                         ("h_app", "tor_build"), ("h_app", "tor_stream"),
                         ("h_deliver", "tor_dir"), ("h_deliver", "tor_build"),
                         ("h_deliver", "tor_relay"), ("h_deliver", "tor_stream")):
        assert any(p.startswith(f"rounds/{pass_}") and scope in p.split("/")
                   for p in paths), (pass_, scope)
    # TCP's flush inside a cell send keeps its own row, and the two sites
    # that nest are rows of their own.
    assert any("tor_relay" in p and p.endswith("tcp_flush") for p in paths)
    assert any("tor_build/tor_stream" in p for p in paths)
    assert any("tor_stream/tor_build" in p for p in paths)
    ops = [[f"%{name} = s32[] fusion()", 10 * i, 7]
           for i, name in enumerate(n for n in table
                                    if not phases.is_control_flow(n))]
    got = phases.attribute(ops, table)
    assert got["unknown_ops"] == 0 and got["busy_ns"] == 7 * len(ops)
    rows = got["rows"]
    assert round(sum(r["seconds"] for r in rows.values()) * 1e9) == got["busy_ns"]
    onion = sum(r["seconds"] for p, r in rows.items()
                if TOR_SCOPES & set(p.split("/")))
    assert 0 < onion < got["busy_ns"] / 1e9
    assert phases.rollup_key("rounds/h_deliver/tor_relay/tcp_flush") == \
        ("handlers", "h_deliver")


def _named_eqns(jaxpr, outer=""):
    """(equation, its whole name stack) of every equation, sub-jaxprs
    included: an inner stack is relative to the equation that holds it."""
    from shadow1_tpu.tools.opcensus import _sub_jaxprs

    for e in jaxpr.eqns:
        full = f"{outer}/{e.source_info.name_stack}"
        yield e, full
        for v in e.params.values():
            for sub in _sub_jaxprs(v):
                yield from _named_eqns(sub, full)


@pytest.mark.parametrize("lanes", [0, 2], ids=["solo", "vmap2"])
def test_every_handler_op_of_the_tor_module_is_under_an_onion_scope(lanes):
    """Whatever ``apps/tor.py``'s handlers trace into a round (an equation
    with one of their frames in its traceback) sits under a handler pass
    and under one of the four scopes, so no device time of onion routing
    falls to a bare ``h_app`` / ``h_deliver`` row."""
    from shadow1_tpu.core.engine import window_frame, window_phases
    from shadow1_tpu.tools.phaseprobe import build_engine

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", UserWarning)
        eng, _ = build_engine(os.path.join(REHEARSAL, "configs", "tor20.yaml"))
    fn = dict(window_phases(eng.ctx, eng._handlers, None, eng._pre_window,
                            eng._model.make_handlers, None))["rounds"]
    fr = window_frame(eng.init_state(), eng.ctx)
    if lanes:
        fn = jax.vmap(fn)
        fr = jax.tree_util.tree_map(lambda x: jnp.stack([x] * lanes), fr)
    # A jnp function traced first at start-up keeps init's frames in its
    # cached body: those equations are not the handlers'.
    not_handlers = {"init", "tables", "summary", "<module>"}
    seen, bare, hoisted, by_scope = 0, [], [], {s: 0 for s in TOR_SCOPES}
    for e, stack in _named_eqns(jax.make_jaxpr(fn)(fr).jaxpr):
        tb = e.source_info.traceback
        fns = {f.function_name for f in (tb.frames if tb else ())
               if f.file_name.endswith("shadow1_tpu/apps/tor.py")}
        # The round's push commit runs after the passes (PR 49): an equation
        # there with a frame of this file is ``jnp.where``'s cached body.
        if not fns - not_handlers or "phase:push_commit" in stack:
            continue
        seen += 1
        scopes = [s for s in phases.PHASE.findall(stack) if s in TOR_SCOPES]
        in_pass = {"h_app", "h_deliver"} & set(phases.PHASE.findall(stack))
        if scopes and in_pass:
            by_scope[scopes[-1]] += 1
        elif in_pass or "phase:" in stack:
            bare.append((e.primitive.name, sorted(fns), stack))
        else:
            # Outside the round loop: the weighted pick's preparation of its
            # constant table, where an earlier trace in this process left it
            # in ``searchsorted``'s cached body (none in a fresh process).
            hoisted.append((e.primitive.name, sorted(fns), stack))
    assert seen > 3000 and not bare, bare[:10]
    assert len(hoisted) <= 4 and all("_pick_weighted" in f for _, f, _ in hoisted)
    assert all(n > 100 for n in by_scope.values()), by_scope


# ---- (e) the real cell's data files ------------------------------------------

def test_the_benchmark_s_experiment_file_is_rung_3_byte_for_byte():
    with open(RUNG3, "rb") as a, \
            open(os.path.join(BENCH, "configs", "tor1k.yaml"), "rb") as b:
        assert a.read() == b.read()


def test_the_cell_s_files_state_what_the_issue_fixed(fleet):
    eng, st = fleet
    with open(os.path.join(BENCH, "configs", "tor1k.json")) as f:
        meta = json.load(f)
    with open(os.path.join(BENCH, "traffic", "seeds8.json")) as f:
        mix = json.load(f)
    assert (meta["experiment"], meta["engine"]) == ("tor1k.yaml", "fleet")
    assert meta["reduced"] == ["stop_time"] == list(meta["reduced_why"])
    assert meta["must_be_zero"] == ["ev_overflow", "ob_overflow",
                                    "round_cap_hits", "total_ct_overflow"]
    assert set(meta["must_be_zero"]) <= set(lane_counters(eng, st, 0))
    assert {k: mix[k] for k in ("lanes", "seed_pool_first", "overrides",
                                "chunk_windows", "cycle_windows",
                                "trace_from_window", "trace_chunks")} == {
        "lanes": 8, "seed_pool_first": 600000003000, "overrides": {},
        "chunk_windows": 5, "cycle_windows": 40, "trace_from_window": 20,
        "trace_chunks": 1}
    # Every value the source does not fix is stated with its reason.
    assert {"why", "relay_mix", "ct_cap", "ev_cap", "compact_cap", "lanes",
            "cycle", "walls"} <= set(meta["assumed"])
    # The rehearsal is the cell's own mix at three lanes.
    with open(os.path.join(REHEARSAL, "traffic", "seeds3.json")) as f:
        small = json.load(f)
    assert {**mix, "lanes": 3, "what": None} == {**small, "what": None}


def test_rung_3_itself_builds_a_fleet_of_eight_at_full_width():
    """The real file (1,000 hosts; config only, no state is made) under the
    cell's eight seeds: its ``compact_cap`` 384 is in force — no warning,
    not one parameter moved — so the lanes' rounds run 384 of 1,000 columns a
    trip, and nothing else of its widths moves. (The name is PR 35's: until
    PR 44 the fleet dropped the cap and ran full width.)"""
    with open(RUNG3) as f:
        doc = yaml.safe_load(f)
    assert doc["engine"]["compact_cap"] == 384
    doc["sweep"] = {"seeds": [600000003000 + i for i in range(8)]}
    plan = expand_sweep(doc, base_dir=os.path.dirname(RUNG3))
    assert plan.params.compact_cap == 384
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        eng = FleetEngine(plan.exps, plan.params, plan.max_rounds)
    assert eng.params == plan.params
    assert compact_cap_of(eng.params, plan.exps[0].n_hosts) == 384
    assert (eng.n_exp, plan.exps[0].n_hosts) == (8, 1000)
    assert (eng.params.ev_cap, eng.params.sockets_per_host,
            eng.params.msgq_cap, eng.params.max_rounds) == (256, 64, 64, 1024)
    cfg = plan.exps[0].model_cfg
    assert (int(cfg["ct_cap"]), int(cfg["cells_max"])) == (512, 120)
    role = np.asarray(cfg["role"])
    assert [int((role == r).sum()) for r in (0, 1, 2)] == [120, 875, 5]


def test_a_tor_file_s_compact_cap_warns_and_the_fleet_runs_full_width(plan, fleet):
    """Beside ``test_fleet.py``'s PHOLD case: here the knob comes from the
    experiment file, as rung 3's does, and it is in force (no warning, the
    plan's parameters as they are): the lanes every other test of this file
    holds to solo and reference run 8 of 20 columns a trip, several trips a
    window, and lane 0 equals its FULL-WIDTH solo run in every leaf but the
    round loop's counts of itself. (The name is PR 35's: until PR 44 the
    fleet warned and ran full width.)"""
    assert doc20()["engine"]["compact_cap"] == 8 == plan.params.compact_cap
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        eng = FleetEngine(plan.exps, plan.params, plan.max_rounds)
    assert eng.params == fleet[0].params == plan.params
    assert eng.variant_signature() == fleet[0].variant_signature()
    st = fleet[1]
    trips = np.asarray(st.compact_buckets)
    lanes = fleet_metrics_per_exp(st)
    # Several trips in some window of every lane, none in a window with no
    # event, and at least one round a trip.
    assert all(m["compact_max_fill"] > 8 for m in lanes)
    assert all(0 < t <= m["rounds"] for t, m in zip(trips, lanes))
    full = Engine(plan.exps[0], dataclasses.replace(plan.params, compact_cap=0))
    want = full.run(n_windows=N_WINDOWS)
    assert unlike_but_trips(slice_experiment(st, 0), want,
                            also_not=LANE_PROGRAM_FIELDS) == []
    assert lanes[0]["rounds"] > Engine.metrics_dict(want)["rounds"]


# ---- (f) the cell in miniature through the benchmark's harness ----------------

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


@pytest.fixture(scope="module")
def sound_runs():
    """The miniature cell under two ``--seed``s that stack the pool in
    different orders."""
    return {seed: _bench(seed) for seed in (7, 3_000_000_019)}


@pytest.mark.parametrize("seed", [7, 3_000_000_019])
def test_the_cell_in_miniature_is_correct_in_every_lane(sound_runs, seed):
    rc, res, lanes = sound_runs[seed]
    assert rc == 0 and res["correct"] is True
    assert (res["attempted"], res["failed"]) == (3, 0)
    for ln in lanes:
        cmp_ = ln["engine_vs_reference"]
        assert ln["ok"] and ln["limit"] == 0 and not ln["must_be_zero"]
        assert {"events", "total_streams_done", "total_cells_rx",
                "total_cells_fwd", "total_ct_overflow", "clients_done"} <= set(cmp_)
        assert all(a == b for a, b in cmp_.values())
        assert cmp_["total_cells_fwd"][0] > 0
    assert all(res["compared"][k + ".must_be_zero"] == [0, 0]
               for k in ("ev_overflow", "ob_overflow", "round_cap_hits",
                         "total_ct_overflow"))
    assert res["metrics"]["events_per_s"]["value"] > 0


def test_a_fixed_pool_does_the_same_work_under_two_seeds(sound_runs, fleet):
    """``--seed`` draws the order the pool's seeds are stacked in and nothing
    else: the set of lane counters is one set, and it is the fixture's."""
    a, b = (sound_runs[s][2] for s in (7, 3_000_000_019))
    assert [ln["seed"] for ln in a] != [ln["seed"] for ln in b]
    by_seed = lambda lanes: {ln["seed"]: ln["engine_vs_reference"] for ln in lanes}
    assert by_seed(a) == by_seed(b) and sorted(by_seed(a)) == SEEDS
    assert len({json.dumps(v) for v in by_seed(a).values()}) == 3
    eng, st = fleet
    for lane, seed in enumerate(SEEDS):
        have = lane_counters(eng, st, lane)
        assert all(have[k] == v[0] for k, v in by_seed(a)[seed].items())


@pytest.mark.parametrize("control", ["wrong_seed", "small_caps"])
def test_the_cell_in_miniature_under_a_control_is_not_correct(control):
    """The reference under the next seed (Tor draws at run time, so every
    lane must differ), and the program with ``ev_cap`` 20 against the
    reference at the file's 256 (the dirauth alone is sent more)."""
    rc, res, lanes = _bench(11, "--control", control)
    assert rc == 0 and res["correct"] is False and res["failed"] == 3
    assert all("events" in ln["differ"] and not ln["ok"] for ln in lanes)
    if control == "wrong_seed":
        assert all(ln["reference_seed"] == ln["seed"] + 1 for ln in lanes)
        assert not any(ln["must_be_zero"] for ln in lanes)
    else:
        assert all(ln["must_be_zero"].get("ev_overflow") for ln in lanes)
        assert res["compared"]["ev_overflow.must_be_zero"][0] > 0


# ---- the command line, as a user starts a study --------------------------------

def test_cli_runs_the_study_under_fleet_and_its_records_carry_the_totals(tmp_path):
    doc = doc20(SEEDS)
    doc["general"]["stop_time"] = "1200 ms"          # the cycle's 40 windows
    cfg = tmp_path / "study.yaml"
    cfg.write_text(yaml.safe_dump(doc))
    out = subprocess.run(
        [sys.executable, "-m", "shadow1_tpu", str(cfg), "--fleet",
         "--heartbeat", "20"], capture_output=True, text=True)
    assert out.returncode == 0, out.stderr[-1500:]
    assert "compact_cap" not in out.stderr      # in force: nothing to warn of
    recs = [json.loads(ln) for ln in out.stdout.strip().splitlines()]
    assert [r["type"] for r in recs] == ["fleet_exp"] * 3 + ["fleet_summary"]
    assert [r["seed"] for r in recs[:3]] == SEEDS
    totals = [r["model"] for r in recs[:3]]
    assert all(set(t) == set(TOR_TOTALS) for t in totals)
    assert all(t["total_cells_fwd"] > 0 and t["total_cell_retries"] == 0
               and t["clients_done"] == 0 for t in totals)
    assert all(r["drops"]["total"] == 0 for r in recs[:3])
    beats = [json.loads(ln) for ln in out.stderr.splitlines()
             if ln.startswith('{"type": "heartbeat"')]
    assert len(beats) == 2
    assert beats[-1]["fleet"]["model_per_exp"] == totals
