"""Fleet mode: batched experiment sweeps as one device program.

The contract under test (docs/SEMANTICS.md "Fleet contract"): lane e of a
vmapped fleet run is bit-indistinguishable from running experiment e
alone — per-window digest streams and every parity counter match the solo
tpu engine AND the cpu oracle; an E=1 fleet equals a plain run; a fleet
snapshot resumes bit-identically and any lane slices out as a
solo-resumable state. Plus the config half: sweep expansion, unknown-key
rejection, and the shape-uniformity errors.
"""

import dataclasses
import json
import subprocess
import sys

import numpy as np
import pytest

from shadow1_tpu.ckpt import load_state, run_chunked, save_state
from shadow1_tpu.config.compiled import single_vertex_experiment
from shadow1_tpu.consts import EXIT_CONFIG, MS, EngineParams
from shadow1_tpu.core.digest import SUBSYSTEMS
from shadow1_tpu.core.engine import Engine
from shadow1_tpu.cpu_engine import CpuEngine
from shadow1_tpu.fleet.engine import FleetEngine, slice_experiment
from shadow1_tpu.fleet.expand import (
    FleetConfigError,
    check_uniform,
    expand_sweep,
    expand_sweep_docs,
)
from shadow1_tpu.telemetry.ring import drain_ring
from tests.parity import lane_metrics, trip_metrics
from shadow1_tpu.txn import CapacityExceededError

N_WINDOWS = 15
PARAMS = EngineParams(ev_cap=32, outbox_cap=16, metrics_ring=N_WINDOWS,
                      state_digest=1)


def base_doc(count=16, stop_ms=150):
    return {
        "general": {"seed": 7, "stop_time": f"{stop_ms} ms"},
        "engine": {"scheduler": "tpu", "ev_cap": 32, "outbox_cap": 16,
                   "metrics_ring": N_WINDOWS, "state_digest": 1},
        "network": {"single_vertex": {"latency": "10 ms"}},
        "hosts": [{"name": "h", "count": count}],
        "app": {"model": "phold",
                "params": {"mean_delay_ns": 2.0e7, "init_events": 2}},
    }


def sweep_doc():
    """The standard 3-experiment sweep: seed change, loss-rate change, and
    a churn (restart) fault schedule — one lane per fleet-variable axis."""
    doc = base_doc()
    doc["sweep"] = {
        "seeds": [7, 8, 9],
        "vary": [
            {},
            {"network": {"single_vertex": {"loss": 0.05}}},
            {"faults": {"hosts": [
                {"group": "h", "down_at": "40 ms", "up_at": "80 ms"}]}},
        ],
    }
    return doc


def digest_stream(st, window_ns):
    return {
        r["window"]: tuple(r[f"dg_{s}"] for s in SUBSYSTEMS)
        for r in drain_ring(st, window_ns)
        if r["type"] == "ring"
    }


@pytest.fixture(scope="module")
def fleet_run():
    """One shared fleet run of the standard sweep (compile amortized
    across the parity tests below)."""
    plan = expand_sweep(sweep_doc())
    eng = FleetEngine(plan.exps, plan.params, plan.max_rounds)
    st = eng.run(n_windows=N_WINDOWS)
    return plan, eng, st


# ---------------------------------------------------------------------------
# sweep expansion / validation
# ---------------------------------------------------------------------------

def test_sweep_expansion_seeds_and_vary():
    plan = expand_sweep(sweep_doc())
    assert plan.n_exp == 3
    assert [e.seed for e in plan.exps] == [7, 8, 9]
    assert float(plan.exps[1].loss_vv[0, 0]) == pytest.approx(0.05)
    assert plan.exps[2].faults is not None and plan.exps[0].faults is None
    assert plan.labels[1] == {"exp": 1, "seed": 8}


def test_sweep_count_generates_seeds():
    doc = base_doc()
    doc["sweep"] = {"count": 4, "base_seed": 20}
    docs = expand_sweep_docs(doc)
    assert [d["general"]["seed"] for d in docs] == [20, 21, 22, 23]
    assert all("sweep" not in d for d in docs)


def test_sweep_unknown_key_and_length_mismatch_rejected():
    doc = base_doc()
    doc["sweep"] = {"seedz": [1, 2]}
    with pytest.raises(FleetConfigError):
        expand_sweep_docs(doc)
    doc["sweep"] = {"seeds": [1, 2], "vary": [{}, {}, {}]}
    with pytest.raises(FleetConfigError, match="disagree"):
        expand_sweep_docs(doc)
    doc["sweep"] = {}
    with pytest.raises(FleetConfigError, match="at least one"):
        expand_sweep_docs(doc)
    # Malformed value TYPES are structured rejections too, never raw
    # TypeError/ValueError tracebacks (the CLI only maps FleetConfigError
    # to the fleet_config record).
    doc["sweep"] = {"seeds": 5}
    with pytest.raises(FleetConfigError, match="must be a list"):
        expand_sweep_docs(doc)
    doc["sweep"] = {"count": "sixteen"}
    with pytest.raises(FleetConfigError, match="must be an integer"):
        expand_sweep_docs(doc)
    doc["sweep"] = {"seeds": ["a", "b"]}
    with pytest.raises(FleetConfigError, match=r"seeds\[0\]"):
        expand_sweep_docs(doc)
    doc["sweep"] = {"vary": {"not": "a list"}}
    with pytest.raises(FleetConfigError, match="must be a list"):
        expand_sweep_docs(doc)


def test_sweep_vary_none_entry_means_no_override():
    """A YAML `- ~` (or bare `-`) vary entry is 'no override', not a
    TypeError: the natural way to hold a lane at the base config."""
    doc = base_doc()
    doc["sweep"] = {"seeds": [3, 4], "vary": [None, {}]}
    docs = expand_sweep_docs(doc)
    assert [d["general"]["seed"] for d in docs] == [3, 4]
    doc["sweep"] = {"vary": [None, 42]}
    with pytest.raises(FleetConfigError, match="must be a mapping"):
        expand_sweep_docs(doc)


def test_sweep_vary_typo_fails_in_standard_validation():
    """A typo inside a vary entry hits the same _reject_unknown wall every
    solo config does — the merged doc compiles through build_experiment."""
    doc = base_doc()
    doc["sweep"] = {"vary": [{"general": {"stop_tme": "1 s"}}]}
    with pytest.raises(AssertionError, match="stop_tme"):
        expand_sweep(doc)


def test_sweep_shape_change_rejected_with_shape_error():
    """Swept knobs that change plane shapes (host count, caps, latency,
    horizon) raise the structured shape error naming the knob."""
    doc = base_doc()
    doc["sweep"] = {"vary": [{}, {"hosts": [{"name": "h", "count": 8}]}]}
    with pytest.raises(FleetConfigError, match="plane shapes") as ei:
        expand_sweep(doc)
    assert ei.value.kind == "shape" and ei.value.knob == "n_hosts"

    doc["sweep"] = {"vary": [{}, {"engine": {"ev_cap": 64}}]}
    with pytest.raises(FleetConfigError, match="fleet-uniform") as ei:
        expand_sweep(doc)
    assert ei.value.kind == "shape" and ei.value.knob == "engine.ev_cap"

    doc["sweep"] = {"vary": [
        {}, {"network": {"single_vertex": {"latency": "5 ms"}}}]}
    with pytest.raises(FleetConfigError, match="conservative window") as ei:
        expand_sweep(doc)
    assert ei.value.kind == "shape"

    doc["sweep"] = {"vary": [{}, {"general": {"stop_time": "1 s"}}]}
    with pytest.raises(FleetConfigError) as ei:
        expand_sweep(doc)
    assert ei.value.knob == "end_time"


def test_sweep_may_vary_max_rounds_only_engine_knob():
    doc = base_doc()
    doc["sweep"] = {"vary": [{}, {"engine": {"max_rounds": 128}}]}
    plan = expand_sweep(doc)
    assert plan.max_rounds == [256, 128]


def test_check_uniform_model_cfg_guard():
    a = single_vertex_experiment(n_hosts=4, seed=1, end_time=20 * MS,
                                 latency_ns=10 * MS, model="phold",
                                 model_cfg={"mean_delay_ns": 1e6})
    b = single_vertex_experiment(n_hosts=4, seed=2, end_time=20 * MS,
                                 latency_ns=10 * MS, model="phold",
                                 model_cfg={"mean_delay_ns": 2e6})
    with pytest.raises(FleetConfigError) as ei:
        check_uniform([a, b], [EngineParams()] * 2)
    assert ei.value.knob == "model_cfg" and ei.value.kind == "uniform"


# ---------------------------------------------------------------------------
# fleet <-> solo parity (the tentpole contract)
# ---------------------------------------------------------------------------

def test_fleet_digest_and_metric_parity_vs_solo_tpu_and_cpu(fleet_run):
    """Every lane's digest stream and metrics bit-match running that
    experiment alone — on the solo batched engine AND the cpu oracle
    (the 3-experiment acceptance gate; ci.sh runs the same check via
    tools/fleetprobe.py)."""
    plan, eng, st = fleet_run
    for e, exp in enumerate(plan.exps):
        lane = slice_experiment(st, e)
        fleet_digs = digest_stream(lane, eng.window)
        fleet_m = {k: int(v) for k, v in lane.metrics._asdict().items()}

        solo = Engine(exp, plan.params)
        st_solo = solo.run(n_windows=N_WINDOWS)
        # All but the program's own counts (a PHOLD lane that sends in a
        # window runs the window end for the lanes that do not).
        assert lane_metrics(Engine.metrics_dict(st_solo)) \
            == lane_metrics(fleet_m), f"exp {e} metrics"
        assert digest_stream(st_solo, solo.window) == fleet_digs, \
            f"exp {e} vs solo tpu"

        cpu = CpuEngine(exp, plan.params)
        cm = cpu.run(n_windows=N_WINDOWS)
        oracle = {r["window"]: tuple(r[f"dg_{s}"] for s in SUBSYSTEMS)
                  for r in cpu.digest_rows}
        assert {w: fleet_digs[w] for w in oracle} == oracle, \
            f"exp {e} vs cpu oracle"
        for k in ("events", "pkts_sent", "pkts_delivered", "pkts_lost",
                  "down_events", "down_pkts", "host_restarts"):
            assert cm[k] == fleet_m[k], (e, k)


def test_fleet_e1_equals_plain_run():
    """An E=1 fleet is exactly a plain run wearing one vmap axis."""
    exp = single_vertex_experiment(
        n_hosts=8, seed=3, end_time=100 * MS, latency_ns=10 * MS,
        loss=0.02, model="phold",
        model_cfg={"mean_delay_ns": float(20 * MS), "init_events": 2})
    fleet = FleetEngine([exp], PARAMS)
    stf = fleet.run(n_windows=10)
    solo = Engine(exp, PARAMS)
    sts = solo.run(n_windows=10)
    lane = slice_experiment(stf, 0)
    assert Engine.metrics_dict(sts) == \
        {k: int(v) for k, v in lane.metrics._asdict().items()}
    assert digest_stream(sts, solo.window) == digest_stream(lane,
                                                            fleet.window)
    # Aggregate view of an E=1 fleet is the solo metrics dict verbatim.
    assert FleetEngine.metrics_dict(stf) == Engine.metrics_dict(sts)


def test_fleet_resume_mid_fleet_bit_identical(fleet_run, tmp_path):
    """Snapshot the whole fleet mid-run, resume into a fresh engine:
    digest stream and final state bit-match the straight run."""
    plan, eng, ref = fleet_run
    path = str(tmp_path / "fleet.npz")
    st_half = eng.run(n_windows=8)
    save_state(st_half, path)

    eng2 = FleetEngine(plan.exps, plan.params, plan.max_rounds)
    st = load_state(eng2.init_state(), path)
    st = eng2.run(st, n_windows=N_WINDOWS - 8)
    for e in range(eng.n_exp):
        a, b = slice_experiment(ref, e), slice_experiment(st, e)
        assert digest_stream(a, eng.window) == digest_stream(b, eng.window)
    for la, lb in zip(np.asarray(ref.win_start), np.asarray(st.win_start)):
        assert la == lb
    for k, v in ref.metrics._asdict().items():
        np.testing.assert_array_equal(np.asarray(v),
                                      np.asarray(getattr(st.metrics, k)), k)


def test_fleet_slice_resumes_solo(fleet_run, tmp_path):
    """Per-experiment resume slicing: lane e of a mid-run fleet snapshot
    loads into a SOLO engine and continues bit-identically to the solo
    straight run."""
    plan, eng, ref = fleet_run
    e = 1  # the loss-rate lane
    st_half = eng.run(n_windows=8)
    path = str(tmp_path / "lane.npz")
    save_state(slice_experiment(st_half, e), path)

    solo = Engine(plan.exps[e], plan.params)
    st = load_state(solo.init_state(), path)
    st = solo.run(st, n_windows=N_WINDOWS - 8)
    ref_digs = digest_stream(slice_experiment(ref, e), eng.window)
    assert digest_stream(st, solo.window) == ref_digs


# ---------------------------------------------------------------------------
# rejections / boundary policies
# ---------------------------------------------------------------------------

def test_fleet_accepts_auto_caps_and_retry():
    """Rejection-lift regression (PR 13): --auto-caps and --on-overflow
    retry were structured kind="mode" rejections through PR 12 — both now
    CONSTRUCT (the recovery semantics are proven in
    tests/test_fleet_recover.py)."""
    plan = expand_sweep(sweep_doc())
    eng = FleetEngine(plan.exps,
                      dataclasses.replace(plan.params, auto_caps=1))
    assert eng.params.auto_caps == 1
    eng = FleetEngine(plan.exps,
                      dataclasses.replace(plan.params, on_overflow="retry"))
    assert eng.params.on_overflow == "retry"


def test_fleet_given_compact_cap_warns_and_runs_full_width(fleet_run):
    """``compact_cap`` is in force on a fleet (no warning, the parameters as
    given): the lanes' rounds run 8 columns a trip, the trip loop's
    predicate reduced over the lanes, and lane for lane every counter but
    the round loop's counts of itself is the full-width run's. (The name is
    PR 12's: until PR 44 the fleet warned and ran full width, because the
    compacted path ended in a ``cond`` on a per-lane predicate.)"""
    import warnings

    plan, _, ref = fleet_run
    params = dataclasses.replace(plan.params, compact_cap=8)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        eng = FleetEngine(plan.exps, params, plan.max_rounds)
    assert eng.params == params
    st = eng.run(n_windows=N_WINDOWS)
    got, want = (FleetEngine.metrics_per_exp(s) for s in (st, ref))
    assert [trip_metrics(m) for m in got] == [trip_metrics(m) for m in want]
    assert ref.compact_buckets is None
    trips = np.asarray(st.compact_buckets)
    # A lane's trips are its own: ceil(active / cap) of each of ITS windows
    # (PHOLD keeps most of a lane's 16 hosts active: two trips in most, none
    # in a window with no event), whatever the other lanes needed.
    active = [[r["active_hosts"] for r in eng.drain_rings(st)
               if r["type"] == "ring" and r["exp"] == e] for e in range(3)]
    assert [sum(-(-a // 8) for a in lane) for lane in active] == list(trips)
    assert any(0 in lane for lane in active) and max(map(max, active)) > 8
    assert all(0 < t <= m["rounds"] for t, m in zip(trips, got))
    assert all(g["rounds"] > w["rounds"] for g, w in zip(got, want))


def test_fleet_halt_names_the_overflowing_experiment():
    """on_overflow=halt under --fleet: the boundary check runs per
    experiment and the structured error names the lane (and seed) whose
    cap overflowed."""
    from shadow1_tpu.fleet.run import run_fleet

    exps = [
        single_vertex_experiment(
            n_hosts=8, seed=5, end_time=20 * MS, latency_ns=1 * MS,
            loss=loss, model="phold",
            model_cfg={"mean_delay_ns": float(2 * MS), "init_events": 6})
        # 50% loss keeps lane 0's event population under ev_cap=8; the
        # lossless lane 1 overflows — halt must blame lane 1, not lane 0.
        for loss in (0.5, 0.0)
    ]
    p = EngineParams(ev_cap=8, on_overflow="halt")
    eng = FleetEngine(exps, p)
    with pytest.raises(CapacityExceededError) as ei:
        run_fleet(eng, n_windows=20, every_windows=5, stream=False,
                  labels=[{"exp": 0, "seed": 5}, {"exp": 1, "seed": 5}])
    assert ei.value.knob == "ev_cap"
    assert "fleet experiment 1" in str(ei.value)


def test_fleet_selfcheck_runs_per_experiment(fleet_run):
    """--selfcheck under fleet verifies the drop-accounting identity per
    lane — a clean sweep passes (violation paths are exercised by the
    solo txn tests; the identity math is shared)."""
    plan, _, _ = fleet_run
    from shadow1_tpu.fleet.run import run_fleet

    p = dataclasses.replace(plan.params, selfcheck=1)
    eng = FleetEngine(plan.exps, p, plan.max_rounds)
    st, hb = run_fleet(eng, n_windows=6, every_windows=3, stream=False,
                       selfcheck=True, labels=plan.labels)
    assert int(np.asarray(st.metrics.windows).max()) == 6
    assert len(hb.records) == 2  # one heartbeat per chunk


def test_run_fleet_enters_the_one_chunk_runner_once(monkeypatch):
    """The fleet runner holds no chunk loop: one call of run_fleet is one
    call of ckpt.run_chunked, handed the fleet's hooks, and the module
    dispatches, syncs and preempts nowhere of its own."""
    import inspect

    import shadow1_tpu.ckpt as ckpt
    import shadow1_tpu.fleet.run as fleet_run_mod

    calls = []
    real = ckpt.run_chunked

    def counting(engine, st=None, **kw):
        calls.append(kw)
        return real(engine, st, **kw)

    monkeypatch.setattr(ckpt, "run_chunked", counting)
    exps = [single_vertex_experiment(
        n_hosts=8, seed=s, end_time=20 * MS, latency_ns=1 * MS, model="phold",
        model_cfg={"mean_delay_ns": float(2 * MS), "init_events": 2})
        for s in (5, 6)]
    eng = FleetEngine(exps, EngineParams(ev_cap=32))
    st, hb = fleet_run_mod.run_fleet(eng, n_windows=6, every_windows=2,
                                     stream=False)
    assert len(calls) == 1 and len(hb.records) == 3
    (kw,) = calls
    assert (kw["n_windows"], kw["chunk"]) == (6, 2) and callable(kw["on_chunk"])
    # Nothing can refuse a chunk of this run and nothing retunes it.
    assert kw["guard"] is None and kw["retune"] is None
    assert int(np.asarray(st.metrics.windows).max()) == 6
    src = inspect.getsource(fleet_run_mod)
    for loop_only in ("while done", "chunks.chunk(", "PH_DISPATCH", "PH_SYNC",
                      "raise PreemptedExit"):
        assert loop_only not in src, loop_only


def test_a_fleet_heartbeat_s_clock_is_read_after_the_chunk_s_fetch(monkeypatch):
    """The run call returns before the chunk ends; the fetch is where the
    loop waits for it. A heartbeat that makes the fetch itself (no commit
    hook ran one) reads its clock after it, as one handed the commit's
    does: ``wall_s`` and ``events_per_sec`` are of the chunk that ended."""
    import types

    import shadow1_tpu.fleet.run as fleet_run_mod

    clock = [100.0]
    monkeypatch.setattr(fleet_run_mod, "time", types.SimpleNamespace(
        perf_counter=lambda: clock[0]))

    class Eng:
        n_exp = 2

        def metrics_per_exp(self, st):
            clock[0] += 2.0             # the chunk ends 2 s after its call
            return [{"events": 30}, {"events": 50}]

        def metrics_dict(self, st):
            return {"events": 80, "windows": 5}

        def drain_rings(self, st, start=0):
            return []

        def model_totals(self, st):
            return [{}, {}]

    st = types.SimpleNamespace(win_start=np.asarray([5, 5]))
    for handed in (None, [{"events": 30}, {"events": 50}]):
        clock[0] = 100.0
        hb = fleet_run_mod.FleetHeartbeat(Eng(), stream=False)
        if handed is not None:
            clock[0] += 2.0             # the commit made the fetch
        hb(st, 5, per_exp=handed)
        (rec,) = hb.records
        assert (rec["wall_s"], rec["events_per_sec"]) == (2.0, 40.0), handed


# ---------------------------------------------------------------------------
# records / report tooling
# ---------------------------------------------------------------------------

def test_final_records_shapes(fleet_run):
    plan, eng, st = fleet_run
    from shadow1_tpu.fleet.run import final_records

    recs, summary = final_records(eng, st, plan.labels, N_WINDOWS, 1.0)
    assert [r["exp"] for r in recs] == [0, 1, 2]
    assert all(r["type"] == "fleet_exp" for r in recs)
    assert recs[2]["faults"]["host_restarts"] > 0
    assert "faults" not in recs[0]
    assert summary["type"] == "fleet_summary"
    assert summary["experiments"] == 3
    assert summary["events_per_exp"] == \
        [r["metrics"]["events"] for r in recs]
    # Aggregate counters sum; gauges max (never E x the lane value).
    assert summary["metrics"]["events"] == sum(summary["events_per_exp"])
    assert summary["metrics"]["windows"] == N_WINDOWS


def test_ring_records_tagged_per_experiment(fleet_run):
    plan, eng, st = fleet_run
    recs = eng.drain_rings(st)
    assert {r["exp"] for r in recs} == {0, 1, 2}
    by_exp = {}
    for r in recs:
        if r["type"] == "ring":
            by_exp.setdefault(r["exp"], []).append(r)
    assert all(len(v) == N_WINDOWS for v in by_exp.values())
    # Lane 1 (5% loss) must record losses some window; lane 0 none.
    assert sum(r["pkts_lost"] for r in by_exp[1]) > 0
    assert sum(r["pkts_lost"] for r in by_exp[0]) == 0


def test_captune_groups_by_experiment(fleet_run):
    """A sweep's cap verdicts come out one per experiment — the experiment
    id is a grouping key only, never part of the peak math."""
    plan, eng, st = fleet_run
    from shadow1_tpu.fleet.run import final_records
    from shadow1_tpu.tools import captune

    recs, summary = final_records(eng, st, plan.labels, N_WINDOWS, 1.0)
    rows = recs + [summary] + eng.drain_rings(st)
    groups = captune.group_records(rows)
    assert {"(run) [exp 0]", "(run) [exp 1]", "(run) [exp 2]"} <= set(groups)
    advice = {g: captune.advise(*captune.peaks_from_records(rs))
              for g, rs in groups.items()}
    for g in ("(run) [exp 0]", "(run) [exp 1]", "(run) [exp 2]"):
        knobs = {r["knob"] for r in advice[g]}
        assert "ev_cap" in knobs
        ev = next(r for r in advice[g] if r["knob"] == "ev_cap")
        assert ev["cap"] == plan.params.ev_cap
        assert 0 < ev["peak"] <= plan.params.ev_cap


def test_heartbeat_report_groups_rings_by_experiment(fleet_run, tmp_path,
                                                     capsys):
    plan, eng, st = fleet_run
    from shadow1_tpu.fleet.run import final_records
    from shadow1_tpu.tools import heartbeat_report

    recs, summary = final_records(eng, st, plan.labels, N_WINDOWS, 1.0)
    log = tmp_path / "fleet.log"
    with open(log, "w") as f:
        for r in recs + [summary] + eng.drain_rings(st):
            f.write(json.dumps(r) + "\n")
    out = heartbeat_report.summarize(heartbeat_report.load_records(str(log)))
    printed = capsys.readouterr().out
    assert out["fleet_experiments"] == 3
    assert out["ring_experiments"] == 3
    assert set(out["ring_by_exp"]) == {0, 1, 2}
    assert "experiment 2" in printed
    # Per-exp stats stay per-exp: lane 0 (lossless) ranks zero pkts_lost
    # even though lane 2 lost plenty.
    assert out["ring_by_exp"][0]["pkts_lost"]["max"] == 0


# ---------------------------------------------------------------------------
# CLI (subprocess — fast config, compile cache shared via conftest env)
# ---------------------------------------------------------------------------

def _write_sweep_cfg(tmp_path, extra=""):
    cfg = tmp_path / "sweep.yaml"
    cfg.write_text(
        "general: {seed: 7, stop_time: 60 ms}\n"
        "engine: {scheduler: tpu, ev_cap: 32, outbox_cap: 16}\n"
        "network: {single_vertex: {latency: 10 ms}}\n"
        "hosts: [{name: h, count: 8}]\n"
        "app: {model: phold, params: {mean_delay_ns: 2.0e7, "
        "init_events: 2}}\n"
        "sweep: {seeds: [7, 8, 9]}\n" + extra
    )
    return cfg


def test_cli_fleet_records(tmp_path):
    cfg = _write_sweep_cfg(tmp_path)
    out = subprocess.run(
        [sys.executable, "-m", "shadow1_tpu", str(cfg), "--fleet"],
        capture_output=True, text=True)
    assert out.returncode == 0, out.stderr[-800:]
    lines = [json.loads(l) for l in out.stdout.strip().splitlines()]
    assert [r["type"] for r in lines] == \
        ["fleet_exp"] * 3 + ["fleet_summary"]
    assert [r["seed"] for r in lines[:3]] == [7, 8, 9]
    assert lines[3]["experiments"] == 3


def test_cli_fleet_faults_off_strips_schedules(tmp_path):
    """--faults off under --fleet is the same healthy-world A/B as solo:
    every experiment's fault schedule (vary[]-added ones included) is
    stripped, so churn lanes run clean."""
    cfg = tmp_path / "churn_sweep.yaml"
    cfg.write_text(
        "general: {seed: 7, stop_time: 60 ms}\n"
        "engine: {scheduler: tpu, ev_cap: 32, outbox_cap: 16}\n"
        "network: {single_vertex: {latency: 10 ms}}\n"
        "hosts: [{name: h, count: 8}]\n"
        "app: {model: phold, params: {mean_delay_ns: 2.0e7, "
        "init_events: 2}}\n"
        "sweep:\n"
        "  seeds: [7, 8]\n"
        "  vary:\n"
        "    - {}\n"
        "    - {faults: {hosts: [{group: h, down_at: 20 ms, "
        "up_at: 40 ms}]}}\n"
    )
    on = subprocess.run(
        [sys.executable, "-m", "shadow1_tpu", str(cfg), "--fleet"],
        capture_output=True, text=True)
    off = subprocess.run(
        [sys.executable, "-m", "shadow1_tpu", str(cfg), "--fleet",
         "--faults", "off"],
        capture_output=True, text=True)
    assert on.returncode == 0 and off.returncode == 0, off.stderr[-500:]
    rec_on = json.loads(on.stdout.strip().splitlines()[1])
    rec_off = json.loads(off.stdout.strip().splitlines()[1])
    assert rec_on["faults"]["host_restarts"] > 0
    assert "faults" not in rec_off
    assert rec_off["metrics"]["host_restarts"] == 0


def test_cli_fleet_corrupt_ckpt_falls_back_to_fresh_start(tmp_path):
    """A supervised fleet child whose --ckpt snapshot is corrupt restarts
    from scratch (solo-path policy) instead of crash-looping."""
    cfg = _write_sweep_cfg(tmp_path)
    ck = tmp_path / "fleet.npz"
    ck.write_bytes(b"not a checkpoint at all")
    out = subprocess.run(
        [sys.executable, "-m", "shadow1_tpu", str(cfg), "--fleet",
         "--ckpt", str(ck), "--supervised-child"],
        capture_output=True, text=True)
    assert out.returncode == 0, out.stderr[-800:]
    assert "discarding corrupt fleet checkpoint" in out.stderr
    summary = json.loads(out.stdout.strip().splitlines()[-1])
    assert summary["type"] == "fleet_summary" and not summary["resumed"]


def test_cli_fleet_structured_rejections(tmp_path):
    cfg = _write_sweep_cfg(tmp_path)

    def run(*flags):
        out = subprocess.run(
            [sys.executable, "-m", "shadow1_tpu", str(cfg), "--fleet",
             *flags], capture_output=True, text=True)
        return out.returncode, out.stdout.strip().splitlines()

    rc, lines = run("--engine", "sharded")
    assert rc == EXIT_CONFIG
    err = json.loads(lines[-1])
    assert err["error"] == "fleet_config" and err["kind"] == "mode"
    # Rejection-lift regression (PR 13): --auto-caps / --on-overflow retry
    # under --fleet no longer exit with the old kind="mode" records — the
    # sweep runs (recovery semantics proven in tests/test_fleet_recover.py).
    for flags in (("--auto-caps",), ("--on-overflow", "retry")):
        rc, lines = run(*flags, "--windows", "4")
        assert rc == 0, (flags, lines[-1:])
        assert json.loads(lines[-1])["type"] == "fleet_summary", flags
    # No sweep: section -> schema-kind rejection.
    solo = tmp_path / "solo.yaml"
    solo.write_text(cfg.read_text().replace("sweep: {seeds: [7, 8, 9]}\n",
                                            ""))
    out = subprocess.run(
        [sys.executable, "-m", "shadow1_tpu", str(solo), "--fleet"],
        capture_output=True, text=True)
    assert out.returncode == EXIT_CONFIG
    assert json.loads(out.stdout.strip().splitlines()[-1])["kind"] == \
        "schema"


@pytest.mark.slow
def test_cli_fleet_ckpt_resume_bit_identical(tmp_path):
    """A --fleet --ckpt run killed mid-flight resumes from the fleet
    snapshot and finishes with per-experiment metrics identical to a
    straight run (the supervised chunk+resume recipe, fleet-shaped)."""
    import os

    cfg = _write_sweep_cfg(tmp_path)
    straight = subprocess.run(
        [sys.executable, "-m", "shadow1_tpu", str(cfg), "--fleet"],
        capture_output=True, text=True)
    assert straight.returncode == 0, straight.stderr[-800:]
    ck = tmp_path / "fleet_ck.npz"
    env = {**os.environ, "SHADOW1_OBS_CRASH_AT_NS": "40000000",
           "SHADOW1_SUPERVISE_BACKOFF_S": "0"}
    sup = subprocess.run(
        [sys.executable, "-m", "shadow1_tpu", str(cfg), "--fleet",
         "--ckpt", str(ck), "--ckpt-every-s", "0", "--heartbeat", "2"],
        capture_output=True, text=True, env=env)
    assert sup.returncode == 0, sup.stderr[-800:]
    assert "respawning" in sup.stderr
    a = [json.loads(l) for l in straight.stdout.strip().splitlines()]
    b = [json.loads(l) for l in sup.stdout.strip().splitlines()]
    for ra, rb in zip(a[:3], b[:3]):
        assert ra["metrics"] == rb["metrics"], ra.get("exp")


def filexfer_exp(seed, loss):
    """Three clients fetching 30 kB from host 0 (also built, not run, by
    ``test_fleet_guards``)."""
    role = np.full(4, 1, np.int64)
    role[0] = 0
    return single_vertex_experiment(
        n_hosts=4, seed=seed, end_time=2_000 * MS, latency_ns=10 * MS,
        loss=loss, bw_bits=10**7, model="net",
        model_cfg={
            "app": "filexfer",
            "role": role,
            "server": np.zeros(4, np.int64),
            "flow_bytes": np.full(4, 30_000, np.int64),
            "start_time": np.full(4, 1 * MS, np.int64),
            "flow_count": np.where(role == 1, 1, 0),
        })


@pytest.mark.slow
def test_fleet_net_model_parity():
    """The TCP/NIC plane rides the experiment axis too: a filexfer fleet
    (loss-rate ladder) lane bit-matches its solo run."""
    exps = [filexfer_exp(11, 0.0), filexfer_exp(11, 0.02),
            filexfer_exp(12, 0.05)]
    n = 40
    p = dataclasses.replace(PARAMS, metrics_ring=n)
    fleet = FleetEngine(exps, p)
    stf = fleet.run(n_windows=n)
    for e, exp in enumerate(exps):
        solo = Engine(exp, p)
        sts = solo.run(n_windows=n)
        lane = slice_experiment(stf, e)
        assert digest_stream(sts, solo.window) == \
            digest_stream(lane, fleet.window), f"exp {e}"
        # runs_* is the fleet program's count, not the lane's.
        assert lane_metrics(Engine.metrics_dict(sts)) == lane_metrics(
            {k: int(v) for k, v in lane.metrics._asdict().items()})
