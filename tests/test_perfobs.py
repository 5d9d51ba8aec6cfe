"""Performance attribution plane (ISSUE 11): wasted-work gauges, phase
attribution, op/fusion census, multi-row bench gate.

Contracts under test:

* the RING_WORK gauge streams (active_hosts / elig_events / outbox_hosts)
  are bit-identical cpu↔tpu↔sharded(8), per window and as run totals, on a
  phold-with-loss config and a TCP (rung-1 filexfer) config;
* the ring schema widened in order (counters, work, gauges, digests) and
  CKPT_FORMAT bumped, with stale-version snapshots rejected;
* tools/opcensus.py: two census runs → identical counts; the drift gate
  trips on an injected extra-op build and on >tolerance baseline drift;
* tools/phaseprobe.py: the phase split reproduces window_step bit-exactly
  and accounts for ≥90% of the straight run's measured ms/round;
* tools/benchgate.py: per-row/per-backend gating logic (pure, unmeasured).
"""

import io
import json

import jax
import numpy as np
import pytest

from shadow1_tpu.config.compiled import single_vertex_experiment
from shadow1_tpu.consts import MS, EngineParams
from shadow1_tpu.core.engine import Engine, Metrics
from shadow1_tpu.cpu_engine import CpuEngine
from shadow1_tpu.telemetry.registry import (
    METRIC_SPECS,
    RING_COUNTERS,
    RING_DIGESTS,
    RING_FIELDS,
    RING_GAUGES,
    RING_WORK,
)
from shadow1_tpu.telemetry.ring import drain_ring

WORK = ("active_hosts", "elig_events", "outbox_hosts")


def phold_exp(n_hosts=32, seed=17, end_time=100 * MS, loss=0.0):
    return single_vertex_experiment(
        n_hosts=n_hosts, seed=seed, end_time=end_time, latency_ns=1 * MS,
        loss=loss, model="phold",
        model_cfg={"mean_delay_ns": float(2 * MS), "init_events": 2},
    )


def rung1_exp():
    import os

    from shadow1_tpu.config.experiment import load_experiment

    cfg = os.path.join(os.path.dirname(__file__), "..", "configs",
                       "rung1_filexfer.yaml")
    return load_experiment(cfg)


# ---------------------------------------------------------------------------
# schema
# ---------------------------------------------------------------------------

def test_work_schema_and_ckpt_format():
    from shadow1_tpu.ckpt import CKPT_FORMAT

    # The work counters are canonical Metrics counters and their ring
    # columns sit between the counter deltas and the gauges.
    assert set(RING_WORK) <= set(METRIC_SPECS)
    assert set(RING_WORK) <= set(Metrics._fields)
    assert RING_FIELDS == RING_COUNTERS + RING_WORK + RING_GAUGES + \
        RING_DIGESTS
    # Widened ring row + new Metrics leaves = snapshot layout change
    # (v10); the flow-probe ring leaf bumped it again (v11), the
    # link-telemetry accumulator leaf once more (v12), the deliver_ranks
    # Metrics leaf again (v13), the five runs_* leaves (v14),
    # runs_window_end (v15), the optional compact_buckets leaf (v16), and the
    # message-boundary pool's leaves with its gauge and counter (v17), the
    # push commit's counter and gauge (v18), route_rows (v19).
    assert CKPT_FORMAT == 19


def test_stale_ckpt_format_rejected(tmp_path):
    from shadow1_tpu import ckpt

    eng = Engine(phold_exp(end_time=20 * MS), EngineParams(metrics_ring=4))
    st = eng.run(n_windows=5)
    path = str(tmp_path / "snap.npz")
    ckpt.save_state(st, path)
    with np.load(path) as d:
        arrs = {k: d[k].copy() for k in d.files}
    arrs["format"][0] = ckpt.CKPT_FORMAT - 1  # the previous layout
    np.savez(path, **arrs)
    with pytest.raises(ValueError, match=f"format v{ckpt.CKPT_FORMAT - 1}"
                                         f".*reads v{ckpt.CKPT_FORMAT}"):
        ckpt.load_state(eng.init_state(), path)


# ---------------------------------------------------------------------------
# gauge parity cpu <-> tpu <-> sharded
# ---------------------------------------------------------------------------

def _assert_work_parity(exp, params, n_windows):
    eng = Engine(exp, params)
    st = eng.run(n_windows=n_windows)
    rows = drain_ring(st, exp.window)
    cpu = CpuEngine(exp, params)
    cm = cpu.run(n_windows)
    tm = Engine.metrics_dict(st)
    assert tm["ev_overflow"] == 0 and tm["ob_overflow"] == 0
    assert len(rows) == len(cpu.work_rows) == n_windows
    for r, w in zip(rows, cpu.work_rows):
        assert w["type"] == "work"
        assert r["window"] == w["window"]
        for f in WORK:
            assert r[f] == w[f], (r["window"], f, r[f], w[f])
    for f in WORK:
        assert tm[f] == cm[f], (f, tm[f], cm[f])
    # The gauges actually observe the pathology signal: some window had
    # fewer active hosts than the plane width.
    assert tm["active_hosts"] > 0
    assert min(r["active_hosts"] for r in rows) <= exp.n_hosts
    return rows, tm


def test_work_gauge_parity_phold_loss():
    rows, tm = _assert_work_parity(
        phold_exp(loss=0.05), EngineParams(metrics_ring=128), 100)
    # elig_events >= active_hosts per window (>=1 event per active host).
    assert all(r["elig_events"] >= r["active_hosts"] for r in rows)


def test_work_gauge_parity_net_tcp():
    exp, params, _ = rung1_exp()
    import dataclasses

    params = dataclasses.replace(params, metrics_ring=64)
    rows, tm = _assert_work_parity(exp, params, 30)
    # The rung-1 flow is SPARSE: most windows touch a strict host subset —
    # the exact wasted-work signal the plane exists to surface.
    assert min(r["active_hosts"] for r in rows) < exp.n_hosts


def test_work_gauge_sharded_bitexact():
    from shadow1_tpu.shard.engine import ShardedEngine

    exp = phold_exp(n_hosts=64, seed=7, end_time=50 * MS)
    params = EngineParams(metrics_ring=64)
    st1 = Engine(exp, params).run(n_windows=50)
    sh = ShardedEngine(exp, params)
    assert sh.n_dev == 8, "conftest must provide 8 virtual devices"
    st8 = sh.run(n_windows=50)
    r1 = drain_ring(st1, exp.window)
    r8 = drain_ring(st8, exp.window)
    for a, b in zip(r1, r8):
        for f in WORK:
            assert a[f] == b[f], (a["window"], f)
    m1, m8 = Engine.metrics_dict(st1), Engine.metrics_dict(st8)
    for f in WORK:
        assert m1[f] == m8[f], f


def test_work_gauges_resume_bitexact(tmp_path):
    """The work-gauge stream is a pure boundary function: a checkpointed +
    resumed run carries the identical per-window rows."""
    from shadow1_tpu.ckpt import load_state, save_state

    eng = Engine(phold_exp(), EngineParams(metrics_ring=64))
    ref = eng.run(n_windows=60)
    st = eng.run(n_windows=25)
    path = str(tmp_path / "work.npz")
    save_state(st, path)
    final = eng.run(load_state(eng.init_state(), path), n_windows=35)
    ra, rb = drain_ring(ref, eng.window), drain_ring(final, eng.window)
    for a, b in zip(ra, rb):
        for f in WORK:
            assert a[f] == b[f]


def test_oracle_work_accounting_gated_on_ring():
    """Pay-for-use on the oracle: without a ring the per-boundary heap
    scans never run (the batched engines record per-window values only via
    the ring, so there is nothing to mirror)."""
    cpu = CpuEngine(phold_exp(), EngineParams())
    cm = cpu.run(20)
    assert not cpu.work_rows
    assert cm["active_hosts"] == 0 and cm["elig_events"] == 0


def test_fleet_lane_work_columns_match_solo():
    """Fleet ring rows carry the same per-lane work columns a solo run of
    that experiment records (the fleet contract extends to the new
    columns)."""
    from shadow1_tpu.fleet.engine import FleetEngine, slice_experiment

    exps = [phold_exp(seed=5, end_time=20 * MS),
            phold_exp(seed=6, end_time=20 * MS)]
    params = EngineParams(metrics_ring=32)
    fleet = FleetEngine(exps, params)
    stf = fleet.run(n_windows=20)
    for e, exp in enumerate(exps):
        lane = slice_experiment(stf, e)
        solo = Engine(exp, params).run(n_windows=20)
        ra = drain_ring(lane, exp.window)
        rb = drain_ring(solo, exp.window)
        for a, b in zip(ra, rb):
            for f in WORK:
                assert a[f] == b[f], (e, a["window"], f)


# ---------------------------------------------------------------------------
# heartbeat + report
# ---------------------------------------------------------------------------

def test_heartbeat_work_block_and_report(capsys):
    from shadow1_tpu.obs import run_with_heartbeat
    from shadow1_tpu.tools import heartbeat_report as hr

    eng = Engine(phold_exp(), EngineParams(metrics_ring=32))
    buf = io.StringIO()
    run_with_heartbeat(eng, n_windows=60, every_windows=20, stream=buf)
    recs = [json.loads(x) for x in buf.getvalue().splitlines()]
    hbs = [r for r in recs if r["type"] == "heartbeat"]
    rings = [r for r in recs if r["type"] == "ring"]
    # The chunk's work block: summed window samples + denominators, and the
    # samples leave ``delta`` like the fill gauges.
    for i, h in enumerate(hbs):
        assert "active_hosts" not in h["delta"]
        w = h["work"]
        assert w["n_hosts"] == 32
        chunk = [r for r in rings if i * 20 <= r["window"] < (i + 1) * 20]
        for f in WORK:
            assert w[f] == sum(r[f] for r in chunk), f
        assert 0 < w["active_frac"] <= 1
    summary = hr.summarize(recs)
    out = capsys.readouterr().out
    ws = summary["work"]
    assert ws["windows"] == 60 and ws["n_hosts"] == 32
    for key in ("active_frac", "pop_scan_eff", "outbox_frac"):
        d = ws[key]
        assert 0 <= d["min"] <= d["p50"] <= d["p95"] <= 1, (key, d)
    assert "== work efficiency (wasted-work accounting) ==" in out
    # The utilization samples stay OUT of the occupancy percentile table.
    assert "active_hosts" not in summary["ring"]
    ring_section = out.split("per-window occupancy (ring)")[1] \
                      .split("== work efficiency")[0]
    assert "active_hosts" not in ring_section


def test_report_on_oracle_work_rows(tmp_path):
    from shadow1_tpu.tools import heartbeat_report as hr

    params = EngineParams(metrics_ring=16)
    cpu = CpuEngine(phold_exp(), params)
    cpu.run(20)
    log = tmp_path / "cpu.log"
    log.write_text("\n".join(json.dumps(r) for r in cpu.work_rows) + "\n")
    summary = hr.summarize(hr.load_records(str(log)), out=io.StringIO())
    assert summary["work"]["windows"] == 20
    assert "active_hosts" in summary["work"]  # absolute stats (no n_hosts)


# ---------------------------------------------------------------------------
# opcensus
# ---------------------------------------------------------------------------

def _small_engine():
    return Engine(phold_exp(n_hosts=16, end_time=20 * MS),
                  EngineParams(ev_cap=16, outbox_cap=8))


def test_opcensus_deterministic():
    from shadow1_tpu.tools.opcensus import census

    a = census(_small_engine(), sources=True)
    b = census(_small_engine(), sources=True)
    assert a == b
    assert a["eqns"]["rounds"] > a["eqns"]["pop"] > 0
    # Source attribution reaches the library layers (the round-5 census's
    # grouping).
    assert any(s.startswith("events.") for s in a["sources"]["rounds"])


def test_opcensus_gate_logic():
    from shadow1_tpu.tools.opcensus import gate_config

    base = {"eqns": {"rounds": 400, "deliver": 250}}
    ok = {"eqns": {"rounds": 420, "deliver": 250}}       # +5% — inside
    assert gate_config(ok, base, 0.10) == []
    drift = {"eqns": {"rounds": 480, "deliver": 250}}    # +20% — drift
    fails = gate_config(drift, base, 0.10)
    assert len(fails) == 1 and "rounds" in fails[0]
    gone = {"eqns": {"deliver": 250}}
    assert any("vanished" in f for f in gate_config(gone, base, 0.10))
    new = {"eqns": {"rounds": 400, "deliver": 250, "extra": 9}}
    assert any("new phase" in f for f in gate_config(new, base, 0.10))


def test_opcensus_injected_ops_trip_gate():
    from shadow1_tpu.tools.opcensus import census, gate_config

    eng = _small_engine()
    clean = census(eng)
    injected = census(eng, inject=max(60, clean["eqns"]["rounds"] // 2))
    assert injected["eqns"]["rounds"] > clean["eqns"]["rounds"]
    fails = gate_config(injected, clean, 0.10)
    assert fails and "rounds" in fails[0]
    # Other phases untouched by the injection.
    assert injected["eqns"]["deliver"] == clean["eqns"]["deliver"]


# ---------------------------------------------------------------------------
# phaseprobe
# ---------------------------------------------------------------------------

def test_phaseprobe_coverage_smoke_phold():
    """The acceptance bound: the phase split accounts for ≥90% of the
    straight run's measured ms/round (attribution() also asserts the staged
    composition reproduced window_step's metrics bit-exactly)."""
    from shadow1_tpu.tools.phaseprobe import attribution, build_engine

    eng, label = build_engine("smoke", hosts=256)
    att = attribution(eng, n_windows=6, warmup=3, reps=2)
    assert label == "smoke_phold"
    assert set(att["phases"]) == {"prepare", "rounds", "deliver", "telem"}
    assert att["coverage"] >= 0.9, att
    assert att["phases"]["rounds"]["pct"] > 50  # rounds dominate phold
    assert "rounds.pop_est" in att["subphases"]


def test_window_phases_compose_to_window_step():
    """The staged composition IS window_step — bit-for-bit, ring included."""
    from shadow1_tpu.core.engine import window_frame, window_phases

    eng = Engine(phold_exp(n_hosts=16, end_time=20 * MS),
                 EngineParams(metrics_ring=8))
    st_a = eng.run(n_windows=10)
    st_b = eng.init_state()
    phases = window_phases(eng.ctx, eng._handlers, None, eng._pre_window,
                           eng._model.make_handlers, None)
    jitted = {n: jax.jit(f) for n, f in phases}
    for _ in range(10):
        fr = window_frame(st_b, eng.ctx)
        for n, _f in phases:
            fr = jitted[n](fr)
        st_b = fr.st
    la = jax.tree_util.tree_leaves(st_a)
    lb = jax.tree_util.tree_leaves(st_b)
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_device_trace_context(tmp_path):
    from shadow1_tpu.telemetry import PhaseProfiler, device_trace

    prof = PhaseProfiler()
    eng = Engine(phold_exp(n_hosts=16, end_time=20 * MS), EngineParams())
    st = eng.run(n_windows=2)
    with device_trace(str(tmp_path / "dt"), profiler=prof):
        jax.block_until_ready(eng.run(st, n_windows=2))
    assert "device-trace" in prof.span_names()


# ---------------------------------------------------------------------------
# benchgate rows
# ---------------------------------------------------------------------------

def test_benchgate_row_logic():
    from shadow1_tpu.tools.benchgate import gate_row

    host = "cpuX x8"
    row = {"ms_per_round": 10.5, "backend": "cpu", "host": host}
    base = {"ms_per_round": 10.0, "tolerance": 0.05, "host": host}
    assert gate_row("r", row, base, host, None)["gate"] == "ok"
    slow = {**row, "ms_per_round": 11.0}                  # +10% > 5%
    assert gate_row("r", slow, base, host, None)["gate"] == "failed"
    assert gate_row("r", slow, base, host, "why")["gate"] == "accepted"
    # Missing baseline for THIS backend: the row reports, never auto-skips
    # the whole gate (a TPU baseline can coexist with the CPU one).
    v = gate_row("r", row, None, host, None)
    assert v["gate"] == "no_baseline_for_backend"
    v = gate_row("r", row, {**base, "host": "other"}, host, None)
    assert v["gate"] == "skipped_host_mismatch"
    # Per-row tolerance honoured.
    wide = {**base, "tolerance": 0.20}
    assert gate_row("r", slow, wide, host, None)["gate"] == "ok"


def test_benchgate_rows_registry():
    from shadow1_tpu.tools import benchgate

    assert set(benchgate.ROWS) == {"phold_smoke", "sparse_rung1",
                                   "fleet_smoke"}
