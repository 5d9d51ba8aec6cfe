"""Fleet-grade recovery: transactional sweep retry, lane quarantine,
mid-sweep lane lifecycle (docs/SEMANTICS.md "Fleet recovery contract").

The contracts under test:

* **fleet transactional retry** — an under-capped sweep under
  ``on_overflow=retry`` rolls the whole [E, ...] pytree back, grows the
  fleet-uniform cap, replays, and every lane's committed digest stream
  bit-matches (a) the straight fleet run at the final caps and (b) the
  cpu oracle at those caps; resume rebuilds at the snapshot's grown caps;
* **fleet auto-caps** — the controller is fed fleet-global gauges; an
  over-provisioned sweep shrinks bit-exactly;
* **lane quarantine** — a deterministically failing lane is sliced out of
  the chunk-start state into a solo-resumable checkpoint + a structured
  fleet_quarantine record, survivors bit-match an E-1-from-scratch sweep,
  and the sweep completes E-1/E (all-lanes-quarantined preserves the
  error/exit taxonomy);
* **mid-sweep lane lifecycle** — drained lanes finalize early (immediate
  fleet_exp record, fleet shrinks); sub-batched downshift composes with
  per-batch checkpointing;
* **rejection lift** — the PR 6 ``kind="mode"`` rejections for
  --auto-caps / --on-overflow retry under --fleet are gone
  (tests/test_fleet.py asserts the CLI side).
"""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import shadow1_tpu.txn as txn
from shadow1_tpu import ckpt
from shadow1_tpu.ckpt import load_state, snapshot_caps
from shadow1_tpu.config.compiled import single_vertex_experiment
from shadow1_tpu.consts import EXIT_CAPACITY, MS, EngineParams
from shadow1_tpu.core.digest import SUBSYSTEMS
from shadow1_tpu.core.engine import Engine
from shadow1_tpu.cpu_engine import CpuEngine
from shadow1_tpu.fleet.engine import (
    FleetEngine,
    fleet_metrics_per_exp,
    select_lanes,
    slice_experiment,
)
from shadow1_tpu.fleet.run import final_records, run_fleet
from shadow1_tpu.lineage import Lineage
from shadow1_tpu.telemetry.ring import drain_ring

N = 20
UNDER = EngineParams(ev_cap=8, metrics_ring=N, state_digest=1)


def mk(seed, loss=0.0, stop=None, n_hosts=8):
    kw = {"stop_time": np.full(n_hosts, stop, np.int64)} if stop else {}
    return single_vertex_experiment(
        n_hosts=n_hosts, seed=seed, end_time=40 * MS, latency_ns=1 * MS,
        loss=loss, model="phold",
        model_cfg={"mean_delay_ns": float(2 * MS), "init_events": 6}, **kw)


def stream(st, window):
    return {r["window"]: tuple(r[f"dg_{s}"] for s in SUBSYSTEMS)
            for r in drain_ring(st, window) if r["type"] == "ring"}


def lane_streams(eng, st):
    return [stream(slice_experiment(st, e), eng.window)
            for e in range(eng.n_exp)]


# ---------------------------------------------------------------------------
# fleet transactional retry
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def retry_run():
    """One shared under-capped 3-lane retry run (compile amortized)."""
    exps = [mk(5), mk(6), mk(7, loss=0.1)]
    params = dataclasses.replace(UNDER, on_overflow="retry")
    eng = FleetEngine(exps, params)
    st, hb = run_fleet(eng, n_windows=N, every_windows=5, stream=False)
    return exps, st, hb


def test_fleet_retry_digest_parity_vs_big_cap_fleet(retry_run):
    """The acceptance gate: a forced-overflow retry sweep's per-lane
    digest streams bit-match the straight fleet run at the final grown
    caps (ci.sh runs the same proof via fleetprobe --retry)."""
    exps, st, hb = retry_run
    guard = hb.guard
    assert guard.chunk_retries >= 1, "under-capped sweep never retried"
    # Committed stream is overflow-free in EVERY lane (the txn contract).
    assert int(np.asarray(st.metrics.ev_overflow).sum()) == 0
    assert int(np.asarray(st.metrics.ob_overflow).sum()) == 0
    big = dataclasses.replace(UNDER, ev_cap=guard.final_caps["ev_cap"],
                              outbox_cap=guard.final_caps["outbox_cap"])
    eng_big = FleetEngine(exps, big)
    st_big = eng_big.run(n_windows=N)
    assert lane_streams(hb.engine, st) == lane_streams(eng_big, st_big)
    # Retry records carry sweep-global lane attribution.
    assert hb.recovery["retry_records"]
    assert all(r["type"] == "fleet_retry"
               for r in hb.recovery["retry_records"])


def test_fleet_retry_matches_cpu_oracle_at_final_caps(retry_run):
    """The cpu side of the proof: each lane's committed stream equals the
    eager oracle run at the final caps (the PR 5 solo proof, fleet-wide)."""
    exps, st, hb = retry_run
    big = dataclasses.replace(UNDER, ev_cap=hb.guard.final_caps["ev_cap"],
                              outbox_cap=hb.guard.final_caps["outbox_cap"])
    streams = lane_streams(hb.engine, st)
    for e, exp in enumerate(exps):
        cpu = CpuEngine(exp, big)
        cpu.run(n_windows=N)
        oracle = {r["window"]: tuple(r[f"dg_{s}"] for s in SUBSYSTEMS)
                  for r in cpu.digest_rows}
        assert oracle == streams[e], f"exp {e} vs cpu oracle"


def test_fleet_retry_ckpt_resumes_at_grown_caps(retry_run, tmp_path):
    """A retry sweep's snapshot carries the GROWN caps; the resume path
    reads them (ckpt.snapshot_caps, leading-axis aware), rebuilds the
    fleet engine there and continues to the identical final streams."""
    exps, st_ref, hb_ref = retry_run
    ck = str(tmp_path / "fleet.npz")
    params = dataclasses.replace(UNDER, on_overflow="retry")
    eng = FleetEngine(exps, params)
    st_half, hb = run_fleet(eng, n_windows=10, every_windows=5,
                            stream=False, ckpt_path=ck, ckpt_every_s=0)
    grown = hb.guard.final_caps["ev_cap"]
    assert grown > UNDER.ev_cap
    # The cli recipe: probe the snapshot's caps, rebuild, load, continue.
    p2 = dataclasses.replace(params, ev_cap=grown)
    eng2 = FleetEngine(exps, p2)
    snap = snapshot_caps(eng2.init_state(), ck)
    assert snap == (grown, UNDER.outbox_cap)
    st = load_state(eng2.init_state(), ck)
    st, hb2 = run_fleet(eng2, st, n_windows=10, every_windows=5,
                        stream=False)
    ref = lane_streams(hb_ref.engine, st_ref)
    got = lane_streams(hb2.engine, st)
    for e in range(len(exps)):
        tail = {w: v for w, v in ref[e].items() if w in got[e]}
        assert got[e] == tail, f"exp {e} resumed tail diverged"


def test_fleet_auto_caps_shrinks_bit_exactly():
    """Fleet --auto-caps: the controller reads fleet-global gauges and an
    over-provisioned sweep shrinks between chunks — digest streams stay
    bit-identical to the straight over-provisioned run (the tune/resize
    exactness argument, fleet-shaped)."""
    exps = [mk(5), mk(6)]
    params = dataclasses.replace(UNDER, ev_cap=64)
    eng = FleetEngine(exps, params)
    st, hb = run_fleet(eng, n_windows=N, every_windows=4, stream=False,
                       auto_caps=True)
    assert hb.engine.params.ev_cap < 64, "never shrank"
    eng_ref = FleetEngine(exps, params)
    st_ref = eng_ref.run(n_windows=N)
    assert lane_streams(hb.engine, st) == lane_streams(eng_ref, st_ref)


# ---------------------------------------------------------------------------
# lane quarantine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def quarantine_run(tmp_path_factory):
    """Shared quarantine run: lane 1 (lossless) overflows ev_cap=8 under
    halt; lanes 0/2 (50% loss) survive."""
    work = tmp_path_factory.mktemp("quar")
    exps = [mk(5, loss=0.5), mk(6), mk(7, loss=0.5)]
    params = dataclasses.replace(UNDER, on_overflow="halt",
                                 on_lane_fail="quarantine")
    eng = FleetEngine(exps, params)
    st, hb = run_fleet(eng, n_windows=N, every_windows=5, stream=False,
                       quarantine_base=str(work / "lane"))
    return exps, params, st, hb


def test_quarantine_slices_failing_lane(quarantine_run):
    exps, params, st, hb = quarantine_run
    q = hb.recovery["quarantined"]
    assert len(q) == 1
    rec = q[0]
    assert rec["exp"] == 1 and rec["seed"] == 6
    assert rec["reason"] == "capacity" and rec["knob"] == "ev_cap"
    assert rec["survivors"] == 2 and os.path.exists(rec["ckpt"])
    assert hb.engine.n_exp == 2
    assert [l["exp"] for l in hb.labels] == [0, 2]
    recs, summary = final_records(hb.engine, st, hb.labels, N, 1.0,
                                  recovery=hb.recovery)
    assert [r["exp"] for r in recs] == [0, 2]
    assert summary["quarantined"] == [1]
    assert summary["experiments"] == 2
    assert summary["experiments_initial"] == 3


def test_quarantine_survivors_match_e1_sweep(quarantine_run):
    """Survivor streams are provably unchanged: they bit-match an
    (E-1)-from-scratch sweep of just the surviving experiments."""
    exps, params, st, hb = quarantine_run
    scratch = FleetEngine([exps[0], exps[2]],
                          dataclasses.replace(params,
                                              on_lane_fail="halt"))
    st2 = scratch.run(n_windows=N)
    assert lane_streams(hb.engine, st) == lane_streams(scratch, st2)


def test_quarantined_ckpt_resumes_solo_bit_identically(quarantine_run):
    """The quarantined lane's sliced checkpoint (chunk-start state) loads
    into a SOLO engine and continues exactly the solo straight run."""
    exps, params, st, hb = quarantine_run
    rec = hb.recovery["quarantined"][0]
    solo_p = dataclasses.replace(params, on_overflow="drop",
                                 on_lane_fail="halt")
    straight = Engine(exps[1], solo_p).run(n_windows=N)
    solo = Engine(exps[1], solo_p)
    lane = load_state(solo.init_state(), rec["ckpt"])
    w0 = int(np.asarray(lane.win_start)) // solo.window
    assert w0 == rec["window"]
    resumed = solo.run(lane, n_windows=N - w0)
    a, b = stream(straight, solo.window), stream(resumed, solo.window)
    assert {w: a[w] for w in b} == b
    assert Engine.metrics_dict(straight) == Engine.metrics_dict(resumed)


def test_quarantine_selfcheck_violation(monkeypatch, tmp_path):
    """A per-lane selfcheck violation quarantines the violating lane with
    reason="selfcheck" instead of killing the sweep."""
    exps = [mk(5, loss=0.5), mk(6, loss=0.5)]
    params = dataclasses.replace(UNDER, ev_cap=32,
                                 on_lane_fail="quarantine")
    real = txn.check_boundary_identity

    def fake(metrics, where=""):
        if "fleet experiment 1" in where:
            raise txn.SelfCheckError({"pkts_sent": 1}, 1, where=where)
        return real(metrics, where)

    monkeypatch.setattr(txn, "check_boundary_identity", fake)
    eng = FleetEngine(exps, params)
    st, hb = run_fleet(eng, n_windows=10, every_windows=5, stream=False,
                       selfcheck=True,
                       quarantine_base=str(tmp_path / "lane"))
    q = hb.recovery["quarantined"]
    assert len(q) == 1 and q[0]["exp"] == 1
    assert q[0]["reason"] == "selfcheck"
    assert hb.engine.n_exp == 1 and hb.labels[0]["exp"] == 0


def test_quarantine_after_committed_grow_migrates_rollback(monkeypatch,
                                                           tmp_path):
    """retry + quarantine + selfcheck compose: when a chunk COMMITS a cap
    grow and the boundary selfcheck then quarantines a lane, the repack
    must migrate the chunk-start rollback state onto the grown caps —
    state shapes and engine caps never diverge, and the committed grow's
    retry records are NOT marked discarded."""
    exps = [mk(5), mk(6), mk(7)]   # all under-capped: the chunk grows
    params = dataclasses.replace(UNDER, on_overflow="retry",
                                 on_lane_fail="quarantine")
    real = txn.check_boundary_identity
    tripped = []

    def fake(metrics, where=""):
        if "fleet experiment 2" in where and not tripped:
            tripped.append(where)
            raise txn.SelfCheckError({"pkts_sent": 1}, 1, where=where)
        return real(metrics, where)

    monkeypatch.setattr(txn, "check_boundary_identity", fake)
    eng = FleetEngine(exps, params)
    st, hb = run_fleet(eng, n_windows=N, every_windows=5, stream=False,
                       selfcheck=True,
                       quarantine_base=str(tmp_path / "lane"))
    assert tripped, "selfcheck hook never fired"
    assert [r["exp"] for r in hb.recovery["quarantined"]] == [2]
    assert hb.engine.n_exp == 2
    # The grow committed at that boundary persisted through the repack:
    # state planes and engine caps agree, and the sweep stayed clean.
    grown = hb.engine.params.ev_cap
    assert grown > UNDER.ev_cap
    assert int(np.asarray(st.evbuf.kind).shape[-2]) == grown
    assert int(np.asarray(st.metrics.ev_overflow).sum()) == 0
    committed = [r for r in hb.recovery["retry_records"]
                 if not r.get("discarded")]
    assert committed, "committed grow was mislabeled discarded"


def test_quarantine_all_lanes_preserves_error(tmp_path):
    """When every lane quarantines, the last failure re-raises — the CLI
    then maps it to the solo exit taxonomy (EXIT_CAPACITY)."""
    exps = [mk(6)]  # the lossless overflowing lane, alone
    params = dataclasses.replace(UNDER, on_overflow="halt",
                                 on_lane_fail="quarantine")
    eng = FleetEngine(exps, params)
    with pytest.raises(txn.CapacityExceededError):
        run_fleet(eng, n_windows=N, every_windows=5, stream=False,
                  quarantine_base=str(tmp_path / "lane"))


def test_retry_ladder_top_quarantines(monkeypatch, tmp_path):
    """Retry exhaustion (cap cannot grow past the ladder top) attributed
    to a lane quarantines it instead of killing the sweep — the
    retry-recovery and quarantine planes compose."""
    class TinyGuard(txn.OverflowGuard):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            self.max_cap = 8  # == ev_cap: the first grow already exceeds

    monkeypatch.setattr(txn, "OverflowGuard", TinyGuard)
    exps = [mk(5, loss=0.5), mk(6), mk(7, loss=0.5)]
    params = dataclasses.replace(UNDER, on_overflow="retry",
                                 on_lane_fail="quarantine")
    eng = FleetEngine(exps, params)
    st, hb = run_fleet(eng, n_windows=10, every_windows=5, stream=False,
                       quarantine_base=str(tmp_path / "lane"))
    q = hb.recovery["quarantined"]
    assert [r["exp"] for r in q] == [1]
    assert q[0]["reason"] == "capacity"
    assert hb.engine.n_exp == 2


def test_resume_mid_quarantined_sweep(tmp_path):
    """A fleet snapshot taken AFTER a quarantine carries the surviving
    lane ids in its lineage manifest; rebuilding exactly that sub-fleet
    and resuming continues bit-identically (the cli._fleet_main recipe)."""
    exps = [mk(5, loss=0.5), mk(6), mk(7, loss=0.5)]
    params = dataclasses.replace(UNDER, on_overflow="halt",
                                 on_lane_fail="quarantine")
    ck = str(tmp_path / "fleet.npz")
    labels = [{"exp": i, "seed": int(e.seed)} for i, e in enumerate(exps)]
    eng = FleetEngine(exps, params)
    st_half, hb = run_fleet(eng, n_windows=10, every_windows=5,
                            stream=False, ckpt_path=ck, ckpt_every_s=0,
                            labels=labels,
                            quarantine_base=str(tmp_path / "lane"))
    assert [r["exp"] for r in hb.recovery["quarantined"]] == [1]
    res = Lineage(ck).resolve()
    assert res is not None and res.meta["lanes"] == [0, 2]
    assert res.meta["quarantined"] == [1]
    # Rebuild exactly the surviving sub-fleet, load, continue.
    keep = [0, 2]
    eng2 = FleetEngine([exps[i] for i in keep], params)
    eng2.exp_ids = keep
    st = load_state(eng2.init_state(), res.path)
    st, hb2 = run_fleet(eng2, st, n_windows=10, every_windows=5,
                        stream=False,
                        labels=[labels[i] for i in keep],
                        recovery_seed={"quarantined":
                                       res.meta["quarantined"],
                                       "finished": []})
    # Straight quarantine run of the full horizon for comparison.
    eng3 = FleetEngine(exps, params)
    st3, hb3 = run_fleet(eng3, n_windows=N, every_windows=5, stream=False,
                         quarantine_base=str(tmp_path / "lane3"))
    ref = lane_streams(hb3.engine, st3)
    got = lane_streams(hb2.engine, st)
    for i in range(2):
        tail = {w: v for w, v in ref[i].items() if w in got[i]}
        assert got[i] == tail, f"survivor {i} resumed tail diverged"
    _, summary = final_records(hb2.engine, st, hb2.labels, N, 1.0,
                               recovery=hb2.recovery)
    assert summary["quarantined"] == [1]
    assert summary["experiments_initial"] == 3


# ---------------------------------------------------------------------------
# mid-sweep lane lifecycle
# ---------------------------------------------------------------------------

def test_lane_finalize_early():
    """A lane whose hosts all stop (legacy stop_time churn) drains and is
    finalized mid-sweep: immediate fleet_exp record with the window count
    it actually ran, fleet shrinks, survivor streams unchanged, and the
    finalized lane's parity metrics equal the straight run's (a dead lane
    accrues nothing but window ticks)."""
    exps = [mk(5, loss=0.5), mk(6, loss=0.5, stop=10 * MS)]
    params = dataclasses.replace(UNDER, ev_cap=32, lane_finalize=1)
    eng = FleetEngine(exps, params)
    st, hb = run_fleet(eng, n_windows=N, every_windows=5, stream=False)
    fin = hb.recovery["finished"]
    assert len(fin) == 1
    rec = fin[0]
    assert rec["exp"] == 1 and rec["finished_early"] is True
    assert rec["windows"] < N and rec["windows_configured"] == N
    assert hb.engine.n_exp == 1
    straight = FleetEngine(exps, dataclasses.replace(params,
                                                     lane_finalize=0))
    st2 = straight.run(n_windows=N)
    assert stream(slice_experiment(st, 0), eng.window) == \
        stream(slice_experiment(st2, 0), straight.window)
    m2 = fleet_metrics_per_exp(st2)[1]
    for k in ("events", "pkts_sent", "pkts_delivered", "down_events",
              "down_pkts"):
        assert rec["metrics"][k] == m2[k], k
    _, summary = final_records(hb.engine, st, hb.labels, N, 1.0,
                               recovery=hb.recovery)
    assert summary["finished_early"] == [1]


def test_lane_finalize_follows_the_boundary_s_snapshot(tmp_path):
    """The one boundary order (docs/SEMANTICS.md "Fleet recovery contract"):
    snapshot in on_chunk, finalize in retune after it. The snapshot taken at
    the boundary a lane finalizes at still holds the lane, under the ``lanes``
    of the state it holds; the next one is the first without it; and a sweep
    resumed from the former (a drain or a crash at that boundary) owes the
    boundary's retune: the lane leaves before the first chunk, with the
    record the uninterrupted sweep gave it, field for field."""
    exps = [mk(5, loss=0.5), mk(6, loss=0.5, stop=10 * MS)]
    params = dataclasses.replace(UNDER, ev_cap=32, lane_finalize=1)
    ck = str(tmp_path / "fleet.npz")
    st, hb = run_fleet(FleetEngine(exps, params), n_windows=N, every_windows=5,
                       stream=False, ckpt_path=ck, ckpt_every_s=0,
                       ckpt_keep=8)
    (rec,) = hb.recovery["finished"]
    at = rec["windows"]
    gens = {g["done_windows"]: g for g in Lineage(ck, keep=8).generations()}
    assert sorted(gens) == [5, 10, 15, 20] and at in (5, 10, 15)
    for done, g in gens.items():
        if done <= at:
            assert g["lanes"] == [0, 1] and "finished" not in g, done
        else:
            assert g["lanes"] == [0] and g["finished"] == [1], done
    # Resume the two-lane snapshot of that boundary.
    eng2 = FleetEngine(exps, params)
    st2 = load_state(eng2.init_state(), gens[at]["file"])
    emitted, ran = [], []
    real = ckpt.run_chunked

    def counting(engine, *a, **kw):
        ran.append((engine.n_exp, len(emitted)))
        return real(engine, *a, **kw)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ckpt, "run_chunked", counting)
        st2, hb2 = run_fleet(eng2, st2, n_windows=N - at, every_windows=5,
                             stream=False, emit_record=emitted.append)
    # The record went out before any chunk ran, and the loop began E=1.
    assert ran == [(1, 1)] and emitted == hb2.recovery["finished"]
    assert emitted == [rec] and rec["windows_configured"] == N
    assert [h["fleet"]["exps"] for h in hb2.records] == \
        [[0]] * ((N - at) // 5)
    assert stream(slice_experiment(st2, 0), eng2.window) == \
        stream(slice_experiment(st, 0), eng2.window)
    # A snapshot one boundary EARLIER holds no finished lane yet: nothing
    # leaves at its start, and the lane leaves at ``at`` as it did.
    if at > 5:
        eng3 = FleetEngine(exps, params)
        st3 = load_state(eng3.init_state(), gens[at - 5]["file"])
        _st3, hb3 = run_fleet(eng3, st3, n_windows=N - at + 5,
                              every_windows=5, stream=False)
        assert hb3.recovery["finished"] == [rec]
        assert hb3.records[0]["fleet"]["exps"] == [0, 1]


def test_fleet_plan_subset():
    """The resume-side twin of select_lanes: a plan subset keeps global
    ids/seeds/max_rounds aligned and ignores stale ids."""
    from shadow1_tpu.fleet.expand import expand_sweep

    doc = {
        "general": {"seed": 7, "stop_time": "60 ms"},
        "engine": {"scheduler": "tpu"},
        "network": {"single_vertex": {"latency": "10 ms"}},
        "hosts": [{"name": "h", "count": 8}],
        "app": {"model": "phold",
                "params": {"mean_delay_ns": 2.0e7, "init_events": 2}},
        "sweep": {"seeds": [7, 8, 9],
                  "vary": [{}, {"engine": {"max_rounds": 128}}, {}]},
    }
    plan = expand_sweep(doc)
    sub = plan.subset([2, 0, 99])
    assert [l["exp"] for l in sub.labels] == [2, 0]
    assert [e.seed for e in sub.exps] == [9, 7]
    assert sub.max_rounds == [256, 256]
    assert plan.subset([1]).max_rounds == [128]


def test_select_lanes_is_lane_exact():
    """The repack primitive: running a selected sub-fleet state forward
    equals the same lanes of the full fleet run forward."""
    exps = [mk(5, loss=0.5), mk(6), mk(7, loss=0.5)]
    params = dataclasses.replace(UNDER, ev_cap=32)
    eng = FleetEngine(exps, params)
    st_half = eng.run(n_windows=10)
    full = eng.run(st_half, n_windows=N - 10)
    sub_eng = FleetEngine([exps[0], exps[2]], params)
    sub = sub_eng.run(select_lanes(st_half, [0, 2]), n_windows=N - 10)
    for i, e in enumerate([0, 2]):
        np.testing.assert_array_equal(
            np.asarray(slice_experiment(full, e).evbuf.kind),
            np.asarray(slice_experiment(sub, i).evbuf.kind))
    assert stream(slice_experiment(full, 0), eng.window) == \
        stream(slice_experiment(sub, 0), sub_eng.window)


# ---------------------------------------------------------------------------
# records / report tooling
# ---------------------------------------------------------------------------

def test_heartbeat_report_fleet_recovery_section(tmp_path, capsys):
    from shadow1_tpu.tools import heartbeat_report

    recs = [
        {"type": "fleet_retry", "retry": 1, "windows": [0, 5],
         # Two counters, one chunk, same lane — counts as ONE taint.
         "lanes": {"ev_overflow": [1, 2], "ob_overflow": [1]},
         "ev_cap": [8, 12]},
        {"type": "fleet_retry", "retry": 2, "windows": [0, 5],
         "lanes": {"ev_overflow": [1]}, "ev_cap": [12, 16]},
        # Rolled back by a quarantine: audit-only, out of every count.
        {"type": "fleet_retry", "retry": 3, "windows": [5, 10],
         "lanes": {"ev_overflow": [3]}, "ev_cap": [16, 24],
         "discarded": True},
        # quarantine record duplicated (stdout + stderr capture) — the
        # report must dedupe by lane.
        {"type": "fleet_quarantine", "exp": 3, "seed": 9,
         "reason": "capacity", "knob": "ev_cap", "window": 5,
         "ckpt": "x.q3.npz", "survivors": 2},
        {"type": "fleet_quarantine", "exp": 3, "seed": 9,
         "reason": "capacity", "knob": "ev_cap", "window": 5,
         "ckpt": "x.q3.npz", "survivors": 2},
        {"type": "fleet_exp", "exp": 0, "seed": 7, "windows": 12,
         "windows_configured": 20, "finished_early": True,
         "metrics": {"events": 10}, "drops": {"total": 0}},
        {"type": "fleet_summary", "experiments": 2,
         "experiments_initial": 4, "quarantined": [3],
         "metrics": {}},
    ]
    log = tmp_path / "rec.log"
    with open(log, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    out = heartbeat_report.summarize(heartbeat_report.load_records(
        str(log)))
    printed = capsys.readouterr().out
    fr = out["fleet_recovery"]
    assert fr["chunk_retries"] == 2
    assert fr["quarantined"] == 1          # deduped
    assert fr["finished_early"] == 1
    assert fr["retries_by_exp"] == {1: 2, 2: 1}
    assert "fleet recovery" in printed
    assert "finished early" in printed
    assert "solo-resumable ckpt" in printed


# ---------------------------------------------------------------------------
# CLI (subprocess; heavy cases slow with fast in-process siblings above)
# ---------------------------------------------------------------------------

def _repo_env(extra=None):
    """Subprocess env that keeps shadow1_tpu importable when the child
    runs with a tmp cwd (quarantine ckpts and .lane files land there,
    never in the repo)."""
    import shadow1_tpu as pkg

    root = os.path.dirname(os.path.dirname(os.path.abspath(pkg.__file__)))
    env = {**os.environ,
           "PYTHONPATH": root + os.pathsep
           + os.environ.get("PYTHONPATH", "")}
    if extra:
        env.update(extra)
    return env


def _quar_sweep_cfg(tmp_path):
    cfg = tmp_path / "sweep.yaml"
    cfg.write_text(
        "general: {seed: 5, stop_time: 40 ms}\n"
        "engine: {scheduler: tpu, ev_cap: 8}\n"
        "network: {single_vertex: {latency: 1 ms}}\n"
        "hosts: [{name: h, count: 8}]\n"
        "app: {model: phold, params: {mean_delay_ns: 2000000.0, "
        "init_events: 6}}\n"
        "sweep:\n"
        "  seeds: [5, 6, 7]\n"
        "  vary:\n"
        "    - {network: {single_vertex: {loss: 0.5}}}\n"
        "    - {}\n"
        "    - {network: {single_vertex: {loss: 0.5}}}\n"
    )
    return cfg


def test_cli_quarantine_records_and_exit(tmp_path):
    """--on-lane-fail quarantine: the sweep completes E-1/E with exit 0,
    a fleet_quarantine stdout record, the ledger in the summary — and the
    all-lanes-fail sibling keeps the capacity exit code."""
    cfg = _quar_sweep_cfg(tmp_path)
    out = subprocess.run(
        [sys.executable, "-m", "shadow1_tpu", str(cfg), "--fleet",
         "--on-overflow", "halt", "--on-lane-fail", "quarantine",
         "--windows", "10", "--ckpt", str(tmp_path / "f.npz"),
         "--supervised-child"],
        capture_output=True, text=True, cwd=tmp_path,
        env=_repo_env())
    assert out.returncode == 0, out.stderr[-800:]
    recs = [json.loads(l) for l in out.stdout.strip().splitlines()]
    q = [r for r in recs if r.get("type") == "fleet_quarantine"]
    assert len(q) == 1 and q[0]["exp"] == 1
    assert os.path.exists(q[0]["ckpt"])
    summary = [r for r in recs if r.get("type") == "fleet_summary"][-1]
    assert summary["quarantined"] == [1]
    assert summary["experiments"] == 2
    # All lanes fail -> the structured capacity exit survives quarantine.
    solo = tmp_path / "solo_sweep.yaml"
    solo.write_text(cfg.read_text().replace(
        "  seeds: [5, 6, 7]\n  vary:\n"
        "    - {network: {single_vertex: {loss: 0.5}}}\n"
        "    - {}\n"
        "    - {network: {single_vertex: {loss: 0.5}}}\n",
        "  seeds: [6]\n"))
    out = subprocess.run(
        [sys.executable, "-m", "shadow1_tpu", str(solo), "--fleet",
         "--on-overflow", "halt", "--on-lane-fail", "quarantine",
         "--windows", "10"],
        capture_output=True, text=True, cwd=tmp_path,
        env=_repo_env())
    assert out.returncode == EXIT_CAPACITY, out.stderr[-500:]
    err = json.loads(out.stdout.strip().splitlines()[-1])
    assert err["error"] == "capacity_exceeded"


def test_cli_lane_flags_require_fleet(tmp_path):
    cfg = _quar_sweep_cfg(tmp_path)
    out = subprocess.run(
        [sys.executable, "-m", "shadow1_tpu", str(cfg),
         "--on-lane-fail", "quarantine"],
        capture_output=True, text=True)
    assert out.returncode == 2
    assert "--fleet" in out.stderr


@pytest.mark.slow
def test_cli_supervised_quarantine_crash_resume(tmp_path):
    """Supervised fleet: quarantine happens, the child crashes at a later
    committed boundary, the respawn resumes the E-1 sub-fleet from the
    lanes manifest and the final per-lane metrics equal the straight
    quarantine run's."""
    cfg = _quar_sweep_cfg(tmp_path)
    straight = subprocess.run(
        [sys.executable, "-m", "shadow1_tpu", str(cfg), "--fleet",
         "--on-overflow", "halt", "--on-lane-fail", "quarantine"],
        capture_output=True, text=True, cwd=tmp_path,
        env=_repo_env())
    assert straight.returncode == 0, straight.stderr[-800:]
    env = {**os.environ, "SHADOW1_OBS_CRASH_AT_NS": "20000000",
           "SHADOW1_SUPERVISE_BACKOFF_S": "0"}
    sup = subprocess.run(
        [sys.executable, "-m", "shadow1_tpu", str(cfg), "--fleet",
         "--on-overflow", "halt", "--on-lane-fail", "quarantine",
         "--ckpt", str(tmp_path / "q.npz"), "--ckpt-every-s", "0",
         "--heartbeat", "5"],
        capture_output=True, text=True, env=_repo_env(env),
        cwd=tmp_path)
    assert sup.returncode == 0, sup.stderr[-800:]
    assert "respawning" in sup.stderr

    def per_exp(out):
        return {r["exp"]: r["metrics"] for r in
                map(json.loads, out.strip().splitlines())
                if r.get("type") == "fleet_exp"}

    a, b = per_exp(straight.stdout), per_exp(sup.stdout)
    assert set(a) == set(b) == {0, 2}
    assert a == b
    summary = [json.loads(l) for l in sup.stdout.strip().splitlines()
               if '"fleet_summary"' in l][-1]
    assert summary["quarantined"] == [1]


@pytest.mark.slow
def test_cli_subbatch_downshift_with_ckpt_crash_resume(tmp_path):
    """--on-oom downshift sub-batching now composes with --ckpt: a
    mid-batch crash respawns, the batch cursor in the lineage manifest
    resumes the right batch, and per-lane results equal the straight
    full-fleet run (the lifted refusal, end to end)."""
    from shadow1_tpu import mem
    from shadow1_tpu.fleet.expand import load_sweep

    cfg = tmp_path / "sweep.yaml"
    cfg.write_text(
        "general: {seed: 7, stop_time: 60 ms}\n"
        "engine: {scheduler: tpu, ev_cap: 32, outbox_cap: 16}\n"
        "network: {single_vertex: {latency: 10 ms}}\n"
        "hosts: [{name: h, count: 8}]\n"
        "app: {model: phold, params: {mean_delay_ns: 2.0e7, "
        "init_events: 2}}\n"
        "sweep: {seeds: [7, 8, 9, 10]}\n"
    )
    plan = load_sweep(str(cfg))
    e2 = mem.estimate(plan.exps[0], plan.params, n_exp=2)
    e4 = mem.estimate(plan.exps[0], plan.params, n_exp=4)
    budget = (e2.peak_bytes + e4.peak_bytes) // 2
    env = {**os.environ, mem.MEM_BYTES_ENV: str(budget),
           "SHADOW1_OBS_CRASH_AT_NS": "40000000",
           "SHADOW1_SUPERVISE_BACKOFF_S": "0"}
    sup = subprocess.run(
        [sys.executable, "-m", "shadow1_tpu", str(cfg), "--fleet",
         "--on-oom", "downshift", "--ckpt", str(tmp_path / "sb.npz"),
         "--ckpt-every-s", "0", "--heartbeat", "2"],
        capture_output=True, text=True, env=_repo_env(env),
        cwd=tmp_path, timeout=600)
    assert sup.returncode == 0, sup.stderr[-800:]
    assert "respawning" in sup.stderr
    straight = subprocess.run(
        [sys.executable, "-m", "shadow1_tpu", str(cfg), "--fleet"],
        capture_output=True, text=True, cwd=tmp_path,
        env=_repo_env(), timeout=600)
    assert straight.returncode == 0

    def ev(out):
        return {r["exp"]: r["metrics"]["events"] for r in
                map(json.loads, out.strip().splitlines())
                if r.get("type") == "fleet_exp"}

    assert ev(sup.stdout) == ev(straight.stdout)
    merged = [json.loads(l) for l in sup.stdout.strip().splitlines()
              if '"fleet_summary"' in l][-1]
    assert merged["experiments"] == 4
    assert merged["sub_batches"] >= 2
