"""Capacity autotuning: ladder, migration, controller, captune.

The contracts under test (ISSUE 2 acceptance):

* migration is BIT-EXACT — a run whose caps grow and shrink mid-flight
  produces the same metrics/model results as a fixed-cap run (pop order is
  decided by the (time, tb) keys, not slot index), single-device and on the
  8-device mesh, for phold and the TCP net model;
* checkpoints cross caps — a snapshot saved at cap A restores into an
  engine at cap B and continues exactly;
* the controller grows BEFORE overflow — on a workload whose occupancy
  ramps past the static starting cap, ``--auto-caps`` keeps the overflow
  counters at 0;
* ``captune.py`` turns run records into the documented recommendations —
  including reproducing the round-5 "rung5 ev_cap ~6x over-provisioned"
  audit finding from its run record.
"""

import dataclasses
import json
import os

import jax
import numpy as np
import pytest

from shadow1_tpu.config.compiled import single_vertex_experiment
from shadow1_tpu.consts import MS, SEC, EngineParams
from shadow1_tpu.core.engine import Engine
from shadow1_tpu.telemetry.registry import LANE_PROGRAM_FIELDS
from shadow1_tpu.tune import (
    CapController,
    CapPolicy,
    cap_ladder,
    next_step,
    quantize_cap,
    recommend_cap,
    resize_state,
)
from shadow1_tpu.tune.ladder import classify
from tests.parity import trip_metrics

REPO = os.path.join(os.path.dirname(__file__), "..")


def phold_exp(n_hosts=32, seed=17, end_time=100 * MS, init_events=2):
    return single_vertex_experiment(
        n_hosts=n_hosts,
        seed=seed,
        end_time=end_time,
        latency_ns=1 * MS,
        model="phold",
        model_cfg={"mean_delay_ns": float(2 * MS), "init_events": init_events},
    )


def tgen_exp(n_hosts=8, seed=21, streams=2, mean_bytes=120_000, end=3 * SEC):
    return single_vertex_experiment(
        n_hosts=n_hosts,
        seed=seed,
        end_time=end,
        latency_ns=10 * MS,
        bw_bits=10**7,
        model="net",
        model_cfg={
            "app": "tgen",
            "active": np.ones(n_hosts, np.int64),
            "streams": np.full(n_hosts, streams, np.int64),
            "mean_bytes": np.full(n_hosts, mean_bytes, np.float64),
            "mean_think_ns": np.full(n_hosts, 50 * MS, np.float64),
            "start_time": np.full(n_hosts, 1 * MS, np.int64),
        },
    )


def migrate(engine, st, ev_cap=None, outbox_cap=None):
    """Host-side cap migration + re-place on the target engine's devices."""
    host = jax.tree.map(np.asarray, st)
    return engine.place_state(
        resize_state(host, ev_cap=ev_cap, outbox_cap=outbox_cap)
    )


# ---------------------------------------------------------------------------
# ladder
# ---------------------------------------------------------------------------

def test_ladder_quantization():
    lad = cap_ladder(600)
    assert lad == [8, 12, 16, 24, 32, 48, 64, 96, 128, 192, 256, 384, 512]
    # Successive steps are bounded geometric (×1.33 / ×1.5): recompiles are
    # O(log range) no matter how occupancy wanders.
    assert all(b / a <= 1.5 for a, b in zip(lad, lad[1:]))
    for need in (1, 8, 9, 64, 65, 96, 97, 500):
        q = quantize_cap(need)
        assert q >= max(need, 8) and q in cap_ladder(2 * q)
    assert quantize_cap(96) == 96  # on-ladder values are fixed points
    assert next_step(64) == 96 and next_step(65) == 96 and next_step(96) == 128
    assert recommend_cap(43) == 96  # the rung5 number (×1.5 → ladder)


def test_classify_matches_round5_audit_conclusions():
    # rung5: 6× over → shrink to 96; rung2/dense: hand-validated tight caps
    # stay "ok"; an under-headroom cap flags grow.
    r5 = classify(43, 256)
    assert r5["verdict"] == "shrink" and r5["recommended"] == 96
    assert r5["over_factor"] == pytest.approx(5.95, abs=0.01)
    assert classify(425, 512)["verdict"] == "ok"
    assert classify(66, 96)["verdict"] == "ok"
    assert classify(425, 480)["verdict"] == "grow"


# ---------------------------------------------------------------------------
# gauges
# ---------------------------------------------------------------------------

def test_gauges_match_cpu_oracle_phold():
    """Window-end fill sampling is engine-independent: the oracle's boundary
    samples equal the batch engine's gauges bit-exactly (overflow-free)."""
    from shadow1_tpu.cpu_engine import CpuEngine

    exp = phold_exp()
    params = EngineParams()
    tm = Engine.metrics_dict(Engine(exp, params).run(n_windows=100))
    cm = CpuEngine(exp, params).run(n_windows=100)
    assert tm["ev_overflow"] == 0 and cm["ev_overflow"] == 0
    assert tm["ev_max_fill"] > 0
    assert tm["ev_max_fill"] == cm["ev_max_fill"]
    assert tm["ob_max_fill"] == cm["ob_max_fill"]


def test_gauges_match_cpu_oracle_tgen():
    from shadow1_tpu.cpu_engine import CpuEngine

    exp = tgen_exp(end=6 * SEC // 10)
    params = EngineParams(ev_cap=256)
    tm = Engine.metrics_dict(Engine(exp, params).run())
    cm = CpuEngine(exp, params).run()
    assert tm["ev_overflow"] == 0 and cm["ev_overflow"] == 0
    assert tm["ev_max_fill"] == cm["ev_max_fill"]
    assert tm["ob_max_fill"] == cm["ob_max_fill"]


def test_compact_gauge_records_bucket_demand():
    """The active-host gauge sizes compact_cap BEFORE enabling it, and its
    recording keeps the compacted/plain engines bit-identical."""
    exp = phold_exp(n_hosts=64, seed=7, end_time=30 * MS)
    m = Engine.metrics_dict(
        Engine(exp, EngineParams(compact_cap=32)).run(n_windows=30)
    )
    m_off = Engine.metrics_dict(Engine(exp, EngineParams()).run(n_windows=30))
    assert m_off["compact_max_fill"] > 0  # measured with compaction OFF too
    # The perf knob stays bit-invisible, gauge included: all but what the
    # round loop counts of itself (a window over the cap takes more trips).
    assert trip_metrics(m) == trip_metrics(m_off)
    assert m["compact_max_fill"] <= 64


# ---------------------------------------------------------------------------
# resize migration — bit-exactness
# ---------------------------------------------------------------------------

def test_grow_then_shrink_bit_exact_phold():
    exp = phold_exp()
    ref_eng = Engine(exp, EngineParams(ev_cap=64))
    ref_st = ref_eng.run(n_windows=90)
    engs = {c: Engine(exp, EngineParams(ev_cap=c)) for c in (64, 96, 24)}
    st = engs[64].run(n_windows=30)
    st = engs[96].run(migrate(engs[96], st, ev_cap=96), n_windows=30)
    st = engs[24].run(migrate(engs[24], st, ev_cap=24), n_windows=30)
    assert Engine.metrics_dict(st) == Engine.metrics_dict(ref_st)
    np.testing.assert_array_equal(
        np.asarray(ref_eng.model_summary(ref_st)["hops"]),
        np.asarray(engs[24].model_summary(st)["hops"]),
    )


def test_grow_then_shrink_bit_exact_phold_outbox():
    exp = phold_exp()
    ref = Engine.metrics_dict(Engine(exp, EngineParams()).run(n_windows=60))
    engs = {c: Engine(exp, EngineParams(outbox_cap=c)) for c in (64, 96, 16)}
    st = engs[64].run(n_windows=20)
    st = engs[96].run(migrate(engs[96], st, outbox_cap=96), n_windows=20)
    st = engs[16].run(migrate(engs[16], st, outbox_cap=16), n_windows=20)
    # route_rows counts the outbox's rows, filled or not: the cap's own.
    rows = exp.n_hosts * (64 + 96 + 16) * 20
    assert Engine.metrics_dict(st) == {**ref, "route_rows": rows}
    assert ref["route_rows"] == exp.n_hosts * 64 * 60


def test_grow_then_shrink_bit_exact_tgen():
    """The TCP net model across an ev_cap shrink + regrow (the model state
    pytree — sockets, timers, NIC queues — rides the migration untouched)."""
    exp = tgen_exp()
    params = EngineParams(ev_cap=256)
    ref = Engine.metrics_dict(Engine(exp, params).run(n_windows=60))
    engs = {c: Engine(exp, dataclasses.replace(params, ev_cap=c))
            for c in (256, 64)}
    st = engs[256].run(n_windows=20)
    st = engs[64].run(migrate(engs[64], st, ev_cap=64), n_windows=10)
    st = engs[256].run(migrate(engs[256], st, ev_cap=256), n_windows=30)
    m = Engine.metrics_dict(st)
    assert m["ev_overflow"] == 0
    assert m == ref


@pytest.mark.parametrize("model", [
    "phold",
    # tier-1 wall budget (PR 4): the tgen variant costs ~40s; the phold
    # one exercises the same sharded migrate/retune path in ~5s.
    pytest.param("tgen", marks=pytest.mark.slow),
])
def test_grow_then_shrink_bit_exact_sharded(model):
    from shadow1_tpu.shard.engine import ShardedEngine

    if model == "phold":
        exp = phold_exp(n_hosts=64, seed=7, end_time=50 * MS)
        caps, spans = (48, 96, 16), (20, 15, 15)
        base = EngineParams(ev_cap=48)
    else:
        exp = tgen_exp(n_hosts=8, end=1 * SEC)  # 1 host/shard on the 8-mesh
        caps, spans = (256, 64, 256), (20, 10, 20)
        # x2x_cap pinned at the worst-case (h_local·outbox_cap): the
        # convergent small mesh would otherwise trip the auto-cap retry and
        # pay an extra recompile per engine.
        base = EngineParams(ev_cap=256, x2x_cap=64)
    n_total = sum(spans)
    ref = Engine.metrics_dict(Engine(exp, base).run(n_windows=n_total))
    engs = {c: ShardedEngine(exp, dataclasses.replace(base, ev_cap=c))
            for c in dict.fromkeys(caps)}
    assert engs[caps[0]].n_dev == 8, "conftest must provide 8 virtual devices"
    st = engs[caps[0]].run(n_windows=spans[0])
    for cap, span in zip(caps[1:], spans[1:]):
        st = engs[cap].run(migrate(engs[cap], st, ev_cap=cap), n_windows=span)
    m = Engine.metrics_dict(st)
    skip = {"rounds", "round_cap_hits", "x2x_max_fill",
            "fires_pkt", "fires_deliver", "fires_timer", "fires_txr",
            "fires_app", "deliver_ranks", "compact_max_fill",
            *LANE_PROGRAM_FIELDS}  # per shard like fires_*, then summed
    for k, v in ref.items():
        if k not in skip:
            assert m[k] == v, (k, m[k], v)


def test_shrink_refuses_to_drop_events():
    exp = phold_exp(init_events=12)
    eng = Engine(exp, EngineParams(ev_cap=64))
    st = eng.run(n_windows=10)
    with pytest.raises(ValueError, match="cannot shrink ev_cap"):
        resize_state(jax.tree.map(np.asarray, st), ev_cap=8)


# ---------------------------------------------------------------------------
# checkpoint across caps
# ---------------------------------------------------------------------------

def test_checkpoint_restores_into_different_cap(tmp_path):
    from shadow1_tpu.ckpt import load_state, save_state

    exp = phold_exp()
    eng_a = Engine(exp, EngineParams(ev_cap=48))
    eng_b = Engine(exp, EngineParams(ev_cap=96))
    ref = Engine.metrics_dict(eng_b.run(n_windows=100))
    st = eng_a.run(n_windows=40)
    path = str(tmp_path / "capA.npz")
    save_state(st, path)
    st_b = load_state(eng_b.init_state(), path)  # cap 48 → 96 on load
    final = eng_b.run(st_b, n_windows=60)
    assert Engine.metrics_dict(final) == ref
    # The strict mismatch contract survives: different host count still fails.
    other = Engine(phold_exp(n_hosts=64, seed=17), EngineParams(ev_cap=48))
    with pytest.raises(ValueError, match="config mismatch"):
        load_state(other.init_state(), path)


# ---------------------------------------------------------------------------
# the controller (--auto-caps)
# ---------------------------------------------------------------------------

def run_auto(exp, params, n_windows, chunk, policy=None):
    from shadow1_tpu.ckpt import run_chunked

    eng = Engine(exp, params)
    ctl = CapController(eng, lambda p: Engine(exp, p), policy=policy)
    st = run_chunked(eng, n_windows=n_windows, chunk=chunk, retune=ctl)
    return st, ctl


def test_autocap_shrinks_overprovisioned_run_bit_exact():
    """4×-over-provisioned phold: the controller shrinks to the measured
    band and final results still bit-match the fixed-cap run."""
    exp = phold_exp()
    fixed = Engine.metrics_dict(Engine(exp, EngineParams(ev_cap=64)).run(n_windows=100))
    st, ctl = run_auto(exp, EngineParams(ev_cap=64), n_windows=100, chunk=20)
    assert ctl.resizes, "an over-provisioned cap must trigger a shrink"
    assert ctl.final_caps["ev_cap"] < 64
    assert Engine.metrics_dict(st) == fixed


@pytest.mark.slow  # tier-1 wall budget (PR 4): heaviest of its family;
# a faster sibling keeps the coverage in the fast tier; ./ci.sh all runs it.
def test_autocap_grows_before_overflow_tgen():
    """A workload whose occupancy ramps ~13× past the starting cap (TCP
    slow-start): the static cap drops events; --auto-caps must grow ahead
    of the ramp and keep ev_overflow at 0, bit-matching a generously-capped
    fixed run."""
    exp = tgen_exp()
    static = Engine.metrics_dict(Engine(exp, EngineParams(ev_cap=48)).run(n_windows=60))
    assert static["ev_overflow"] > 0, "the static cap must actually overflow"
    big = Engine.metrics_dict(Engine(exp, EngineParams(ev_cap=256)).run(n_windows=60))
    assert big["ev_overflow"] == 0
    st, ctl = run_auto(exp, EngineParams(ev_cap=48), n_windows=60, chunk=2,
                       policy=CapPolicy(headroom=2.0))
    m = Engine.metrics_dict(st)
    assert m["ev_overflow"] == 0, (ctl.resizes, m["ev_overflow"])
    assert ctl.final_caps["ev_cap"] > 48
    assert m == big


def test_autocap_sharded_parity():
    """--auto-caps on the 8-device mesh: resizes reshard the migrated state
    and results stay identical to the single-device auto run."""
    from shadow1_tpu.ckpt import run_chunked
    from shadow1_tpu.shard.engine import ShardedEngine

    exp = phold_exp(n_hosts=64, seed=7, end_time=50 * MS)
    st1, ctl1 = run_auto(exp, EngineParams(ev_cap=96), n_windows=50, chunk=10)
    sh = ShardedEngine(exp, EngineParams(ev_cap=96))
    ctl8 = CapController(sh, lambda p: ShardedEngine(exp, p))
    st8 = run_chunked(sh, n_windows=50, chunk=10, retune=ctl8)
    assert ctl1.resizes and ctl8.resizes
    assert ctl1.final_caps == ctl8.final_caps
    m1, m8 = Engine.metrics_dict(st1), Engine.metrics_dict(st8)
    for k in ("events", "pkts_sent", "pkts_delivered", "ev_overflow",
              "ob_overflow", "ev_max_fill", "ob_max_fill", "windows"):
        assert m1[k] == m8[k], k


def test_autocap_through_run_with_heartbeat(tmp_path):
    """The CLI wiring: controller + heartbeat + ring + checkpoint in one
    chunked run; heartbeats carry the live caps in their fill block."""
    import io

    from shadow1_tpu.obs import run_with_heartbeat

    exp = phold_exp()
    eng = Engine(exp, EngineParams(ev_cap=96, metrics_ring=16))
    ctl = CapController(eng, lambda p: Engine(exp, p))
    buf = io.StringIO()
    st, hb = run_with_heartbeat(eng, n_windows=80, every_windows=16,
                                stream=buf, controller=ctl,
                                ckpt_path=str(tmp_path / "auto.npz"),
                                ckpt_every_s=0.0)
    assert ctl.resizes
    recs = [json.loads(x) for x in buf.getvalue().splitlines()]
    hbs = [r for r in recs if r["type"] == "heartbeat"]
    assert hbs and all("ev_max_fill" in r.get("fill", {}) for r in hbs)
    # Gauges leave the delta block (they are high-water marks, not rates).
    assert all("ev_max_fill" not in r["delta"] for r in hbs)
    # Later heartbeats report the shrunk cap the run actually used.
    assert hbs[-1]["fill"]["ev_cap"] == ctl.final_caps["ev_cap"]
    # The checkpoint (saved at the resized cap) restores into the config cap.
    from shadow1_tpu.ckpt import load_state

    st2 = load_state(eng.init_state(), str(tmp_path / "auto.npz"))
    assert int(st2.metrics.windows) == 80


def test_autocap_overflow_backstop_grows():
    """Mid-window overflow can hide from the window-end fill gauges (burst
    push that drains before the sample) — any fresh overflow must force a
    grow step regardless of the gauge."""
    import jax.numpy as jnp

    exp = phold_exp()
    eng = Engine(exp, EngineParams(ev_cap=64))
    ctl = CapController(eng, lambda p: Engine(exp, p))
    st = eng.run(n_windows=10)
    assert int(st.metrics.ev_max_fill) < 48  # gauge alone would not grow
    lossy = st._replace(metrics=st.metrics._replace(
        ev_overflow=jnp.asarray(5, jnp.int64)))
    eng2, st2 = ctl(eng, lossy)
    assert eng2.params.ev_cap == 96  # one ladder step up
    # Same cumulative count next chunk = no NEW loss: no further grow —
    # and no shrink back below the lossy cap either (the lossless floor):
    # low window-end fill would otherwise re-trigger the overflow forever.
    quiet = st2._replace(metrics=st2.metrics._replace(
        ev_overflow=jnp.asarray(5, jnp.int64)))
    for _ in range(4):  # > shrink_patience
        eng_n, _ = ctl(eng2, quiet)
        assert eng_n.params.ev_cap == 96
    # A resumed run baselines the counters from its initial state: the
    # historical overflow must not force a spurious grow on respawn.
    ctl2 = CapController(eng, lambda p: Engine(exp, p), initial_state=lossy)
    eng4, _ = ctl2(eng, lossy)
    assert eng4.params.ev_cap == 64


def test_autocap_resume_uses_snapshot_caps(tmp_path):
    """The supervised-respawn path: a checkpoint saved at a grown cap whose
    occupancy no longer fits the config's static cap must resume at the
    SNAPSHOT's caps (ckpt.snapshot_caps), not die in the shrink check."""
    from shadow1_tpu.ckpt import load_state, save_state, snapshot_caps

    exp = phold_exp(init_events=12)  # ~12+ events/host: never fits cap 8
    eng_grown = Engine(exp, EngineParams(ev_cap=64))
    st = eng_grown.run(n_windows=10)
    path = str(tmp_path / "grown.npz")
    save_state(st, path)
    eng_cfg = Engine(exp, EngineParams(ev_cap=8))
    assert snapshot_caps(eng_cfg.init_state(), path) == (64, 64)
    with pytest.raises(ValueError, match="snapshot's caps|--auto-caps"):
        load_state(eng_cfg.init_state(), path)  # the loud, actionable path
    # What cli.py --auto-caps does: rebuild at the snapshot caps and resume.
    st2 = load_state(eng_grown.init_state(), path)
    assert int(eng_grown.run(st2, n_windows=10).metrics.windows) == 20


def test_cli_config_auto_caps_inert_on_cpu_engine(tmp_path):
    """engine.auto_caps in YAML follows the metrics_ring precedent: inert
    (with a warning) under --engine cpu so shared configs still run on the
    oracle; the explicit --auto-caps flag errors."""
    import subprocess
    import sys

    cfg = tmp_path / "auto.yaml"
    cfg.write_text(
        "general: {seed: 3, stop_time: 10 ms}\n"
        "engine: {scheduler: cpu, auto_caps: 1}\n"
        "network: {single_vertex: {latency: 1 ms}}\n"
        "hosts:\n"
        "  - {name: h, count: 4}\n"
        "app:\n"
        "  model: phold\n"
        "  params: {mean_delay_ns: 2000000.0}\n"
    )
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, "-m", "shadow1_tpu", str(cfg)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-500:]
    assert "auto_caps ignored" in r.stderr
    r2 = subprocess.run([sys.executable, "-m", "shadow1_tpu", str(cfg),
                         "--auto-caps"],
                        env=env, capture_output=True, text=True, timeout=600)
    assert r2.returncode != 0 and "--auto-caps" in r2.stderr


# ---------------------------------------------------------------------------
# captune
# ---------------------------------------------------------------------------

def test_captune_reproduces_rung5_audit(capsys):
    """The acceptance reproduction: from the recorded round-5 audit row,
    captune finds rung5's ev_cap ~6× over-provisioned and recommends the
    96 the config now carries."""
    from shadow1_tpu.tools import captune

    recs = captune.load_records([os.path.join(REPO, "AUDIT_r05_occupancy.jsonl")])
    groups = captune.group_records(recs)
    rows = captune.advise(*captune.peaks_from_records(
        groups["configs/rung5_bitcoin5k.yaml"]))
    (row,) = rows
    assert row["knob"] == "ev_cap" and row["verdict"] == "shrink"
    assert row["recommended"] == 96
    assert 5.9 <= row["over_factor"] <= 6.0  # "~6× over-provisioned"
    assert row["plane_pass_saving"] == pytest.approx(0.62, abs=0.01)
    # The hand-validated caps stay untouched.
    for cfg in ("configs/rung2_tgen100.yaml", "configs/dense_tgen50k.yaml"):
        (r,) = captune.advise(*captune.peaks_from_records(groups[cfg]))
        assert r["verdict"] == "ok", cfg
    # CLI end-to-end: the YAML block carries the provenance comment.
    rc = captune.main([os.path.join(REPO, "AUDIT_r05_occupancy.jsonl")])
    out = capsys.readouterr().out
    assert rc == 0
    assert "ev_cap: 96  # captune: measured peak 43" in out


def test_captune_outbox_pacing_is_not_grow_advice():
    """A full outbox with 0 drops is TCP flow control, not overflow risk —
    and outbox_cap is semantic for TCP, so captune must not advise resizing
    it from fill alone (the rung1 CLI drive surfaces exactly this shape)."""
    from shadow1_tpu.tools import captune

    (row,) = captune.advise({"outbox_cap": 64}, {"outbox_cap": 64}, {})
    assert row["verdict"] == "pacing" and row["recommended"] == 64
    assert "send pacing" in captune.advise_lines([row])[0]
    assert "keep" in captune.render_yaml([row])
    # With actual drops the grow advice stands.
    (row,) = captune.advise({"outbox_cap": 64}, {"outbox_cap": 64},
                            {"outbox_cap": 5})
    assert row["verdict"] == "grow"


def test_captune_sees_overflow_in_heartbeat_deltas():
    """A heartbeat-only log (no ring, no final JSON) must still flag an
    overflowed run — a shrink recommendation from a lossy run's 'peak'
    would repeat the rung2 mistake (the peak is a floor)."""
    from shadow1_tpu.tools import captune

    recs = [
        {"type": "heartbeat", "delta": {"events": 10, "ev_overflow": 100},
         "fill": {"ev_max_fill": 20, "ev_cap": 256}},
        {"type": "heartbeat", "delta": {"events": 10, "ev_overflow": 78},
         "fill": {"ev_max_fill": 20, "ev_cap": 256}},
    ]
    peaks, caps, overflow = captune.peaks_from_records(recs)
    assert overflow["ev_cap"] == 178
    (row,) = captune.advise(peaks, caps, overflow)
    assert row["overflowed"]
    assert "OVERFLOWED" in captune.advise_lines([row])[0]
    # Redundant channels (ring rows sum to heartbeat deltas) never
    # double-count: max of the channels, not their sum.
    recs.append({"type": "ring", "window": 0, "ev_overflow": 178,
                 "evbuf_fill": 20})
    assert captune.peaks_from_records(recs)[2]["ev_cap"] == 178


def test_captune_reads_live_run_records(tmp_path):
    """captune on the records a real run emits: ring JSONL + the CLI's
    final metrics/caps JSON."""
    from shadow1_tpu.obs import run_with_heartbeat
    from shadow1_tpu.tools import captune

    import io

    exp = phold_exp()
    params = EngineParams(ev_cap=96, metrics_ring=32)
    eng = Engine(exp, params)
    buf = io.StringIO()
    st, _ = run_with_heartbeat(eng, n_windows=60, every_windows=20, stream=buf)
    final = {"metrics": Engine.metrics_dict(st),
             "caps": {"ev_cap": params.ev_cap,
                      "outbox_cap": params.outbox_cap}}
    log = tmp_path / "run.log"
    log.write_text(buf.getvalue() + json.dumps(final) + "\n")
    recs = captune.load_records([str(log)])
    peaks, caps, overflow = captune.peaks_from_records(recs)
    assert peaks["ev_cap"] == int(st.metrics.ev_max_fill)
    assert caps["ev_cap"] == 96
    rows = captune.advise(peaks, caps, overflow)
    by_knob = {r["knob"]: r for r in rows}
    assert by_knob["ev_cap"]["verdict"] == "shrink"  # phold barely fills 96
    assert by_knob["ev_cap"]["recommended"] == recommend_cap(peaks["ev_cap"])


def test_heartbeat_report_surfaces_gauges_and_captune(tmp_path, capsys):
    from shadow1_tpu.tools import heartbeat_report as hr

    lines = [
        json.dumps({"type": "heartbeat", "sim_time_s": 0.5, "wall_s": 1.0,
                    "windows": 5, "events_per_sec": 10.0, "sim_per_wall": 0.5,
                    "delta": {"events": 10},
                    "fill": {"ev_max_fill": 43, "ev_cap": 256}}),
        json.dumps({"type": "ring", "window": 0, "sim_time_s": 1e-3,
                    "events": 5, "evbuf_fill": 40, "ev_max_fill": 40,
                    "ob_max_fill": 3, "compact_max_fill": 0,
                    "x2x_max_fill": 0, "ev_overflow": 0}),
    ]
    log = tmp_path / "r.log"
    log.write_text("\n".join(lines) + "\n")
    summary = hr.summarize(hr.load_records(str(log)))
    out = capsys.readouterr().out
    assert "== captune recommendation ==" in out
    assert "SHRINK -> 96" in out
    assert summary["captune"][0]["knob"] == "ev_cap"
    assert "ev_max_fill" in summary["ring"]


# ---------------------------------------------------------------------------
# CLI --auto-caps end to end
# ---------------------------------------------------------------------------

def test_cli_auto_caps(tmp_path):
    import subprocess
    import sys

    cfg = tmp_path / "phold.yaml"
    cfg.write_text(
        "general: {seed: 3, stop_time: 60 ms}\n"
        "engine: {scheduler: tpu, ev_cap: 96}\n"
        "network: {single_vertex: {latency: 1 ms}}\n"
        "hosts:\n"
        "  - {name: h, count: 16}\n"
        "app:\n"
        "  model: phold\n"
        "  params: {mean_delay_ns: 2000000.0, init_events: 2}\n"
    )
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run(
        [sys.executable, "-m", "shadow1_tpu", str(cfg), "--auto-caps",
         "--heartbeat", "10"],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, (r.stdout[-400:], r.stderr[-800:])
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["caps"]["ev_cap"] == 96
    assert out["auto_caps"]["resizes"], "96 is far over phold's peak"
    assert out["auto_caps"]["final"]["ev_cap"] < 96
    assert out["metrics"]["ev_overflow"] == 0


# ---------------------------------------------------------------------------
# the measured win (slow tier: wall-clock assertion)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_autocap_recovers_wallclock_on_overprovisioned_phold():
    """The acceptance benchmark: ev_cap at 4× the measured peak; --auto-caps
    must recover ≥20% wall vs the static cap (numbers recorded in
    docs/PERF.md "cap economics")."""
    import time

    exp = phold_exp(n_hosts=2048, seed=11, end_time=200 * MS, init_events=4)
    peak = int(Engine(exp, EngineParams(ev_cap=96))
               .run(n_windows=40).metrics.ev_max_fill)
    cap = 4 * peak

    def timed(auto: bool):
        params = EngineParams(ev_cap=cap)
        eng = Engine(exp, params)
        ctl = CapController(eng, lambda p: Engine(exp, p)) if auto else None
        from shadow1_tpu.ckpt import run_chunked

        jax.block_until_ready(eng.run(eng.init_state(), n_windows=0))
        if auto:  # pre-build the shrunk engine: compile time is not run time
            tgt = Engine(exp, EngineParams(ev_cap=quantize_cap(
                int(peak * 1.5) + 1)))
            jax.block_until_ready(tgt.run(tgt.init_state(), n_windows=0))
            ctl._engines[(tgt.params.ev_cap, tgt.params.outbox_cap)] = tgt
        t0 = time.perf_counter()
        st = run_chunked(eng, n_windows=200, chunk=20, retune=ctl)
        jax.block_until_ready(st)
        return time.perf_counter() - t0, Engine.metrics_dict(st)

    wall_static, m_static = timed(False)
    wall_auto, m_auto = timed(True)
    assert m_auto == m_static  # bit-exact while saving the wall
    assert wall_auto < 0.8 * wall_static, (wall_static, wall_auto)
