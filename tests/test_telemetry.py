"""Telemetry plane: on-device ring, phase profiler, unified registry.

The ring's contract (ISSUE 1 acceptance): one record per window whose
per-window deltas sum (within the ring horizon) to the heartbeat's chunk
deltas, recorded with ZERO host↔device syncs inside the window loop; the
profiler's contract: Chrome trace-event JSON that parses cleanly and
carries the compile / run-chunk / drain spans on both batched engines.
"""

import io
import json
import types
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shadow1_tpu.config.compiled import single_vertex_experiment
from shadow1_tpu.consts import MS, EngineParams
from shadow1_tpu.core.engine import Engine, Metrics
from shadow1_tpu.obs import Heartbeat, run_with_heartbeat
from shadow1_tpu.telemetry import (
    METRIC_SPECS,
    RING_COUNTERS,
    RING_FIELDS,
    ExpositionServer,
    PhaseProfiler,
    normalize,
    to_prometheus,
)
from shadow1_tpu.telemetry.ring import drain_ring


def phold_exp(n_hosts=32, seed=17, end_time=100 * MS):
    return single_vertex_experiment(
        n_hosts=n_hosts,
        seed=seed,
        end_time=end_time,
        latency_ns=1 * MS,
        model="phold",
        model_cfg={"mean_delay_ns": float(2 * MS), "init_events": 2},
    )


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_in_sync_with_engine_metrics():
    """The canonical namespace IS the engine's Metrics fields — the guard
    that keeps the tpu/sharded/cpu schemas from drifting apart again.
    The declared HOST_FIELDS (overflow-retry counters, maintained by the
    chunk runner on the host) are the one sanctioned extension."""
    from shadow1_tpu.telemetry.registry import HOST_FIELDS

    assert set(HOST_FIELDS) <= set(METRIC_SPECS)
    assert set(METRIC_SPECS) - set(HOST_FIELDS) == set(Metrics._fields)
    # Every ring counter is a canonical counter (deltas of real metrics).
    assert set(RING_COUNTERS) <= set(METRIC_SPECS)


def test_registry_link_schema_pinned():
    """The link-record schema is part of the sync contract: the oracle and
    the traced scatter address columns by position, so the declared order
    is load-bearing — a reorder is a schema break, not a refactor."""
    from shadow1_tpu.telemetry.registry import (
        LINK_FIELDS,
        LINK_MAX_COL,
        RECORD_TYPES,
        REC_LINK,
        REC_LINK_GAP,
        SERVE_SPECS,
    )

    assert REC_LINK in RECORD_TYPES and REC_LINK_GAP in RECORD_TYPES
    assert LINK_FIELDS == (
        "pkts", "bytes", "loss_drops", "link_down_drops",
        "nic_backlog_drops", "queued_ns_sum", "queued_ns_max")
    # The gauge column is last: the additive prefix buf[..., :LINK_MAX_COL]
    # is what shard/engine.py psums; the max column pmax-reduces.
    assert LINK_MAX_COL == len(LINK_FIELDS) - 1
    # The serve ledger exports the hot-edge gauges under SERVE_SPECS.
    assert SERVE_SPECS["top_edge_bytes"][0] == "gauge"
    assert SERVE_SPECS["top_edge_drops"][0] == "gauge"


def test_normalize_fills_missing_and_keeps_extras():
    d = normalize({"events": 7, "custom_counter": 3})
    assert d["events"] == 7
    assert d["windows"] == 0 and d["tcp_rto"] == 0  # filled, no KeyError
    assert d["custom_counter"] == 3
    assert list(d)[: len(METRIC_SPECS)] == list(METRIC_SPECS)  # canonical order


def test_prometheus_exposition_format():
    text = to_prometheus({"events": 41, "x2x_max_fill": 9},
                         labels={"engine": "tpu"})
    assert '# TYPE shadow1_events_total counter' in text
    assert 'shadow1_events_total{engine="tpu"} 41' in text
    # Gauges are exported without the counter suffix.
    assert '# TYPE shadow1_x2x_max_fill gauge' in text
    assert 'shadow1_x2x_max_fill{engine="tpu"} 9' in text
    assert text.endswith("\n")


def test_exposition_server_scrape():
    srv = ExpositionServer(lambda: {"events": 5}, port=0).start()
    try:
        url = f"http://127.0.0.1:{srv.port}/metrics"
        with urllib.request.urlopen(url, timeout=5) as resp:
            body = resp.read().decode()
            assert resp.headers["Content-Type"].startswith("text/plain")
        assert "shadow1_events_total 5" in body
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# on-device ring
# ---------------------------------------------------------------------------

def test_ring_one_record_per_window_sums_to_heartbeat_deltas():
    eng = Engine(phold_exp(), EngineParams(metrics_ring=32))
    buf = io.StringIO()
    st, hb = run_with_heartbeat(eng, n_windows=100, every_windows=25,
                                stream=buf)
    lines = [json.loads(x) for x in buf.getvalue().splitlines()]
    rings = [r for r in lines if r["type"] == "ring"]
    hbs = [r for r in lines if r["type"] == "heartbeat"]
    # One record per window, in window order, none lost (ring depth 32 > 25).
    assert [r["window"] for r in rings] == list(range(100))
    assert not [r for r in lines if r["type"] == "ring_gap"]
    # Ring deltas sum to the heartbeat chunk deltas — same counters, finer
    # resolution (the acceptance identity).
    for i, h in enumerate(hbs):
        chunk = [r for r in rings if i * 25 <= r["window"] < (i + 1) * 25]
        for field in ("events", "rounds", "pkts_sent", "pkts_delivered"):
            assert sum(r[field] for r in chunk) == h["delta"][field], field
        # Drop counters ride the structured ``drops`` block (same deltas).
        for field in ("pkts_lost", "ev_overflow"):
            assert sum(r[field] for r in chunk) == h["drops"][field], field
        assert h["drops"]["total"] == sum(
            v for k, v in h["drops"].items() if k != "total")
    # The gauge actually observes occupancy.
    assert max(r["evbuf_fill"] for r in rings) > 0
    assert int(st.metrics.events) == sum(r["events"] for r in rings)


def test_ring_no_host_sync_inside_window_loop():
    """The acceptance's zero-sync clause, proven two ways: (1) the whole
    window loop with ring recording traces to a jaxpr (any host fetch of a
    traced value would raise ConcretizationTypeError); (2) running chunks
    performs no block_until_ready at all beyond the explicit warmup."""
    eng = Engine(phold_exp(), EngineParams(metrics_ring=16))
    st = eng.init_state()
    jaxpr = jax.make_jaxpr(eng._make_run())(st, jnp.asarray(8, jnp.int32))
    assert jaxpr is not None  # traced end-to-end: device-resident recording

    calls = {"n": 0}
    real = jax.block_until_ready

    def counting(x):
        calls["n"] += 1
        return real(x)

    from shadow1_tpu.ckpt import run_chunked

    jax.block_until_ready(eng.run(st, n_windows=0))  # warmup outside count
    try:
        jax.block_until_ready = counting
        run_chunked(eng, st, n_windows=32, chunk=8)
    finally:
        jax.block_until_ready = real
    assert calls["n"] == 0


def test_ring_gap_is_reported_not_silent():
    """A chunk longer than the ring keeps the LAST W windows and says so."""
    eng = Engine(phold_exp(), EngineParams(metrics_ring=8))
    buf = io.StringIO()
    run_with_heartbeat(eng, n_windows=40, every_windows=20, stream=buf)
    lines = [json.loads(x) for x in buf.getvalue().splitlines()]
    rings = [r for r in lines if r["type"] == "ring"]
    gaps = [r for r in lines if r["type"] == "ring_gap"]
    # Each 20-window chunk recovers its last 8 windows + one gap record.
    assert [r["window"] for r in rings] == list(range(12, 20)) + list(range(32, 40))
    assert [g["windows_lost"] for g in gaps] == [12, 12]


def test_ring_sharded_parity_and_global_reduction():
    """The sharded ring must record the same global per-window series the
    single-device engine records (counters psum'd, fill max'd across the
    8-device mesh) — the ring analogue of the shard parity invariant.
    ``rounds`` is excluded like the metric itself (per-shard loops);
    ``x2x_max_fill`` only exists under sharding; ``compact_max_fill``
    counts the LOCAL block's active hosts (the per-shard bucket is the
    resource it sizes), so like ``rounds`` it is per-shard by design."""
    from shadow1_tpu.shard.engine import ShardedEngine

    exp = phold_exp(n_hosts=64, seed=7, end_time=50 * MS)
    params = EngineParams(metrics_ring=64)
    st1 = Engine(exp, params).run(n_windows=50)
    sh = ShardedEngine(exp, params)
    assert sh.n_dev == 8, "conftest must provide 8 virtual devices"
    st8 = sh.run(n_windows=50)
    r1 = drain_ring(st1, exp.window)
    r8 = drain_ring(st8, exp.window)
    assert len(r1) == len(r8) == 50
    skip = {"rounds", "x2x_max_fill", "compact_max_fill"}
    for a, b in zip(r1, r8):
        for field in RING_FIELDS:
            if field not in skip:
                assert a[field] == b[field], (a["window"], field)
    assert max(r["x2x_max_fill"] for r in r8) > 0  # exchange actually observed


def test_ring_survives_checkpoint_resume(tmp_path):
    """The ring is engine state: a checkpointed+resumed run carries the
    identical ring rows an uninterrupted run produces."""
    from shadow1_tpu.ckpt import load_state, save_state

    eng = Engine(phold_exp(), EngineParams(metrics_ring=16))
    ref = eng.run(n_windows=60)
    st = eng.run(n_windows=25)
    path = str(tmp_path / "ring.npz")
    save_state(st, path)
    st2 = load_state(eng.init_state(), path)
    final = eng.run(st2, n_windows=35)
    np.testing.assert_array_equal(
        np.asarray(ref.telem.buf), np.asarray(final.telem.buf)
    )
    assert drain_ring(ref, eng.window) == drain_ring(final, eng.window)


def test_ring_off_keeps_legacy_state_layout():
    """metrics_ring=0 must not grow the SimState pytree — checkpoints and
    sharding specs of ring-less runs stay exactly as before."""
    eng_off = Engine(phold_exp(), EngineParams())
    st = eng_off.init_state()
    assert st.telem is None
    n_leaves = len(jax.tree_util.tree_leaves(st))
    eng_on = Engine(phold_exp(), EngineParams(metrics_ring=4))
    assert len(jax.tree_util.tree_leaves(eng_on.init_state())) == n_leaves + 1


# ---------------------------------------------------------------------------
# phase profiler
# ---------------------------------------------------------------------------

def test_profiler_chrome_trace_roundtrip(tmp_path):
    prof = PhaseProfiler()
    with prof.span("compile"):
        with prof.span("inner", detail=3):
            pass
    path = str(tmp_path / "trace.json")
    prof.write(path)
    with open(path) as f:
        doc = json.load(f)  # must parse cleanly (the acceptance clause)
    names = [e["name"] for e in doc["traceEvents"]]
    assert "compile" in names and "inner" in names
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in spans)
    inner = next(e for e in spans if e["name"] == "inner")
    assert inner["args"] == {"detail": 3}


@pytest.mark.parametrize("engine_kind", ["tpu", "sharded"])
def test_profiler_spans_cover_run_phases(engine_kind):
    """compile / run-chunk / drain spans on both batched engines (the
    acceptance's span set), via the same hook the CLI --trace uses."""
    if engine_kind == "sharded":
        from shadow1_tpu.shard.engine import ShardedEngine as Eng

        eng = Eng(phold_exp(n_hosts=64, seed=7, end_time=20 * MS),
                  EngineParams(metrics_ring=8))
    else:
        eng = Engine(phold_exp(end_time=20 * MS),
                     EngineParams(metrics_ring=8))
    prof = PhaseProfiler()
    run_with_heartbeat(eng, n_windows=20, every_windows=10, stream=False,
                       profiler=prof)
    names = set(prof.span_names())
    assert {"init", "compile", "run-chunk", "drain"} <= names


def test_profiler_checkpoint_span(tmp_path):
    eng = Engine(phold_exp(end_time=20 * MS), EngineParams())
    prof = PhaseProfiler()
    run_with_heartbeat(eng, n_windows=20, every_windows=10, stream=False,
                       ckpt_path=str(tmp_path / "ck.npz"), ckpt_every_s=0.0,
                       profiler=prof)
    assert "checkpoint" in prof.span_names()


# ---------------------------------------------------------------------------
# heartbeat robustness (satellite: alternate engines reuse it unchanged)
# ---------------------------------------------------------------------------

def test_heartbeat_tolerates_engines_without_canonical_fields():
    fake_engine = types.SimpleNamespace(window=1000, n_windows=4)
    buf = io.StringIO()
    hb = Heartbeat(fake_engine, stream=buf)
    st = types.SimpleNamespace(
        metrics={"custom": 3},  # no events/windows/rounds anywhere
        win_start=2000,
        telem=None,
    )
    hb(st, 2)  # must not KeyError
    rec = json.loads(buf.getvalue().splitlines()[0])
    assert rec["delta"]["events"] == 0
    assert rec["rounds_per_window"] is None
    assert rec["delta"]["custom"] == 3


def test_log_level_validation():
    from shadow1_tpu.log import SimLogger

    with pytest.raises(ValueError, match="error.*warning.*message.*info.*debug"):
        SimLogger(level="verbose")
    log = SimLogger(stream=io.StringIO())
    with pytest.raises(ValueError, match="valid levels"):
        log.log("loud", "boom")


# ---------------------------------------------------------------------------
# CLI: --trace + --metrics-ring end to end (tpu and sharded engines)
# ---------------------------------------------------------------------------

def _run_cli(args, env):
    import subprocess
    import sys

    return subprocess.run([sys.executable, "-m", "shadow1_tpu", *args],
                          env=env, capture_output=True, text=True,
                          timeout=600)


def _cli_env():
    import os

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"
    return env


def _assert_trace_and_ring(r, trace_path, n_windows):
    assert r.returncode == 0, (r.stdout[-400:], r.stderr[-800:])
    with open(trace_path) as f:
        doc = json.load(f)  # acceptance: json.loads cleanly
    spans = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert {"compile", "run-chunk", "drain"} <= spans, spans
    rings = [json.loads(x) for x in r.stderr.splitlines()
             if x.startswith("{") and '"type": "ring"' in x]
    assert [rec["window"] for rec in rings] == list(range(n_windows))
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["metrics"]["events"] == sum(rec["events"] for rec in rings)


def test_cli_trace_and_metrics_ring_tpu(tmp_path):
    import os

    cfg = os.path.join(os.path.dirname(__file__), "..", "configs",
                       "rung1_filexfer.yaml")
    trace = str(tmp_path / "tpu.trace.json")
    r = _run_cli([cfg, "--windows", "12", "--metrics-ring", "6",
                  "--trace", trace], _cli_env())
    _assert_trace_and_ring(r, trace, 12)


def test_cli_trace_and_metrics_ring_sharded(tmp_path):
    cfg = tmp_path / "phold8.yaml"
    cfg.write_text(
        "general: {seed: 3, stop_time: 20 ms}\n"
        "engine: {scheduler: sharded, ev_cap: 64}\n"
        "network: {single_vertex: {latency: 1 ms}}\n"
        "hosts:\n"
        "  - {name: h, count: 8}\n"
        "app:\n"
        "  model: phold\n"
        "  params: {mean_delay_ns: 2000000.0, init_events: 2}\n"
    )
    trace = str(tmp_path / "sharded.trace.json")
    r = _run_cli([str(cfg), "--windows", "10", "--metrics-ring", "5",
                  "--trace", trace], _cli_env())
    _assert_trace_and_ring(r, trace, 10)


# ---------------------------------------------------------------------------
# tools/heartbeat_report.py (satellite: synthetic-log coverage)
# ---------------------------------------------------------------------------

def _synthetic_log(tmp_path):
    lines = [
        "booting the simulator...",                       # non-JSON noise
        '{"truncated": ',                                 # broken JSON
        json.dumps({"type": "heartbeat", "sim_time_s": 0.5, "wall_s": 1.0,
                    "windows": 5, "events_per_sec": 100.0,
                    "sim_per_wall": 0.5,
                    "delta": {"events": 100, "pkts_delivered": 40,
                              "tcp_rto": 1, "tcp_fast_rtx": 2}}),
        json.dumps({"type": "heartbeat", "sim_time_s": 1.0, "wall_s": 2.0,
                    "windows": 10, "events_per_sec": 300.0,
                    "sim_per_wall": 0.5,
                    "delta": {"events": 300, "pkts_delivered": 60}}),
        json.dumps({"type": "ring_gap", "windows_lost": 2,
                    "first_window": 0, "ring_slots": 4}),
        json.dumps({"type": "tracker", "sim_s": 1.0, "host": 0,
                    "nic_tx_bytes": 999, "nic_rx_bytes": 10,
                    "pending_events": 3}),
        json.dumps({"type": "tracker", "sim_s": 1.0, "host": 1,
                    "nic_tx_bytes": 5, "nic_rx_bytes": 700,
                    "pending_events": 0}),
        "still not json {",
    ]
    for w, (ev, fill) in enumerate([(10, 2), (20, 8), (30, 4), (40, 6)],
                                   start=2):
        lines.append(json.dumps({
            "type": "ring", "window": w, "sim_time_s": (w + 1) * 1e-3,
            "events": ev, "rounds": 3, "pkts_sent": ev, "pkts_delivered": ev,
            "pkts_lost": 0, "ev_overflow": 0, "ob_overflow": 0,
            "x2x_overflow": 0, "down_events": 0, "down_pkts": 0,
            "evbuf_fill": fill, "x2x_max_fill": 0,
        }))
    path = tmp_path / "run.log"
    path.write_text("\n".join(lines) + "\n")
    return str(path)


def test_heartbeat_report_summary_and_csv(tmp_path, capsys):
    from shadow1_tpu.tools import heartbeat_report as hr

    log = _synthetic_log(tmp_path)
    recs = hr.load_records(log)
    assert len(recs) == 9  # garbage lines skipped, records kept
    summary = hr.summarize(recs)
    out = capsys.readouterr().out
    assert summary["heartbeats"] == 2
    assert summary["tracker_records"] == 2
    assert summary["ring_records"] == 4
    assert summary["events"] == 400
    assert summary["retransmits"] == 3
    assert summary["ring"]["events"] == {"p50": 20, "p95": 40, "max": 40}
    assert summary["ring"]["evbuf_fill"]["max"] == 8
    assert summary["ring_windows_lost"] == 2
    assert "== run summary ==" in out
    assert "== per-window occupancy (ring) ==" in out
    assert "WINDOWS LOST TO RING OVERWRITE: 2" in out
    assert "host 0: tx 999 B" in out

    csv_path = str(tmp_path / "hb.csv")
    ring_csv = str(tmp_path / "ring.csv")
    rc = hr.main([log, "--csv", csv_path, "--ring-csv", ring_csv])
    assert rc == 0
    with open(csv_path) as f:
        rows = f.read().splitlines()
    assert rows[0].startswith("sim_time_s,wall_s")
    assert len(rows) == 3 and rows[1].split(",")[4] == "100"
    with open(ring_csv) as f:
        rrows = [line.split(",") for line in f.read().splitlines()]
    assert rrows[0][:3] == ["window", "sim_time_s", "events"]
    assert len(rrows) == 5
    assert rrows[2][0] == "3" and rrows[2][2] == "20"


def test_heartbeat_report_empty_log(tmp_path):
    from shadow1_tpu.tools import heartbeat_report as hr

    p = tmp_path / "empty.log"
    p.write_text("nothing json here\n")
    assert hr.main([str(p)]) == 1
