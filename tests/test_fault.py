"""Fault plane: churn/outage semantics, digest parity, hardened recovery.

The deterministic fault plane (shadow1_tpu/fault/, docs/SEMANTICS.md
§"Fault plane") is only trustworthy if killing hosts and links perturbs
every engine identically — so the tests here are parity tests first:
dead-host discards, restart resets, link outages and loss ramps must land
bit-identically on the CPU oracle, the batched engine, and the sharded
engine, with the per-window digest stream as the continuous witness. The
recovery half covers the hardened checkpoint path: integrity-digest
rejection of truncated/bit-flipped snapshots, and the supervisor surviving
an injected crash plus a corrupted checkpoint in one run.
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from shadow1_tpu.config.compiled import NO_STOP, single_vertex_experiment
from shadow1_tpu.consts import MS, SEC, EngineParams
from shadow1_tpu.core.engine import Engine
from shadow1_tpu.cpu_engine import CpuEngine
from shadow1_tpu.fault.schedule import (
    FaultSchedule,
    host_interval_tensors,
    parse_faults,
)

CFG_DIR = os.path.join(os.path.dirname(__file__), "..", "configs")

FAULT_KEYS = [
    "events", "pkts_sent", "pkts_delivered", "pkts_lost", "link_down_pkts",
    "down_events", "down_pkts", "host_restarts", "tcp_rto", "tcp_fast_rtx",
    "tcp_ooo_drops", "ev_overflow", "ob_overflow",
]


def assert_fault_parity(cm, tm):
    from shadow1_tpu.telemetry.registry import normalize

    cm, tm = normalize(cm), normalize(tm)
    assert tm["ev_overflow"] == 0 and tm["ob_overflow"] == 0, (
        "fault tests must be provisioned overflow-free (parity contract)"
    )
    for k in FAULT_KEYS:
        assert cm[k] == tm[k], (k, cm[k], tm[k])


# ---------------------------------------------------------------------------
# Schedule compilation
# ---------------------------------------------------------------------------

def test_host_interval_tensors_merge_and_quantize():
    exp = single_vertex_experiment(
        n_hosts=4, seed=1, end_time=100 * MS, latency_ns=10 * MS,
        model="phold", model_cfg={"mean_delay_ns": float(MS)},
    )
    exp.stop_time[3] = 55 * MS  # legacy knob merges in
    exp.faults = FaultSchedule(
        host_id=[1, 1], host_down=[15 * MS, 61 * MS],
        host_up=[23 * MS, 75 * MS],  # neither is window-aligned
    )
    down, up = host_interval_tensors(exp)
    assert down.shape == (2, 4)
    # host 1: two cycles, up times quantized UP to the 10 ms window
    assert down[:, 1].tolist() == [15 * MS, 61 * MS]
    assert up[:, 1].tolist() == [30 * MS, 80 * MS]
    # host 3: the legacy stop_time is a [stop, never) interval
    assert down[0, 3] == 55 * MS and up[0, 3] == NO_STOP
    # untouched hosts: empty-interval padding
    assert down[:, 0].tolist() == [NO_STOP, NO_STOP]


def test_host_intervals_overlap_after_quantization_rejected():
    exp = single_vertex_experiment(
        n_hosts=2, seed=1, end_time=100 * MS, latency_ns=10 * MS,
        model="phold", model_cfg={"mean_delay_ns": float(MS)},
    )
    exp.faults = FaultSchedule(
        host_id=[0, 0], host_down=[15 * MS, 22 * MS],
        host_up=[21 * MS, 40 * MS],  # up quantizes to 30ms > next down 22ms
    )
    with pytest.raises(ValueError, match="overlap"):
        host_interval_tensors(exp)


def test_faults_yaml_parsing():
    from shadow1_tpu.config.experiment import build_experiment

    doc = {
        "general": {"seed": 3, "stop_time": "2 s"},
        "network": {"single_vertex": {"latency": "10 ms"}},
        "hosts": [{"name": "a", "count": 2}, {"name": "b", "count": 2}],
        "app": {"model": "phold"},
        "faults": {
            "hosts": [
                {"group": "b", "down_at": "100 ms", "up_at": "200 ms"},
                {"host": 0, "down_at": "1 s"},  # no up_at = kill
            ],
            "links": [{"src_vertex": 0, "dst_vertex": 0,
                       "down_at": "300 ms", "up_at": "400 ms"}],
            "loss": [{"src_vertex": 0, "dst_vertex": 0, "from": "1 s",
                      "until": "1.5 s", "loss": 0.25}],
        },
    }
    exp, _params, _sched = build_experiment(doc)
    fs = exp.faults
    assert fs.host_id.tolist() == [2, 3, 0]
    assert fs.host_up[2] == NO_STOP
    assert len(fs.link_src) == 1  # src == dst: no bidirectional double
    assert fs.ramp_loss.tolist() == [0.25]
    # empty section → None
    assert parse_faults({}, [], []) is None


# ---------------------------------------------------------------------------
# Churn semantics parity (oracle vs batched)
# ---------------------------------------------------------------------------

def _phold_churn_exp():
    exp = single_vertex_experiment(
        n_hosts=8, seed=3, end_time=40 * MS, latency_ns=2 * MS,
        model="phold", model_cfg={"mean_delay_ns": float(MS),
                                  "init_events": 2},
    )
    exp.faults = FaultSchedule(
        host_id=[1, 1, 5], host_down=[5 * MS, 20 * MS, 11 * MS],
        host_up=[9 * MS, 26 * MS, NO_STOP],
    )
    return exp


def test_dead_host_drop_accounting_parity():
    """Dead-host event discards and delivery drops are counted identically
    by both engines, and every routed packet is accounted for."""
    exp = _phold_churn_exp()
    pr = EngineParams()
    cm = CpuEngine(exp, pr).run()
    st = Engine(exp, pr).run()
    tm = Engine.metrics_dict(st)
    assert_fault_parity(cm, tm)
    assert tm["down_pkts"] > 0 and tm["host_restarts"] == 2
    # accounting: sent packets all land somewhere counted
    assert tm["pkts_sent"] == (tm["pkts_delivered"] + tm["pkts_lost"]
                               + tm["down_pkts"] + tm["link_down_pkts"])


def test_restart_resets_model_state():
    """A restarted host comes back with its post-init model state: the
    PHOLD draw counters reset (so its post-restart draws replay the t=0
    stream), bit-identically on both engines."""
    exp = _phold_churn_exp()
    pr = EngineParams()
    cpu = CpuEngine(exp, pr)
    cm = cpu.run()
    eng = Engine(exp, pr)
    st = eng.run()
    assert_fault_parity(cm, Engine.metrics_dict(st))
    ts = eng.model_summary(st)
    cs = cpu.summary()
    np.testing.assert_array_equal(np.asarray(ts["hops"]),
                                  np.asarray(cs["hops"]))
    # Host 5 died for good at 11 ms: its counters froze well below the
    # healthy hosts'. Host 1 restarted twice: each reset zeroed its hops.
    hops = np.asarray(ts["hops"])
    assert hops[1] < hops[0]


# ---------------------------------------------------------------------------
# Link outage + loss ramp (net model, TCP recovery)
# ---------------------------------------------------------------------------

def _outage_exp():
    h = 2
    cfg = dict(
        app="filexfer",
        role=np.array([0, 1]), server=np.zeros(h, np.int64),
        flow_bytes=np.full(h, 1_200_000, np.int64),
        start_time=np.full(h, 1 * MS, np.int64),
        flow_count=np.array([0, 1], np.int64),
    )
    exp = single_vertex_experiment(
        n_hosts=h, seed=5, end_time=4 * SEC, latency_ns=20 * MS,
        model="net", model_cfg=cfg, bw_bits=10**7,
    )
    exp.faults = FaultSchedule(
        link_src=[0], link_dst=[0], link_t0=[300 * MS], link_t1=[500 * MS],
        # Ramp covers the post-outage recovery stretch so it provably hits
        # traffic (the flow completes ~2.0 s in).
        ramp_src=[0], ramp_dst=[0], ramp_t0=[1200 * MS],
        ramp_t1=[1800 * MS], ramp_loss=[0.05],
    )
    return exp


def test_tcp_flow_survives_link_outage_via_rto():
    """A 200 ms outage mid-transfer drops the in-flight window; the sender
    must recover via the retransmit timer and still complete the flow —
    with both engines agreeing on every counter, including the outage's
    own drop reason and the loss-ramp casualties."""
    exp = _outage_exp()
    pr = EngineParams(ev_cap=256)
    cpu = CpuEngine(exp, pr)
    cm = cpu.run()
    eng = Engine(exp, pr)
    st = eng.run()
    tm = Engine.metrics_dict(st)
    assert_fault_parity(cm, tm)
    assert tm["link_down_pkts"] > 0, "outage never hit traffic"
    assert tm["tcp_rto"] >= 1, "recovery must ride the RTO path"
    assert tm["pkts_lost"] > 0, "loss ramp never hit traffic"
    ts = eng.model_summary(st)
    assert int(np.asarray(ts["flows_done"]).sum()) == 1, (
        "flow must complete despite the outage")
    np.testing.assert_array_equal(np.asarray(ts["rx_bytes"]),
                                  np.asarray(cpu.summary()["rx_bytes"]))


# ---------------------------------------------------------------------------
# Digest-stream parity matrix + checkpoint/resume under an active schedule
# ---------------------------------------------------------------------------

def _churn_matrix_exp():
    """8 hosts (sharding-friendly), host cycles + outage + ramp all active
    inside 150 windows (every fault counter verified nonzero below)."""
    h = 8
    cfg = dict(
        app="filexfer",
        role=np.array([0] + [1] * 7),
        server=np.zeros(h, np.int64),
        flow_bytes=np.full(h, 150_000, np.int64),
        start_time=(1 * MS + np.arange(h) * 10 * MS).astype(np.int64),
        flow_count=np.array([0] + [6] * 7, np.int64),
    )
    exp = single_vertex_experiment(
        n_hosts=h, seed=5, end_time=3 * SEC, latency_ns=20 * MS,
        model="net", model_cfg=cfg, bw_bits=10**7,
    )
    exp.faults = FaultSchedule(
        host_id=[3, 3, 5],
        host_down=[200 * MS, 900 * MS, 400 * MS],
        host_up=[400 * MS, 1200 * MS, 700 * MS],
        link_src=[0], link_dst=[0], link_t0=[600 * MS], link_t1=[750 * MS],
        ramp_src=[0], ramp_dst=[0], ramp_t0=[1300 * MS], ramp_t1=[1800 * MS],
        ramp_loss=[0.05],
    )
    return exp


def _digest_tuples(rows):
    from shadow1_tpu.core.digest import DIGEST_FIELDS

    return {r["window"]: tuple(r[f] for f in DIGEST_FIELDS) for r in rows
            if r.get("type") in ("ring", "digest")}


def test_digest_parity_cpu_tpu_sharded_under_faults():
    """The acceptance matrix: with host churn (restarts included), a link
    outage and a loss ramp all firing, the per-window digest stream is
    bit-identical cpu ↔ tpu ↔ sharded, and so is every fault counter."""
    from shadow1_tpu.shard.engine import ShardedEngine
    from shadow1_tpu.telemetry.ring import drain_ring

    exp = _churn_matrix_exp()
    n_win = int(-(-exp.end_time // exp.window))
    pr = EngineParams(ev_cap=256, metrics_ring=n_win, state_digest=1)

    cpu = CpuEngine(exp, pr)
    cm = cpu.run()
    cpu_dg = _digest_tuples(cpu.digest_rows)

    eng = Engine(exp, pr)
    st = eng.run()
    tm = Engine.metrics_dict(st)
    assert_fault_parity(cm, tm)
    assert tm["host_restarts"] == 3 and tm["link_down_pkts"] > 0
    tpu_dg = _digest_tuples(drain_ring(st, exp.window))
    assert len(tpu_dg) == n_win
    assert tpu_dg == cpu_dg, "digest stream diverged cpu↔tpu"

    sh = ShardedEngine(exp, pr)
    sst = sh.run()
    assert_fault_parity(cm, ShardedEngine.metrics_dict(sst))
    assert _digest_tuples(drain_ring(sst, exp.window)) == cpu_dg, (
        "digest stream diverged cpu↔sharded")


def test_ckpt_resume_mid_outage_bit_identical():
    """Snapshot taken while a host is DOWN and the link outage is armed;
    the resumed run must continue the restart schedule and digest stream
    bit-identically to the straight run."""
    from shadow1_tpu.ckpt import load_state, save_state

    exp = _churn_matrix_exp()
    n_win = int(-(-exp.end_time // exp.window))
    pr = EngineParams(ev_cap=256, metrics_ring=n_win, state_digest=1)
    eng = Engine(exp, pr)
    ref = eng.run(n_windows=n_win)
    # Window 50 = sim 1.0 s: host 3 is inside its second down interval.
    mid = eng.run(n_windows=50)
    path = "/tmp/shadow1_fault_mid.npz"
    save_state(mid, path)
    resumed = eng.run(load_state(eng.init_state(), path),
                      n_windows=n_win - 50)
    la = jax.tree_util.tree_leaves(ref)
    lb = jax.tree_util.tree_leaves(resumed)
    for i, (a, b) in enumerate(zip(la, lb)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"leaf {i}")


# ---------------------------------------------------------------------------
# Checkpoint integrity
# ---------------------------------------------------------------------------

def _small_engine():
    exp = single_vertex_experiment(
        n_hosts=16, seed=9, end_time=50 * MS, latency_ns=1 * MS,
        model="phold", model_cfg={"mean_delay_ns": float(2 * MS)},
    )
    return Engine(exp, EngineParams())


def test_checkpoint_rejects_truncated_and_bitflipped(tmp_path):
    from shadow1_tpu.ckpt import (
        CorruptCheckpointError,
        load_state,
        save_state,
        verify_file,
    )

    eng = _small_engine()
    st = eng.run(n_windows=10)
    path = str(tmp_path / "snap.npz")
    save_state(st, path)
    ok, why = verify_file(path)
    assert ok, why
    load_state(eng.init_state(), path)  # intact: loads fine

    raw = open(path, "rb").read()
    # Truncation: half the zip is gone.
    trunc = str(tmp_path / "trunc.npz")
    with open(trunc, "wb") as f:
        f.write(raw[: len(raw) // 2])
    assert verify_file(trunc)[0] is False
    with pytest.raises(CorruptCheckpointError):
        load_state(eng.init_state(), trunc)

    # Single flipped bit inside one leaf's payload. (Flipping a raw file
    # byte can land in zip padding or trip the member CRC first; rewriting
    # one payload bit while keeping the stored integrity word is the exact
    # scenario the digest exists for: plausible-looking state that is not
    # the state that was saved.)
    flip = str(tmp_path / "flip.npz")
    with np.load(path) as d:
        arrs = {k: d[k].copy() for k in d.files}
    leaf = next(k for k in arrs if k.startswith("leaf_")
                and arrs[k].size and arrs[k].dtype != np.bool_)
    arrs[leaf].reshape(-1).view(np.uint8)[0] ^= 0x10
    np.savez(flip, **arrs)  # stored integrity word is now stale
    ok, why = verify_file(flip)
    assert ok is False, "bit flip must not verify"
    assert "integrity" in (why or "")
    with pytest.raises(CorruptCheckpointError, match="integrity"):
        load_state(eng.init_state(), flip)


# ---------------------------------------------------------------------------
# Supervisor: crash + corrupt checkpoint in ONE run; failure classification
# ---------------------------------------------------------------------------

def test_supervise_survives_crash_and_corrupt_checkpoint(tmp_path):
    """The acceptance recovery run: a leftover checkpoint is bit-corrupted
    AND the child crashes mid-run. The supervisor must discard the corrupt
    snapshot (not crash-loop), respawn through the injected crash, and the
    final state must bit-match an uninterrupted run."""
    cfg = os.path.join(CFG_DIR, "rung1_filexfer.yaml")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "SHADOW1_SUPERVISE_BACKOFF_S": "0"}
    ref_npz = str(tmp_path / "ref.npz")
    sup_npz = str(tmp_path / "sup.npz")
    ck = str(tmp_path / "ck.npz")
    base = [sys.executable, "-m", "shadow1_tpu", cfg, "--windows", "40"]
    r = subprocess.run([*base, "--save-state", ref_npz], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-800:]

    # A corrupt leftover checkpoint with a MATCHING config fingerprint —
    # exactly the state after a crash flipped bits in the snapshot.
    import hashlib

    with open(cfg, "rb") as f:
        fp = hashlib.sha256(f.read()).hexdigest()
    body = bytearray(open(ref_npz, "rb").read())
    # A run of bytes, not one: the reader takes a member's sizes from the
    # central directory, so a flip inside a local header's zip64 record
    # (where the midpoint fell once the snapshot gained the deliver_ranks
    # leaf) changes nothing it reads, and an intact snapshot is rightly not
    # discarded. 128 bytes span a whole header (61) and reach into data.
    mid = len(body) // 2
    body[mid:mid + 128] = bytes(b ^ 0x40 for b in body[mid:mid + 128])
    with open(ck, "wb") as f:
        f.write(bytes(body))
    with open(ck + ".meta", "w") as f:
        json.dump({"config_sha256": fp}, f)

    from shadow1_tpu.config.experiment import load_experiment

    exp, _, _ = load_experiment(cfg)
    env["SHADOW1_OBS_CRASH_AT_NS"] = str(20 * exp.window)
    r = subprocess.run(
        [*base, "--ckpt", ck, "--ckpt-every-s", "0", "--heartbeat", "10",
         "--save-state", sup_npz],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, (r.stdout[-400:], r.stderr[-800:])
    assert "discarding corrupt checkpoint" in r.stderr
    assert "respawning" in r.stderr
    with np.load(ref_npz) as a, np.load(sup_npz) as b:
        assert set(a.files) == set(b.files)
        for k in a.files:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def test_supervise_classifies_deterministic_no_progress_crash(tmp_path):
    """Two crashes with zero forward progress at the same point must abort
    early with a diagnosis (pointing at the probe tools), not burn all
    MAX_RESPAWNS."""
    cfg = os.path.join(CFG_DIR, "rung1_filexfer.yaml")
    from shadow1_tpu.config.experiment import load_experiment

    exp, _, _ = load_experiment(cfg)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "SHADOW1_SUPERVISE_BACKOFF_S": "0",
           # Die at the first chunk boundary BEFORE the checkpoint is
           # written: every attempt crashes with no recorded progress.
           "SHADOW1_OBS_CRASH_PRE_SAVE_AT_NS": str(10 * exp.window)}
    ck = str(tmp_path / "ck.npz")
    r = subprocess.run(
        [sys.executable, "-m", "shadow1_tpu", cfg, "--windows", "40",
         "--ckpt", ck, "--ckpt-every-s", "0", "--heartbeat", "10"],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 41, (r.returncode, r.stderr[-600:])
    assert "no forward progress" in r.stderr
    assert "faultprobe" in r.stderr and "paritytrace" in r.stderr
    # Classified after exactly two attempts: one respawn line, not seven.
    assert r.stderr.count("respawning") == 1, r.stderr[-800:]
