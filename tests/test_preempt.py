"""Preemption-safe execution: signal drain, checkpoint lineage, watchdog.

The preemption contract (docs/SEMANTICS.md): a run is survivable at any
real-time instant — SIGTERM drains (commit, snapshot, EXIT_PREEMPTED),
a corrupt snapshot head falls back one lineage generation instead of
restarting the run, a wedged child is killed and classified within the
watchdog deadline — and every recovery path ends bit-identical to a run
nothing ever touched. tools/chaosprobe.py proves the same contract under
randomized kills; these tests pin the mechanisms deterministically.
"""

import dataclasses
import io
import json
import os
import subprocess
import sys

import pytest

import jax
import numpy as np

from shadow1_tpu.ckpt import load_state
from shadow1_tpu.config.compiled import single_vertex_experiment
from shadow1_tpu.consts import (
    EXIT_CAPACITY,
    EXIT_CODES,
    EXIT_CONFIG,
    EXIT_HUNG,
    EXIT_OK,
    EXIT_PREEMPTED,
    MS,
    EngineParams,
)
from shadow1_tpu.core.engine import Engine
from shadow1_tpu.lineage import Lineage, write_json_atomic
from shadow1_tpu.preempt import DrainHandler, PreemptedExit

CFG_DIR = os.path.join(os.path.dirname(__file__), "..", "configs")
RUNG1 = os.path.join(CFG_DIR, "rung1_filexfer.yaml")


def phold_engine(n_hosts=16):
    return Engine(single_vertex_experiment(
        n_hosts=n_hosts, seed=11, end_time=200 * MS, latency_ns=1 * MS,
        model="phold", model_cfg={"mean_delay_ns": float(2 * MS),
                                  "init_events": 2}), EngineParams())


def state_equal(a, b) -> bool:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


def _truncate(path):
    """The torn-write corruption shape: guaranteed to fail verification
    (a mid-file bit flip can land in zip padding and slip through)."""
    with open(path, "r+b") as f:
        f.truncate(max(os.path.getsize(path) // 2, 1))


# ---------------------------------------------------------------------------
# exit-code taxonomy (consts.py — the one table everything asserts against)
# ---------------------------------------------------------------------------

def test_exit_code_taxonomy():
    from shadow1_tpu.consts import (
        EXIT_DEADLINE,
        EXIT_MEMORY,
        EXIT_QUEUE_FULL,
        EXIT_SERVE_SHUTDOWN,
        EXIT_SERVE_SPOOL,
    )

    codes = (EXIT_OK, EXIT_CONFIG, EXIT_CAPACITY, EXIT_PREEMPTED,
             EXIT_HUNG, EXIT_MEMORY, EXIT_SERVE_SHUTDOWN,
             EXIT_SERVE_SPOOL, EXIT_QUEUE_FULL, EXIT_DEADLINE)
    assert len(set(codes)) == len(codes), "codes must be distinct"
    assert set(EXIT_CODES) == set(codes), "every code documented"
    # Codes must stay clear of shell/signal conventions: 1 is a generic
    # crash, 126-128 shell-reserved, >=128 signal deaths.
    assert all(0 <= c < 126 for c in codes)
    # txn re-exports the capacity code from the same table.
    from shadow1_tpu.txn import EXIT_CAPACITY as TXN_CAP

    assert TXN_CAP is EXIT_CAPACITY


def test_write_json_atomic(tmp_path):
    p = str(tmp_path / "x.json")
    write_json_atomic(p, {"a": 1})
    assert json.load(open(p)) == {"a": 1}
    assert not os.path.exists(p + ".tmp"), "no tmp residue"


# ---------------------------------------------------------------------------
# lineage: rotation, pruning, newest-valid resolution, fallback exactness
# ---------------------------------------------------------------------------

def test_lineage_rotation_prune_and_resolve(tmp_path):
    eng = phold_engine(8)
    path = str(tmp_path / "ck.npz")
    lin = Lineage(path, keep=3)
    st = eng.init_state()
    for i in range(5):
        st = eng.run(st, n_windows=10)
        seq = lin.save(st, {"win_start": int(st.win_start),
                            "done_windows": (i + 1) * 10})
        assert seq == i  # monotonic sequence numbers
    gens = lin.generations()
    assert [g["seq"] for g in gens] == [2, 3, 4], gens  # pruned to keep=3
    assert gens[-1]["file"] == path  # newest generation IS the bare path
    assert gens[-1]["win_start"] == int(st.win_start)
    assert {"ev_cap", "outbox_cap"} <= set(gens[-1]["caps"])
    res = lin.resolve()
    assert res.path == path and res.seq == 4 and not res.skipped


def test_lineage_fallback_costs_one_generation(tmp_path):
    """Corrupt the newest generation: resume must land on the previous one
    and the continued run must bit-match a straight run — the acceptance
    shape (one generation of progress lost, never the run)."""
    eng = phold_engine(8)
    path = str(tmp_path / "ck.npz")
    lin = Lineage(path, keep=3)
    st = eng.init_state()
    for i in range(5):
        st = eng.run(st, n_windows=10)
        lin.save(st, {"win_start": int(st.win_start),
                      "done_windows": (i + 1) * 10})
    _truncate(path)  # torn head (generation 4)
    res = lin.resolve()
    assert res.seq == 3 and res.path.endswith(".g000003")
    assert res.skipped and res.skipped[0]["file"] == path
    # Child mode discards the corrupt head so it can't rotate back in.
    res = lin.resolve(discard_invalid=True)
    assert not os.path.exists(path)
    st2 = load_state(eng.init_state(), res.path)
    assert int(st2.win_start) == 40 * eng.window  # one generation behind
    final = eng.run(st2, n_windows=10)
    assert state_equal(final, st)
    # Lineage continues monotonically past the repaired head.
    assert lin.save(final, {"win_start": int(final.win_start),
                            "done_windows": 60}) == 5
    assert lin.resolve().seq == 5


def test_lineage_keep1_crash_mid_write_keeps_a_snapshot(tmp_path):
    """Even at --ckpt-keep 1 a kill between head-rotation and install must
    leave a resumable generation on disk (the old head is rotated, never
    deleted, and pruned only AFTER the new head installs)."""
    script = (
        "import os, shadow1_tpu\n"
        "from shadow1_tpu.config.compiled import single_vertex_experiment\n"
        "from shadow1_tpu.consts import MS, EngineParams\n"
        "from shadow1_tpu.core.engine import Engine\n"
        "from shadow1_tpu.lineage import Lineage\n"
        "eng = Engine(single_vertex_experiment(n_hosts=8, seed=4,\n"
        "    end_time=100 * MS, latency_ns=1 * MS, model='phold',\n"
        "    model_cfg={'mean_delay_ns': float(2 * MS)}), EngineParams())\n"
        "lin = Lineage(os.environ['CK'], keep=1)\n"
        "st = eng.run(n_windows=5)\n"
        "lin.save(st, {'win_start': int(st.win_start), 'done_windows': 5})\n"
        "os.environ['SHADOW1_LINEAGE_CRASH_BETWEEN'] = os.environ['FLAG']\n"
        "lin.save(eng.run(st, n_windows=5), {'win_start': 0})\n"
    )
    ck = str(tmp_path / "ck.npz")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "CK": ck,
           "FLAG": str(tmp_path / "between.flag")}
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 137, (r.returncode, r.stderr[-500:])
    res = Lineage(ck, keep=1).resolve()
    assert res is not None and res.path is not None, \
        "a kill mid-checkpoint-write left zero snapshots"
    assert res.seq == 0  # the rotated previous generation survived


def test_lineage_fallback_fleet(tmp_path):
    """Same fallback exactness fleet-shaped: the snapshot holds [E, ...]
    leaves, the head is torn, resume lands a generation back and the
    continued fleet bit-matches the straight fleet run."""
    from shadow1_tpu.fleet.engine import FleetEngine
    from shadow1_tpu.fleet.expand import expand_sweep

    doc = {
        "general": {"seed": 7, "stop_time": "150 ms"},
        "engine": {"scheduler": "tpu", "ev_cap": 32, "outbox_cap": 16},
        "network": {"single_vertex": {"latency": "10 ms"}},
        "hosts": [{"name": "h", "count": 8}],
        "app": {"model": "phold",
                "params": {"mean_delay_ns": 2.0e7, "init_events": 2}},
        "sweep": {"seeds": [7, 8]},
    }
    plan = expand_sweep(doc)
    eng = FleetEngine(plan.exps, plan.params, plan.max_rounds)
    path = str(tmp_path / "fleet.npz")
    lin = Lineage(path, keep=2)
    st = eng.init_state()
    for i in range(3):
        st = eng.run(st, n_windows=4)
        lin.save(st, {"win_start": int(np.asarray(st.win_start).max()),
                      "done_windows": (i + 1) * 4})
    _truncate(path)
    res = lin.resolve(discard_invalid=True)
    assert res.seq == 1
    st2 = load_state(eng.init_state(), res.path)
    final = eng.run(st2, n_windows=4)
    assert state_equal(final, st)


# ---------------------------------------------------------------------------
# drain semantics in the chunk runner (in-process, no signals)
# ---------------------------------------------------------------------------

def _solo_runner():
    from shadow1_tpu.obs import run_with_heartbeat

    return phold_engine(8), run_with_heartbeat, {}


def _fleet_runner():
    from shadow1_tpu.fleet.engine import FleetEngine
    from shadow1_tpu.fleet.run import run_fleet

    exp = phold_engine(8).exp
    eng = FleetEngine([exp, dataclasses.replace(exp, seed=12)], EngineParams())
    return eng, run_fleet, {"lanes": [0, 1]}


def _latch_at(monkeypatch, drain, sim_ns, signame):
    """Latch ``drain`` from inside on_chunk at sim time ``sim_ns``, where
    the injection hooks deliver a real run's SIGTERM: after the heartbeat,
    before the snapshot."""
    import shadow1_tpu.preempt as preempt

    def hooks(now_ns):
        if now_ns == sim_ns:
            drain.signame = signame

    monkeypatch.setattr(preempt, "run_injection_hooks", hooks)


RUNNERS = pytest.mark.parametrize("runner", [_solo_runner, _fleet_runner],
                                  ids=["solo", "fleet"])


@RUNNERS
def test_run_chunked_drain_commits_inflight_chunk(runner, tmp_path,
                                                  monkeypatch):
    """A drain requested mid-run stops AFTER the in-flight chunk commits:
    the carried state equals a straight run of exactly the committed
    windows — the when-work-is-lost half of the preemption contract.
    The latch is sampled BEFORE on_chunk at each boundary, so a request
    landing inside on_chunk (as the injection hooks do) is honored one
    boundary later — never without the forced snapshot. Solo and fleet
    reach ckpt.run_chunked through the one boundary hook
    (obs.boundary_hook): same snapshots, same sidecar, same exit."""
    eng, run, meta = runner()
    ck = str(tmp_path / "ck.npz")
    drain = DrainHandler()  # not installed: no real signals in-process
    _latch_at(monkeypatch, drain, 20 * eng.window, "SIGTERM")
    with pytest.raises(PreemptedExit) as ei:
        run(eng, n_windows=50, every_windows=10, stream=False, ckpt_path=ck,
            drain=drain)
    e = ei.value
    assert e.done_windows == 30 and e.signame == "SIGTERM"
    assert e.win_start == 30 * eng.window
    assert state_equal(e.st, eng.run(n_windows=30))
    # Both boundaries that saw the latch forced their snapshot (the wall
    # throttle alone would have saved neither), the newest holds the
    # carried state, and the sidecar ticked with it.
    gens = Lineage(ck).generations()
    assert [g["done_windows"] for g in gens] == [20, 30]
    assert [g["win_start"] for g in gens] == [20 * eng.window,
                                              30 * eng.window]
    assert all({k: g[k] for k in meta} == meta for g in gens)
    assert state_equal(load_state(eng.init_state(), ck), e.st)
    with open(ck + ".progress") as f:
        assert json.load(f) == {"done_windows": 30, "total": 50,
                                "win_start": 30 * eng.window,
                                "seq": gens[-1]["seq"]}


@RUNNERS
def test_drain_on_final_chunk_is_a_normal_exit(runner, tmp_path,
                                               monkeypatch):
    eng, run, _meta = runner()
    drain = DrainHandler()
    # The last chunk: nothing left to preempt.
    _latch_at(monkeypatch, drain, 30 * eng.window, "SIGINT")
    st, _hb = run(eng, n_windows=30, every_windows=10, stream=False,
                  ckpt_path=str(tmp_path / "ck.npz"), drain=drain)
    assert drain.requested
    assert state_equal(st, eng.run(n_windows=30))


# ---------------------------------------------------------------------------
# CLI end-to-end: SIGTERM drain → EXIT_PREEMPTED → bit-identical resume
# ---------------------------------------------------------------------------

def test_cli_sigterm_drain_preempted_then_resume(tmp_path):
    """The acceptance run: SIGTERM mid-run exits EXIT_PREEMPTED after
    committing the in-flight chunk (parseable stdout record, supervisor
    classifies clean-resume and KEEPS the checkpoint), and rerunning the
    same command resumes to a final state bit-identical to an
    uninterrupted run."""
    from shadow1_tpu.config.experiment import load_experiment

    exp, _, _ = load_experiment(RUNG1)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "SHADOW1_SUPERVISE_BACKOFF_S": "0"}
    ref_npz = str(tmp_path / "ref.npz")
    fin_npz = str(tmp_path / "fin.npz")
    ck = str(tmp_path / "ck.npz")
    base = [sys.executable, "-m", "shadow1_tpu", RUNG1, "--windows", "40",
            "--heartbeat", "10", "--ckpt-every-s", "0"]
    r = subprocess.run([*base, "--save-state", ref_npz], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == EXIT_OK, r.stderr[-800:]

    env2 = {**env, "SHADOW1_OBS_SIGTERM_SELF_AT_NS": str(20 * exp.window)}
    r = subprocess.run([*base, "--ckpt", ck], env=env2,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == EXIT_PREEMPTED, (r.returncode, r.stderr[-800:])
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["preempted"] is True and rec["signal"] == "SIGTERM"
    # The hook delivers SIGTERM inside the window-20 boundary's on_chunk,
    # so the drain is honored (with its forced snapshot) one chunk later.
    assert rec["win_start"] == 30 * exp.window
    assert "child drained" in r.stderr  # supervisor: clean-resume class
    assert "respawning" not in r.stderr  # no crash accounting, no backoff
    assert os.path.exists(ck), "checkpoint must be KEPT for the resume"

    # Rerun the SAME command: resumes (resume record names the generation)
    # and finishes; final state bit-matches the uninterrupted run.
    r = subprocess.run([*base, "--ckpt", ck, "--save-state", fin_npz],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == EXIT_OK, r.stderr[-800:]
    resumes = [json.loads(line) for line in r.stderr.splitlines()
               if line.startswith("{")
               and json.loads(line).get("type") == "resume"]
    assert resumes and resumes[0]["win_start"] == 30 * exp.window
    assert resumes[0]["fallback_skipped"] == 0
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["resumed"] is True
    with np.load(ref_npz) as a, np.load(fin_npz) as b:
        assert set(a.files) == set(b.files)
        for k in a.files:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def test_cli_watchdog_kills_and_recovers_hung_child(tmp_path):
    """The stale-progress acceptance: a child whose sidecar stops ticking
    (alive but stuck) is killed within the watchdog deadline,
    classified 'hung' (not crashed), respawned, and the finished run
    bit-matches an uninterrupted one."""
    from shadow1_tpu.config.experiment import load_experiment

    exp, _, _ = load_experiment(RUNG1)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "SHADOW1_SUPERVISE_BACKOFF_S": "0"}
    ref_npz = str(tmp_path / "ref.npz")
    fin_npz = str(tmp_path / "fin.npz")
    ck = str(tmp_path / "ck.npz")
    base = [sys.executable, "-m", "shadow1_tpu", RUNG1, "--windows", "40",
            "--heartbeat", "10", "--ckpt-every-s", "0"]
    r = subprocess.run([*base, "--save-state", ref_npz], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == EXIT_OK, r.stderr[-800:]

    env2 = {**env, "SHADOW1_OBS_HANG_AT_NS": str(20 * exp.window),
            "SHADOW1_OBS_HANG_ONCE_FLAG": str(tmp_path / "hung.flag")}
    # 12 s, not 5: a child gets 3x the deadline to its first beat, and under
    # tier-1's six workers one has taken 19-23 s to get there (ROADMAP
    # Design 13: the grace ran out, two kills without progress, rc 6).
    r = subprocess.run([*base, "--ckpt", ck, "--watchdog-s", "12",
                        "--save-state", fin_npz],
                       env=env2, capture_output=True, text=True, timeout=600)
    assert r.returncode == EXIT_OK, (r.returncode, r.stderr[-1500:])
    assert "child hung" in r.stderr  # classified hung, not crashed
    assert "watchdog_kill" in r.stderr  # parseable lineage event
    assert "respawning" in r.stderr
    with np.load(ref_npz) as a, np.load(fin_npz) as b:
        for k in a.files:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)


@pytest.mark.slow  # ~25s: two watchdog deadlines back to back; the
# recoverable-hang sibling above keeps fast-tier watchdog coverage
def test_cli_watchdog_classifies_deterministic_hang(tmp_path):
    """Two consecutive watchdog kills with no forward progress abort with
    the dedicated EXIT_HUNG code and the no-kill probe playbook — not a
    burned respawn budget."""
    from shadow1_tpu.config.experiment import load_experiment

    exp, _, _ = load_experiment(RUNG1)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "SHADOW1_SUPERVISE_BACKOFF_S": "0",
           "SHADOW1_OBS_HANG_AT_NS": str(10 * exp.window)}
    r = subprocess.run(
        [sys.executable, "-m", "shadow1_tpu", RUNG1, "--windows", "40",
         "--heartbeat", "10", "--ckpt-every-s", "0",
         "--ckpt", str(tmp_path / "ck.npz"), "--watchdog-s", "4"],
        env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == EXIT_HUNG, (r.returncode, r.stderr[-1500:])
    assert "watchdog kills" in r.stderr and "faultprobe" in r.stderr
    assert r.stderr.count("respawning") == 1  # classified after 2, not 8


# ---------------------------------------------------------------------------
# reporting: heartbeat_report lineage section
# ---------------------------------------------------------------------------

def test_heartbeat_report_lineage_section():
    from shadow1_tpu.tools.heartbeat_report import summarize

    recs = [
        {"type": "resume", "ckpt": "ck.npz", "generation": 4,
         "win_start": 800, "fallback_skipped": 1,
         "discarded": ["ck.npz"], "generations_kept": 3},
        {"type": "lineage", "event": "watchdog_kill", "stale_s": 5.0,
         "sim_ns": 400, "attempt": 1},
        {"type": "lineage", "event": "preempted", "rc": EXIT_PREEMPTED},
    ]
    buf = io.StringIO()
    summary = summarize(recs, out=buf)
    text = buf.getvalue()
    assert summary["lineage"] == {
        "resumes": 1, "fallback_skipped": 1, "watchdog_kills": 1,
        "preempted_drains": 1, "generations_kept": 3}
    assert "lineage (preemption/resume)" in text
    assert "resume: generation 4" in text
    assert "corrupt newer generation(s) skipped" in text
    assert "watchdog kill" in text
