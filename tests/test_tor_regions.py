"""Tor over a lossy network of more than one vertex: ``tor1k_regions`` (config
3's 1,000-host Tor network over six measured regions, a packet loss on every
path; ``configs/geo_tor1k.yaml``) and its cell ``tor1k_regions.lossy3s``.

(a) ``compile_paths`` keeps a self-loop's ``packetloss`` and changes no file
the repo had; (b) the data files state what ISSUE 47 fixed, and the real width
is held by ``eval_shape`` only; (c) what RUNS is the deployment's shape in
miniature (``tests/rehearsal_tor_lossy``: rung 3's relay classes and weights,
2 authorities, 26 clients as 12 host groups over three vertices 5 / 20 / 60 ms
apart, a loss on every edge, self-loops too) as two lanes of the fleet engine
for 300 windows of 5 ms — packets lost, fast retransmits, RTOs and
out-of-order drops all live — held to the C++ reference counter for counter,
to the solo engine leaf for leaf and to the CPU oracle; (d) the cell in
miniature through the benchmark's own harness with its controls, and the two
new per-layer readers.
"""

import contextlib
import glob
import io
import json
import os
import time
import types
import warnings

import jax
import numpy as np
import pytest
import yaml

from shadow1_tpu.config.topology import compile_paths, load_graphml
from shadow1_tpu.consts import MS
from shadow1_tpu.core.engine import Engine, compact_cap_of
from shadow1_tpu.cpu_engine import CpuEngine
from shadow1_tpu.fleet.engine import (
    FleetEngine,
    fleet_metrics_per_exp,
    slice_experiment,
)
from shadow1_tpu.fleet.expand import expand_sweep
from shadow1_tpu.telemetry import chunk_log
from shadow1_tpu.telemetry.registry import CHUNK_LOSS_TOTALS
from tests.parity import PARITY_KEYS, lane_metrics, unlike_leaves

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REHEARSAL = os.path.join(ROOT, "tests", "rehearsal_tor_lossy")
CFG_DIR = os.path.join(REHEARSAL, "configs")
BENCH = os.path.join(ROOT, "benchmarks")
GEO = os.path.join(ROOT, "configs", "geo_tor1k.yaml")
RUNG3 = os.path.join(ROOT, "configs", "rung3_tor1k.yaml")
LOSSY = os.path.join(ROOT, "configs", "topology_6region_lossy.graphml")
CELL = "tor35.lossy300"
N_WINDOWS = 300
SEEDS = [600000007000, 600000007001]    # the cell's pool's first; past 2**32
MUST_BE_ZERO = ["ev_overflow", "ob_overflow", "round_cap_hits",
                "total_ct_overflow"]
TOR_KEYS = ("streams_done", "cells_rx", "bootstrap_time", "done_time",
            "cells_fwd", "ct_overflow", "cell_retries")
REGIONS = ["north_america", "europe", "south_america", "asia_pacific",
           "japan", "australia"]
SHARES = [0.3316, 0.4998, 0.0090, 0.1177, 0.0224, 0.0195]
# The GraphML files the repo had before this PR, under configs/ and
# benchmarks/configs/.
OLD_GRAPHML = ["configs/topology_2pop.graphml",
               "configs/topology_6region.graphml",
               "benchmarks/configs/topology_6region.graphml"]


# ---- (a) a self-loop's loss -------------------------------------------------------

def _old_rule(loss_vv):
    """``compile_paths``'s loss as it ended before this PR: the diagonal
    zeroed."""
    loss = loss_vv.copy()
    np.fill_diagonal(loss, 0.0)
    return loss


def test_a_self_loop_s_packetloss_reaches_the_diagonal_and_no_self_loop_keeps_0():
    """Three vertices: a self-loop with a loss, a self-loop without, no
    self-loop. Off the diagonal nothing moves."""
    inf = np.inf
    lat = np.array([[4.0, 10.0, inf], [10.0, 6.0, 20.0], [inf, 20.0, inf]]) * MS
    loss = np.array([[0.25, 0.1, 0.0], [0.1, 0.0, 0.2], [0.0, 0.2, 0.0]])
    lat_vv, loss_vv = compile_paths(lat, loss)
    assert np.allclose(np.diag(loss_vv), [0.25, 0.0, 0.0])
    assert np.diag(lat_vv).tolist() == [4 * MS, 6 * MS, 10 * MS]
    assert loss_vv[0, 1] == pytest.approx(0.1)
    assert loss_vv[0, 2] == pytest.approx(1 - 0.9 * 0.8)
    # A loss written on a diagonal entry that is no edge (no latency) is not
    # a self-loop's.
    loss[2, 2] = 0.5
    assert compile_paths(lat, loss)[1][2, 2] == 0.0
    # network.single_vertex always honoured its loss: the two now agree.
    from shadow1_tpu.config.compiled import single_vertex_experiment

    sv = single_vertex_experiment(n_hosts=2, seed=1, end_time=10 * MS,
                                  latency_ns=4 * MS, loss=0.25, model="phold",
                                  model_cfg={})
    assert float(np.asarray(sv.loss_vv)[0, 0]) == pytest.approx(loss_vv[0, 0])


@pytest.mark.parametrize("rel", OLD_GRAPHML)
def test_every_graphml_the_repo_had_compiles_to_the_loss_it_compiled_to(rel):
    """Each has no self-loop or no ``packetloss``, so the old rule (zero the
    diagonal) and the new one give the same tables: no experiment of any
    cell or test changed."""
    _, lat_e, loss_e, directed, prefer, _ = load_graphml(
        os.path.join(ROOT, rel))
    lat_vv, loss_vv = compile_paths(lat_e, loss_e, directed=directed,
                                    prefer_direct=prefer)
    assert np.array_equal(loss_vv, _old_rule(loss_vv)) and (lat_vv > 0).all()
    assert not (np.isfinite(np.diag(lat_e)).any() and loss_e.any())


def test_no_other_graphml_is_in_the_two_directories():
    have = sorted(os.path.relpath(p, ROOT) for d in ("configs", "benchmarks/configs")
                  for p in glob.glob(os.path.join(ROOT, d, "*.graphml")))
    assert have == sorted(OLD_GRAPHML + [
        "configs/topology_6region_lossy.graphml",
        "benchmarks/configs/topology_6region_lossy.graphml",
        # PR 50: the 200-city graph, one copy (tests/test_bitcoin_cities.py).
        "benchmarks/configs/topology_cities200.graphml"])


# ---- (b) the files ---------------------------------------------------------------

@pytest.mark.parametrize("user,bench", [
    ("configs/geo_tor1k.yaml", "benchmarks/configs/tor1k_regions.yaml"),
    ("configs/topology_6region_lossy.graphml",
     "benchmarks/configs/topology_6region_lossy.graphml")])
def test_the_benchmark_s_files_are_byte_copies_of_the_user_s(user, bench):
    with open(os.path.join(ROOT, user), "rb") as a, \
            open(os.path.join(ROOT, bench), "rb") as b:
        assert a.read() == b.read()


def test_the_lossy_graphml_is_the_six_regions_with_the_rule_s_21_losses():
    names, lat_e, loss_e, directed, prefer, _ = load_graphml(LOSSY)
    _, lat0, loss0, _, prefer0, _ = load_graphml(
        os.path.join(ROOT, "configs", "topology_6region.graphml"))
    assert names == REGIONS and not directed and prefer and prefer0
    assert np.array_equal(lat_e, lat0) and not loss0.any()
    edges = np.isfinite(lat_e)
    assert int(np.triu(edges).sum()) == 21 and edges.all()
    rule = np.round(0.015 * np.minimum(lat_e / MS, 300.0) / 300.0, 6)
    assert np.array_equal(loss_e, rule)
    assert loss_e.min() == 0.00055 == loss_e[1, 1] and loss_e.max() == 0.015
    assert int((np.triu(loss_e) == 0.015).sum()) == 3
    assert (loss_e[0, 0], loss_e[2, 2]) == (0.0016, 0.0044)
    lat_vv, loss_vv = compile_paths(lat_e, loss_e, prefer_direct=True)
    assert np.array_equal(loss_vv, loss_e.astype(np.float32))
    assert int(lat_vv.min()) == 11 * MS and int(lat_vv.max()) == 325 * MS


def _largest_remainder(n):
    q = [s * n for s in SHARES]
    out = [int(x) for x in q]
    for i in sorted(range(6), key=lambda i: -(q[i] - out[i]))[:n - sum(out)]:
        out[i] += 1
    return out


def test_the_experiment_is_rung_3_s_tor_network_on_25_host_groups():
    with open(GEO) as f:
        doc = yaml.safe_load(f)
    with open(RUNG3) as f:
        rung3 = yaml.safe_load(f)
    assert doc["general"] == rung3["general"]
    assert doc["engine"] == {**rung3["engine"], "ev_cap": 512,
                             "sockets_per_host": 128}
    assert doc["network"] == {"graphml": "topology_6region_lossy.graphml"}
    assert doc["app"]["params"] == rung3["app"]["params"]
    assert doc["app"]["defaults"] == rung3["app"]["defaults"]
    short = dict(zip(("na", "eu", "sa", "ap", "jp", "au"), REGIONS))
    want = []
    for g in rung3["hosts"]:
        for (tag, vertex), count in zip(short.items(),
                                        _largest_remainder(g["count"])):
            if count:
                want.append({**g, "name": f"{g['name']}_{tag}", "count": count,
                             "vertex": vertex})
    assert doc["hosts"] == want and len(want) == 25
    assert sum(g["count"] for g in want) == 1000
    assert list(doc["app"]["groups"]) == [g["name"] for g in want]
    for name, block in doc["app"]["groups"].items():
        assert block == rung3["app"]["groups"][name.rsplit("_", 1)[0]], name


def test_the_cell_s_files_state_what_the_issue_fixed():
    with open(os.path.join(BENCH, "configs", "tor1k_regions.json")) as f:
        meta = json.load(f)
    with open(os.path.join(BENCH, "traffic", "lossy3s.json")) as f:
        mix = json.load(f)
    assert (meta["experiment"], meta["engine"], meta["architecture"]) == (
        "tor1k_regions.yaml", "fleet", None)
    assert meta["reduced"] == ["stop_time"] == list(meta["reduced_why"])
    assert meta["must_be_zero"] == MUST_BE_ZERO
    assert {k: mix[k] for k in ("lanes", "seed_pool_first", "overrides",
                                "chunk_windows", "cycle_windows",
                                "trace_from_window", "trace_chunks")} == {
        "lanes": 8, "seed_pool_first": 600000007000, "overrides": {},
        "chunk_windows": 5, "cycle_windows": 300, "trace_from_window": 250,
        "trace_chunks": 1}
    assert {"why", "placement", "relay_mix", "clients", "jitter", "ev_cap",
            "sockets_per_host", "ct_cap", "compact_cap", "lanes", "cycle",
            "walls", "provenance"} <= set(meta["assumed"])
    src = meta["from_the_source"]
    assert len(src["loss_by_edge"]) == 21 and "provenance" in src
    _, lat_e, loss_e, _, _, _ = load_graphml(LOSSY)
    for edge, p in src["loss_by_edge"].items():
        a, b = (REGIONS.index(v) for v in edge.split("-"))
        assert loss_e[a, b] == p, edge
    by_class = src["hosts_by_class_and_region"]
    assert by_class["order"] == REGIONS
    for name, n in (("guard", 30), ("middle", 60), ("exit", 30),
                    ("dirauth", 5), ("client", 875)):
        assert by_class[name] == _largest_remainder(n), name
    # The rehearsal is the cell's own mix at two lanes (and no prose).
    with open(os.path.join(REHEARSAL, "traffic", "lossy300.json")) as f:
        small = json.load(f)
    assert {**mix, "lanes": 2, "what": None} == {**small, "what": None}


def test_the_full_width_file_builds_a_fleet_of_eight_by_eval_shape():
    """The real file under the cell's eight seeds (config and shapes only, no
    state is made): V = 6, 25 runs of ``host_vertex`` (the dense route forms
    hold up to 32), an 11 ms window, the intra-region loss live, and
    509.4 MB of state (1,074.6 while the message boundaries were
    ``[64, 128, H]`` planes: a pool of 256 slots a host since PR 48)."""
    with open(GEO) as f:
        doc = yaml.safe_load(f)
    doc["sweep"] = {"seeds": [600000007000 + i for i in range(8)]}
    plan = expand_sweep(doc, base_dir=os.path.dirname(GEO))
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        eng = FleetEngine(plan.exps, plan.params, plan.max_rounds)
    exp = plan.exps[0]
    assert (eng.n_exp, exp.n_hosts, exp.window) == (8, 1000, 11 * MS)
    assert compact_cap_of(eng.params, exp.n_hosts) == 384
    assert (eng.params.ev_cap, eng.params.sockets_per_host,
            eng.params.msgq_cap, eng.params.max_rounds) == (512, 128, 64, 1024)
    hv = np.asarray(exp.host_vertex)
    assert 1 + int((np.diff(hv) != 0).sum()) == 25
    assert np.bincount(hv).tolist() == [332, 499, 9, 117, 23, 20]
    loss = np.asarray(exp.loss_vv)
    assert loss.shape == (6, 6) and float(loss.max()) == pytest.approx(0.015)
    assert np.allclose(np.diag(loss), [0.0016, 0.00055, 0.0044, 0.00425,
                                       0.0006, 0.0008])
    role = np.asarray(exp.model_cfg["role"])
    assert [int((role == r).sum()) for r in (0, 1, 2)] == [120, 875, 5]
    # Relay i of rung 3 is relay i here: the classes keep rung 3's order.
    assert np.asarray(exp.model_cfg["is_guard"])[:30].all()
    assert np.asarray(exp.model_cfg["is_exit"])[90:120].all()
    st = jax.eval_shape(eng.init_state)
    size = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(st))
    assert round(size / 1e6, 1) == 509.4
    assert st.model.tcp["mq_sock"].shape == (8, 256, 1000)
    assert st.compact_buckets.shape == (8,)


# ---- (c) the miniature: two lanes = the reference = the solo engine = the oracle --

def doc35(seeds=None):
    with open(os.path.join(CFG_DIR, "tor35.yaml")) as f:
        doc = yaml.safe_load(f)
    # 300 whole windows of 5 ms, so that run() with no count is the same run
    # on every engine.
    doc["general"]["stop_time"] = f"{N_WINDOWS * 5} ms"
    if seeds is not None:
        doc["sweep"] = {"seeds": list(seeds)}
    return doc


@pytest.fixture(scope="module")
def plan():
    return expand_sweep(doc35(SEEDS), base_dir=CFG_DIR)


@pytest.fixture(scope="module")
def fleet(plan):
    eng = FleetEngine(plan.exps, plan.params, plan.max_rounds)
    return eng, eng.run(n_windows=N_WINDOWS)


def lane_counters(eng, st, lane):
    return {**eng.model_totals(st)[lane], **fleet_metrics_per_exp(st)[lane]}


def test_the_miniature_has_the_deployment_s_shape(plan):
    with open(RUNG3) as f:
        rung3 = yaml.safe_load(f)
    small = doc35()
    for name, block in small["app"]["groups"].items():
        assert block == rung3["app"]["groups"][name.rsplit("_", 1)[0]], name
    assert small["app"]["defaults"] == rung3["app"]["defaults"]
    exp = plan.exps[0]
    assert exp.window == 5 * MS and exp.end_time == N_WINDOWS * 5 * MS
    assert np.array_equal(np.asarray(exp.lat_vv),
                          np.array([[5, 20, 60], [20, 5, 60], [60, 60, 20]]) * MS)
    loss = np.asarray(exp.loss_vv)
    assert (loss > 0).all() and np.allclose(np.diag(loss), [0.02, 0.02, 0.03])
    hv = np.asarray(exp.host_vertex)
    assert np.bincount(hv).tolist() == [14, 13, 8]
    assert 1 + int((np.diff(hv) != 0).sum()) == 12
    role = np.asarray(exp.model_cfg["role"])
    assert [int((role == r).sum()) for r in (0, 1, 2)] == [7, 26, 2]
    assert compact_cap_of(plan.params, exp.n_hosts) == 8


@pytest.mark.parametrize("lane", range(len(SEEDS)))
def test_a_lane_equals_the_reference_with_loss_recovery_live(fleet, plan, lane):
    """Counter for counter, and in the stretch compared packets are lost,
    fast retransmits and RTOs start, and the Go-Back-N receiver drops what
    arrives past a hole — in every lane."""
    from benchmarks.reference import comparator

    eng, st = fleet
    ref = comparator.counters(plan.exps[lane], eng.params, SEEDS[lane], N_WINDOWS)
    have = lane_counters(eng, st, lane)
    compared = {k: (have.get(k), v) for k, v in ref.items()
                if k not in comparator.NOT_COUNTERS}
    assert len(compared) >= 18, sorted(compared)
    assert {"pkts_lost", "tcp_fast_rtx", "tcp_rto", "tcp_ooo_drops",
            "total_streams_done", "total_cells_fwd"} <= set(compared)
    assert all(a == b for a, b in compared.values()), compared
    assert have["pkts_lost"] > 20 and have["tcp_fast_rtx"] > 0
    assert have["tcp_rto"] > 0 and have["tcp_ooo_drops"] > 10
    assert have["total_streams_done"] > 3
    assert all(have[k] == 0 for k in (*MUST_BE_ZERO, "mq_overflow"))


def test_the_loss_inside_a_vertex_is_live(fleet, plan):
    """Under the old rule (the diagonal zeroed) the same file is another
    simulation: the reference handed that network ends on other counters
    than the lane, from the packets lost on."""
    import dataclasses

    from benchmarks.reference import comparator

    eng, st = fleet
    exp = plan.exps[0]
    loss = np.asarray(exp.loss_vv).copy()
    np.fill_diagonal(loss, 0.0)
    old = comparator.counters(dataclasses.replace(exp, loss_vv=loss),
                              eng.params, SEEDS[0], N_WINDOWS)
    have = lane_counters(eng, st, 0)
    assert all(old[k] != have[k] for k in ("pkts_lost", "events", "pkts_sent"))


def test_a_lane_equals_the_solo_engine_leaf_for_leaf(fleet, plan):
    eng, st = fleet
    solo = Engine(plan.exps[1], eng.params)
    want = solo.run(n_windows=N_WINDOWS)
    assert not unlike_leaves(slice_experiment(st, 1), want)
    assert lane_metrics(fleet_metrics_per_exp(st)[1]) \
        == lane_metrics(Engine.metrics_dict(want))
    assert eng.model_totals(st)[1] == solo.model_totals(want)


@pytest.mark.parametrize("lane", range(len(SEEDS)))
def test_a_lane_equals_the_cpu_oracle(fleet, plan, lane):
    eng, st = fleet
    cpu = CpuEngine(plan.exps[lane], plan.params)
    cm, cs = cpu.run(), cpu.summary()
    have = fleet_metrics_per_exp(st)[lane]
    assert {k: have[k] for k in PARITY_KEYS} == {k: cm[k] for k in PARITY_KEYS}
    got = eng.model_summary(st, lane)
    for k in TOR_KEYS:
        assert np.array_equal(np.asarray(got[k]), np.asarray(cs[k])), k


def test_the_two_lanes_differ(fleet):
    _, st = fleet
    a, b = fleet_metrics_per_exp(st)
    assert all(a[k] != b[k] for k in ("events", "pkts_sent", "pkts_lost",
                                      "tcp_ooo_drops"))


# ---- (d) the cell in miniature through the benchmark's harness -------------------

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


def test_the_cell_in_miniature_is_correct_in_every_lane(fleet):
    rc, res, lanes = _bench(3_000_000_019)
    assert rc == 0 and res["correct"] is True
    assert (res["attempted"], res["failed"]) == (2, 0)
    assert sorted(ln["seed"] for ln in lanes) == SEEDS
    for ln in lanes:
        assert ln["ok"] and ln["limit"] == 0 and not ln["must_be_zero"]
        assert ln["windows"] == N_WINDOWS and ln["seed"] == ln["reference_seed"]
        have = lane_counters(fleet[0], fleet[1], SEEDS.index(ln["seed"]))
        assert all(a == b == have[k]
                   for k, (a, b) in ln["engine_vs_reference"].items())
        assert ln["engine_vs_reference"]["tcp_fast_rtx"][0] > 0
    assert all(res["compared"][k + ".must_be_zero"] == [0, 0] for k in MUST_BE_ZERO)
    assert set(res["metrics"]) == {"events_per_s", "peak_hbm_mb", "setup_s"}


@pytest.mark.parametrize("control", ["wrong_seed", "small_caps"])
def test_the_cell_in_miniature_under_a_control_is_not_correct(control):
    """The reference under the next seed draws other losses and other relays;
    with ``ev_cap`` 20 the authorities drop events."""
    rc, res, lanes = _bench(11, "--control", control)
    assert rc == 0 and res["correct"] is False and res["failed"] == 2
    assert all("events" in ln["differ"] and not ln["ok"] for ln in lanes)
    if control == "wrong_seed":
        assert all(ln["reference_seed"] == ln["seed"] + 1 for ln in lanes)
        assert all("pkts_lost" in ln["differ"] for ln in lanes)
        assert not any(ln["must_be_zero"] for ln in lanes)
    else:
        assert all(ln["must_be_zero"].get("ev_overflow") for ln in lanes)
        assert res["compared"]["ev_overflow.must_be_zero"][0] > 0


# ---- (e) the two new readers ------------------------------------------------------

@pytest.fixture()
def traced_rows(fleet, plan):
    """The chunk log after what a traced run of the miniature leaves in it: a
    warm-up chunk, the cycle's sixty, the replay of windows 0-255 (one row of
    250 windows, five of one). Gives the metrics at windows 250 and 255."""
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
        if done in (250, 255):
            at[done] = jax.device_get(st.metrics)
        st = loop.run_chunk(sim, st, 5)
    loop._replay_rounds(sim, {"traced": (250, 255)}, at[255])
    yield types.SimpleNamespace(m250=at[250], m255=at[255])
    log.clear()


def _reader(name):
    from benchmarks.harness import manifest as mf

    m = mf.load(REHEARSAL)
    assert name in [e["name"] for e in mf.metrics_of(m, "per_layer", CELL)]
    return mf.reader(REHEARSAL, m, "layer_metrics", name)


def test_retransmits_per_kpkt_reads_the_traced_chunk_off_the_chunk_log(
        traced_rows, monkeypatch):
    read = _reader("retransmits_per_kpkt")
    counters = {"chunks": 1, "windows": 5, "rounds": 1, "lanes": 2}
    m250, m255 = traced_rows.m250, traced_rows.m255

    def delta(k):
        return int(np.sum(getattr(m255, k)) - np.sum(getattr(m250, k)))

    assert delta("pkts_sent") > 0
    assert read(None, counters, {}) == pytest.approx(
        1000.0 * (delta("tcp_fast_rtx") + delta("tcp_rto")) / delta("pkts_sent"))
    # Every row of a real engine's chunk carries the five totals.
    log = chunk_log()
    rows = log.rows()
    assert all(set(CHUNK_LOSS_TOTALS) <= set(r) for r in rows)
    # No traced chunk in the counters, or a stretch this log has no rows of.
    assert read(None, {"chunks": 0, "windows": 0}, {}) is None
    assert read(None, {"chunks": 2, "windows": 20}, {}) is None
    # The parent's rows (PR 43's to 46's): the totals of work, none of loss.
    monkeypatch.setattr(log, "rows", lambda wait_s=1.0: [
        {k: v for k, v in r.items() if k not in CHUNK_LOSS_TOTALS} for r in rows])
    assert _reader("events_per_round")(None, counters, {}) is not None
    assert read(None, counters, {}) is None
    # A stretch in which nothing was sent has no rate.
    monkeypatch.setattr(log, "rows", lambda wait_s=1.0: [
        {**r, "pkts_sent": 7} for r in rows])
    assert read(None, counters, {}) is None


def test_push_commit_trips_per_round_reads_the_traced_chunk_off_the_chunk_log(
        traced_rows, monkeypatch):
    """PR 49's reader: the commits' trips a round over the traced stretch,
    a lane's own summed over the lanes; under one where most rounds stage
    nothing; nothing from the parent's rows, which lack the total."""
    read = _reader("push_commit_trips_per_round")
    counters = {"chunks": 1, "windows": 5, "rounds": 1, "lanes": 2}
    m250, m255 = traced_rows.m250, traced_rows.m255

    def delta(k):
        return int(np.sum(getattr(m255, k)) - np.sum(getattr(m250, k)))

    assert 0 < delta("push_commit_trips") < delta("rounds")
    assert read(None, counters, {}) == pytest.approx(
        delta("push_commit_trips") / delta("rounds"))
    assert read(None, {"chunks": 0, "windows": 0}, {}) is None
    log = chunk_log()
    rows = log.rows()
    assert all("push_commit_trips" in r for r in rows)
    monkeypatch.setattr(log, "rows", lambda wait_s=1.0: [
        {k: v for k, v in r.items() if k != "push_commit_trips"} for r in rows])
    assert _reader("events_per_round")(None, counters, {}) is not None
    assert read(None, counters, {}) is None
    # The real manifest lists the cells whose rows the stretch is read off.
    from benchmarks.harness import manifest as mf

    (entry,) = [e for e in mf.load(ROOT)["per_layer"]
                if e["name"] == "push_commit_trips_per_round"]
    assert entry == {
        "name": "push_commit_trips_per_round", "unit": "count",
        "better": "lower", "source": "program_counter",
        "layer": "window program", "moves": "events_per_s",
        "workloads": ["tor1k.seeds8", "tor10k.join", "tor1k_regions.lossy3s"]}


def test_timer_ms_per_round_reads_the_timer_pass_s_row_of_the_roll_up():
    read = _reader("timer_ms_per_round")
    rollup = {"handlers": 0.5, "h_deliver": 0.3, "h_timer": 0.04}
    assert read(None, {"phase_s": rollup, "rounds": 80}, {}) == pytest.approx(0.5)
    # A TCP program whose guarded timer pass ran no op in the stretch (no
    # deadline was due: tor1k.seeds8's windows 20-25) spent 0 on it; a
    # program with no TCP pass, no roll-up at all or no rounds say nothing.
    assert read(None, {"phase_s": {"handlers": 0.5, "h_deliver": 0.3},
                       "rounds": 80}, {}) == 0.0
    assert read(None, {"phase_s": {"handlers": 0.5, "h_phold": 0.5},
                       "rounds": 80}, {}) is None
    assert read(None, {"rounds": 80}, {}) is None
    assert read(None, {"phase_s": rollup, "rounds": 0}, {}) is None
    # The real manifest lists the three cells that ran the pass when the
    # metric came, first (a later cell is appended: a prefix, not the list).
    from benchmarks.harness import manifest as mf

    m = mf.load(ROOT)
    (entry,) = [e for e in m["per_layer"] if e["name"] == "timer_ms_per_round"]
    assert entry["workloads"][:3] == ["tgen100.seeds32", "tor1k.seeds8",
                                      "tor1k_regions.lossy3s"]
    (entry,) = [e for e in m["per_layer"] if e["name"] == "retransmits_per_kpkt"]
    assert entry["workloads"][:2] == ["tor1k.seeds8", "tor1k_regions.lossy3s"]


def test_the_retransmission_paths_have_scopes_of_their_own(fleet):
    """``phase:tcp_fast_rtx`` inside the deliver pass, ``phase:tcp_rto``
    inside the timer pass: rows of the phase table, nested where they
    stand."""
    from shadow1_tpu.telemetry import phases

    paths = set(phases.phase_table(fleet[0].hlo_text()).values())
    assert any(p.endswith("h_timer/tcp_rto") for p in paths), sorted(paths)[:40]
    assert any("h_deliver" in p and p.endswith("tcp_fast_rtx") for p in paths)
    # Every op under them rolls up into its pass's row (the scalar bodies
    # of their reductions name the scope alone, as ``tcp_flush``'s do: no
    # device op).
    mine = [p for p in paths if "tcp_rto" in p or "tcp_fast_rtx" in p]
    assert {"tcp_flush", "tcp_rto", "tcp_fast_rtx"} <= paths
    assert {phases.rollup_key(p) for p in mine if "/" in p} == {
        (phases.HANDLERS, "h_timer"), (phases.HANDLERS, "h_deliver")}
