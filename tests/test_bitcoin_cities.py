"""Bitcoin on a Shadow-format Internet graph of 200 city vertices:
``bitcoin5k_cities`` (``configs/geo_bitcoin5k_cities.yaml``) and its cell
``bitcoin5k_cities.lossyflood6s``.

(a) the seeded topology generator and the one file it left
(``benchmarks/configs/topology_cities200.graphml``); (b) what the loader reads
of a GraphML and what it says of the rest, and a host group's placement by
``countrycode``; (c) what RUNS is the deployment in miniature
(``tests/rehearsal_bitcoin_cities``: the 200-city file itself, two hosts a
city, 16 transactions from 300 ms) as two lanes of the fleet engine for 100
windows of 11 ms — ``host_vertex[dst]`` a lookup per outbox row, the path
tables read per host row and picked per slot (PR 51), packets lost, RTOs and out-of-order drops live — held to the solo engine leaf for
leaf, to the CPU oracle and to the C++ reference counter for counter, with
``Metrics.route_rows``; (d) the new per-layer reader; (e) the cell in
miniature through the benchmark's own harness, and under ``wrong_seed``.
"""

import contextlib
import hashlib
import io
import json
import os
import time
import types
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import yaml

from shadow1_tpu.config.experiment import build_experiment, load_experiment
from shadow1_tpu.config.topology import load_graphml
from shadow1_tpu.consts import MS
from shadow1_tpu.core.engine import (
    MAX_DENSE_VERTICES,
    MAX_ROW_VERTICES,
    MAX_VERTEX_RUNS,
    Engine,
    route_outbox,
)
from shadow1_tpu.cpu_engine import CpuEngine
from shadow1_tpu.fleet.engine import (
    FleetEngine,
    fleet_metrics_per_exp,
    slice_experiment,
)
from shadow1_tpu.fleet.expand import expand_sweep
from shadow1_tpu.telemetry import chunk_log
from shadow1_tpu.telemetry.registry import (
    CHUNK_ROUTE_TOTALS,
    LANE_PROGRAM_FIELDS,
    METRIC_SPECS,
)
from shadow1_tpu.tools import topogen
from tests.parity import PARITY_KEYS, lane_metrics, unlike_leaves
from tests.test_tor_fleet import _named_eqns

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REHEARSAL = os.path.join(ROOT, "tests", "rehearsal_bitcoin_cities")
CFG_DIR = os.path.join(REHEARSAL, "configs")
BACKBONE = os.path.join(ROOT, "configs", "topology_6region.graphml")
CITIES = os.path.join(ROOT, "benchmarks", "configs",
                      "topology_cities200.graphml")
USER = os.path.join(ROOT, "configs", "geo_bitcoin5k_cities.yaml")
CITIES_SHA = "c0af3ee50e25bfaee6e98092111603f3098d6835765940db476eeee3fd724b45"
PER_REGION = [66, 98, 4, 24, 4, 4]
PREFIXES = ["na", "eu", "sa", "ap", "jp", "au"]
REGIONS = ["north_america", "europe", "south_america", "asia_pacific",
           "japan", "australia"]
CELL = "bitcoin400_cities.lossyflood2"
N_WINDOWS = 100
SEEDS = [600000008000, 600000008001]    # the cell's pool; past 2**32
TABLE_KEYS = ("seen", "seen_time", "tx_rx", "reach", "msg_retries")


# ---- (a) the generator and its one file ------------------------------------------

def test_the_generator_is_byte_stable_and_its_seed_is_its_only_draw():
    small = dict(cities=[2, 3, 1, 1, 1, 1], access_ms=10)
    a = topogen.generate(BACKBONE, seed=7, **small)
    assert a == topogen.generate(BACKBONE, seed=7, **small)
    assert a != topogen.generate(BACKBONE, seed=8, **small)
    # Default prefixes are the region ids' initials; a comment may hold no
    # double hyphen, so the header spells the arguments without their flags.
    assert '<node id="na00">' in a and '<node id="ja00">' in a
    assert "--" not in a.split("-->")[0].split("<!--")[1]


def test_the_committed_arguments_give_the_committed_file():
    text = topogen.generate(BACKBONE, PER_REGION, seed=50, access_ms=10,
                            prefixes=PREFIXES)
    with open(CITIES, "rb") as f:
        have = f.read()
    assert text.encode() == have
    assert hashlib.sha256(have).hexdigest() == CITIES_SHA


def test_the_command_line_is_the_one_the_user_s_file_gives(capsys):
    with open(USER) as f:
        header = " ".join(ln.lstrip("# ").rstrip("\\").strip()
                          for ln in f.read().split("general:")[0].splitlines())
    argv = header.split("shadow1_tpu.tools.topogen ")[1].split(" > ")[0].split()
    argv[0] = os.path.join(ROOT, argv[0])
    assert topogen.main(argv) == 0
    assert hashlib.sha256(capsys.readouterr().out.encode()).hexdigest() \
        == CITIES_SHA


@pytest.mark.parametrize("kw,match", [
    (dict(cities=[2, 3, 1]), "6 regions need as many"),
    (dict(cities=[2, 3, 1, 1, 1, 0]), "each at least 1"),
    (dict(cities=[1] * 6, prefixes=["a"] * 6), "share a prefix"),
], ids=["too_few_counts", "an_empty_region", "one_prefix_twice"])
def test_the_generator_refuses_arguments_that_cannot_be_meant(kw, match):
    with pytest.raises(ValueError, match=match):
        topogen.generate(BACKBONE, seed=1, **kw)


@pytest.fixture(scope="module")
def graph():
    with warnings.catch_warnings():
        warnings.simplefilter("error")      # it carries nothing unread
        return load_graphml(CITIES)


def test_the_file_is_complete_and_every_latency_and_loss_obeys_the_rule(graph):
    names, lat_e, loss_e, directed, prefer, codes = graph
    assert len(names) == 200 and not directed and prefer
    assert names[0] == "na00" and names[65] == "na65" and names[66] == "eu00"
    assert names[-1] == "au03" and codes == list(np.repeat(REGIONS, PER_REGION))
    assert np.isfinite(lat_e).all()             # complete, self-loops too
    assert int(np.triu(np.isfinite(lat_e)).sum()) == 20_100
    backbone = load_graphml(BACKBONE)
    assert backbone.vertex_ids == REGIONS and backbone.countrycodes == [None] * 6
    big = backbone.lat_e / MS
    region = np.repeat(np.arange(6), PER_REGION)
    access = np.random.default_rng(50).integers(0, 11, size=200)
    want = big[region[:, None], region[None, :]] \
        + access[:, None] + access[None, :]
    np.fill_diagonal(want, big[region, region])
    assert np.array_equal(lat_e / MS, want)
    assert lat_e.min() == 11 * MS and lat_e.max() == 342 * MS
    assert (np.diag(lat_e)[66:164] == 11 * MS).all()         # europe's loops
    assert np.array_equal(
        loss_e, np.round(0.015 * np.minimum(lat_e / MS, 300.0) / 300.0, 6))
    assert loss_e.min() == 0.00055 and loss_e.max() == 0.015


# ---- (b) what the loader reads, and placement by hint -------------------------------

GRAPHML = """<?xml version="1.0" encoding="UTF-8"?>
<graphml xmlns="http://graphml.graphdrawing.org/xmlns">
  <key id="d0" for="edge" attr.name="latency" attr.type="double"/>
  <key id="d1" for="edge" attr.name="packetloss" attr.type="double"/>
  <key id="d2" for="edge" attr.name="jitter" attr.type="double"/>
  <key id="d3" for="node" attr.name="countrycode" attr.type="string"/>
  <key id="d4" for="node" attr.name="bandwidthup" attr.type="int"/>
  <key id="d5" for="node" attr.name="packetloss" attr.type="double"/>
  <key id="d6" for="graph" attr.name="preferdirectpaths" attr.type="string"/>
  <graph id="G" edgedefault="undirected">
    <data key="d6">True</data>
    <node id="a"><data key="d3">US</data>{extra}</node>
    <node id="b"><data key="d3">DE</data></node>
    <node id="c"><data key="d3">US</data></node>
    <edge source="a" target="a"><data key="d0">4.0</data></edge>
    <edge source="a" target="b"><data key="d0">30.0</data><data key="d1">0.01</data>{jitter}</edge>
    <edge source="a" target="c"><data key="d0">9.0</data></edge>
    <edge source="b" target="c"><data key="d0">35.0</data></edge>
  </graph>
</graphml>
"""


def test_the_loader_names_once_what_a_file_carries_and_nothing_reads(tmp_path):
    path = tmp_path / "upstream_like.graphml"
    path.write_text(GRAPHML.format(
        extra='<data key="d4">1024</data><data key="d5">0.0</data>',
        jitter='<data key="d2">2.5</data>'))
    with pytest.warns(UserWarning) as seen:
        g = load_graphml(str(path))
    assert len(seen) == 1
    said = str(seen[0].message)
    assert "edge jitter, vertex bandwidthup, vertex packetloss" in said
    assert "latency" not in said and "countrycode" not in said
    assert g.countrycodes == ["US", "DE", "US"] and g.prefer_direct
    assert g.loss_e[0, 1] == 0.01 and g.lat_e[0, 0] == 4 * MS
    path.write_text(GRAPHML.format(extra="", jitter=""))
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert load_graphml(str(path)).countrycodes == ["US", "DE", "US"]


def doc400(seeds=None):
    with open(os.path.join(CFG_DIR, "bitcoin400_cities.yaml")) as f:
        doc = yaml.safe_load(f)
    doc["general"]["stop_time"] = f"{N_WINDOWS * 11} ms"
    if seeds is not None:
        doc["sweep"] = {"seeds": list(seeds)}
    return doc


def _placed(tmp_path, vertex, count=5):
    path = tmp_path / "three.graphml"
    path.write_text(GRAPHML.format(extra="", jitter=""))
    doc = {"general": {"stop_time": "1 s"},
           "network": {"graphml": str(path)},
           "hosts": [{"name": "first", "count": 2, "vertex": "b"},
                     {"name": "dealt", "count": count, "vertex": vertex}],
           "app": {"model": "phold"}}
    return build_experiment(doc, base_dir=str(tmp_path))[0].host_vertex


def test_a_hint_deals_a_group_round_robin_over_its_code_s_vertices_in_file_order(
        tmp_path):
    hv = _placed(tmp_path, {"spread": {"countrycode": "US"}})
    assert hv.tolist() == [1, 1, 0, 2, 0, 2, 0]       # a, c, a, c, a
    assert _placed(tmp_path, {"spread": {"countrycode": "DE"}}, 3).tolist() \
        == [1, 1, 1, 1, 1]
    assert _placed(tmp_path, "spread", 4).tolist() == [1, 1, 0, 1, 2, 0]


@pytest.mark.parametrize("vertex,match", [
    ({"spread": {"countrycode": "FR"}},
     r"hosts\[dealt\].vertex: no vertex has countrycode 'FR'.*carry \['DE', 'US'\]"),
    ({"spread": {"citycode": "US"}}, r"hosts\[dealt\].vertex: a mapping must be"),
    ({"countrycode": "US"}, r"hosts\[dealt\].vertex: a mapping must be"),
], ids=["unknown_code", "another_hint", "no_spread"])
def test_a_hint_that_cannot_be_followed_is_a_config_error_naming_the_group(
        tmp_path, vertex, match):
    with pytest.raises(AssertionError, match=match):
        _placed(tmp_path, vertex)


def test_a_hint_on_a_file_whose_vertices_carry_no_code_is_refused():
    doc = doc400()
    doc["network"]["graphml"] = BACKBONE
    refusal = (r"hosts\[na\].vertex: no vertex has countrycode "
               r"'north_america' \(the topology's vertices carry no countrycode\)")
    with pytest.raises(AssertionError, match=refusal):
        build_experiment(doc, base_dir=CFG_DIR)
    del doc["network"]                   # one vertex, no file at all
    with pytest.raises(AssertionError, match=refusal):
        build_experiment(doc, base_dir=CFG_DIR)


def test_the_user_s_file_is_the_regional_one_on_the_city_graph_at_real_width():
    """Loaded, not run: 5,000 hosts in SimBlock's shares, dealt over their
    regions' cities, on tables of 200 vertices; the benchmark's copy differs
    in the topology's path alone, and from ``general:`` down both are
    ``geo_bitcoin5k.yaml`` but for that path and the six ``vertex:``."""
    exp, params, _ = load_experiment(USER)
    assert exp.n_hosts == 5000 and exp.lat_vv.shape == (200, 200)
    assert exp.window == 11 * MS and int(exp.lat_vv.max()) == 342 * MS
    hv = np.asarray(exp.host_vertex)
    assert hv[:67].tolist() == [*range(66), 0]
    assert hv[1658:1660].tolist() == [66, 67]
    per_city = np.bincount(hv, minlength=200)
    firsts = np.cumsum([0, *PER_REGION])
    assert [int(per_city[a:b].sum()) for a, b in zip(firsts, firsts[1:])] \
        == [1658, 2499, 45, 588, 112, 98]
    assert per_city.min() == 11 and per_city.max() == 28
    assert int((np.diff(hv) != 0).sum()) + 1 == 5000 > MAX_VERTEX_RUNS
    assert (params.ev_cap, params.outbox_cap, params.sockets_per_host) \
        == (96, 64, 32)

    def body(rel):
        with open(os.path.join(ROOT, rel)) as f:
            return f.read()

    user, bench = body("configs/geo_bitcoin5k_cities.yaml"), \
        body("benchmarks/configs/bitcoin5k_cities.yaml")
    assert user.replace("../benchmarks/configs/topology_cities200.graphml",
                        "topology_cities200.graphml") == bench
    regional = "general:" + body("configs/geo_bitcoin5k.yaml").split("general:")[1]
    for region in REGIONS:
        regional = regional.replace(
            f"vertex: {region},",
            f"vertex: {{spread: {{countrycode: {region}}}}},")
    assert regional.replace("topology_6region.graphml",
                            "topology_cities200.graphml") \
        == "general:" + bench.split("general:")[1]


# ---- (c) 400 nodes, two a city ---------------------------------------------------------

@pytest.fixture(scope="module")
def plan():
    return expand_sweep(doc400(SEEDS), base_dir=CFG_DIR)


@pytest.fixture(scope="module")
def fleet(plan):
    eng = FleetEngine(plan.exps, plan.params, plan.max_rounds)
    return eng, eng.run(n_windows=N_WINDOWS)


@pytest.fixture(scope="module")
def solo(plan):
    eng = Engine(plan.exps[0], plan.params)
    return eng, eng.run(n_windows=N_WINDOWS)


def lane_counters(eng, st, lane):
    return {**eng.model_totals(st)[lane], **fleet_metrics_per_exp(st)[lane]}


def test_the_miniature_is_on_the_city_graph_two_hosts_a_city(plan):
    exp = plan.exps[0]
    assert exp.n_hosts == 400 and exp.lat_vv.shape == (200, 200)
    assert exp.window == 11 * MS and exp.end_time == N_WINDOWS * 11 * MS
    assert np.bincount(exp.host_vertex).tolist() == [2] * 200
    assert 0.00054 < float(np.asarray(exp.loss_vv).min()) \
        and float(np.asarray(exp.loss_vv).max()) == pytest.approx(0.015)
    assert all(np.array_equal(e.host_vertex, exp.host_vertex)
               and np.array_equal(e.lat_vv, exp.lat_vv) for e in plan.exps)


@pytest.mark.parametrize("lanes", [0, 2], ids=["solo", "vmap2"])
def test_only_host_vertex_of_dst_is_read_with_an_index_per_row(solo, lanes):
    """Past ``MAX_VERTEX_RUNS`` runs ``host_vertex[dst]`` stays a lookup with
    an index per outbox row, the only one ``route_outbox`` traces: on 200
    vertices (past ``MAX_DENSE_VERTICES``, within ``MAX_ROW_VERTICES``) the
    path tables are read per HOST row, as a product with a one-hot of the
    hosts' vertices, and picked per slot by a masked sum — no ``gather``
    under ``phase:route_path``, alone and under the fleet's ``vmap``."""
    eng, st = solo
    ctx = eng.ctx
    assert ctx.vertex_runs is None
    assert MAX_DENSE_VERTICES < ctx.lat_vv.shape[0] == 200 <= MAX_ROW_VERTICES
    route, ob = (lambda o: route_outbox(ctx, o)), st.outbox
    if lanes:
        route = jax.vmap(route)
        ob = jax.tree_util.tree_map(lambda x: jnp.stack([x] * lanes), ob)
    eqns = list(_named_eqns(jax.make_jaxpr(route)(ob).jaxpr))
    gathers = [(e, stack) for e, stack in eqns if e.primitive.name == "gather"]
    assert len(gathers) == 1
    (e, stack), = gathers
    assert e.outvars[0].aval.size == ob.dst.size
    assert "vertex_of" in {f.function_name
                           for f in e.source_info.traceback.frames}
    assert "phase:route_vertex" in stack and "phase:route_path" not in stack
    # The guard can see the path reads: a product per table under the scope.
    assert sum(e.primitive.name == "dot_general" and "phase:route_path" in stack
               for e, stack in eqns) == 2


@pytest.mark.parametrize("lane", range(len(SEEDS)))
def test_a_lane_equals_the_cpu_oracle_and_the_cpp_reference(fleet, plan, lane):
    from benchmarks.reference import comparator

    eng, st = fleet
    have = lane_counters(eng, st, lane)
    cpu = CpuEngine(plan.exps[lane], plan.params)
    cm, cs = cpu.run(), cpu.summary()
    assert {k: have[k] for k in PARITY_KEYS} == {k: cm[k] for k in PARITY_KEYS}
    got = eng.model_summary(st, lane)
    for k in TABLE_KEYS:
        assert np.array_equal(got[k], np.asarray(cs[k])), k
    ref = comparator.counters(plan.exps[lane], plan.params, SEEDS[lane],
                              N_WINDOWS)
    compared = {k: (have.get(k), v) for k, v in ref.items()
                if k not in comparator.NOT_COUNTERS}
    assert len(compared) >= 15 and {"total_seen", "pkts_lost"} <= set(compared)
    assert all(a == b for a, b in compared.values()), compared
    assert have["windows"] == N_WINDOWS and have["pkts_lost"] > 20
    assert min(have[k] for k in ("tcp_rto", "tcp_fast_rtx", "tcp_ooo_drops")) > 5
    assert have["total_seen"] > 2000
    assert have["ev_overflow"] == have["ob_overflow"] == 0
    assert have["round_cap_hits"] == have["mq_overflow"] == 0


def test_a_lane_equals_its_solo_run_leaf_for_leaf(fleet, solo):
    eng, st = fleet
    solo_eng, want = solo
    assert not unlike_leaves(slice_experiment(st, 0), want)
    assert lane_metrics(fleet_metrics_per_exp(st)[0]) \
        == lane_metrics(Engine.metrics_dict(want))
    assert eng.model_totals(st)[0] == solo_eng.model_totals(want)


def test_the_two_lanes_lose_other_packets(fleet):
    _, st = fleet
    a, b = fleet_metrics_per_exp(st)
    assert all(a[k] != b[k] for k in ("events", "pkts_sent", "pkts_lost"))


def test_route_rows_is_the_outbox_rows_of_the_window_ends_that_ran(fleet, solo,
                                                                  plan):
    """``outbox_cap × hosts`` in each executed window end, per lane, the
    same in every lane (the program's count) and reported once by the
    aggregate; a solo run counts its own executed window ends."""
    _, st = fleet
    rows = plan.params.outbox_cap * plan.exps[0].n_hosts
    lanes = fleet_metrics_per_exp(st)
    for ln in lanes:
        assert 0 < ln["runs_window_end"] < N_WINDOWS     # quiet windows skip
        assert ln["route_rows"] == rows * ln["runs_window_end"]
    assert FleetEngine.metrics_dict(st)["route_rows"] == lanes[0]["route_rows"]
    sm = Engine.metrics_dict(solo[1])
    assert sm["route_rows"] == rows * sm["runs_window_end"]
    assert sm["runs_window_end"] <= lanes[0]["runs_window_end"]
    assert "route_rows" in LANE_PROGRAM_FIELDS
    assert METRIC_SPECS["route_rows"][0] == "counter"
    assert "route_rows" not in PARITY_KEYS
    assert CHUNK_ROUTE_TOTALS == ("route_rows",)


# ---- (d) the new reader -----------------------------------------------------------------

@pytest.fixture()
def traced_rows(fleet, plan):
    """The chunk log after what a traced run of the miniature leaves in it: a
    warm-up chunk, the cycle's ten, the replay of windows 0-90 (one row of 80
    windows, ten of one). Gives the metrics at windows 80 and 90."""
    from benchmarks.harness import loop
    from benchmarks.harness import sim as simmod

    eng = fleet[0]
    sim = simmod.Sim(eng, plan.exps, plan.params, True)
    log = chunk_log()
    log.clear()
    log.enabled = True
    loop.run_chunk(sim, eng.init_state(), 10)
    st, at = eng.init_state(), {}
    for done in range(0, N_WINDOWS, 10):
        if done in (80, 90):
            at[done] = jax.device_get(st.metrics)
        st = loop.run_chunk(sim, st, 10)
    loop._replay_rounds(sim, {"traced": (80, 90)}, at[90])
    yield types.SimpleNamespace(first=at[80], after=at[90])
    log.clear()


def _reader(name):
    from benchmarks.harness import manifest as mf

    m = mf.load(REHEARSAL)
    assert name in [e["name"] for e in mf.metrics_of(m, "per_layer", CELL)]
    return mf.reader(REHEARSAL, m, "layer_metrics", name)


def test_route_rows_useful_share_reads_the_traced_chunk_off_the_chunk_log(
        traced_rows, plan, monkeypatch):
    read = _reader("route_rows_useful_share")
    counters = {"chunks": 1, "windows": 10, "rounds": 1, "lanes": 2}

    def delta(k):
        return int(np.sum(getattr(traced_rows.after, k))
                   - np.sum(getattr(traced_rows.first, k)))

    rows = plan.params.outbox_cap * plan.exps[0].n_hosts
    assert delta("pkts_sent") > 0 and delta("route_rows") % (2 * rows) == 0
    got = read(None, counters, {})
    assert got == pytest.approx(100.0 * delta("pkts_sent") / delta("route_rows"))
    assert 0 < got < 100
    # Bitcoin's rows serve PR 47's reader too (PERF.md 7a9 doubted it).
    assert _reader("retransmits_per_kpkt")(None, counters, {}) is not None
    log = chunk_log()
    real = log.rows()
    assert all("route_rows" in r for r in real)
    assert read(None, {"chunks": 0, "windows": 0}, {}) is None
    # The parent's rows: every total but this one.
    monkeypatch.setattr(log, "rows", lambda wait_s=1.0: [
        {k: v for k, v in r.items() if k != "route_rows"} for r in real])
    assert _reader("retransmits_per_kpkt")(None, counters, {}) is not None
    assert read(None, counters, {}) is None
    # A stretch whose window ends were all skipped looked nothing up.
    monkeypatch.setattr(log, "rows", lambda wait_s=1.0: [
        {**r, "route_rows": 0} for r in real])
    assert read(None, counters, {}) is None


def test_the_manifest_lists_the_cells_the_reader_reads_in():
    from benchmarks.harness import manifest as mf

    man = mf.load(ROOT)
    (entry,) = [e for e in man["per_layer"]
                if e["name"] == "route_rows_useful_share"]
    assert entry == {
        "name": "route_rows_useful_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "window program",
        "moves": "events_per_s",
        "workloads": ["bitcoin5k_cities.lossyflood6s", "tor1k_regions.lossy3s"]}
    cell, cfg = mf.cell(man, "bitcoin5k_cities.lossyflood6s")
    assert (cell["chips"], cfg["reduced"]) == (1, ["stop_time"])
    assert len(man["workloads"]) == 8 and man["workloads"][-1] is cell


# ---- (e) the cell in miniature through the benchmark's harness -----------------------

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
        assert ln["engine_vs_reference"]["pkts_lost"][0] > 20
    assert set(res["metrics"]) == {"events_per_s", "peak_hbm_mb", "setup_s"}


def test_wrong_seed_sees_a_bitcoin_cell_whose_packets_are_drawn_away():
    """The first Bitcoin cell ``wrong_seed`` can see: the reference under the
    next seed keeps the lane's origins and draws other packets away."""
    rc, res, lanes = _bench(11, "--control", "wrong_seed")
    assert rc == 0 and res["correct"] is False and res["failed"] == 2
    assert all(ln["reference_seed"] == ln["seed"] + 1 for ln in lanes)
    assert all({"events", "pkts_lost"} <= set(ln["differ"]) and not ln["ok"]
               for ln in lanes)
    assert not any(ln["must_be_zero"] for ln in lanes)
