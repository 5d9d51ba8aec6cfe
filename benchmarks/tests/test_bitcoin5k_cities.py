"""The cell ``bitcoin5k_cities.lossyflood6s`` as the manifest and the harness
see it: data files, one reader and entries only (the cell in miniature runs
in the repo's ``tests/test_bitcoin_cities.py``). Lists of cells and metrics
are held by prefix and by membership, never as whole lists: the next PR
appends to them."""

import hashlib
import os
import types

from benchmarks.harness import loop
from benchmarks.harness import manifest as mf

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "bitcoin5k_cities.lossyflood6s"
PARENT_CELLS = ["phold65k.dense", "tgen100.seeds32", "bitcoin5k.flood",
                "tor1k.seeds8", "bitcoin5k_regions.flood6s", "tor10k.join",
                "tor1k_regions.lossy3s"]
NEW_METRIC = "route_rows_useful_share"
GRAPH_SHA = "c0af3ee50e25bfaee6e98092111603f3098d6835765940db476eeee3fd724b45"


def test_the_first_seven_cells_are_the_parent_s_and_the_new_one_is_next():
    m = mf.load(ROOT)
    assert [w["name"] for w in m["workloads"]][:8] == PARENT_CELLS + [CELL]
    assert [c["name"] for c in m["configs"]][:8] == [
        "phold65k", "tgen100", "bitcoin5k", "tor1k", "bitcoin5k_regions",
        "tor10k", "tor1k_regions", "bitcoin5k_cities"]
    cell, cfg = mf.cell(m, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "bitcoin5k_cities", "lossyflood6s", 1)
    assert cfg == m["configs"][7] and cfg["reduced"] == ["stop_time"]
    assert all(len(e["why"]) <= 200 for e in (cell, cfg)) and len(cfg["source"]) <= 200
    assert all(w["chips"] == 1 for w in m["workloads"][:8])


def test_the_configuration_states_its_sources_its_assumptions_and_its_guarantees():
    m = mf.load(ROOT)
    _, cfg = mf.cell(m, CELL)
    meta = mf.read_json(os.path.join(ROOT, cfg["file"]))
    assert meta["name"] == "bitcoin5k_cities" and meta["architecture"] is None
    assert (meta["experiment"], meta["engine"]) == ("bitcoin5k_cities.yaml", "fleet")
    assert meta["reduced"] == ["stop_time"] == list(meta["reduced_why"])
    for who in ("Shadow v1.x", "shadow-plugin-bitcoin", "CSET 2015", "SimBlock",
                "LATENCY_2019", "Once is Never Enough", "net.h"):
        assert who in meta["source"], who
    assert {"vertices", "cities_per_region", "access_latency", "topogen_seed",
            "self_loop", "jitter", "hosts_over_cities", "ev_cap", "outbox_cap",
            "lanes", "cycle", "provenance"} <= set(meta["assumed"])
    assert GRAPH_SHA in meta["assumed"]["topogen_seed"]
    assert meta["from_the_source"]["nodes_by_region"] == [
        1658, 2499, 45, 588, 112, 98]
    regional = mf.read_json(os.path.join(ROOT, "benchmarks", "configs",
                                         "bitcoin5k_regions.json"))
    assert regional["assumed"]["provenance"] in meta["from_the_source"]["provenance"]
    assert meta["must_be_zero"] == ["ev_overflow", "ob_overflow", "round_cap_hits"]
    assert any("wrong_seed" in g and "small_caps" in g for g in meta["guarantees"])
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "topology_cities200.graphml"), "rb") as f:
        assert hashlib.sha256(f.read()).hexdigest() == GRAPH_SHA


def test_the_cell_reports_the_fleets_metrics_and_the_new_one():
    m = mf.load(ROOT)
    assert [e["name"] for e in mf.metrics_of(m, "end_to_end", CELL)] == [
        "events_per_s", "peak_hbm_mb", "setup_s"]
    layer = [e["name"] for e in mf.metrics_of(m, "per_layer", CELL)]
    everywhere = [e["name"] for e in m["per_layer"] if "workloads" not in e]
    assert set(everywhere) | {"handler_pass_useful_share", NEW_METRIC} <= set(layer)
    assert "chunk_gap_ms" not in layer
    # The three Tor-only metrics stay the Tor cells'.
    assert not {"active_host_share", "events_per_round", "buckets_per_window",
                "push_commit_trips_per_round"} & set(layer)
    by_name = {e["name"]: e for e in m["per_layer"]}
    new = by_name[NEW_METRIC]
    assert {k: v for k, v in new.items() if k != "workloads"} == {
        "name": NEW_METRIC, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "window program",
        "moves": "events_per_s"}
    assert new["workloads"][:2] == [CELL, "tor1k_regions.lossy3s"]
    # Lists this PR appended the cell to keep what they had, in order.
    assert by_name["handler_pass_useful_share"]["workloads"][:6] == PARENT_CELLS[1:]
    assert CELL in by_name["handler_pass_useful_share"]["workloads"]
    # No accepted cell but tor1k_regions.lossy3s gained a metric.
    gained = {c for c in PARENT_CELLS
              if NEW_METRIC in {e["name"] for e in mf.metrics_of(m, "per_layer", c)}}
    assert gained == {"tor1k_regions.lossy3s"}


def test_each_new_file_is_found_exactly_once_and_the_cell_loads():
    m = mf.load(ROOT)
    for parts in (("configs", "bitcoin5k_cities.json"),
                  ("configs", "bitcoin5k_cities.yaml"),
                  ("configs", "topology_cities200.graphml"),
                  ("traffic", "lossyflood6s.json"),
                  ("layer_metrics", NEW_METRIC + ".py")):
        assert mf.find(ROOT, m, *parts) == os.path.join(ROOT, "benchmarks", *parts)
    assert callable(mf.reader(ROOT, m, "layer_metrics", NEW_METRIC))
    for control in (None, "wrong_seed", "small_caps"):
        c = loop._load_cell(ROOT, types.SimpleNamespace(workload=CELL,
                                                        control=control))
        assert (c["chunk"], c["cycle"], c["traced"]) == (10, 550, (500, 510))
        assert c["meta"]["engine"] == "fleet" and c["traffic"]["lanes"] == 2
        assert c["traffic"]["seed_pool_first"] == 600000008000
        assert not c["traffic"]["overrides"] and c["traffic"]["trace_chunks"] == 1
        assert c["cfg_path"] == os.path.join(ROOT, "benchmarks", "configs",
                                             "bitcoin5k_cities.json")


def test_the_reader_returns_nothing_where_the_program_gives_it_nothing():
    """The parent's program: a chunk log whose rows lack ``route_rows`` (or no
    log at all: nothing ran in this process)."""
    m = mf.load(ROOT)
    read = mf.reader(ROOT, m, "layer_metrics", NEW_METRIC)
    assert read(None, {"chunks": 1, "windows": 10, "rounds": 10}, {}) is None
    assert read(None, {"chunks": 0, "windows": 0, "rounds": 0}, {}) is None
    assert read(None, {}, {}) is None
