"""The configuration ``bitcoin5k_regions`` and its cell as files: the numbers
its JSON states as the source's are the ones the experiment file and the
topology file run, and the benchmark's copies are byte copies of the files a
user is given under ``configs/`` (the cell in miniature runs in the repo's
``tests/test_bitcoin_regions.py``)."""

import os
import types

import networkx as nx
import yaml

from benchmarks.harness import loop
from benchmarks.harness import manifest as mf

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIGS = os.path.join(ROOT, "benchmarks", "configs")
CELL = "bitcoin5k_regions.flood6s"
PROVENANCE = ("there is no network here and /root/reference/ is empty; the "
              "matrix and the shares above are the issue writer's "
              "transcription of that source from memory and could not be "
              "re-read. Nobody is to fetch anything.")


def _meta():
    return mf.read_json(os.path.join(CONFIGS, "bitcoin5k_regions.json"))


def _experiment():
    with open(os.path.join(CONFIGS, "bitcoin5k_regions.yaml")) as f:
        return yaml.safe_load(f)


def test_the_group_counts_sum_to_5000_and_round_the_shares():
    src = _meta()["from_the_source"]
    counts, shares = src["nodes_by_region"], src["node_share"]
    assert sum(counts) == src["nodes"] == 5000 and len(counts) == len(shares) == 6
    assert abs(sum(shares) - 1.0) < 1e-9
    assert all(abs(c - s * 5000) <= 0.5 + 1e-9 for c, s in zip(counts, shares))
    hosts = _experiment()["hosts"]
    assert [g["count"] for g in hosts] == counts
    assert [g["vertex"] for g in hosts] == src["vertices"]


def test_the_topology_file_is_the_matrix_in_the_json():
    src = _meta()["from_the_source"]
    g = nx.read_graphml(os.path.join(CONFIGS, "topology_6region.graphml"))
    assert not g.is_directed() and list(g.nodes()) == src["vertices"]
    assert g.number_of_edges() == 21 and nx.number_of_selfloops(g) == 6
    assert str(g.graph["preferdirectpaths"]).lower() == "true"
    m = src["latency_ms"]
    for i, a in enumerate(src["vertices"]):
        for j, b in enumerate(src["vertices"]):
            assert m[i][j] == m[j][i] == g.edges[a, b]["latency"], (a, b)
    assert min(min(r) for r in m) == 11 and max(max(r) for r in m) == 325


def test_the_two_copies_are_byte_copies_and_the_experiment_names_its_topology():
    for ours, theirs in (("bitcoin5k_regions.yaml", "geo_bitcoin5k.yaml"),
                         ("topology_6region.graphml", "topology_6region.graphml")):
        with open(os.path.join(CONFIGS, ours), "rb") as a, \
                open(os.path.join(ROOT, "configs", theirs), "rb") as b:
            assert a.read() == b.read(), ours
    doc = _experiment()
    assert doc["network"] == {"graphml": "topology_6region.graphml"}
    assert doc["app"]["params"]["graph"] == {"kind": "random_regular", "k": 8,
                                             "seed": 41}


def test_the_file_carries_the_provenance_sentence_and_a_reason_for_every_assumed_value():
    meta = _meta()
    assert meta["from_the_source"]["provenance"] == PROVENANCE
    assert meta["assumed"]["provenance"] == PROVENANCE
    assert meta["architecture"] is None and meta["engine"] == "fleet"
    assert meta["reduced"] == ["stop_time"] == list(meta["reduced_why"])
    assert meta["must_be_zero"] == ["ev_overflow", "ob_overflow", "round_cap_hits"]
    assert all(isinstance(v, str) and len(v) > 20 for v in meta["assumed"].values())
    assert {"graph", "tx", "inv_size", "latency_variance", "loss",
            "bandwidth_up/down", "sockets_per_host", "msgq_cap", "ev_cap",
            "outbox_cap", "max_rounds", "lanes", "cycle"} <= set(meta["assumed"])


def test_the_manifest_gained_one_configuration_one_cell_and_one_list_entry():
    m = mf.load(ROOT)
    # The first five, so that the next cell does not fail this test (as this
    # one fails test_tor1k.py's "four cells", which this PR may not edit).
    assert [w["name"] for w in m["workloads"]][:5] == [
        "phold65k.dense", "tgen100.seeds32", "bitcoin5k.flood", "tor1k.seeds8",
        CELL]
    cell, cfg = mf.cell(m, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "bitcoin5k_regions", "flood6s", 1)
    assert cfg == m["configs"][4] and cfg["reduced"] == ["stop_time"]
    assert all(len(e["why"]) <= 200 for e in (cell, cfg)) and len(cfg["source"]) <= 200
    assert [e["name"] for e in mf.metrics_of(m, "per_layer", CELL)] == \
        [e["name"] for e in mf.metrics_of(m, "per_layer", "bitcoin5k.flood")]
    share = [e for e in m["per_layer"] if e["name"] == "handler_pass_useful_share"]
    assert share[0]["workloads"][3] == CELL


def test_the_cell_loads_with_each_control():
    for control in (None, "wrong_seed", "small_caps", "other_origins"):
        c = loop._load_cell(ROOT, types.SimpleNamespace(workload=CELL,
                                                        control=control))
        assert (c["chunk"], c["cycle"], c["traced"]) == (10, 550, (500, 510))
        assert c["meta"]["engine"] == "fleet" and c["traffic"]["lanes"] == 2
        assert c["traffic"]["seed_pool_first"] == 600000006000
