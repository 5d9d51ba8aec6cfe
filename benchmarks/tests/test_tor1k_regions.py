"""The cell ``tor1k_regions.lossy3s`` as the manifest and the harness see it:
data files, two readers and entries only (the cell in miniature runs in the
repo's ``tests/test_tor_regions.py``)."""

import os
import types

from benchmarks.harness import loop
from benchmarks.harness import manifest as mf

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "tor1k_regions.lossy3s"
PARENT_CELLS = ["phold65k.dense", "tgen100.seeds32", "bitcoin5k.flood",
                "tor1k.seeds8", "bitcoin5k_regions.flood6s", "tor10k.join"]
NEW_METRICS = ["timer_ms_per_round", "retransmits_per_kpkt"]
TOR_METRICS = ["active_host_share", "events_per_round", "buckets_per_window"]


def test_the_first_six_cells_are_the_parent_s_and_the_new_one_is_last():
    m = mf.load(ROOT)
    # A prefix, so that the next cell does not fail this test.
    assert [w["name"] for w in m["workloads"]][:7] == PARENT_CELLS + [CELL]
    assert [c["name"] for c in m["configs"]][:7] == [
        "phold65k", "tgen100", "bitcoin5k", "tor1k", "bitcoin5k_regions",
        "tor10k", "tor1k_regions"]
    cell, cfg = mf.cell(m, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tor1k_regions", "lossy3s", 1)
    assert cfg == m["configs"][6] and cfg["reduced"] == ["stop_time"]
    assert all(len(e["why"]) <= 200 for e in (cell, cfg)) and len(cfg["source"]) <= 200
    meta = mf.read_json(os.path.join(ROOT, cfg["file"]))
    assert meta["name"] == "tor1k_regions" and meta["architecture"] is None
    # The lists PRs 41, 43 and 44 appended their cells to: this one is next.
    lists = {e["name"]: e["workloads"] for e in m["per_layer"] if "workloads" in e}
    assert lists["handler_pass_useful_share"][:6] == PARENT_CELLS[1:] + [CELL]
    for name in TOR_METRICS:
        assert lists[name][:3] == ["tor1k.seeds8", "tor10k.join", CELL]
    assert lists["chunk_gap_ms"] == ["phold65k.dense"]


def test_the_cell_reports_the_fleets_metrics_the_tor_cells_three_and_the_two_new_ones():
    m = mf.load(ROOT)
    assert [e["name"] for e in mf.metrics_of(m, "end_to_end", CELL)] == [
        "events_per_s", "peak_hbm_mb", "setup_s"]
    layer = [e["name"] for e in mf.metrics_of(m, "per_layer", CELL)]
    everywhere = [e["name"] for e in m["per_layer"] if "workloads" not in e]
    assert set(layer) == set(everywhere) | {"handler_pass_useful_share"} \
        | set(TOR_METRICS) | set(NEW_METRICS)
    assert "chunk_gap_ms" not in layer
    # tor1k.seeds8 reports the same list; tgen the timer pass's metric only;
    # no other cell gained a metric.
    assert layer == [e["name"] for e in mf.metrics_of(m, "per_layer",
                                                      "tor1k.seeds8")]
    gained = {c: set(NEW_METRICS) & {e["name"] for e in mf.metrics_of(
        m, "per_layer", c)} for c in PARENT_CELLS}
    assert gained == {"phold65k.dense": set(), "bitcoin5k.flood": set(),
                      "bitcoin5k_regions.flood6s": set(), "tor10k.join": set(),
                      "tgen100.seeds32": {"timer_ms_per_round"},
                      "tor1k.seeds8": set(NEW_METRICS)}
    by_name = {e["name"]: e for e in m["per_layer"]}
    assert by_name["timer_ms_per_round"] == {
        "name": "timer_ms_per_round", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "window program",
        "moves": "events_per_s",
        "workloads": ["tgen100.seeds32", "tor1k.seeds8", CELL]}
    assert by_name["retransmits_per_kpkt"] == {
        "name": "retransmits_per_kpkt", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "window program",
        "moves": "events_per_s", "workloads": ["tor1k.seeds8", CELL]}


def test_each_new_file_is_found_exactly_once_and_the_cell_loads():
    m = mf.load(ROOT)
    for parts in (("configs", "tor1k_regions.json"),
                  ("configs", "tor1k_regions.yaml"),
                  ("configs", "topology_6region_lossy.graphml"),
                  ("traffic", "lossy3s.json"),
                  ("layer_metrics", "timer_ms_per_round.py"),
                  ("layer_metrics", "retransmits_per_kpkt.py")):
        assert mf.find(ROOT, m, *parts) == os.path.join(ROOT, "benchmarks", *parts)
    for name in NEW_METRICS:
        assert callable(mf.reader(ROOT, m, "layer_metrics", name))
    for control in (None, "wrong_seed", "small_caps"):
        c = loop._load_cell(ROOT, types.SimpleNamespace(workload=CELL,
                                                        control=control))
        # ISSUE 47's table (8 lanes), or its one fallback (4).
        assert (c["chunk"], c["cycle"], c["traced"]) == (5, 300, (250, 255))
        assert c["meta"]["engine"] == "fleet" and c["traffic"]["lanes"] in (8, 4)
        assert c["traffic"]["seed_pool_first"] == 600000007000
        assert not c["traffic"]["overrides"]
        assert c["cfg_path"] == os.path.join(ROOT, "benchmarks", "configs",
                                             "tor1k_regions.json")


def test_the_readers_return_nothing_where_the_program_gives_them_nothing():
    """The parent's program: a chunk log whose rows lack the loss totals (or
    no log at all: nothing ran in this process), and a roll-up without the
    timer pass's row."""
    m = mf.load(ROOT)
    timer, resent = (mf.reader(ROOT, m, "layer_metrics", n) for n in NEW_METRICS)
    counters = {"chunks": 1, "windows": 5, "rounds": 10}
    assert resent(None, counters, {}) is None
    assert resent(None, {"chunks": 0, "windows": 0, "rounds": 0}, {}) is None
    assert timer(None, counters, {}) is None
    assert timer(None, {**counters, "phase_s": {"handlers": 1.0}}, {}) is None
    assert timer(None, {**counters, "phase_s": {"handlers": 1.0, "h_txr": 0.1}},
                 {}) == 0.0
    assert timer(None, {**counters, "phase_s": {"handlers": 1.0, "h_timer": 0.02}},
                 {}) == 2.0
