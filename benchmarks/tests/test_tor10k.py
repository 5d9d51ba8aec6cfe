"""The cell ``tor10k.join`` as the manifest and the harness see it: data
files, two readers and entries only (the cell in miniature runs in the
repo's ``tests/test_tor10k.py``)."""

import os
import types

from benchmarks.harness import loop
from benchmarks.harness import manifest as mf

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "tor10k.join"
NEW_METRICS = ["active_host_share", "events_per_round"]


def test_the_first_five_cells_are_the_parent_s_and_the_new_one_is_last():
    m = mf.load(ROOT)
    # A prefix, so that the next cell does not fail this test.
    assert [w["name"] for w in m["workloads"]][:6] == [
        "phold65k.dense", "tgen100.seeds32", "bitcoin5k.flood", "tor1k.seeds8",
        "bitcoin5k_regions.flood6s", CELL]
    cell, cfg = mf.cell(m, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("tor10k", "join", 1)
    assert cfg == m["configs"][5] and cfg["reduced"] == ["stop_time"]
    assert all(len(e["why"]) <= 200 for e in (cell, cfg)) and len(cfg["source"]) <= 200
    assert cfg["source"] == mf.read_json(os.path.join(ROOT, cfg["file"]))["source"]
    share = [e for e in m["per_layer"] if e["name"] == "handler_pass_useful_share"]
    assert share[0]["workloads"][:5] == [
        "tgen100.seeds32", "bitcoin5k.flood", "tor1k.seeds8",
        "bitcoin5k_regions.flood6s", CELL]


def test_the_cell_reports_the_fleets_metrics_and_the_two_new_ones_and_not_dense_s():
    m = mf.load(ROOT)
    assert [e["name"] for e in mf.metrics_of(m, "end_to_end", CELL)] == [
        "events_per_s", "peak_hbm_mb", "setup_s"]
    layer = [e["name"] for e in mf.metrics_of(m, "per_layer", CELL)]
    # What a TCP fleet reports (tgen's list), then the two that read the
    # chunk log's totals; the other Tor cell reports the same list.
    assert layer == [e["name"] for e in mf.metrics_of(
        m, "per_layer", "tgen100.seeds32")] + NEW_METRICS
    assert layer == [e["name"] for e in mf.metrics_of(m, "per_layer",
                                                      "tor1k.seeds8")]
    assert "handler_pass_useful_share" in layer and "chunk_gap_ms" not in layer
    assert {"ms_per_round", "rounds_per_window", "ops_per_round",
            "round_hbm_share", "handlers_ms_per_round", "device_idle_share",
            "build_s", "compile_s", "cache_misses", "chunk_turnaround_ms",
            "dispatch_call_ms_per_chunk"} <= set(layer)
    for e in m["per_layer"][-2:]:
        assert e["name"] in NEW_METRICS and e["workloads"] == ["tor1k.seeds8", CELL]
        assert (e["source"], e["layer"], e["moves"], e["better"]) == (
            "program_counter", "window program", "events_per_s", "higher")
    # No other cell gained a metric.
    for other in ("phold65k.dense", "bitcoin5k.flood", "bitcoin5k_regions.flood6s"):
        assert not set(NEW_METRICS) & {e["name"] for e in mf.metrics_of(
            m, "per_layer", other)}


def test_each_new_file_is_found_exactly_once_and_the_cell_loads():
    m = mf.load(ROOT)
    for parts in (("configs", "tor10k.json"), ("configs", "tor10k.yaml"),
                  ("traffic", "join.json"),
                  ("layer_metrics", "active_host_share.py"),
                  ("layer_metrics", "events_per_round.py")):
        assert mf.find(ROOT, m, *parts) == os.path.join(ROOT, "benchmarks", *parts)
    for name in NEW_METRICS:
        assert callable(mf.reader(ROOT, m, "layer_metrics", name))
    for control in (None, "wrong_seed", "small_caps"):
        c = loop._load_cell(ROOT, types.SimpleNamespace(workload=CELL,
                                                        control=control))
        # ISSUE 43's table, or its one fallback.
        assert c["chunk"] == 5
        assert (c["cycle"], c["traced"]) in ((120, (60, 65)), (80, (40, 45)))
        assert c["meta"]["engine"] == "fleet" and c["traffic"]["lanes"] == 1
        assert c["traffic"]["seed_pool_first"] == 600000004000
        assert c["cfg_path"] == os.path.join(ROOT, "benchmarks", "configs",
                                             "tor10k.json")


def test_the_readers_return_nothing_where_the_program_keeps_no_such_rows():
    """The parent's program: a chunk log whose rows lack the totals (or no
    log at all). Nothing ran in this process, so the log is empty."""
    m = mf.load(ROOT)
    counters = {"chunks": 1, "windows": 5, "rounds": 10}
    for name in NEW_METRICS:
        read = mf.reader(ROOT, m, "layer_metrics", name)
        assert read(None, counters, {}) is None
        assert read(None, {"chunks": 0, "windows": 0, "rounds": 0}, {}) is None
