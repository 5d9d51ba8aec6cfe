"""The cell ``tor1k.seeds8`` as the manifest and the harness see it: data
files and entries only (the harness itself, ``tests/test_manifest.py`` and
``tests/test_rehearsal.py`` are as they were; the cell in miniature runs in
the repo's ``tests/test_tor_fleet.py``)."""

import os
import types

from benchmarks.harness import loop
from benchmarks.harness import manifest as mf

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "tor1k.seeds8"


def test_the_manifest_has_four_cells_and_the_new_one_is_last():
    m = mf.load(ROOT)
    assert [w["name"] for w in m["workloads"]] == [
        "phold65k.dense", "tgen100.seeds32", "bitcoin5k.flood", CELL]
    cell, cfg = mf.cell(m, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("tor1k", "seeds8", 1)
    assert cfg == m["configs"][-1] and cfg["reduced"] == ["stop_time"]
    assert all(len(e["why"]) <= 200 for e in (cell, cfg)) and len(cfg["source"]) <= 200


def test_the_cell_reports_the_fleets_metrics_and_not_dense_s():
    m = mf.load(ROOT)
    assert [e["name"] for e in mf.metrics_of(m, "end_to_end", CELL)] == [
        "events_per_s", "peak_hbm_mb", "setup_s"]
    layer = [e["name"] for e in mf.metrics_of(m, "per_layer", CELL)]
    # What a TCP fleet reports: every metric without a list, and the share
    # of useful handler passes, which lists its cells.
    assert layer == [e["name"] for e in mf.metrics_of(m, "per_layer",
                                                      "tgen100.seeds32")]
    assert "handler_pass_useful_share" in layer and "chunk_gap_ms" not in layer
    assert {"ms_per_round", "rounds_per_window", "ops_per_round",
            "round_hbm_share", "handlers_ms_per_round", "device_idle_share",
            "build_s", "compile_s", "cache_misses"} <= set(layer)


def test_each_new_file_is_found_exactly_once_and_the_cell_loads():
    m = mf.load(ROOT)
    for parts in (("configs", "tor1k.json"), ("configs", "tor1k.yaml"),
                  ("traffic", "seeds8.json")):
        assert mf.find(ROOT, m, *parts) == os.path.join(ROOT, "benchmarks", *parts)
    for control in (None, "wrong_seed", "small_caps"):
        c = loop._load_cell(ROOT, types.SimpleNamespace(workload=CELL,
                                                        control=control))
        assert (c["chunk"], c["cycle"], c["traced"]) == (5, 40, (20, 25))
        assert c["meta"]["engine"] == "fleet" and c["traffic"]["lanes"] == 8
        assert c["cfg_path"] == os.path.join(ROOT, "benchmarks", "configs",
                                             "tor1k.json")
