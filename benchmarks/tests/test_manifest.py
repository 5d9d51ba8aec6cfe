"""BENCHMARK.json and the files it names: what the loader takes and refuses."""

import copy
import glob
import json
import os
import re

import pytest

from benchmarks.harness import manifest as mf

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _write(tmp_path, m):
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    return str(tmp_path)


@pytest.fixture()
def good():
    return mf.read_json(os.path.join(ROOT, "BENCHMARK.json"))


def test_the_manifest_loads_and_every_file_it_names_is_there(good):
    m = mf.load(ROOT)
    for c in m["configs"]:
        meta = mf.read_json(os.path.join(ROOT, c["file"]))
        assert meta["engine"] in ("solo", "fleet")
        assert sorted(meta["reduced"]) == sorted(c["reduced"])
        assert os.path.isfile(os.path.join(
            os.path.dirname(os.path.join(ROOT, c["file"])), meta["experiment"]))
    for w in m["workloads"]:
        cell, cfg = mf.cell(m, w["name"])
        assert cfg["name"] == w["config"]
        t = mf.read_json(mf.find(ROOT, m, "traffic", w["traffic"] + ".json"))
        assert t["cycle_windows"] % t["chunk_windows"] == 0
        assert (t.get("trace_from_window", 0)
                + t["trace_chunks"] * t["chunk_windows"]) <= t["cycle_windows"]
        e2e = {e["name"] for e in mf.metrics_of(m, "end_to_end", w["name"])}
        assert len(e2e) >= 3        # a rate, the memory peak, the set-up time
        layer = mf.metrics_of(m, "per_layer", w["name"])
        assert layer and all(e["moves"] in e2e for e in layer)
    for kind, folder in (("end_to_end", "end_to_end"),
                         ("per_layer", "layer_metrics")):
        for e in m[kind]:
            assert callable(mf.reader(ROOT, m, folder, e["name"]))


@pytest.mark.parametrize("bad", ["a b", "a,b", "a/b", "", ".a", "x" * 65,
                                 "eventsµ"])
def test_a_name_outside_the_alphabet_is_refused(tmp_path, good, bad):
    for key in ("workloads", "configs", "end_to_end", "per_layer"):
        m = copy.deepcopy(good)
        m[key][0]["name"] = bad
        with pytest.raises(mf.ManifestError):
            mf.load(_write(tmp_path, m))
    with pytest.raises(mf.ManifestError):
        mf.cell(good, bad)


@pytest.mark.parametrize("bad", ["tokens per second", "x" * 17, "", "µs"])
def test_a_unit_over_16_characters_or_with_a_space_is_refused(tmp_path, good, bad):
    m = copy.deepcopy(good)
    m["end_to_end"][0]["unit"] = bad
    with pytest.raises(mf.ManifestError):
        mf.load(_write(tmp_path, m))


def test_two_entries_may_not_share_a_name(tmp_path, good):
    m = copy.deepcopy(good)
    m["per_layer"][0]["name"] = m["end_to_end"][0]["name"]
    with pytest.raises(mf.ManifestError):
        mf.load(_write(tmp_path, m))


def test_an_unknown_cell_is_refused(good):
    with pytest.raises(mf.ManifestError):
        mf.cell(good, "no.such.cell")


def test_the_harness_spells_no_cell_configuration_metric_or_unit(good):
    """Adding a cell, a configuration, a mix or a metric is adding files and a
    manifest entry: the code knows none of them by name."""
    code = ""
    for p in [os.path.join(ROOT, "benchmarks", "run.py")] + glob.glob(
            os.path.join(ROOT, "benchmarks", "harness", "*.py")):
        with open(p) as f:
            code += f.read()
    # jax's own monitoring events are named as jax names them.
    code = re.sub(r'"/jax/[a-z_/]+"', '""', code)
    names = {e["name"] for k in ("workloads", "configs", "end_to_end", "per_layer")
             for e in good[k]}
    names |= {w["traffic"] for w in good["workloads"]}
    # Units too, but for the plain words that the result line's own keys and
    # ordinary prose share with them.
    names |= {e["unit"] for k in ("end_to_end", "per_layer") for e in good[k]
              if len(e["unit"]) > 2} - {"count"}
    spelled = sorted(n for n in names
                     if re.search(r"(?<![A-Za-z0-9_])" + re.escape(n)
                                  + r"(?![A-Za-z0-9_])", code))
    assert not spelled, spelled


def test_the_rehearsal_manifest_has_the_real_one_s_metrics(good):
    """tests/rehearsal/BENCHMARK.json differs in cells and paths alone."""
    reh = mf.read_json(os.path.join(ROOT, "benchmarks", "tests", "rehearsal",
                                    "BENCHMARK.json"))
    strip = lambda es: [{k: v for k, v in e.items() if k != "workloads"} for e in es]
    for kind in ("end_to_end", "per_layer"):
        assert strip(reh[kind]) == strip(good[kind])
