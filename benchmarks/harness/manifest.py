"""BENCHMARK.json and the data files it names.

The manifest says which cells, configurations and metrics exist. Everything
that belongs to one configuration, one traffic mix or one metric sits in a
file of its own under one of the manifest's ``paths``, found by name:

    <path>/configs/<config>.json      what is run and why (+ the experiment file it names)
    <path>/traffic/<mix>.json         lanes, seed rule, overrides, chunking, check, trace
    <path>/end_to_end/<quantity>.py     read(window) -> number
    <path>/layer_metrics/<quantity>.py  read(trace, counters, spans) -> number | None
    <path>/controls/<control>.json    a deliberately wrong run (tests and limits only)
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


class ManifestError(ValueError):
    pass


def check_name(name, what: str) -> str:
    if not isinstance(name, str) or not NAME.match(name):
        raise ManifestError(
            f"{what} {name!r}: a name starts with a letter, a digit or _ and "
            "has at most 64 of letters, digits, _ . -")
    return name


def check_unit(unit, what: str) -> str:
    if not isinstance(unit, str) or not UNIT.match(unit):
        raise ManifestError(
            f"{what}: unit {unit!r} must be 1 to 16 of letters, digits, "
            "_ / % . -")
    return unit


def load(root: str) -> dict:
    """Read and check ``<root>/BENCHMARK.json``."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        m = json.load(f)
    for key in ("paths", "configs", "workloads", "end_to_end", "per_layer"):
        if not isinstance(m.get(key), list) or not m[key]:
            raise ManifestError(f"BENCHMARK.json: {key} must be a list with entries")
    for c in m["configs"]:
        check_name(c.get("name"), "configuration")
        for k in c.get("reduced", []):
            check_name(k, f"configuration {c['name']}: reduced key")
    for w in m["workloads"]:
        check_name(w.get("name"), "cell")
        check_name(w.get("config"), f"cell {w['name']}: config")
        check_name(w.get("traffic"), f"cell {w['name']}: traffic")
    for kind in ("end_to_end", "per_layer"):
        for e in m[kind]:
            check_name(e.get("name"), f"{kind} metric")
            check_unit(e.get("unit"), f"{kind} metric {e['name']}")
    for kind, key in (("configuration", "configs"), ("cell", "workloads")):
        names = [e["name"] for e in m[key]]
        if len(set(names)) != len(names):
            raise ManifestError(f"two {kind}s share a name")
    metrics = [e["name"] for e in m["end_to_end"] + m["per_layer"]]
    if len(set(metrics)) != len(metrics):
        raise ManifestError("two metrics share a name")
    return m


def cell(m: dict, name: str) -> tuple[dict, dict]:
    """The cell ``name`` and its configuration, as the manifest has them."""
    check_name(name, "cell")
    cells = [w for w in m["workloads"] if w["name"] == name]
    if not cells:
        raise ManifestError(f"no cell {name!r} in BENCHMARK.json")
    cfgs = [c for c in m["configs"] if c["name"] == cells[0]["config"]]
    if not cfgs:
        raise ManifestError(f"cell {name!r}: no configuration "
                            f"{cells[0]['config']!r} in BENCHMARK.json")
    return cells[0], cfgs[0]


def metrics_of(m: dict, kind: str, cell_name: str) -> list[dict]:
    """The ``kind`` metrics this cell reports: those whose ``workloads`` key
    lists it; of those without the key, every end-to-end metric, and every
    per-layer metric that ``moves`` an end-to-end metric the cell reports."""
    def listed(e):
        return "workloads" not in e or cell_name in e["workloads"]

    end_to_end = {e["name"] for e in m["end_to_end"] if listed(e)}
    return [e for e in m[kind] if listed(e)
            and ("workloads" in e or e.get("moves", e["name"]) in end_to_end)]


def find(root: str, m: dict, *parts: str) -> str:
    """The one file ``<path>/<parts...>`` under the manifest's paths."""
    hits = [p for p in (os.path.join(root, d, *parts) for d in m["paths"])
            if os.path.isfile(p)]
    if len(hits) != 1:
        raise ManifestError(
            f"{os.path.join(*parts)}: found {len(hits)} times under "
            f"{m['paths']}, need exactly one")
    return hits[0]


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def reader(root: str, m: dict, folder: str, name: str):
    """The ``read`` function of ``<path>/<folder>/<name>.py``."""
    path = find(root, m, folder, check_name(name, "metric") + ".py")
    spec = importlib.util.spec_from_file_location(
        f"_bench_{folder}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
