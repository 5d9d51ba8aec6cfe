"""Config front-end: YAML schema, GraphML loading, path compilation, CLI.

The engine-selector seam (BASELINE.json: "CPU and TPU engines are selected
from the same config file") is exercised by running ladder rung 1 from its
YAML file on both engines and asserting identical results.
"""

import os

import numpy as np
import pytest

from shadow1_tpu.config.experiment import (
    build_experiment,
    load_experiment,
    parse_bw_bits,
    parse_time_ns,
)
from shadow1_tpu.config.topology import compile_paths
from shadow1_tpu.consts import MS, SEC

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs")


def test_unit_parsers():
    assert parse_time_ns("10 ms") == 10 * MS
    assert parse_time_ns("2 s") == 2 * SEC
    assert parse_time_ns(1500) == 1500
    assert parse_time_ns("250us") == 250_000
    assert parse_bw_bits("10 Mbit") == 10**7
    assert parse_bw_bits("1 Gbit") == 10**9


def test_compile_paths_line_graph():
    # v0 -10ms- v1 -20ms- v2, loss 0.1 each edge.
    inf = np.inf
    lat = np.array([[inf, 10 * MS, inf], [10 * MS, inf, 20 * MS], [inf, 20 * MS, inf]], float)
    loss = np.array([[0, 0.1, 0], [0.1, 0, 0.1], [0, 0.1, 0]], float)
    lat_vv, loss_vv = compile_paths(lat, loss)
    assert lat_vv[0, 2] == 30 * MS
    assert lat_vv[0, 0] == 10 * MS  # intra-vertex default: min edge latency
    np.testing.assert_allclose(loss_vv[0, 2], 1 - 0.9 * 0.9, rtol=1e-6)
    np.testing.assert_allclose(loss_vv[0, 1], 0.1, rtol=1e-6)


def test_rung1_yaml_roundtrip_both_engines():
    exp, params, scheduler = load_experiment(os.path.join(CONFIGS, "rung1_filexfer.yaml"))
    assert scheduler == "tpu"
    assert exp.n_hosts == 2
    assert exp.window == 40 * MS  # GraphML edge latency
    assert exp.model_cfg["server"][1] == 0  # "@server" reference resolved

    from shadow1_tpu.core.engine import Engine
    from shadow1_tpu.cpu_engine import CpuEngine

    cpu = CpuEngine(exp, params)
    cm = cpu.run()
    cs = cpu.summary()
    eng = Engine(exp, params)
    st = eng.run()
    tm = Engine.metrics_dict(st)
    ts = eng.model_summary(st)
    assert int(ts["total_flows_done"]) == 1
    assert int(ts["total_rx_bytes"]) == 1_000_000
    for k in ("events", "pkts_sent", "pkts_delivered", "pkts_lost"):
        assert tm[k] == cm[k], k


def test_all_rung_configs_build():
    for name in ("rung2_tgen100.yaml", "rung3_tor1k.yaml",
                 "rung4_tor10k.yaml", "rung5_bitcoin5k.yaml"):
        exp, params, _ = load_experiment(os.path.join(CONFIGS, name))
        exp.validate()
        assert exp.n_hosts in (100, 1000, 10000, 5000), name
    # bitcoin generator produced a symmetric graph
    exp, _, _ = load_experiment(os.path.join(CONFIGS, "rung5_bitcoin5k.yaml"))
    peers = exp.model_cfg["peers"]
    assert peers.shape == (5000, 8)
    for h in (0, 17, 4999):
        for p in peers[h]:
            assert h in peers[p], "peer graph must be symmetric"


def test_cli_runs_rung1(capsys):
    import json

    from shadow1_tpu.cli import main

    rc = main([os.path.join(CONFIGS, "rung1_filexfer.yaml"), "--engine", "cpu"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["engine"] == "cpu"
    assert out["metrics"]["events"] > 0


def _phold_doc(**over):
    doc = {
        "general": {"seed": 1, "stop_time": "10 ms"},
        "engine": {"scheduler": "tpu"},
        "network": {"single_vertex": {"latency": "1 ms"}},
        "hosts": [{"name": "h", "count": 2}],
        "app": {"model": "phold"},
    }
    doc.update(over)
    return doc


def test_unknown_keys_fail_fast():
    """Config hardening: a typo anywhere in the experiment schema fails at
    load (fault/schedule.py-style rejection), never a silent default run."""
    build_experiment(_phold_doc())  # the baseline doc itself is valid
    cases = [
        _phold_doc(egine={"scheduler": "tpu"}),               # top-level typo
        _phold_doc(general={"seed": 1, "stop_tme": "10 ms"}),  # general typo
        _phold_doc(network={"single_vertex": {"latncy": "1 ms"}}),
        _phold_doc(network={"single_vertex": {"latency": "1 ms"},
                            "jitterr": "1 us"}),
        _phold_doc(hosts=[{"name": "h", "countt": 2}]),        # host typo
        _phold_doc(app={"model": "phold", "prams": {}}),       # app typo
    ]
    for doc in cases:
        with pytest.raises(AssertionError, match="unknown"):
            build_experiment(doc)
    # The engine section already rejected typos; keep that contract pinned.
    # So does a knob that no longer exists (pop_impl went with the Pallas
    # kernels in PR 30): the error names the key.
    for eng in ({"ev_capp": 64}, {"pop_impl": "pallas"}):
        with pytest.raises(AssertionError,
                           match=f"unknown engine params.*{list(eng)[0]}"):
            build_experiment(_phold_doc(engine={"scheduler": "tpu", **eng}))


def test_engine_section_coerces_by_declared_type():
    """``engine:`` values take their EngineParams field's declared type: a
    quoted number becomes an int, the str knobs stay str and are held to
    their own value lists, and the probe watchlist is not settable there."""
    _, params, _ = build_experiment(_phold_doc(engine={
        "ev_cap": "256", "outbox_cap": 8, "on_overflow": "retry"}))
    assert params.ev_cap == 256 and isinstance(params.ev_cap, int)
    assert params.outbox_cap == 8 and params.on_overflow == "retry"
    with pytest.raises(AssertionError, match="sometimes"):
        build_experiment(_phold_doc(engine={"on_overflow": "sometimes"}))
    with pytest.raises(ValueError):
        build_experiment(_phold_doc(engine={"ev_cap": "many"}))
    with pytest.raises(AssertionError, match="engine.probes is not settable"):
        build_experiment(_phold_doc(engine={"probes": [[0, -1]]}))


def test_engine_params_hold_each_knob_to_its_values():
    """EngineParams refuses, at construction, a value outside a knob's own
    list — whoever builds it (YAML, CLI override, a test): a bad value never
    reaches a trace."""
    from shadow1_tpu.consts import EngineParams

    EngineParams(sockets_per_host=256, probes=((3, -1), (0, 15)))
    for bad in ({"sockets_per_host": 257}, {"metrics_ring": -1},
                {"state_digest": 2}, {"link_telem": 2}, {"auto_caps": -1},
                {"on_overflow": "sometimes"}, {"on_lane_fail": "retry"},
                {"lane_finalize": 2}, {"selfcheck": 2},
                {"probes": [(0, 0)]},            # a list, not a tuple
                {"probes": ((0, 16),)},          # sock past sockets_per_host
                {"probes": ((-1, 0),)}, {"probes": (("h", 0),)}):
        with pytest.raises(AssertionError):
            EngineParams(**bad)


def test_stagger_start_times():
    """Group param dict form {start, interval}: host i of the group gets
    start + i*interval (the rung-4 client-bootstrap stagger)."""
    from shadow1_tpu.consts import MS

    exp, _, _ = load_experiment(os.path.join(CONFIGS, "rung4_tor10k.yaml"))
    st = exp.model_cfg["start_time"]
    clients = np.where(exp.model_cfg["role"] == 1)[0]
    assert st[clients[0]] == 200 * MS
    assert st[clients[1]] - st[clients[0]] == 2 * MS
    assert st[clients[-1]] == 200 * MS + (len(clients) - 1) * 2 * MS
    relays = np.where(exp.model_cfg["role"] == 0)[0]
    assert (st[relays] == 200 * MS).all()  # non-staggered groups untouched
