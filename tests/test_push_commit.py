"""A round's local pushes staged as ``[H]``-vectors and written in ONE commit
(``core/events.Stage`` / ``push_commit``, ``core/engine.run_round``; PR 49).

(1) the rows each app's passes declare (``core/engine.pass_rows``) are the
sites a trace of them counts, with the virtual CPU and the receive queue on
too, and a handler with one site more than it declares fails the trace naming
its pass; (2) the miniatures of the benchmark's TCP cells equal the CPU oracle
counter for counter and every digest word window for window, the event
buffer's among them, with more than one event staged by one host in one round
in three of the four (and more than one trip in Bitcoin's, whose nodes
announce to eight peers); (3) the two counters the commit brings.
``tests/test_events.py`` holds the staged round against the same pushes
written one by one, leaf for leaf; ``tests/test_dense.py`` that no pass sweeps
the payload plane; ``tests/test_bitcoin_regions.py`` that fleet lanes which
need different trip counts are their solo runs.
"""

import dataclasses
import os

import jax.numpy as jnp
import numpy as np
import pytest
import yaml

from shadow1_tpu.config.compiled import single_vertex_experiment
from shadow1_tpu.config.experiment import build_experiment
from shadow1_tpu.consts import K_APP, KIND_NAMES, MS, SEC, EngineParams
from shadow1_tpu.core.engine import (
    Engine,
    count_push_sites,
    pass_rows,
    push_local_event,
    rows_of,
)
from shadow1_tpu.core.events import PUSH_RB, PushRowsError
from shadow1_tpu.cpu_engine import CpuEngine
from shadow1_tpu.telemetry.registry import (
    CHUNK_PUSH_TOTALS,
    LANE_PROGRAM_FIELDS,
    METRIC_SPECS,
    RING_GAUGES,
    ROUND_PROGRAM_FIELDS,
)
from shadow1_tpu.telemetry.ring import drain_ring
from tests.parity import PARITY_KEYS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# file, stop_time (whole windows), the most events one host stages in a round
MINIATURES = {
    "tor35": ("tests/rehearsal_tor_lossy/configs/tor35.yaml", "1500 ms", 2),
    "tor33": ("tests/rehearsal_tor_join/configs/tor33.yaml", "1800 ms", 2),
    "bitcoin120": ("tests/rehearsal_bitcoin_regions/configs/"
                   "bitcoin120_regions.yaml", "3300 ms", 8),
    "tgen100": ("configs/rung2_tgen100.yaml", "400 ms", 1),
}


def build(path, stop=None, hosts=None, **engine):
    with open(os.path.join(ROOT, path)) as f:
        doc = yaml.safe_load(f)
    if stop:
        doc["general"]["stop_time"] = stop
    for group in doc["hosts"]:
        group.update(hosts or {})
    exp, params, _ = build_experiment(
        doc, base_dir=os.path.dirname(os.path.join(ROOT, path)))
    return exp, dataclasses.replace(params, **engine)


# ---- (1) the rows a pass declares against the sites a trace counts ----------------

def _dgram():
    n = 4
    return single_vertex_experiment(
        n_hosts=n, seed=3, end_time=1 * SEC, latency_ns=10 * MS, model="net",
        model_cfg={"app": "dgram", "dst": (np.arange(n) + 1) % n,
                   "payload": np.full(n, 100, np.int64),
                   "interval": np.full(n, 5 * MS, np.int64),
                   "count": np.full(n, 3, np.int64),
                   "start_time": np.full(n, 1 * MS, np.int64)}), EngineParams()


# With the virtual CPU or a receive queue on, arrivals keep their own pass
# (``h_pkt``: net.make_pre_window) and the CPU model defers by ``push_back``.
QUEUES = {"cpu_per_event": "1 us", "rx_queue_bytes": 30000}
APPS = {
    "tgen": lambda: build("configs/rung2_tgen100.yaml"),
    "tgen_cpu_rxq": lambda: build("configs/rung2_tgen100.yaml", hosts=QUEUES),
    "filexfer": lambda: build("configs/rung1_filexfer.yaml"),
    "filexfer_cpu_rxq": lambda: build("configs/rung1_filexfer.yaml",
                                      hosts=QUEUES),
    "dgram": _dgram,
    "bitcoin_k4": lambda: build(
        "tests/rehearsal_bitcoin64/configs/bitcoin64.yaml"),
    "bitcoin_k8_cpu_rxq": lambda: build(MINIATURES["bitcoin120"][0],
                                        hosts=QUEUES),
    "tor": lambda: build(MINIATURES["tor35"][0]),
    "tor_cpu_rxq": lambda: build(MINIATURES["tor33"][0], hosts=QUEUES),
}


@pytest.mark.parametrize("app", APPS)
def test_a_pass_declares_the_push_sites_a_trace_of_it_counts(app):
    exp, params = APPS[app]()
    eng = Engine(exp, params)
    handlers = eng._model.make_handlers(eng.ctx)
    declared = {f"h_{KIND_NAMES[k]}": rows_of(fn, eng.ctx)
                for k, fn in handlers.items()}
    counted = count_push_sites(eng.init_state(), eng.ctx, handlers)
    if eng.ctx.has_cpu:
        assert counted.pop("cpu_defer") == 1
    assert eng.ctx.has_cpu == eng.ctx.has_rx_qlen == ("cpu_rxq" in app)
    assert ("h_pkt" in declared) == eng.ctx.has_rx_qlen
    # Not fewer rows than sites, and no row that no site uses.
    assert counted == declared
    assert max(declared.values()) >= 1


def test_a_handler_with_one_site_too_many_fails_the_trace_naming_its_pass():
    exp, params = APPS["tgen"]()
    eng = Engine(exp, params)
    handlers = dict(eng._model.make_handlers(eng.ctx))
    on_app = handlers[K_APP]

    @pass_rows(rows_of(on_app, eng.ctx))
    def one_more(st, ev):
        st = on_app(st, ev)
        return push_local_event(st, eng.ctx, jnp.zeros(exp.n_hosts, bool),
                                ev.time, K_APP)

    handlers[K_APP] = one_more
    with pytest.raises(PushRowsError, match="pass 'h_app' traces more than "
                                            "the 2 push sites it declares"):
        count_push_sites(eng.init_state(), eng.ctx, handlers)
    # Declared, the same handlers trace.
    handlers[K_APP] = pass_rows(3)(one_more)
    assert count_push_sites(eng.init_state(), eng.ctx, handlers)["h_app"] == 3


# ---- (2) the miniatures against the oracle, digests on ------------------------------

@pytest.fixture(scope="module", params=sorted(MINIATURES))
def both(request):
    path, stop, _ = MINIATURES[request.param]
    exp, params = build(path, stop, metrics_ring=512, state_digest=1)
    cpu = CpuEngine(exp, params)
    cm = cpu.run()
    eng = Engine(exp, params)
    return request.param, eng, eng.run(), cpu, cm


def test_a_miniature_equals_the_oracle_counter_for_counter(both):
    _, _, st, _, cm = both
    tm = Engine.metrics_dict(st)
    assert {k: tm[k] for k in PARITY_KEYS} == {k: cm[k] for k in PARITY_KEYS}
    assert tm["ev_overflow"] == tm["round_cap_hits"] == 0 < tm["events"]


def test_a_miniature_s_digests_are_the_oracle_s_window_for_window(both):
    """Every word, the event buffer's among them: the events a commit wrote
    are the events the oracle's heap holds at each window's end."""
    _, eng, st, cpu, _ = both
    rows = [r for r in drain_ring(st, eng.window) if r["type"] == "ring"]
    words = sorted(k for k in rows[0] if k.startswith("dg_"))
    assert "dg_evbuf" in words and len(words) == 5 and len(rows) >= 20
    for k in words:
        assert {r["window"]: r[k] for r in rows} \
            == {r["window"]: r[k] for r in cpu.digest_rows}, k


def test_the_commit_counts_its_trips_and_the_fullest_stage(both):
    name, eng, st, _, _ = both
    tm = Engine.metrics_dict(st)
    stage_max = MINIATURES[name][2]
    assert tm["push_stage_max"] == stage_max
    # A round that staged nothing makes no trip; one that did makes at least
    # one, and more where a host staged more than PUSH_RB events.
    assert 0 < tm["push_commit_trips"] < tm["rounds"] * -(-stage_max // PUSH_RB)
    if stage_max <= PUSH_RB:
        assert tm["push_commit_trips"] < tm["rounds"]
    # The ring's column is the running gauge.
    rows = [r for r in drain_ring(st, eng.window) if r["type"] == "ring"]
    assert rows[-1]["push_stage_max"] == stage_max
    assert [r["push_stage_max"] for r in rows] \
        == sorted(r["push_stage_max"] for r in rows)


def test_some_miniature_stages_more_than_one_event_and_one_needs_two_trips():
    assert sum(m[2] >= 2 for m in MINIATURES.values()) >= 3
    assert max(m[2] for m in MINIATURES.values()) > PUSH_RB


# ---- (3) the two counters' places ---------------------------------------------------

def test_the_two_counters_are_the_engine_s_own():
    """Batch-engine counts like ``rounds``: in the registry, outside the
    oracle's parity keys; the trips a lane's OWN (so a lane equals its solo
    run in them) but summed over a compacted window's buckets; the gauge a
    ring column; the trips a running total on the chunk log's rows."""
    assert METRIC_SPECS["push_commit_trips"][0] == "counter"
    assert METRIC_SPECS["push_stage_max"][0] == "gauge"
    assert not {"push_commit_trips", "push_stage_max"} & set(PARITY_KEYS)
    assert not {"push_commit_trips", "push_stage_max"} & set(LANE_PROGRAM_FIELDS)
    assert "push_commit_trips" in ROUND_PROGRAM_FIELDS
    assert "push_stage_max" not in ROUND_PROGRAM_FIELDS
    assert "push_stage_max" in RING_GAUGES
    assert CHUNK_PUSH_TOTALS == ("push_commit_trips",)
