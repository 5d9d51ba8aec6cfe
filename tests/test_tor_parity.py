"""Tor circuit-model parity: batched engine vs CPU oracle (BASELINE 3/4).

A small Tor net: weighted relays (guard/exit subsets), dirauths serving the
consensus, clients bootstrapping then building telescoped circuits and
streaming through them. Parity must be exact: same circuits, same cells,
same completion times — including under loss.
"""

import numpy as np
import pytest

from shadow1_tpu.config.compiled import single_vertex_experiment
from shadow1_tpu.consts import MS, SEC, EngineParams
from tests.parity import assert_parity, run_both

TOR_KEYS = (
    "streams_done", "cells_rx", "bootstrap_time", "done_time",
    "cells_fwd", "ct_overflow", "cell_retries",
)


def tor_exp(seed=31, loss=0.0, end=30 * SEC, n_circuits=2, n_streams=2,
            mean_cells=20.0, bw=10**7):
    n = 24
    role = np.full(n, 1, np.int64)          # clients by default
    role[0:8] = 0                           # 8 relays
    role[8:10] = 2                          # 2 dirauths
    role[22:24] = 3                         # 2 idle
    is_guard = np.zeros(n, bool)
    is_guard[0:3] = True
    is_exit = np.zeros(n, bool)
    is_exit[5:8] = True
    weight = np.zeros(n, np.int64)
    weight[0:8] = 100 + 10 * np.arange(8)
    return single_vertex_experiment(
        n_hosts=n,
        seed=seed,
        end_time=end,
        latency_ns=10 * MS,
        loss=loss,
        bw_bits=bw,
        model="net",
        model_cfg={
            "app": "tor",
            "role": role,
            "relay_weight": weight,
            "is_guard": is_guard,
            "is_exit": is_exit,
            "n_circuits": np.where(role == 1, n_circuits, 0),
            "n_streams": np.full(n, n_streams, np.int64),
            "mean_stream_cells": np.full(n, mean_cells, np.float64),
            "mean_think_ns": np.full(n, 100 * MS, np.float64),
            "start_time": np.full(n, 1 * MS, np.int64),
            "ct_cap": 64,
        },
    )


PARAMS = EngineParams(ev_cap=256, sockets_per_host=32)


def test_tor_circuits_parity_fast():
    """Tier-1 wall sibling (PR 9 budget pass): one circuit and one stream
    per client on a shorter horizon — the same full bootstrap → telescope →
    stream → completion parity contract as the slow original below."""
    exp = tor_exp(end=20 * SEC, n_circuits=1, n_streams=1, mean_cells=10.0)
    cm, cs, tm, ts = run_both(exp, PARAMS)
    n_clients = 12
    assert int(ts["clients_done"]) == n_clients
    assert int(ts["total_streams_done"]) == n_clients * 1 * 1
    assert int(ts["total_cells_rx"]) > 0
    assert int(ts["total_cells_fwd"]) > 0
    assert int(ts["total_ct_overflow"]) == 0
    # The run totals are the oracle's too (registry.MODEL_TOTALS).
    assert {k: int(v) for k, v in ts.items() if np.ndim(v) == 0} == \
        {k: int(v) for k, v in cs.items() if np.ndim(v) == 0}
    assert int(ts["total_cell_retries"]) == int(np.sum(ts["cell_retries"]))
    assert_parity(cm, cs, tm, ts, keys=TOR_KEYS)


@pytest.mark.slow  # tier-1 wall budget (PR 9): the full 2-circuit/2-stream
# matrix; the fast sibling above keeps the contract in the fast tier.
def test_tor_circuits_parity():
    exp = tor_exp()
    cm, cs, tm, ts = run_both(exp, PARAMS)
    n_clients = 12
    # Every client bootstraps and completes all circuits/streams.
    assert int(ts["clients_done"]) == n_clients
    assert int(ts["total_streams_done"]) == n_clients * 2 * 2
    assert int(ts["total_cells_rx"]) > 0
    assert int(ts["total_cells_fwd"]) > 0
    assert int(ts["total_ct_overflow"]) == 0
    assert_parity(cm, cs, tm, ts, keys=TOR_KEYS)


@pytest.mark.slow  # tier-1 wall budget (PR 4): heaviest of its family;
# a faster sibling keeps the coverage in the fast tier; ./ci.sh all runs it.
def test_tor_under_loss_parity():
    exp = tor_exp(seed=5, loss=0.01, end=60 * SEC)
    cm, cs, tm, ts = run_both(exp, PARAMS)
    assert int(ts["clients_done"]) == 12
    assert tm["tcp_rto"] + tm["tcp_fast_rtx"] > 0
    assert_parity(cm, cs, tm, ts, keys=TOR_KEYS)
