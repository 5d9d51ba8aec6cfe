"""PHOLD: batched TPU engine vs the sequential CPU oracle.

The reference's analogous gate is its PHOLD scheduler stress plus its
determinism diff-tests (SURVEY §4): identical seeds must yield identical
event streams regardless of execution strategy. Here the two strategies are
a heapq loop and windowed tensor rounds; event counts, per-host hop vectors,
and packet counters must match exactly.
"""

import numpy as np
import pytest

from shadow1_tpu.config.compiled import single_vertex_experiment
from shadow1_tpu.consts import MS, SEC, EngineParams
from shadow1_tpu.core.engine import Engine
from tests.parity import assert_parity, run_both


def make_exp(n_hosts=16, seed=7, loss=0.0, end=1 * SEC, mean=20 * MS):
    return single_vertex_experiment(
        n_hosts=n_hosts,
        seed=seed,
        end_time=end,
        latency_ns=10 * MS,
        loss=loss,
        model="phold",
        model_cfg={"mean_delay_ns": mean, "init_events": 2},
    )


@pytest.mark.parametrize("loss", [0.0, 0.3])
def test_phold_parity(loss):
    exp = make_exp(loss=loss)
    cm, cs, tm, ts = run_both(exp, EngineParams(ev_cap=64, outbox_cap=64))
    assert cm["ev_overflow"] == 0 and cm["ob_overflow"] == 0
    assert_parity(cm, cs, tm, ts, keys=("hops",),
                  metric_keys=("events", "pkts_sent", "pkts_delivered",
                               "pkts_lost"))


def test_phold_seed_determinism():
    exp = make_exp(seed=123)
    e1 = Engine(exp)
    e2 = Engine(exp)
    s1, s2 = e1.run(), e2.run()
    np.testing.assert_array_equal(
        np.asarray(e1.model_summary(s1)["hops"]), np.asarray(e2.model_summary(s2)["hops"])
    )
    assert Engine.metrics_dict(s1) == Engine.metrics_dict(s2)


def test_phold_seeds_differ():
    m1 = Engine.metrics_dict(Engine(make_exp(seed=1)).run())
    m2 = Engine.metrics_dict(Engine(make_exp(seed=2)).run())
    assert m1["events"] != m2["events"] or m1["pkts_sent"] != m2["pkts_sent"]
