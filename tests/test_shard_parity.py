"""Sharding parity: the 8-device host-axis mesh vs the single-device engine.

Determinism across shardings is a hard invariant inherited from the
reference ("same config ⇒ same results regardless of worker count",
SURVEY §4): every semantic metric and model summary must be bit-identical
between the single-device engine and the shard_map engine on the virtual
8-device CPU mesh. Round counters are excluded — each shard runs its own
inner round loop, so their sum legitimately differs from the global count.
"""

import numpy as np
import pytest

from shadow1_tpu.config.compiled import single_vertex_experiment
from shadow1_tpu.consts import MS, SEC, EngineParams
from shadow1_tpu.core.engine import Engine
from shadow1_tpu.shard.engine import ShardedEngine

SEMANTIC_KEYS = [
    "events", "windows", "pkts_sent", "pkts_delivered", "pkts_lost",
    "ev_overflow", "ob_overflow", "tcp_fast_rtx", "tcp_rto", "tcp_ooo_drops",
    "nic_tx_drops", "nic_rx_drops", "nic_aqm_drops",
    "x2x_overflow",  # all_to_all bucket drops: must be 0 (== single-device)
]


def run_pair(exp, params=None):
    params = params or EngineParams()
    eng = Engine(exp, params)
    st1 = eng.run()
    sh = ShardedEngine(exp, params)
    assert sh.n_dev == 8, "conftest must provide 8 virtual devices"
    st8 = sh.run()
    return (
        Engine.metrics_dict(st1),
        eng.model_summary(st1),
        ShardedEngine.metrics_dict(st8),
        sh.model_summary(st8),
    )


def assert_same(m1, s1, m8, s8, summary_keys):
    for k in SEMANTIC_KEYS:
        assert m8[k] == m1[k], (k, m8[k], m1[k])
    for k in summary_keys:
        np.testing.assert_array_equal(np.asarray(s8[k]), np.asarray(s1[k]), err_msg=k)


def test_phold_sharded_parity():
    exp = single_vertex_experiment(
        n_hosts=64,
        seed=7,
        end_time=50 * MS,
        latency_ns=1 * MS,
        model="phold",
        model_cfg={"mean_delay_ns": float(2 * MS), "init_events": 2},
    )
    m1, s1, m8, s8 = run_pair(exp)
    assert m1["events"] > 500  # the workload actually ran
    assert_same(m1, s1, m8, s8, summary_keys=("hops",))


def test_x2x_bucket_overflow_is_counted():
    """A deliberately tiny all_to_all bucket must DROP (not corrupt), count
    every dropped packet in x2x_overflow, and fail loudly by default."""
    import pytest

    exp = single_vertex_experiment(
        n_hosts=64, seed=7, end_time=50 * MS, latency_ns=1 * MS,
        model="phold",
        model_cfg={"mean_delay_ns": float(2 * MS), "init_events": 4},
    )
    sh_full = ShardedEngine(exp, EngineParams())
    full = sh_full.run()
    fm = ShardedEngine.metrics_dict(full)
    assert fm["x2x_overflow"] == 0
    # Occupancy observability: the busiest-bucket high-water mark is
    # recorded, positive (traffic flowed), and within the cap that held.
    assert 0 < fm["x2x_max_fill"] <= sh_full._x2x_cap
    with pytest.raises(RuntimeError, match="x2x_cap"):
        ShardedEngine(exp, EngineParams(x2x_cap=1)).run()
    tiny = ShardedEngine(exp, EngineParams(x2x_cap=1)).run(check_x2x=False)
    tm = ShardedEngine.metrics_dict(tiny)
    assert tm["x2x_overflow"] > 0
    # The high-water mark records DEMANDED fill, so it exceeds the cap of 1
    # exactly when overflow happens — users can read the needed cap off it.
    assert tm["x2x_max_fill"] > 1
    assert tm["x2x_max_fill"] == fm["x2x_max_fill"]  # demand is cap-independent
    # sent minus (lost + delivered + dropped buckets + full-evbuf drops) = 0
    assert (
        tm["pkts_sent"]
        == tm["pkts_lost"] + tm["pkts_delivered"] + tm["x2x_overflow"]
        + tm["ev_overflow"]
    ), tm


def test_x2x_auto_retry_convergent_traffic():
    """Convergent (all clients → one server) traffic overflows the uniform
    auto cap by design; run() must escalate to the worst-case cap and
    produce results bit-identical to the single-device engine — the exact
    failure shape that broke the round-3 multichip gate."""
    import __graft_entry__ as ge

    # The gate's own flagship shape (4 hosts/shard), auto cap instead of
    # the gate's pinned one so the escalation path is what runs.
    exp = ge._flagship_exp(32, 1 * SEC)
    params = EngineParams(ev_cap=64, outbox_cap=16, sockets_per_host=4)
    assert params.x2x_cap == 0  # auto-sized: the path under test
    sh = ShardedEngine(exp, params)
    start_cap = sh._x2x_cap
    st8 = sh.run(n_windows=4)
    m8 = ShardedEngine.metrics_dict(st8)
    assert m8["x2x_overflow"] == 0
    # The workload converges on shard 0, so the retry must actually fire —
    # otherwise this test is not exercising the escalation path.
    assert sh._x2x_cap == sh._full_cap > start_cap
    eng = Engine(exp, params)
    st1 = eng.run(n_windows=4)
    m1 = Engine.metrics_dict(st1)
    for k in SEMANTIC_KEYS:
        assert m8[k] == m1[k], (k, m8[k], m1[k])


def test_dryrun_multichip_gate():
    """Execute the driver's own multichip gate (__graft_entry__) so its exact
    parameterization is covered by CI — round 3 shipped a gate-only failure
    because nothing in tests/ ran this path, and round 4 left this test in
    the slow tier only, so the default ``./ci.sh`` could still go green while
    the gate drifted. It costs ~5 sharded-program compiles (minutes) and is
    budgeted into the fast tier deliberately."""
    import __graft_entry__ as ge  # repo root is on pythonpath (pyproject)

    ge.dryrun_multichip(8)


@pytest.mark.slow  # tier-1 wall budget (PR 4): heaviest of its family;
# a faster sibling keeps the coverage in the fast tier; ./ci.sh all runs it.
def test_tor_sharded_parity():
    """The flagship multi-chip workload (rung 4 is sharded Tor): clients,
    weighted relays and dirauths spread across all 8 shards; every semantic
    counter and per-host summary must bit-match the single-device engine."""
    from tests.test_tor_parity import TOR_KEYS, tor_exp

    exp = tor_exp(seed=11, end=30 * SEC)
    m1, s1, m8, s8 = run_pair(exp, EngineParams(ev_cap=256, sockets_per_host=32))
    assert int(s1["clients_done"]) == 12  # the workload actually completed
    assert_same(m1, s1, m8, s8, summary_keys=TOR_KEYS)


def _filexfer_exp(end_s: int, loss: float):
    n = 8
    role = np.full(n, 1, np.int64)
    role[0] = 0
    return single_vertex_experiment(
        n_hosts=n,
        seed=3,
        end_time=end_s * SEC,
        latency_ns=10 * MS,
        loss=loss,
        bw_bits=10**7,
        model="net",
        model_cfg={
            "app": "filexfer",
            "role": role,
            "server": np.zeros(n, np.int64),
            "flow_bytes": np.full(n, 30_000, np.int64),
            "start_time": np.full(n, 1 * MS, np.int64),
            "flow_count": np.where(role == 1, 1, 0),
        },
    )


def test_filexfer_sharded_parity_fast():
    """Tier-1 wall sibling (PR 9 budget pass): the same convergent
    filexfer-on-a-mesh contract on a quarter of the window count — every
    flow still completes and every counter/summary bit-matches."""
    m1, s1, m8, s8 = run_pair(_filexfer_exp(5, 0.01), EngineParams(ev_cap=256))
    assert int(s1["total_flows_done"]) == 7
    assert_same(m1, s1, m8, s8, summary_keys=("rx_bytes", "flows_done", "done_time"))


@pytest.mark.slow  # tier-1 wall budget (PR 9): the 20-sim-second horizon;
# the fast sibling above keeps the contract in the fast tier.
def test_filexfer_sharded_parity():
    m1, s1, m8, s8 = run_pair(_filexfer_exp(20, 0.01), EngineParams(ev_cap=256))
    assert int(s1["total_flows_done"]) == 7
    assert_same(m1, s1, m8, s8, summary_keys=("rx_bytes", "flows_done", "done_time"))


@pytest.mark.slow  # tier-1 wall budget (PR 4): RED parity is covered by
# test_fidelity.test_red_aqm_parity; the sharded combination runs in all.
def test_filexfer_red_aqm_sharded_parity():
    """RED AQM under sharding: the per-host aqm columns (thresholds, coin
    counters) ride the mesh like every other [H] tensor; drops must land on
    the exact same packets as the single-device engine."""
    n = 8
    role = np.full(n, 1, np.int64)
    role[0] = 0
    exp = single_vertex_experiment(
        n_hosts=n,
        seed=3,
        end_time=20 * SEC,
        latency_ns=10 * MS,
        bw_bits=10**6,
        model="net",
        model_cfg={
            "app": "filexfer",
            "role": role,
            "server": np.zeros(n, np.int64),
            "flow_bytes": np.full(n, 60_000, np.int64),
            "start_time": np.full(n, 1 * MS, np.int64),
            "flow_count": np.where(role == 1, 1, 0),
        },
        aqm_min_bytes=np.full(n, 2_000, np.int64),
        aqm_max_bytes=np.full(n, 12_000, np.int64),
        aqm_pmax=np.full(n, 0.3, np.float64),
    )
    m1, s1, m8, s8 = run_pair(exp, EngineParams(ev_cap=256))
    assert m1["nic_aqm_drops"] > 0  # RED actually fired
    assert_same(m1, s1, m8, s8, summary_keys=("rx_bytes", "flows_done"))
