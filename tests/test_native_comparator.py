"""The C++ thread-per-core comparator simulates the identical experiment.

Counter equality against the Python oracle (itself parity-locked to the
TPU engine) is what entitles bench.py to quote the comparator's wall clock
as the honest thread-per-core baseline (SURVEY §7.3.5).
"""

import pytest

from shadow1_tpu.config.compiled import single_vertex_experiment
from shadow1_tpu.consts import MS, EngineParams
from shadow1_tpu.cpu_engine import CpuEngine

native = pytest.importorskip("shadow1_tpu.native")


def _config(n_hosts=256, windows=40, init=3):
    exp = single_vertex_experiment(
        n_hosts=n_hosts, seed=77, end_time=windows * MS, latency_ns=1 * MS,
        model="phold",
        model_cfg={"mean_delay_ns": float(2 * MS), "init_events": init},
    )
    params = EngineParams(ev_cap=32, outbox_cap=16, max_rounds=64)
    return exp, params, windows


def _run_native(exp, params, windows, n_threads):
    try:
        return native.run_phold(
            n_hosts=exp.n_hosts, seed=exp.seed, n_windows=windows,
            window_ns=exp.window, mean_delay_ns=exp.model_cfg["mean_delay_ns"],
            init_events=exp.model_cfg["init_events"], ev_cap=params.ev_cap,
            outbox_cap=params.outbox_cap, n_threads=n_threads,
        )
    except native.NativeUnavailable as e:
        pytest.skip(str(e))


@pytest.mark.parametrize("n_threads", [1, 4])
def test_native_matches_oracle(n_threads):
    exp, params, windows = _config()
    cm = CpuEngine(exp, params).run()
    assert cm["ev_overflow"] == 0 and cm["ob_overflow"] == 0, (
        "config must be overflow-free for exact parity"
    )
    nm = _run_native(exp, params, windows, n_threads)
    for k in ("events", "pkts_sent", "pkts_delivered", "ev_overflow", "ob_overflow"):
        assert nm[k] == cm[k], (k, nm[k], cm[k], f"threads={n_threads}")


# --------------------------------------------------------------------------
# Net-model comparator (round 4): full virtual-TCP stack + model apps.
# Counter equality against the oracle on every app family is what entitles
# a benchmark to quote vs_cpp on the net rungs (VERDICT r3 missing #3).
# --------------------------------------------------------------------------
NET_KEYS = (
    "events", "pkts_sent", "pkts_delivered", "pkts_lost", "ev_overflow",
    "ob_overflow", "tcp_fast_rtx", "tcp_rto", "tcp_ooo_drops",
    "pops_deliver", "pops_timer", "pops_txr", "pops_app",
)


def _compare_net(exp, params, windows, summary_keys, n_threads=2):
    import numpy as np

    cpu = CpuEngine(exp, params)
    cm = cpu.run(n_windows=windows)
    cs = cpu.summary()
    try:
        nm = native.run_net(exp, params, windows, n_threads=n_threads)
    except native.NativeUnavailable as e:
        pytest.skip(str(e))
    for k in NET_KEYS:
        assert nm[k] == cm[k], (k, nm[k], cm[k])
    for k in summary_keys:
        want = cs[k]
        want = int(want if np.ndim(want) == 0 else np.asarray(want).sum())
        assert int(nm[k]) == want, (k, nm[k], want)


def test_net_native_filexfer_lossy():
    import numpy as np
    from shadow1_tpu.consts import SEC

    n = 8
    role = np.full(n, 1, np.int64)
    role[0] = 0
    exp = single_vertex_experiment(
        n_hosts=n, seed=3, end_time=20 * SEC, latency_ns=10 * MS,
        loss=0.01, bw_bits=10**7, model="net",
        model_cfg={
            "app": "filexfer", "role": role, "server": np.zeros(n, np.int64),
            "flow_bytes": np.full(n, 30_000, np.int64),
            "start_time": np.full(n, MS, np.int64),
            "flow_count": np.where(role == 1, 1, 0),
        },
    )
    _compare_net(exp, EngineParams(ev_cap=256), 2000,
                 ("total_flows_done", "total_rx_bytes"))


def test_net_native_tor():
    from shadow1_tpu.consts import SEC
    from tests.test_tor_parity import tor_exp

    exp = tor_exp(seed=11, end=30 * SEC)
    _compare_net(exp, EngineParams(ev_cap=256, sockets_per_host=32), 1000,
                 ("total_streams_done", "total_cells_fwd", "total_cells_rx",
                  "clients_done", "total_ct_overflow"))


def test_net_native_bitcoin():
    from tests.test_bitcoin_parity import btc_exp

    exp = btc_exp(seed=5)
    _compare_net(exp, EngineParams(ev_cap=256), 1200,
                 ("total_seen", "total_tx_rx"))


def test_net_native_refuses_unmodeled_fidelity():
    import numpy as np
    from shadow1_tpu.consts import SEC

    n = 4
    role = np.full(n, 1, np.int64)
    role[0] = 0
    exp = single_vertex_experiment(
        n_hosts=n, seed=3, end_time=2 * SEC, latency_ns=10 * MS,
        bw_bits=10**7, model="net",
        model_cfg={
            "app": "filexfer", "role": role, "server": np.zeros(n, np.int64),
            "flow_bytes": np.full(n, 1_000, np.int64),
            "start_time": np.full(n, MS, np.int64),
            "flow_count": np.where(role == 1, 1, 0),
        },
        cpu_ns_per_event=np.full(n, 100, np.int64),
    )
    with pytest.raises(native.NativeUnavailable, match="virtual CPU"):
        native.run_net(exp, EngineParams(), 10)
