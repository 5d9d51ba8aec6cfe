"""Active-host compaction (core/compact.py): bit-parity with the full path.

The compaction contract is strict identity — same pops, same handler
order, same RNG draws, same metrics (including engine-only counters like
``rounds``) — whether or not a window ran compacted, and regardless of the
bucket size. These tests compare compact_cap engines against the plain
engine AND the CPU oracle, on phold (dense-ish, exercises the full-width
fallback) and on the lossy-TCP net model (the sparse workload the knob
exists for).
"""

import dataclasses

import numpy as np
import pytest

from shadow1_tpu.config.compiled import single_vertex_experiment
from shadow1_tpu.consts import MS, SEC, EngineParams
from shadow1_tpu.core.engine import Engine
from shadow1_tpu.cpu_engine import CpuEngine


def _phold_exp(n_hosts=24, seed=11):
    return single_vertex_experiment(
        n_hosts=n_hosts, seed=seed, end_time=1 * SEC, latency_ns=10 * MS,
        model="phold",
        model_cfg={"mean_delay_ns": 20 * MS, "init_events": 2},
    )


@pytest.mark.parametrize("cap", [8, 16])
def test_phold_compact_parity(cap):
    """PHOLD keeps most hosts active — windows straddle the bucket bound,
    exercising both the compact branch and the full-width fallback."""
    exp = _phold_exp()
    base = EngineParams(ev_cap=64, outbox_cap=64)
    plain = Engine(exp, base).run()
    comp_eng = Engine(
        exp, EngineParams(ev_cap=64, outbox_cap=64, compact_cap=cap)
    )
    comp = comp_eng.run()
    pm, cm = Engine.metrics_dict(plain), Engine.metrics_dict(comp)
    assert pm == cm
    np.testing.assert_array_equal(
        np.asarray(comp_eng.model_summary(comp)["hops"]),
        np.asarray(Engine(exp, base).model_summary(plain)["hops"]),
    )
    for a, b in zip(
        [plain.evbuf.abs_time(), plain.evbuf.kind, plain.cpu_busy],
        [comp.evbuf.abs_time(), comp.evbuf.kind, comp.cpu_busy],
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _net_doc(n_hosts=40, loss=0.02):
    return {
        "general": {"seed": 29, "stop_time": "4 s"},
        "engine": {
            "scheduler": "tpu", "ev_cap": 64, "outbox_cap": 32,
            "sockets_per_host": 4, "msgq_cap": 8,
        },
        "network": {"single_vertex": {"latency": "25 ms", "loss": loss}},
        "hosts": [
            {"name": "server", "count": 2,
             "bandwidth_up": "10 Mbit", "bandwidth_down": "10 Mbit"},
            {"name": "client", "count": n_hosts - 2,
             "bandwidth_up": "10 Mbit", "bandwidth_down": "10 Mbit"},
        ],
        "app": {
            "model": "filexfer",
            "groups": {
                "server": {"role": 0},
                "client": {"role": 1, "server": "@server",
                           "flow_bytes": 40000, "flow_count": 2,
                           "start_time": "50 ms"},
            },
        },
    }


def test_net_compact_parity_vs_oracle():
    """Lossy TCP file transfers: only a handful of the 40 hosts are active
    per window — the design-point workload. Compact engine must match the
    CPU oracle bit-for-bit on the semantic counter set."""
    from shadow1_tpu.config.experiment import build_experiment

    exp, params, _ = build_experiment(_net_doc())
    import dataclasses

    cparams = dataclasses.replace(params, compact_cap=16)
    cpu = CpuEngine(exp, params)
    cm = cpu.run()
    eng = Engine(exp, cparams)
    st = eng.run()
    tm = Engine.metrics_dict(st)
    assert tm["ev_overflow"] == 0 and cm["ev_overflow"] == 0
    for k in ["events", "pkts_sent", "pkts_delivered", "pkts_lost",
              "tcp_fast_rtx", "tcp_rto", "tcp_ooo_drops"]:
        assert tm[k] == cm[k], (k, tm[k], cm[k])
    ts, cs = eng.model_summary(st), cpu.summary()
    np.testing.assert_array_equal(
        np.asarray(ts["rx_bytes"]), np.asarray(cs["rx_bytes"])
    )


def test_net_compact_matches_plain_engine():
    """Engine-vs-engine: identical final state pytrees (stronger than the
    counter set — catches state corruption in gather/scatter)."""
    from shadow1_tpu.config.experiment import build_experiment
    import dataclasses
    import jax

    exp, params, _ = build_experiment(_net_doc(loss=0.0))
    st_a = Engine(exp, params).run()
    st_b = Engine(exp, dataclasses.replace(params, compact_cap=12)).run()

    def cmp(a, b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    jax.tree.map(cmp, st_a, st_b)


@pytest.mark.slow  # tier-1 wall budget (PR 4): heaviest of its family;
# a faster sibling keeps the coverage in the fast tier; ./ci.sh all runs it.
def test_tor_compact_parity():
    """Tor: the widest model state (relay tables, circuit maps, cell
    streams) through the gather/scatter round-trip, vs the plain engine."""
    import jax
    from tests.test_tor_parity import tor_exp, PARAMS
    import dataclasses

    exp = tor_exp(end=10 * SEC)
    st_a = Engine(exp, PARAMS).run()
    st_b = Engine(exp, dataclasses.replace(PARAMS, compact_cap=12)).run()

    def cmp(a, b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    jax.tree.map(cmp, st_a, st_b)


@pytest.mark.slow  # tier-1 wall budget (PR 4): heaviest of its family;
# a faster sibling keeps the coverage in the fast tier; ./ci.sh all runs it.
def test_sharded_compact_parity():
    """Compaction inside shard_map: each shard compacts its local block;
    results must equal the plain single-device engine. Sparse TCP traffic
    (few active clients per window) so the per-shard compact branch
    genuinely fires (global cap 64 → 8 lanes/shard < h_local 16)."""
    from shadow1_tpu.config.experiment import build_experiment
    import dataclasses
    from tests.test_shard_parity import run_pair, assert_same

    exp, params, _ = build_experiment(_net_doc(n_hosts=128))
    params = dataclasses.replace(params, compact_cap=64)
    m1, s1, m8, s8 = run_pair(exp, params)
    assert_same(m1, s1, m8, s8, ["rx_bytes"])


def test_compacted_run_keeps_n_elig_equal_to_plane_scan():
    """The maintained eligibility counters survive the gather into the
    bucket and the scatter back: after a run whose windows took both the
    compacted and the full-width branch, and whose round cap left events
    eligible (so the counters are not all zero), ``n_elig`` equals a scan
    of the planes against ``u32`` and the plain engine's counters."""
    from shadow1_tpu.telemetry.ring import drain_ring

    exp, cap, n_win = _phold_exp(), 12, 40
    base = EngineParams(ev_cap=64, outbox_cap=64, max_rounds=1,
                        metrics_ring=n_win)
    eng = Engine(exp, dataclasses.replace(base, compact_cap=cap))
    st = eng.run(n_windows=n_win)
    active = [r["active_hosts"] for r in drain_ring(st, eng.window)
              if r["type"] == "ring"]
    assert len(active) == n_win
    assert min(active) <= cap < max(active), active   # both branches ran
    buf = st.evbuf
    scan = ((np.asarray(buf.kind) != 0)
            & (np.asarray(buf.t32) < int(buf.u32))).sum(axis=0)
    assert scan.sum() > 0 and Engine.metrics_dict(st)["round_cap_hits"] > 0
    np.testing.assert_array_equal(np.asarray(buf.n_elig), scan)
    plain = Engine(exp, base).run(n_windows=n_win)
    np.testing.assert_array_equal(np.asarray(buf.n_elig),
                                  np.asarray(plain.evbuf.n_elig))
