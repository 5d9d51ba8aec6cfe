"""Active-host compaction (core/compact.py): bit-parity with the full path.

The compaction contract is strict identity — same pops, same handler
order, same RNG draws, same metrics — regardless of the bucket size: a
window whose active set exceeds the cap takes more trips, never another
path. Only what the round loop counts of itself (``rounds``, ``fires_*``,
``runs_*``: sums over a window's trips, registry.ROUND_PROGRAM_FIELDS) and
the trips (``SimState.compact_buckets``) tell a compacted run from a plain
one. These tests compare compact_cap engines against the plain engine AND
the CPU oracle, on phold (dense-ish: windows of several trips) and on the
lossy-TCP net model (the sparse workload the knob exists for).
"""

import dataclasses

import numpy as np
import pytest

from shadow1_tpu.config.compiled import single_vertex_experiment
from shadow1_tpu.consts import MS, SEC, EngineParams
from shadow1_tpu.core.engine import Engine
from shadow1_tpu.cpu_engine import CpuEngine
from tests.parity import trip_metrics, unlike_but_trips


def _phold_exp(n_hosts=24, seed=11):
    return single_vertex_experiment(
        n_hosts=n_hosts, seed=seed, end_time=1 * SEC, latency_ns=10 * MS,
        model="phold",
        model_cfg={"mean_delay_ns": 20 * MS, "init_events": 2},
    )


@pytest.mark.parametrize("cap", [8, 16])
def test_phold_compact_parity(cap):
    """PHOLD keeps most hosts active — windows straddle the bucket bound,
    exercising windows of one trip and of several (exceeds the cap → more
    trips)."""
    exp = _phold_exp()
    base = EngineParams(ev_cap=64, outbox_cap=64)
    plain = Engine(exp, base).run()
    comp_eng = Engine(
        exp, EngineParams(ev_cap=64, outbox_cap=64, compact_cap=cap)
    )
    comp = comp_eng.run()
    pm, cm = Engine.metrics_dict(plain), Engine.metrics_dict(comp)
    assert trip_metrics(pm) == trip_metrics(cm)
    # Some window took several trips, and each trip at least one round.
    assert pm["windows"] < int(comp.compact_buckets) <= cm["rounds"]
    assert cm["rounds"] > pm["rounds"]
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
    counter set — catches state corruption in the column mover), whether a
    window's active set fits the bucket or exceeds the cap → more trips."""
    from shadow1_tpu.config.experiment import build_experiment
    import dataclasses

    exp, params, _ = build_experiment(_net_doc(loss=0.0))
    st_a = Engine(exp, params).run()
    st_b = Engine(exp, dataclasses.replace(params, compact_cap=12)).run()
    assert unlike_but_trips(st_b, st_a) == []
    m = Engine.metrics_dict(st_b)
    assert 0 < int(st_b.compact_buckets) and m["compact_max_fill"] > 12


@pytest.mark.slow  # tier-1 wall budget (PR 4): heaviest of its family;
# a faster sibling keeps the coverage in the fast tier; ./ci.sh all runs it.
def test_tor_compact_parity():
    """Tor: the widest model state (relay tables, circuit maps, cell
    streams) through the column mover's round-trip, vs the plain engine
    (exceeds the cap → more trips)."""
    from tests.test_tor_parity import tor_exp, PARAMS
    import dataclasses

    exp = tor_exp(end=10 * SEC)
    st_a = Engine(exp, PARAMS).run()
    st_b = Engine(exp, dataclasses.replace(PARAMS, compact_cap=12)).run()
    assert unlike_but_trips(st_b, st_a) == []


@pytest.mark.slow  # tier-1 wall budget (PR 4): heaviest of its family;
# a faster sibling keeps the coverage in the fast tier; ./ci.sh all runs it.
def test_sharded_compact_parity():
    """Compaction inside shard_map: each shard compacts its local block;
    results must equal the plain single-device engine. Sparse TCP traffic
    (few active clients per window) so the per-shard bucket genuinely
    engages (global cap 64 → 8 lanes/shard < h_local 16; a shard whose
    active set exceeds the cap → more trips, each shard its own number)."""
    from shadow1_tpu.config.experiment import build_experiment
    import dataclasses
    from tests.test_shard_parity import run_pair, assert_same

    exp, params, _ = build_experiment(_net_doc(n_hosts=128))
    params = dataclasses.replace(params, compact_cap=64)
    m1, s1, m8, s8 = run_pair(exp, params)
    assert_same(m1, s1, m8, s8, ["rx_bytes"])


def test_compacted_run_keeps_n_elig_equal_to_plane_scan():
    """The maintained eligibility counters survive the gather into the
    bucket and the put-back: after a run whose windows took one trip and
    several (exceeds the cap → more trips), and whose round cap left events
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
    assert min(active) <= cap < max(active), active   # one trip, and several
    assert int(st.compact_buckets) == sum(-(-a // cap) for a in active)
    buf = st.evbuf
    scan = ((np.asarray(buf.kind) != 0)
            & (np.asarray(buf.t32) < int(buf.u32))).sum(axis=0)
    assert scan.sum() > 0 and Engine.metrics_dict(st)["round_cap_hits"] > 0
    np.testing.assert_array_equal(np.asarray(buf.n_elig), scan)
    plain = Engine(exp, base).run(n_windows=n_win)
    np.testing.assert_array_equal(np.asarray(buf.n_elig),
                                  np.asarray(plain.evbuf.n_elig))


# ---- the mechanism alone: the buckets and the column mover ---------------------

@pytest.mark.parametrize("h,cap,n_active", [
    (33, 8, 0), (33, 8, 5), (33, 8, 8), (33, 8, 9), (33, 8, 33),
    (1000, 384, 875), (200, 7, 61),
])
def test_the_trips_partition_the_active_set(h, cap, n_active):
    """Every active host is in exactly one bucket, lowest ids first, an
    inactive one in none; ceil(n / cap) buckets, none for an empty set; the
    padding lanes come last and hold no host."""
    import jax.numpy as jnp

    from shadow1_tpu.core.compact import next_bucket

    rs = np.random.default_rng(h * cap + n_active)
    active = np.zeros(h, bool)
    active[rs.choice(h, n_active, replace=False)] = True
    remaining, seen, trips = jnp.asarray(active), np.zeros(h, int), 0
    while bool(remaining.any()):
        idx, lane_pad, taken = map(np.asarray, next_bucket(remaining, cap))
        real = idx[~lane_pad]
        assert (idx[lane_pad] == h).all() and not lane_pad[:len(real)].any()
        assert (np.diff(real) > 0).all()
        np.testing.assert_array_equal(np.flatnonzero(taken), real)
        assert len(real) == min(cap, int(np.asarray(remaining).sum()))
        seen[real] += 1
        remaining = remaining & ~jnp.asarray(taken)
        trips += 1
    np.testing.assert_array_equal(seen, active.astype(int))
    assert trips == -(-n_active // cap)


def _edge_values(dtype, shape, rs):
    from shadow1_tpu.consts import K_NONE
    from shadow1_tpu.core.events import I32_FREE

    if dtype == np.bool_:
        return rs.integers(0, 2, shape).astype(bool)
    if dtype == np.float32:
        x = rs.standard_normal(shape).astype(np.float32)
        x.flat[:4] = [np.inf, -0.0, np.float32(1e-45), -np.inf]
        return x
    info = np.iinfo(dtype)
    x = rs.integers(info.min, info.max, shape, dtype=dtype, endpoint=True)
    edges = [info.min, info.max, 0, 2**31 - 1, I32_FREE, K_NONE, 255, 256]
    if info.min < 0:
        edges += [-1, -2**31, -256]
    edges = np.asarray([e for e in edges if info.min <= e <= info.max], dtype)
    x.flat[:len(edges)] = edges
    return x


@pytest.mark.parametrize("lanes", [1, 3])
@pytest.mark.parametrize("h,cap", [(33, 8), (40, 16), (100, 7)],
                         ids=["33x8", "40x16", "100x7"])
@pytest.mark.parametrize("dtype", [np.int32, np.bool_, np.int64, np.uint64,
                                   np.uint32, np.float32],
                         ids=lambda d: np.dtype(d).name)
def test_the_mover_round_trips_every_bit(dtype, h, cap, lanes):
    """``take_cols`` is ``take`` on the real lanes, bit for bit (INT32_MIN,
    −1, 2**31 − 1, I32_FREE, K_NONE and bool planes among the values),
    whether or not ``cap`` divides ``H``; and ``put_cols`` writes the taken
    columns back and no other. Under ``jax.vmap`` as the fleet runs it, a
    different ``remaining`` a lane: lane 0 has more active hosts than the
    cap (a full bucket, then a padded one), lane 1 has NONE (an all-padding
    bucket is the identity), lane 2 fewer than the cap; lane 0 un-batched
    too, as the solo and the sharded engine run it."""
    import jax
    import jax.numpy as jnp

    from shadow1_tpu.core.compact import next_bucket, put_cols, take_cols

    rs = np.random.default_rng(cap)
    x = _edge_values(dtype, (lanes, 3, 5, h), rs)
    x[..., :h] = x[..., rs.permutation(h)]      # the edges on any column
    active = np.zeros((lanes, h), bool)
    for lane, n in enumerate([cap + 3, 0, cap - 2][:lanes]):
        active[lane, rs.choice(h, n, replace=False)] = True
    remaining = jnp.asarray(active)
    full = {"plane": jnp.asarray(x), "no_host_axis": jnp.zeros((lanes, h + 1))}
    for n_real in ([cap, 0, cap - 2][:lanes], [3, 0, 0][:lanes]):
        idx, lane_pad, taken = jax.vmap(lambda r: next_bucket(r, cap))(remaining)
        assert list(np.asarray(~lane_pad).sum(axis=1)) == n_real
        got = jax.vmap(lambda t, i: take_cols(t, i, h))(full, idx)
        assert got["plane"].dtype == x.dtype
        np.testing.assert_array_equal(got["no_host_axis"],
                                      full["no_host_axis"])
        # Put back something else on every lane: only the taken hosts move.
        other = _edge_values(dtype, got["plane"].shape, rs)
        back = jax.vmap(put_cols)(
            full, {"plane": jnp.asarray(other), "no_host_axis": None}, taken)
        assert back["no_host_axis"] is None     # the round loop's value
        for lane in range(lanes):
            at = np.asarray(idx[lane])
            np.testing.assert_array_equal(
                np.asarray(got["plane"][lane]).view(np.uint8),
                np.take(x[lane], np.minimum(at, h - 1), axis=-1)
                .view(np.uint8))                 # pads clone host H - 1
            want = x[lane].copy()
            want[..., at[:n_real[lane]]] = other[lane][..., :n_real[lane]]
            np.testing.assert_array_equal(
                np.asarray(back["plane"][lane]).view(np.uint8),
                want.view(np.uint8))
        solo = take_cols(full["plane"][0], idx[0], h)
        np.testing.assert_array_equal(
            np.asarray(solo).view(np.uint8),
            np.asarray(got["plane"][0]).view(np.uint8))
        np.testing.assert_array_equal(
            np.asarray(put_cols(full["plane"][0], jnp.asarray(other[0]),
                                taken[0])).view(np.uint8),
            np.asarray(back["plane"][0]).view(np.uint8))
        remaining = remaining & ~taken
    assert not bool(remaining.any())


@pytest.mark.parametrize("taken_hosts", [[], [0], [32], [5, 6, 20], [1, 31]],
                         ids=lambda t: "hosts_" + "_".join(map(str, t)))
def test_a_host_that_was_not_taken_keeps_its_column_whatever_its_position(
        taken_hosts):
    """``put_cols`` reads a host's bucket lane through its rank among the
    taken hosts: before the first taken host that rank is −1 (clipped to
    lane 0), after the last it is the last taken host's, and a padding
    lane is nobody's — so neither lane 0's, nor the last real lane's, nor
    a padding lane's bits may reach a host that is not in ``taken``."""
    import jax.numpy as jnp

    from shadow1_tpu.core.compact import put_cols

    h, cap = 33, 8
    old = np.arange(2 * h, dtype=np.int64).reshape(2, h)
    bucket = -1 - np.arange(2 * cap, dtype=np.int64).reshape(2, cap)
    taken = np.zeros(h, bool)
    taken[taken_hosts] = True
    back = np.asarray(put_cols(jnp.asarray(old), jnp.asarray(bucket),
                               jnp.asarray(taken)))
    want = old.copy()
    want[:, taken_hosts] = bucket[:, :len(taken_hosts)]
    np.testing.assert_array_equal(back, want)
    assert (back[:, ~taken] >= 0).all() and (back[:, taken] < 0).all()
