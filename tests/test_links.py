"""Link-telemetry plane: per-edge counters, drop attribution, resume.

The link contract (network-observability acceptance): the cumulative
per-edge snapshots are bit-identical cpu-oracle ↔ tpu ↔ sharded(8) ↔
fleet-lane, a resumed run's stream continues the straight run's exactly,
every per-edge drop column reconciles with its global drop counter on
both engines, and links-off leaves the state pytree (and thus the traced
program) untouched.

The straight filexfer run and the solo churn run are module-scoped
fixtures — one engine compile each, shared across the parity, resume,
gap, digest and reconciliation tests.
"""

import numpy as np
import pytest

from shadow1_tpu.consts import EngineParams
from shadow1_tpu.core.engine import Engine
from shadow1_tpu.cpu_engine import CpuEngine
from shadow1_tpu.telemetry.links import drain_links
from shadow1_tpu.telemetry.registry import LINK_FIELDS, LINK_MAX_COL
from tests.test_net_parity import filexfer_exp

N_WINDOWS = 25
PARAMS = EngineParams(link_telem=1)
CHURN_PARAMS = EngineParams(ev_cap=256, link_telem=1, x2x_cap=64)


def _key(r):
    return (r.get("exp", -1), r.get("src_vertex", -1),
            r.get("dst_vertex", -1), r.get("window", -1))


def tpu_rows(exp, params=PARAMS, n_windows=N_WINDOWS, st=None, start=0):
    eng = Engine(exp, params)
    st = eng.run(st, n_windows=n_windows)
    return st, sorted(drain_links(st, eng.window, start=start), key=_key)


def cpu_rows(exp, params=PARAMS, n_windows=N_WINDOWS):
    eng = CpuEngine(exp, params)
    eng.run(n_windows=n_windows)
    return sorted(eng.link_rows, key=_key)


@pytest.fixture(scope="module")
def straight():
    """One full 25-window filexfer run with links on: (engine, state, rows)."""
    exp = filexfer_exp()
    eng = Engine(exp, PARAMS)
    st = eng.run(n_windows=N_WINDOWS)
    rows = sorted(drain_links(st, eng.window), key=_key)
    return exp, eng, st, rows


@pytest.fixture(scope="module")
def churn():
    """One full solo churn-matrix run: (exp, rows, metrics).

    The churn matrix (8 hosts, outage + ramp + host cycles) exercises
    every drop column of the link accumulator, not just pkts/bytes.
    """
    from tests.test_fault import _churn_matrix_exp

    exp = _churn_matrix_exp()
    eng = Engine(exp, CHURN_PARAMS)
    st = eng.run()
    rows = sorted(drain_links(st, eng.window), key=_key)
    return exp, rows, Engine.metrics_dict(st)


# ---------------------------------------------------------------------------
# parity
# ---------------------------------------------------------------------------

def test_link_rows_bit_identical_cpu_vs_tpu(straight):
    exp, _, _, trows = straight
    crows = cpu_rows(exp)
    assert trows == crows
    assert trows  # an empty parity proves nothing
    for r in trows:
        assert all(f in r and isinstance(r[f], int) for f in LINK_FIELDS)
    # Traffic actually crossed the edge.
    assert any(r["pkts"] > 0 and r["bytes"] > 0 for r in trows)


@pytest.mark.slow
def test_link_rows_bit_identical_sharded(churn):
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 (virtual) devices")
    from shadow1_tpu.shard.engine import ShardedEngine

    exp, solo, _ = churn
    sh = ShardedEngine(exp, CHURN_PARAMS)
    st = sh.run(sh.init_state(), n_windows=sh.n_windows)
    shrows = sorted(drain_links(st, sh.window), key=_key)
    assert shrows == solo
    assert any(r["link_down_drops"] > 0 for r in solo)


@pytest.mark.slow
def test_link_rows_fleet_lane_vs_solo():
    from shadow1_tpu.fleet.engine import FleetEngine

    exp_a = filexfer_exp(seed=11)
    exp_b = filexfer_exp(seed=12)
    fleet = FleetEngine([exp_a, exp_b], PARAMS)
    st = fleet.run(n_windows=N_WINDOWS)
    recs = fleet.drain_rings(st)
    links = [r for r in recs if r["type"] == "link"]
    assert {r["exp"] for r in links} == {0, 1}
    for gid, exp in ((0, exp_a), (1, exp_b)):
        lane = sorted(
            ({k: v for k, v in r.items() if k != "exp"}
             for r in links if r["exp"] == gid), key=_key)
        _, solo = tpu_rows(exp)
        assert lane == solo, f"lane {gid} diverged from its solo run"


@pytest.mark.slow
def test_link_resume_reproduces_straight_run(tmp_path, straight):
    from shadow1_tpu.ckpt import load_state, save_state

    exp, _, _, straight_rows = straight
    eng = Engine(exp, PARAMS)
    st = eng.run(n_windows=12)
    first = drain_links(st, eng.window)
    assert all(r["window"] == 11 for r in first)
    path = str(tmp_path / "link.ckpt")
    save_state(st, path)
    eng2 = Engine(exp, PARAMS)
    st2 = load_state(eng2.init_state(), path)
    st2 = eng2.run(st2, n_windows=N_WINDOWS - 12)
    # Cumulative snapshots: the resumed run's boundary drain is the
    # straight run's, bit-identical — no baseline bookkeeping to restore.
    rest = sorted(drain_links(st2, eng2.window, start=12), key=_key)
    assert rest == straight_rows
    # The cursor never re-emits an already-drained boundary.
    assert drain_links(st2, eng2.window, start=N_WINDOWS) == []


def test_link_gap_on_cursor_regression(straight):
    # A fleet lane rebinding to a new experiment mid-sweep regresses the
    # window count below the stream cursor: one rebase marker, no rows.
    _, eng, st, _ = straight
    recs = drain_links(st, eng.window, start=N_WINDOWS + 5)
    assert recs == [{"type": "link_gap", "window": N_WINDOWS,
                     "expected_window": N_WINDOWS + 5}]


# ---------------------------------------------------------------------------
# drop attribution reconciles with the global counters (both engines)
# ---------------------------------------------------------------------------

def test_link_drop_columns_reconcile_with_global_counters(churn):
    exp, trows, tm = churn
    ceng = CpuEngine(exp, CHURN_PARAMS)
    ceng.run()
    crows = sorted(ceng.link_rows, key=_key)
    assert trows == crows
    for rows, m in ((trows, tm), (crows, ceng.metrics)):
        assert sum(r["pkts"] for r in rows) == m["pkts_sent"]
        assert sum(r["loss_drops"] for r in rows) == m["pkts_lost"]
        assert sum(r["link_down_drops"] for r in rows) == m["link_down_pkts"]
    # The scenario actually produced each drop class.
    assert tm["pkts_lost"] > 0 and tm["link_down_pkts"] > 0


def test_fault_and_link_planes_on_three_vertices_equal_the_oracle():
    """The fault plane's gates and the link plane's scatter take
    ``route_outbox``'s ``vs`` / ``vd``, which on a network of a few vertices
    are compares against the runs of ``host_vertex`` and no lookup (PR 42):
    three vertices, four runs of unequal length whose vertex ids do not rise
    with host id, an outage on one directed edge, a ramp on another — the
    drop counters and the [V, V] matrix of every link column equal the CPU
    oracle's."""
    import dataclasses

    from shadow1_tpu.consts import MS, SEC
    from shadow1_tpu.fault.schedule import FaultSchedule
    from tests.test_fault import assert_fault_parity

    h = 8
    exp = filexfer_exp(n_hosts=h, seed=5, flow=300_000, end=2 * SEC)
    exp.model_cfg["start_time"] = (1 + np.arange(h) * 10) * MS
    exp = dataclasses.replace(
        exp,
        # the server alone on vertex 2; clients on 0, 1 and 0 again
        host_vertex=np.array([2, 0, 0, 0, 1, 1, 0, 0], np.int32),
        lat_vv=np.array([[10, 30, 20], [30, 12, 25], [20, 25, 15]],
                        np.int64) * MS,
        loss_vv=np.array([[0, 0, 0.01], [0, 0, 0], [0.02, 0, 0]], np.float32),
        # under a packet's 1 ms on the wire: no reordering
        jitter_vv=np.array([[0, 0, 2], [0, 0, 1], [2, 1, 0]], np.int64)
        * (MS // 10),
        faults=FaultSchedule(
            link_src=[2], link_dst=[0], link_t0=[500 * MS], link_t1=[620 * MS],
            ramp_src=[2, 1], ramp_dst=[1, 2], ramp_t0=[700 * MS, 700 * MS],
            ramp_t1=[1600 * MS, 1600 * MS], ramp_loss=[0.10, 0.10]),
    )
    exp.validate()
    params = EngineParams(ev_cap=512, link_telem=1)
    ctx = Engine(exp, params).ctx
    assert ctx.vertex_runs == ((0, 2), (1, 0), (4, 1), (6, 0))
    assert ctx.has_link_fault and ctx.has_loss_ramp and ctx.has_jitter

    st, trows = tpu_rows(exp, params, n_windows=None)
    tm = Engine.metrics_dict(st)
    ceng = CpuEngine(exp, params)
    cm = ceng.run()
    assert_fault_parity(cm, tm)
    assert trows == sorted(ceng.link_rows, key=_key)

    def matrix(col):
        m = np.zeros((3, 3), np.int64)
        for r in trows:   # cumulative snapshots: the last window's stands
            m[r["src_vertex"], r["dst_vertex"]] = r[col]
        return m

    pkts, down, lost = (matrix(c) for c in
                        ("pkts", "link_down_drops", "loss_drops"))
    assert pkts.sum() == tm["pkts_sent"]
    # Clients talk to the server only: every packet has vertex 2 at an end.
    assert (pkts[2, :2] > 0).all() and (pkts[:2, 2] > 0).all()
    assert not pkts[:2, :2].any() and not pkts[2, 2]
    # The outage took packets on its edge alone; the ramp's edges, which
    # have no loss of their own, lost some.
    assert down[2, 0] == tm["link_down_pkts"] > 0 and down.sum() == down[2, 0]
    assert lost[2, 1] > 0 and lost[1, 2] > 0
    assert lost.sum() == tm["pkts_lost"]


@pytest.mark.slow
def test_link_nic_backlog_attribution():
    from tests.test_fidelity import _filexfer

    # A 3000-byte tx queue forces drop-tail: the per-edge column must
    # equal the global nic_tx_drops counter exactly (RED drops excluded).
    exp = _filexfer(qlen=3000)
    params = EngineParams(ev_cap=256, link_telem=1)
    eng = Engine(exp, params)
    st = eng.run()
    trows = sorted(drain_links(st, eng.window), key=_key)
    tm = Engine.metrics_dict(st)
    ceng = CpuEngine(exp, params)
    ceng.run()
    assert trows == sorted(ceng.link_rows, key=_key)
    assert tm["nic_tx_drops"] > 0
    for rows, m in ((trows, tm), (ceng.link_rows, ceng.metrics)):
        assert sum(r["nic_backlog_drops"] for r in rows) == m["nic_tx_drops"]


# ---------------------------------------------------------------------------
# off-state and guards
# ---------------------------------------------------------------------------

def test_links_off_leaves_state_layout_unchanged():
    import jax

    exp = filexfer_exp()
    off = Engine(exp, EngineParams())
    assert off.init_state().links is None
    # Same treedef as a pre-link state: checkpoints, sharding specs and
    # the traced program are untouched unless the plane is actually on
    # (the --state-digest zero-cost rule; opcensus guards the op counts).
    on = Engine(exp, PARAMS)
    t_off = jax.tree_util.tree_structure(off.init_state())
    t_on = jax.tree_util.tree_structure(on.init_state())
    assert t_off != t_on
    n_off = len(jax.tree_util.tree_leaves(off.init_state()))
    n_on = len(jax.tree_util.tree_leaves(on.init_state()))
    assert n_on == n_off + 1  # exactly the [V, V, F] accumulator


def test_link_buf_shape_and_dtype(straight):
    exp, _, st, _ = straight
    v = np.asarray(exp.lat_vv).shape[0]
    assert st.links.buf.shape == (v, v, len(LINK_FIELDS))
    assert st.links.buf.dtype == np.int64
    assert LINK_FIELDS[LINK_MAX_COL] == "queued_ns_max"


def test_link_telem_guards():
    from shadow1_tpu.telemetry.links import check_link_params

    from types import SimpleNamespace

    # EngineParams itself rejects anything but 0/1 at construction...
    with pytest.raises(AssertionError):
        EngineParams(link_telem=2)
    # ...and the engine-side guard reserves >1 for the top-K follow-up
    # (configs built outside the dataclass) and bounds the dense tensor.
    with pytest.raises(ValueError, match="top-K"):
        check_link_params(SimpleNamespace(link_telem=2), 4)
    with pytest.raises(ValueError, match="dense"):
        check_link_params(EngineParams(link_telem=1), 2000)


def test_link_records_digest_neutral(straight):
    # Turning the plane on must not perturb the state digests: the
    # accumulator is observability-only, never part of simulated state.
    import jax.numpy as jnp

    from shadow1_tpu.core.digest import state_digests

    exp, on, st_on, _ = straight
    off = Engine(exp, EngineParams())
    st_off = off.run(n_windows=N_WINDOWS)
    zero = jnp.zeros((), jnp.int64)
    d_off = np.asarray(state_digests(st_off, off.ctx, zero))
    d_on = np.asarray(state_digests(st_on, on.ctx, zero))
    assert (d_on == d_off).all()


# ---------------------------------------------------------------------------
# edge resolution (pcapdump --edge) and heartbeat emission
# ---------------------------------------------------------------------------

def test_resolve_edges_forms():
    from shadow1_tpu.config.experiment import resolve_edges

    names = ["nyc", "lon", "fra"]
    got = resolve_edges(["nyc:lon", "1:2", "fra:0", "nyc:lon"], names)
    assert got == ((0, 1), (1, 2), (2, 0))  # duplicates collapse


def test_resolve_edges_rejects_typos_with_suggestion():
    from shadow1_tpu.config.experiment import WatchlistError, resolve_edges

    names = ["nyc", "lon", "fra"]
    with pytest.raises(WatchlistError, match="did you mean 'lon'"):
        resolve_edges(["nyc:lno"], names)
    with pytest.raises(WatchlistError, match="out of range"):
        resolve_edges(["0:7"], names)
    with pytest.raises(WatchlistError, match="SRC_VERTEX:DST_VERTEX"):
        resolve_edges(["nyc"], names)
    with pytest.raises(WatchlistError, match="SRC_VERTEX:DST_VERTEX"):
        resolve_edges(["nyc:"], names)


def test_heartbeat_emits_link_records():
    import io
    import json

    from shadow1_tpu.obs import run_with_heartbeat

    exp = filexfer_exp()
    eng = Engine(exp, PARAMS)
    buf = io.StringIO()
    _, hb = run_with_heartbeat(eng, n_windows=20, every_windows=10,
                               stream=buf)
    lines = [json.loads(x) for x in buf.getvalue().splitlines()]
    links = [r for r in lines if r["type"] == "link"]
    # Two chunk boundaries, one cumulative snapshot per active edge each.
    assert sorted({r["window"] for r in links}) == [9, 19]
    assert hb.link_records == links
