"""The two window-end reads that are no longer gathers (PR 34), each against
the gathered form it replaced, which is kept here as reference code.

``net.make_pre_window``: the packet-length plane rides the (time, tb) sort as
a payload operand where it used to be read back with ``take_along_axis``
through the sorted index. The sort is stable, so the two agree on every row,
tied ones included; the whole output state must be equal on a buffer with
time ties, full (time, tb) ties, unselected slots and a down host.

``core/engine.route_outbox``: on a one-vertex network (table shape [1, 1])
``lat_vv[vs, vd]``, ``jitter_vv[vs, vd]`` and ``loss_thr_vv[vs, vd]`` are the
table's one element broadcast over the outbox rows; under ``vmap`` each lane
keeps its own threshold. With more vertices (PR 42) ``vs`` is ``host_vertex``
broadcast down the slot axis, ``vd`` compares of ``dst`` against the runs of
``host_vertex`` and ``table[vs, vd]`` two steps, the table's rows per host
and a pick over ``vd`` per slot: held to the gathered form on two, three and
six vertices (both steps selects), on seventeen and on two hundred (PR 51:
past MAX_DENSE_VERTICES the per-host step is one read of H table rows,
``core/dense.table_rows``, and the pick a masked sum), on runs of unequal
length and vertex ids that do not rise with host id, on a ``spread`` map
(``host_vertex[dst]`` stays a lookup), with latencies above 2**32 ns and
thresholds whose two half words are both set, for a shard's block of hosts,
and under ``vmap`` with per-lane ``[V, V]`` thresholds; one vertex past
MAX_ROW_VERTICES ``table[vs, vd]`` is still what is traced.

And the guard on the whole window end (PR 40): ``core/engine.deliver_window``
runs its body only when some host (of some lane, on a fleet) sent this
window. Held against the unguarded body on states that sent and that did
not, solo and under the fleet's named ``vmap``; on a fleet whose lanes
disagree, lane against solo run; in the traced programs (the merge's sort
under a ``cond``); and on the sharded engine, which keeps its window end
unguarded because its exchange is a collective.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shadow1_tpu import rng
from shadow1_tpu.config.compiled import CompiledExperiment
from shadow1_tpu.consts import (
    K_NONE,
    K_PKT,
    K_PKT_DELIVER,
    MS,
    NP,
    R_JITTER,
    R_LOSS,
    SEC,
    WIRE_OVERHEAD,
    EngineParams,
    packet_tb,
)
from shadow1_tpu.core.engine import (
    MAX_DENSE_VERTICES,
    MAX_ROW_VERTICES,
    MAX_VERTEX_RUNS,
    Engine,
    FlatPackets,
    _window_end,
    deliver_window,
    route_outbox,
)
from shadow1_tpu.core.events import I64_MAX, tb_split
from shadow1_tpu.core.outbox import Outbox
from shadow1_tpu.fleet.engine import (
    LANE_AXIS,
    FleetEngine,
    fleet_metrics_per_exp,
    slice_experiment,
)
from shadow1_tpu.net.nic import ser_delay
from shadow1_tpu.shard.engine import ShardedEngine
from shadow1_tpu.telemetry.registry import LANE_PROGRAM_FIELDS
from shadow1_tpu.tools.opcensus import _sub_jaxprs
from tests.parity import lane_metrics, unlike_leaves
from tests.test_net_parity import filexfer_exp
from tests.test_tor_fleet import _named_eqns

H, EV_CAP, OB_CAP = 6, 16, 8
WIN = 10 * MS
MODES = ("jit", "vmap2")


def _assert_trees_equal(got, want):
    g, tree_g = jax.tree_util.tree_flatten(got)
    w, tree_w = jax.tree_util.tree_flatten(want)
    assert tree_g == tree_w
    for a, b in zip(g, w):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _stack(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


# ---------------------------------------------------------------------------
# pre_window
# ---------------------------------------------------------------------------

def _net_exp(seed=5, loss=0.0, stop=True):
    exp = filexfer_exp(n_hosts=H, seed=seed, loss=loss, flow=30_000, end=SEC)
    if stop:
        exp.stop_time[3] = 2 * MS  # host 3 is down from 2 ms on: has_stop
    return exp


def _net_engine(**params) -> Engine:
    return Engine(_net_exp(), EngineParams(ev_cap=EV_CAP, outbox_cap=OB_CAP,
                                           **params))


def _arrivals(seed: int):
    """EventBuf planes of a window's start: K_PKT slots due in the window and
    after it, other kinds, free slots holding stale payload; times from a
    handful of values (ties), tie-breaks from three (full ties)."""
    r = np.random.default_rng(seed)
    shape = (EV_CAP, H)
    kind = r.choice([K_PKT, K_PKT, K_PKT, K_NONE, K_PKT_DELIVER], shape)
    time = r.choice([1 * MS, 2 * MS, 2 * MS, 3 * MS, 7 * MS, 12 * MS], shape)
    time = np.where(kind == K_NONE, I64_MAX, time)
    tb = r.choice([5, (3 << 32) + 1, (3 << 32) + 0x9000_0000], shape)
    p = r.integers(0, 1 << 20, (NP,) + shape)
    p[4] = r.integers(1, 1461, shape)  # the packet length: no two runs alike
    thi, tlo = tb_split(jnp.asarray(time, jnp.int64))
    bhi, blo = tb_split(jnp.asarray(tb, jnp.int64))
    return dict(time_hi=thi, time_lo=tlo, tb_hi=bhi, tb_lo=blo,
                kind=jnp.asarray(kind, jnp.int32), p=jnp.asarray(p, jnp.int32))


def _sort_keys(buf, sel):
    """pre_window's sort operands, payload aside."""
    cap, h = buf.kind.shape
    i32max = jnp.iinfo(jnp.int32).max
    idx = jnp.broadcast_to(jnp.arange(cap, dtype=jnp.int32)[:, None], (cap, h))
    return (jnp.where(sel, buf.abs_time(), I64_MAX),
            jnp.where(sel, buf.tb_hi, i32max),
            jnp.where(sel, buf.tb_lo, i32max), idx)


def _pre_window_gathered(ctx):
    """net.make_pre_window as it stood before PR 34: plen read back through
    the sorted index, rx_free as ``free[-1, :]``."""
    from shadow1_tpu.fault.plane import hosts_down_at

    neg = -(1 << 62)

    def pre_window(st, _ctx, win_end):
        buf = st.evbuf
        abs_t = buf.abs_time()
        sel = (buf.kind == K_PKT) & (abs_t < win_end)
        kind0, time0 = buf.kind, abs_t
        m = st.metrics
        if ctx.has_stop:
            down = sel & hosts_down_at(ctx.fault_down, ctx.fault_up, abs_t)
            sel = sel & ~down
            kind0 = jnp.where(down, K_NONE, kind0)
            time0 = jnp.where(down, I64_MAX, time0)
            m = m._replace(down_events=m.down_events
                           + down.sum(dtype=jnp.int64))
        t_s, _hi_s, _lo_s, idx_s = jax.lax.sort(
            _sort_keys(buf, sel), dimension=0, num_keys=3)
        valid = t_s < I64_MAX
        plen = jnp.take_along_axis(buf.p[4], idx_s, axis=0)
        wire = jnp.where(valid, plen.astype(jnp.int64) + WIRE_OVERHEAD, 0)
        ser = jnp.where(
            valid, ser_delay(wire, ctx.bw_dn[None, :], ctx.ser_dn), 0)
        pq = (ser, jnp.where(valid, t_s + ser, neg))
        p_pre, q_pre = jax.lax.associative_scan(
            lambda a, b: (a[0] + b[0], jnp.maximum(a[1] + b[0], b[1])),
            pq, axis=0,
        )
        free = jnp.maximum(st.model.nic.rx_free[None, :] + p_pre, q_pre)
        ready = free - ser
        _i, ready_o, valid_o = jax.lax.sort(
            (idx_s, ready, valid.astype(jnp.int32)), dimension=0, num_keys=1)
        vo = valid_o != 0
        nic = st.model.nic._replace(
            rx_free=free[-1, :],
            rx_bytes=st.model.nic.rx_bytes + wire.sum(axis=0),
        )
        thi, tlo = tb_split(jnp.where(vo, ready_o, time0))
        evbuf = buf._replace(
            kind=jnp.where(vo, K_PKT_DELIVER, kind0), time_hi=thi, time_lo=tlo)
        return st._replace(
            evbuf=evbuf, model=st.model._replace(nic=nic), metrics=m)

    return pre_window


@pytest.fixture(scope="module")
def net():
    eng = _net_engine()
    assert eng.ctx.has_stop and eng._pre_window is not None
    st0 = eng.init_state()

    def state(seed):
        st = st0._replace(evbuf=st0.evbuf._replace(**_arrivals(seed)))
        # A downlink still busy from the window before on some hosts.
        busy = jnp.asarray([0, 3 * MS, 0, 0, 1 * MS, 9 * MS], jnp.int64)
        return st._replace(model=st.model._replace(
            nic=st.model.nic._replace(rx_free=busy)))

    return eng, state


@pytest.mark.parametrize("mode", MODES)
def test_pre_window_equals_gathered_form(net, mode):
    eng, state = net
    new, old = eng._pre_window, _pre_window_gathered(eng.ctx)
    win_end = jnp.asarray(WIN, jnp.int64)
    if mode == "vmap2":
        st = _stack([state(1), state(2)])
        run = lambda f: jax.jit(jax.vmap(lambda s: f(s, eng.ctx, win_end)))(st)
    else:
        st = state(1)
        run = lambda f: jax.jit(lambda s: f(s, eng.ctx, win_end))(st)
    got, want = run(new), run(old)
    _assert_trees_equal(got, want)
    # The case is not empty: arrivals were scheduled, some behind a busy
    # downlink, the down host's were discarded, later ones left alone.
    n_pkt = lambda s: int((np.asarray(s.evbuf.kind) == K_PKT).sum())
    n_dlv = lambda s: int((np.asarray(s.evbuf.kind) == K_PKT_DELIVER).sum())
    assert n_dlv(got) - n_dlv(st) >= 20
    assert 0 < n_pkt(got) < n_pkt(st)
    assert int(np.asarray(got.metrics.down_events).sum()) > 0
    assert (np.asarray(got.model.nic.rx_free)
            != np.asarray(st.model.nic.rx_free)).any()


@pytest.mark.parametrize("mode", MODES)
def test_plen_as_sort_operand_equals_take_along_axis(net, mode):
    """The premise alone: a payload operand of the stable sort is the plane
    gathered through the sorted index — on every row, ties included."""
    _, state = net

    def both(buf):
        sel = (buf.kind == K_PKT) & (buf.abs_time() < WIN)
        keys = _sort_keys(buf, sel)
        *_, idx_s = jax.lax.sort(keys, dimension=0, num_keys=3)
        t_s, *_, plen = jax.lax.sort(keys + (buf.p[4],), dimension=0,
                                     num_keys=3)
        return plen, jnp.take_along_axis(buf.p[4], idx_s, axis=0), t_s, keys

    if mode == "vmap2":
        buf = _stack([state(3).evbuf, state(4).evbuf])
        plen, taken, t_s, keys = jax.jit(jax.vmap(both))(buf)
    else:
        plen, taken, t_s, keys = jax.jit(both)(state(3).evbuf)
    valid = np.asarray(t_s) < I64_MAX
    assert valid.any() and not valid.all()
    np.testing.assert_array_equal(np.asarray(plen)[valid],
                                  np.asarray(taken)[valid])
    np.testing.assert_array_equal(np.asarray(plen), np.asarray(taken))
    # Some host holds two selected rows with one (time, tb_hi, tb_lo).
    t, hi, lo, _ = (np.asarray(k).reshape(-1, EV_CAP, H)[0] for k in keys)
    assert any(
        len({(t[c, h], hi[c, h], lo[c, h]) for c in range(EV_CAP)
             if t[c, h] < I64_MAX}) < (t[:, h] < I64_MAX).sum()
        for h in range(H))


# ---------------------------------------------------------------------------
# route_outbox
# ---------------------------------------------------------------------------

def _route_outbox_gathered(ctx, ob):
    """core/engine.route_outbox as it stood before PR 34, fault and link
    planes off: one lookup per outbox row whatever the tables' shape."""
    cap, h = ob.dst.shape
    mask = jnp.arange(cap)[:, None] < ob.cnt[None, :]
    src = jnp.broadcast_to(ctx.hosts[None, :], (cap, h))

    def flat(x):
        return x.reshape(x.shape[:-2] + (cap * h,))

    fmask, fsrc, fdst = flat(mask), flat(src), flat(ob.dst)
    fdst_safe = jnp.where(fmask, fdst, 0)
    fdep = flat(ob.abs_depart())
    fctr = flat(ob.ctr).astype(jnp.int64)
    vs = ctx.host_vertex[fsrc]
    vd = ctx.host_vertex[fdst_safe]
    arrival = fdep + ctx.lat_vv[vs, vd]
    if ctx.has_jitter:
        jit = ctx.jitter_vv[vs, vd]
        jbits = rng.bits_v(ctx.key, R_JITTER, fsrc, fctr)
        arrival = (arrival + rng.randint(jbits, 2 * jit + 1).astype(jnp.int64)
                   - jit)
    bits = rng.bits_v(ctx.key, R_LOSS, fsrc, fctr)
    lost = fmask & rng.uniform_lt(bits, ctx.loss_thr_vv[vs, vd])
    fp = FlatPackets(
        dst=fdst_safe, arrival=arrival,
        tb=packet_tb(fsrc.astype(jnp.int64), fctr), kind=flat(ob.kind),
        p=flat(ob.p), keep=fmask & ~lost,
    )
    return (fp, fmask.sum(dtype=jnp.int64), lost.sum(dtype=jnp.int64),
            jnp.zeros((), jnp.int64))


def _phold(**net):
    h = len(net["host_vertex"])
    return Engine(CompiledExperiment(
        n_hosts=h, seed=7, end_time=SEC,
        bw_up=np.full(h, 10**9, np.int64), bw_dn=np.full(h, 10**9, np.int64),
        model="phold", model_cfg={"mean_delay_ns": float(MS)}, **net)).ctx


def _runs(*runs):
    """host_vertex from (vertex, length) runs."""
    return np.repeat([v for v, _ in runs], [n for _, n in runs]).astype(
        np.int32)


# name -> (host_vertex, vertex_runs is kept). V1 and V2 have written-out
# tables; the others' are drawn: every entry of a table distinct, so that a
# read of the wrong entry shows.
NETS = {
    "V1": (np.zeros(H, np.int32), True),
    "V2": (np.array([0, 1, 1, 0, 1, 0], np.int32), True),
    # six runs of unequal length, as bitcoin5k_regions' six regions
    "V6_runs6": (_runs((0, 5), (1, 7), (2, 1), (3, 3), (4, 2), (5, 2)), True),
    # vertex ids that do not rise with host id, one vertex in two runs
    "V3_unordered": (_runs((2, 3), (0, 4), (1, 2), (0, 3)), True),
    # ``vertex: spread``: a run per host, more than MAX_VERTEX_RUNS
    "V3_spread": (np.arange(MAX_VERTEX_RUNS + 8, dtype=np.int32) % 3, False),
    # one vertex more than MAX_DENSE_VERTICES: the per-host step is the read
    # of H table rows
    "V17": (np.repeat(np.arange(MAX_DENSE_VERTICES + 1), 2).astype(np.int32),
            True),
    # bitcoin5k_cities in miniature: 200 vertices, two hosts each, dealt
    "V200_spread": (np.random.default_rng(200).permutation(
        np.arange(400, dtype=np.int32) % 200), False),
    # one vertex past MAX_ROW_VERTICES (few hosts: most vertices are empty)
    "V_past_rows": (np.array([0, MAX_ROW_VERTICES, 512, 1, MAX_ROW_VERTICES,
                              7], np.int32), True),
}
# The nets whose path tables are read per host row and picked per slot.
ROW_NETS = ("V17", "V200_spread")


def _ctx(net: str):
    host_vertex, _ = NETS[net]
    if net == "V1":
        return _phold(lat_vv=np.full((1, 1), WIN, np.int64),
                      loss_vv=np.full((1, 1), 0.3, np.float32),
                      jitter_vv=np.full((1, 1), 2 * MS, np.int64),
                      host_vertex=host_vertex)
    if net == "V2":
        return _phold(lat_vv=np.array([[10, 25], [40, 15]], np.int64) * MS,
                      loss_vv=np.array([[0.0, 0.5], [0.9, 0.2]], np.float32),
                      jitter_vv=np.array([[0, 2], [3, 1]], np.int64) * MS,
                      host_vertex=host_vertex)
    v = int(host_vertex.max()) + 1
    r = np.random.default_rng(v)
    # Two thirds of the latencies beyond 32 bits of ns, so that both half
    # words are read; the smallest, 7 ms, is the window.
    perm = r.permutation(v * v).reshape(v, v).astype(np.int64)
    lat = (1 + perm) * 7 * MS + (perm % 3 << 33)
    ctx = _phold(lat_vv=lat,
                 loss_vv=(r.permutation(v * v).reshape(v, v)
                          / (v * v)).astype(np.float32),
                 # up to 4 ms, under every latency
                 jitter_vv=(r.permutation(v * v).reshape(v, v)
                            * (4 * MS // (v * v)) + 1),
                 host_vertex=host_vertex)
    if v <= MAX_DENSE_VERTICES:
        return ctx
    # A third of the thresholds with both half words set (such a path loses
    # every packet): a high word read as 0, or another entry's, shows.
    hi = jnp.asarray((perm % 3 == 1).astype(np.uint64) << np.uint64(32))
    return dataclasses.replace(ctx, loss_thr_vv=ctx.loss_thr_vv | hi)


def _outbox(seed: int, h: int = H) -> Outbox:
    r = np.random.default_rng(seed)
    shape = (OB_CAP, h)
    dhi, dlo = tb_split(jnp.asarray(r.integers(0, WIN, shape), jnp.int64))
    i32 = lambda a: jnp.asarray(a, jnp.int32)
    cnt = r.integers(0, OB_CAP + 1, h)
    cnt[0], cnt[1] = OB_CAP, 0  # a full column and an empty one
    # Live rows name real hosts, as the engine's do; rows at or above cnt
    # hold stale destinations, some out of range.
    dst = r.integers(0, h + 3, shape)
    dst = np.where(np.arange(OB_CAP)[:, None] < cnt[None, :], dst % h, dst)
    return Outbox(
        dst=i32(dst), kind=i32(r.integers(1, 5, shape)),
        depart_hi=dhi, depart_lo=dlo, ctr=i32(r.integers(0, 1 << 20, shape)),
        p=i32(r.integers(0, 1 << 20, (NP,) + shape)), cnt=i32(cnt),
        pkt_ctr=jnp.asarray(r.integers(0, 1 << 20, h), jnp.int64),
    )


def _path_lookups(ctx, ob) -> int:
    """The ``gather`` equations ``route_outbox`` traces under
    ``phase:route_path``: ``table[vs, vd]`` of a path table."""
    jaxpr = jax.make_jaxpr(lambda o: route_outbox(ctx, o))(ob).jaxpr
    return sum(e.primitive.name == "gather" and "phase:route_path" in stack
               for e, stack in _named_eqns(jaxpr))


@pytest.mark.parametrize("net", NETS)
def test_route_outbox_equals_gathered_form(net):
    ctx = _ctx(net)
    host_vertex, few_runs = NETS[net]
    v = int(host_vertex.max()) + 1
    assert ctx.has_jitter and ctx.lat_vv.shape == (v, v)
    assert (ctx.vertex_runs is not None) == few_runs
    if few_runs:
        starts, verts = zip(*ctx.vertex_runs)
        assert starts[0] == 0 and len(starts) <= MAX_VERTEX_RUNS
        np.testing.assert_array_equal(
            np.repeat(verts, np.diff(starts + (len(host_vertex),))),
            host_vertex)
    ob = _outbox(11, ctx.n_hosts)
    # Latency, jitter and threshold: a lookup each past MAX_ROW_VERTICES,
    # none below it.
    assert (v > MAX_ROW_VERTICES) == (net == "V_past_rows")
    assert _path_lookups(ctx, ob) == (3 if net == "V_past_rows" else 0)
    got = jax.jit(lambda o: route_outbox(ctx, o))(ob)
    want = jax.jit(lambda o: _route_outbox_gathered(ctx, o))(ob)
    _assert_trees_equal(got, want)
    fp, n_sent, n_lost, n_linkdown = got
    assert int(n_sent) == int(np.asarray(ob.cnt).sum()) > 0
    assert 0 < int(n_lost) < int(n_sent) and int(n_linkdown) == 0
    flight = (np.asarray(fp.arrival)
              - np.asarray(ob.abs_depart()).reshape(-1))[np.asarray(fp.keep)]
    if net == "V1":
        assert flight.min() >= 8 * MS and flight.max() <= 12 * MS
        assert len(set(flight.tolist())) > 1          # the jitter draws
    elif net == "V2":
        assert flight.min() < 12 * MS and flight.max() > 35 * MS  # by path
    else:
        # Many paths of the network were taken, each at its own latency.
        assert len(set((flight // MS).tolist())) > min(v, ctx.n_hosts)


@pytest.mark.parametrize("block", [0, 1], ids=["lo", "hi"])
@pytest.mark.parametrize("net", ["V6_runs6", "V3_spread", *ROW_NETS])
def test_route_outbox_of_a_shard_block_equals_gathered_form(net, block):
    """As the sharded engine calls it: ``hosts`` a traced block of the
    global ids, ``host_vertex`` the whole map, destinations global. On the
    ROW_NETS the table rows read are the block's hosts'."""
    ctx = _ctx(net)
    n = ctx.n_hosts // 2
    ob = _outbox(31 + block, n)
    # Live rows name hosts of either block.
    ob = ob._replace(dst=jnp.where(
        jnp.arange(OB_CAP)[:, None] < ob.cnt[None, :],
        (ob.dst * 7 + 3) % ctx.n_hosts, ob.dst))
    hosts = jnp.arange(block * n, (block + 1) * n, dtype=jnp.int32)

    def of_block(route):
        return jax.jit(lambda o, hs: route(
            dataclasses.replace(ctx, n_hosts=n, hosts=hs), o))(ob, hosts)

    got = of_block(route_outbox)
    _assert_trees_equal(got, of_block(_route_outbox_gathered))
    assert int(got[1]) > int(got[2]) > 0
    assert (np.asarray(got[0].dst)[np.asarray(got[0].keep)] >= n).any()


def _lanes_keep_their_thresholds(ctx, thr, key, seeds=(21, 22)):
    """Under the fleet's vmap the lane's loss_thr_vv (and its key) are
    batched leaves: each lane draws against its own, and equals the solo
    program closed over that lane's constants."""
    obs = [_outbox(s, ctx.n_hosts) for s in seeds]

    def lanes(route):
        return jax.jit(jax.vmap(lambda o, t, k: route(
            dataclasses.replace(ctx, loss_thr_vv=t, key=k), o)))(
                _stack(obs), thr, key)

    got = lanes(route_outbox)
    _assert_trees_equal(got, lanes(_route_outbox_gathered))
    for i, ob in enumerate(obs):
        solo = route_outbox(
            dataclasses.replace(ctx, loss_thr_vv=thr[i], key=key[i]), ob)
        _assert_trees_equal(jax.tree_util.tree_map(lambda x: x[i], got), solo)
    return got, obs


def test_route_outbox_one_vertex_fleet_lanes_keep_their_thresholds():
    thr = jnp.stack([
        jnp.asarray(rng.prob_threshold(np.full((1, 1), p, np.float32)))
        for p in (0.05, 0.6)])
    key = jnp.stack([rng.base_key(101), rng.base_key(202)])
    got, _ = _lanes_keep_their_thresholds(_ctx("V1"), thr, key)
    n_sent, n_lost = np.asarray(got[1]), np.asarray(got[2])
    assert 0 < n_lost[0] / n_sent[0] < 0.25 < 0.4 < n_lost[1] / n_sent[1] < 0.8


@pytest.mark.parametrize("net", ["V2", "V6_runs6", "V3_spread", *ROW_NETS])
def test_route_outbox_fleet_lanes_keep_their_path_thresholds(net):
    """The same with V > 1: a traced, batched [V, V] table read by selects
    (on the spread map through the gathered vd), on the ROW_NETS cut into
    its byte planes lane by lane."""
    ctx = _ctx(net)
    v = ctx.lat_vv.shape[0]
    # Lane 0 loses only on paths out of vertex 0, lane 1 only on the others.
    p = np.zeros((2, v, v), np.float32)
    p[0, 0, :], p[1, 1:, :] = 0.7, 0.7
    thr = jnp.stack([jnp.asarray(rng.prob_threshold(x)) for x in p])
    key = jnp.stack([rng.base_key(101), rng.base_key(202)])
    (fp, _, n_lost, _), obs = _lanes_keep_their_thresholds(ctx, thr, key)
    sent = (np.arange(OB_CAP)[:, None]
            < np.asarray(_stack(obs).cnt)[:, None, :]).reshape(2, -1)
    lost = ~np.asarray(fp.keep) & sent
    from_v0 = np.tile(np.asarray(ctx.host_vertex) == 0, OB_CAP)
    assert lost[0].any() and lost[1].any()
    assert not (lost[0] & ~from_v0).any() and not (lost[1] & from_v0).any()
    assert (np.asarray(n_lost) == lost.sum(axis=1)).all()


# ---------------------------------------------------------------------------
# deliver_window: the window end runs only when some host sent
# ---------------------------------------------------------------------------

def _end_state(eng: Engine, seed: int, sent: bool):
    """A state at a window's end: events pending, and an outbox that holds
    rows (``sent``) or none — stale rows above ``cnt`` either way."""
    st = eng.init_state()
    ob = _outbox(seed)
    if not sent:
        ob = ob._replace(cnt=jnp.zeros_like(ob.cnt))
    return st._replace(evbuf=st.evbuf._replace(**_arrivals(seed)),
                       outbox=ob._replace(pkt_ctr=st.outbox.pkt_ctr))


def _counted(st, runs):
    m = st.metrics
    runs = jnp.asarray(runs, jnp.int64)
    return st._replace(metrics=m._replace(
        runs_window_end=m.runs_window_end + runs,
        route_rows=m.route_rows + runs * int(np.prod(st.outbox.dst.shape[-2:]))))


@pytest.fixture(scope="module", params=[0, 1], ids=["links_off", "links_on"])
def end_engine(request):
    eng = _net_engine(link_telem=request.param)
    assert eng.ctx.has_stop and eng.ctx.lane_axis is None
    assert (eng.init_state().links is not None) == bool(request.param)
    return eng


@pytest.mark.parametrize("sent", [False, True], ids=["empty", "sent"])
def test_the_guarded_window_end_equals_the_unguarded_body(end_engine, sent):
    """Solo: with an empty outbox the state comes back leaf for leaf
    (``runs_window_end`` too: + 0), which is also what the body makes of it;
    with rows in it, the body's result and one window counted."""
    ctx = end_engine.ctx
    st = _end_state(end_engine, 31, sent)
    got = jax.jit(lambda s: deliver_window(s, ctx))(st)
    body = jax.jit(lambda s: _window_end(s, ctx))(st)
    _assert_trees_equal(got, _counted(body, int(sent)))
    if not sent:
        _assert_trees_equal(got, st)
    else:
        assert int(got.metrics.pkts_sent) == int(np.asarray(st.outbox.cnt).sum())
        assert int(got.metrics.pkts_delivered) > 0
        assert int(got.metrics.down_pkts) > 0          # has_stop is in play
        assert int(np.asarray(got.outbox.cnt).sum()) == 0
        assert unlike_leaves(got, st)


@pytest.mark.parametrize("sent", [(False, False), (True, False), (True, True)],
                         ids=["none_sent", "one_sent", "both_sent"])
def test_under_the_lane_axis_the_window_end_runs_for_all_lanes_or_none(
        end_engine, sent):
    """Fleet: the predicate is "some host of some lane". A lane that sent
    nothing beside one that did runs the body, as the identity; every lane
    equals its solo ``deliver_window`` but for the program's own count,
    which is one number in all lanes."""
    ctx = dataclasses.replace(end_engine.ctx, lane_axis=LANE_AXIS)
    sts = [_end_state(end_engine, 41 + i, s) for i, s in enumerate(sent)]
    got = jax.jit(jax.vmap(lambda s: deliver_window(s, ctx),
                           axis_name=LANE_AXIS))(_stack(sts))
    body = jax.jit(jax.vmap(lambda s: _window_end(s, ctx)))(_stack(sts))
    _assert_trees_equal(got, _counted(body, [int(any(sent))] * len(sent)))
    for i, st in enumerate(sts):
        lane = jax.tree_util.tree_map(lambda x: x[i], got)
        solo = jax.jit(lambda s: deliver_window(s, end_engine.ctx))(st)
        assert not unlike_leaves(lane, solo)
        assert int(solo.metrics.runs_window_end) == int(sent[i])
        assert int(lane.metrics.runs_window_end) == int(any(sent))
        if not sent[i]:
            assert not unlike_leaves(lane, st)    # rode along as the identity


def _eqns_under(jaxpr, inside=()):
    """(eqn, names of the control-flow eqns it sits in) for every equation,
    sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn, inside
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from _eqns_under(sub, inside + (eqn.primitive.name,))


def _merge_sorts(jaxpr):
    """``deliver_batch``'s sort (one operand; ``pre_window``'s carry four or
    five), each with the control flow it sits in."""
    return [inside for eqn, inside in _eqns_under(jaxpr)
            if eqn.primitive.name == "sort" and len(eqn.invars) == 1]


@pytest.mark.parametrize("which", ["solo", "fleet"])
def test_the_merge_s_sort_sits_inside_the_window_end_s_conditional(which):
    """The traced window program: the one sort of ``deliver_batch`` is under
    a ``cond`` (so an empty window does not pay it), on a fleet too: a
    ``cond`` with a per-lane predicate would have been batched away
    (``test_fleet_guards`` counts the lowered ``case`` ops)."""
    if which == "solo":
        eng = _net_engine()
        args = (jax.eval_shape(eng.init_state), jnp.asarray(0, jnp.int32))
    else:
        eng = FleetEngine([_net_exp(5), _net_exp(6, loss=0.05)],
                          EngineParams(ev_cap=EV_CAP, outbox_cap=OB_CAP))
        args = (jax.eval_shape(eng.init_state), jnp.asarray(0, jnp.int32),
                eng._variants)
    jaxpr = jax.make_jaxpr(eng._run_jit)(*args).jaxpr
    sorts = _merge_sorts(jaxpr)
    assert len(sorts) == 1 and "cond" in sorts[0], sorts
    # The window loop holds it; the round loop (a while inside) does not.
    assert sorts[0].count("while") == 1, sorts


N_RUN = 120


@pytest.fixture(scope="module")
def quiet_fleet():
    """Two filexfer lanes: the lossy one stalls on a lost segment in window
    10 while the lossless one sends on to window 16; both are silent until
    the lossy lane's retransmit timers pop at 1.0 s (window 100)."""
    exps = [_net_exp(5, stop=False), _net_exp(6, loss=0.2, stop=False)]
    params = EngineParams(ev_cap=256, outbox_cap=32, metrics_ring=N_RUN)
    st = FleetEngine(exps, params).run(n_windows=N_RUN)
    solos = [Engine(exp, params).run(n_windows=N_RUN) for exp in exps]
    return st, solos


@pytest.mark.parametrize("lane", range(2))
def test_a_lane_of_a_fleet_whose_lanes_send_in_other_windows_equals_its_solo_run(
        quiet_fleet, lane):
    st, solos = quiet_fleet
    assert not unlike_leaves(slice_experiment(st, lane), solos[lane])
    m = fleet_metrics_per_exp(st)[lane]
    assert m["pkts_sent"] > 40 and m["ev_overflow"] == m["ob_overflow"] == 0
    assert lane_metrics(m) == lane_metrics(Engine.metrics_dict(solos[lane]))


def test_runs_window_end_counts_the_windows_in_which_some_lane_sent(quiet_fleet):
    from shadow1_tpu.telemetry.ring import drain_ring

    st, solos = quiet_fleet
    assert "runs_window_end" in LANE_PROGRAM_FIELDS
    lanes = fleet_metrics_per_exp(st)
    solo = [Engine.metrics_dict(s) for s in solos]

    def sent_windows(s):
        rows = drain_ring(s, 10 * MS)
        assert len(rows) == N_RUN
        return {r["window"] for r in rows if r["pkts_sent"]}

    per_lane = [sent_windows(s) for s in solos]
    assert all(len(w) == m["runs_window_end"] for w, m in zip(per_lane, solo))
    union = per_lane[0] | per_lane[1]
    # One number in every lane: the windows in which SOME lane sent — more
    # than either lane's own (the lanes disagreed), fewer than all (the
    # guard engaged).
    assert [ln["runs_window_end"] for ln in lanes] == [len(union)] * 2
    assert max(m["runs_window_end"] for m in solo) < len(union) < N_RUN
    assert all(ln["windows"] == N_RUN for ln in lanes)
    assert FleetEngine.metrics_dict(st)["runs_window_end"] == len(union)


# ---- the sharded engine keeps its window end unguarded ------------------------

@pytest.fixture(scope="module")
def sharded2():
    exp = _net_exp(5, stop=False)
    params = EngineParams(ev_cap=256, outbox_cap=32)
    return exp, params, ShardedEngine(exp, params, devices=jax.devices()[:2])


def test_the_sharded_window_end_s_all_to_all_is_under_no_conditional(sharded2):
    """Every shard must enter the exchange every window: a predicate that
    differed by shard would hang the collective."""
    _, _, sh = sharded2
    assert sh.n_dev == 2
    st = jax.eval_shape(sh.init_state)
    jaxpr = jax.make_jaxpr(sh._get_run(sh._x2x_cap))(
        st, jnp.asarray(0, jnp.int32)).jaxpr
    a2a = [inside for eqn, inside in _eqns_under(jaxpr)
           if eqn.primitive.name == "all_to_all"]
    assert a2a and all("cond" not in inside for inside in a2a), a2a
    assert all("cond" not in inside for inside in _merge_sorts(jaxpr))


def test_the_sharded_engine_keeps_parity_over_empty_windows_and_counts_them_all(
        sharded2):
    exp, params, sh = sharded2
    m2 = ShardedEngine.metrics_dict(sh.run(n_windows=N_RUN))
    m1 = Engine.metrics_dict(Engine(exp, params).run(n_windows=N_RUN))
    for k in ("events", "windows", "pkts_sent", "pkts_delivered", "pkts_lost",
              "ev_overflow", "ob_overflow", "x2x_overflow", "pops_deliver",
              "pops_timer", "pops_app", "outbox_hosts", "active_hosts"):
        assert m2[k] == m1[k], (k, m2[k], m1[k])
    assert m1["pkts_sent"] > 40
    # The solo engine skipped its empty windows; the sharded one ran all.
    assert m1["runs_window_end"] < m1["windows"] == N_RUN
    assert m2["runs_window_end"] == m2["windows"] == N_RUN
