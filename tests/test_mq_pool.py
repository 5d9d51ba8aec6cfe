"""The message-boundary queue as one pool a host (``tcp/tcp.py``: ``mq_sock`` /
``mq_end`` / ``mq_meta`` ``[P, H]``, P = ``EngineParams.mq_pool``).

(1) the miniatures equal the CPU oracle counter for counter and ``dg_tcp`` word
for word, the Tor ones under the derived pool and ``bitcoin64`` under a pool of
16 slots for a host that holds 8; (2) ``mq_max_fill`` is the oracle's high-water of a host's
Σ ``len(k.mq)``; (3) a host handed more boundaries than its pool holds counts
``mq_overflow`` exactly, the fleet's halt names ``msgq_pool``, and a
transactional retry grows the pool to the straight run's result; (4) a socket
at ``msgq_cap`` refuses the next boundary whatever room the pool has (the
reference's rule, in both apps); (5) the pool migrates between sizes as a set.
"""

import dataclasses
import os
import types

import jax.numpy as jnp
import numpy as np
import pytest
import yaml

from shadow1_tpu.config.experiment import build_experiment
from shadow1_tpu.consts import K_APP, NP, EngineParams
from shadow1_tpu.core.engine import Engine
from shadow1_tpu.core.events import Popped
from shadow1_tpu.cpu_engine import CpuEngine
from shadow1_tpu.tcp import tcp as T
from shadow1_tpu.telemetry.ring import drain_ring
from tests.parity import PARITY_KEYS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# file, stop_time (whole windows), the oracle's mq_max_fill, the pool run
# under (0 = the derived one), the derived pool
MINIATURES = {
    "tor35": ("tests/rehearsal_tor_lossy/configs/tor35.yaml", "1500 ms", 14, 0, 128),
    "bitcoin64": ("tests/rehearsal_bitcoin64/configs/bitcoin64.yaml", "1 s", 8, 16, 64),
    "tor20": ("tests/rehearsal_tor20/configs/tor20.yaml", "1200 ms", 14, 0, 128),
}


def build(name, **engine):
    path, stop = MINIATURES[name][:2]
    with open(os.path.join(ROOT, path)) as f:
        doc = yaml.safe_load(f)
    doc["general"]["stop_time"] = stop
    exp, params, _ = build_experiment(
        doc, base_dir=os.path.dirname(os.path.join(ROOT, path)))
    return exp, dataclasses.replace(params, **engine)


@pytest.fixture(scope="module", params=sorted(MINIATURES))
def both(request):
    """A miniature on the solo engine and on the oracle, digests on."""
    exp, params = build(request.param, metrics_ring=512, state_digest=1,
                        msgq_pool=MINIATURES[request.param][3])
    cpu = CpuEngine(exp, params)
    cm = cpu.run()
    eng = Engine(exp, params)
    return request.param, eng, eng.run(), cpu, cm


# ---- (1), (2) the derived pool: the oracle's counters, digests and gauge -----------

@pytest.mark.parametrize("socks,cap,pool", [
    (128, 64, 256),     # tor10k, tor1k_regions
    (64, 64, 128), (32, 64, 128),       # tor1k; both Bitcoin files
    (32, 32, 64),       # tgen100
    (12, 16, 64),       # bitcoin120_regions: a host holds 41
    (8, 8, 64), (8, 4, 32), (2, 4, 8),  # what no host can exceed
])
def test_the_pool_is_derived_from_the_two_widths_the_file_states(socks, cap, pool):
    pr = EngineParams(sockets_per_host=socks, msgq_cap=cap)
    assert pr.msgq_pool == 0 and pr.mq_pool == pr.cap("msgq_pool") == pool
    assert dataclasses.replace(pr, msgq_pool=24).mq_pool == 24


def test_the_state_holds_the_pool_and_no_plane_a_queue_a_socket_tall(both):
    name, eng, st, _, _ = both
    pr = eng.params
    assert pr.mq_pool == (MINIATURES[name][3] or MINIATURES[name][4])
    tcp = st.model.tcp
    assert {tcp[k].shape for k in ("mq_sock", "mq_end", "mq_meta")} \
        == {(pr.mq_pool, eng.exp.n_hosts)}
    assert "mq_valid" not in tcp
    tall = (pr.msgq_cap, pr.sockets_per_host, eng.exp.n_hosts)
    import jax

    assert not [x.shape for x in jax.tree.leaves(st) if x.shape == tall]


def test_a_miniature_equals_the_oracle_counter_for_counter(both):
    _, _, st, _, cm = both
    tm = Engine.metrics_dict(st)
    assert {k: tm[k] for k in PARITY_KEYS} == {k: cm[k] for k in PARITY_KEYS}
    assert tm["mq_overflow"] == cm["mq_overflow"] == 0


def test_a_miniature_s_tcp_digest_is_the_oracle_s_window_for_window(both):
    name, eng, st, cpu, _ = both
    have = {r["window"]: r["dg_tcp"] for r in drain_ring(st, eng.window)
            if r["type"] == "ring"}
    want = {r["window"]: r["dg_tcp"] for r in cpu.digest_rows}
    assert have == want and len(have) >= 20
    # Boundaries were pending at window ends: the words are not the sockets'
    # alone.
    assert Engine.metrics_dict(st)["mq_max_fill"] > 0


def test_mq_max_fill_is_the_oracle_s_high_water_of_a_host_s_boundaries(both):
    name, _, st, _, cm = both
    assert Engine.metrics_dict(st)["mq_max_fill"] == cm["mq_max_fill"] \
        == MINIATURES[name][2]
    # ... and the ring's column is the running gauge.
    rows = [r for r in drain_ring(st, both[1].window) if r["type"] == "ring"]
    assert rows[-1]["mq_max_fill"] == cm["mq_max_fill"]
    assert all(r["mq_overflow"] == 0 for r in rows)


# ---- (3) a pool below the demand --------------------------------------------------

@pytest.fixture(scope="module")
def small_pool():
    exp, params = build("bitcoin64", msgq_pool=2)
    eng = Engine(exp, params)
    return eng, eng.init_state()


def _send(eng, st, hosts, sock, meta=7):
    h = eng.exp.n_hosts
    mask = jnp.zeros(h, bool).at[jnp.asarray(hosts)].set(True)
    full = lambda v: jnp.full(h, v, jnp.int32)
    st, acc = T.tcp_send(st, eng.ctx, mask, full(sock), full(100), full(meta),
                         jnp.zeros(h, jnp.int64))
    assert np.asarray(acc)[hosts].tolist() == [100] * len(hosts)
    return st


def test_a_host_handed_more_boundaries_than_its_pool_counts_each_one(small_pool):
    eng, st = small_pool
    assert st.model.tcp["mq_sock"].shape == (2, eng.exp.n_hosts)
    hosts = [0, 3, 4, 9, 63]
    for sock in (1, 2):
        st = _send(eng, st, hosts, sock)
    assert int(st.metrics.mq_overflow) == 0
    assert int(T.mq_fill(st.model.tcp)) == 2
    st = _send(eng, st, hosts[:3], 3)       # three hosts, a third boundary
    assert int(st.metrics.mq_overflow) == 3
    st = _send(eng, st, hosts[1:], 1)       # four more, on a socket that has one
    assert int(st.metrics.mq_overflow) == 7
    sock = np.asarray(st.model.tcp["mq_sock"])
    assert sorted(sock[:, 3].tolist()) == [1, 2] and (sock[:, 1] == -1).all()
    # The bytes went on without their boundary (a free socket counts from 0):
    # only the framing is lost.
    assert int(np.asarray(st.model.tcp["app_end"])[1, 3]) == 200


def test_the_fleet_s_halt_names_msgq_pool():
    from shadow1_tpu.fleet.run import _check_halt
    from shadow1_tpu.txn import CapacityExceededError

    eng = types.SimpleNamespace(params=EngineParams(msgq_pool=12))
    with pytest.raises(CapacityExceededError) as e:
        _check_halt(eng, None, [{"mq_overflow": 0}, {"mq_overflow": 5,
                                                     "mq_max_fill": 12}],
                    [{"mq_overflow": 0}, {"mq_overflow": 2}], 10, 5)
    err = e.value
    assert (err.knob, err.counter, err.cap, err.overflow, err.lanes) \
        == ("msgq_pool", "mq_overflow", 12, 3, [1])
    assert "msgq_pool: 24" in err.advice


def test_a_retry_grows_the_pool_until_the_run_is_the_straight_one():
    """``--on-overflow retry`` on a pool of 4 where a host holds 8: every
    tainted chunk is replayed on a grown pool, and the run ends on the
    derived pool's counters with nothing dropped."""
    from shadow1_tpu.ckpt import run_chunked
    from shadow1_tpu.txn import OverflowGuard

    exp, params = build("bitcoin64", msgq_pool=4)
    eng = Engine(exp, params)
    guard = OverflowGuard(
        eng, make_engine=lambda p: Engine(exp, p), mode="retry")
    st = run_chunked(eng, None, n_windows=20, chunk=5, guard=guard)
    assert guard.chunk_retries >= 1
    grown = guard.final_caps["msgq_pool"]
    assert grown >= 8 and st.model.tcp["mq_sock"].shape[0] == grown
    assert [r["msgq_pool"][0] for r in guard.resizes][0] == 4
    tm = Engine.metrics_dict(st)
    want = CpuEngine(exp, params).run()
    assert tm["mq_overflow"] == 0 and tm["mq_max_fill"] == 8
    assert {k: tm[k] for k in PARITY_KEYS} == {k: want[k] for k in PARITY_KEYS}


# ---- (4) msgq_cap is still the socket's bound -------------------------------------

def _app_event(eng, host, op, sock, meta, nbytes):
    h = eng.exp.n_hosts
    p = jnp.zeros((NP, h), jnp.int32)
    for i, v in enumerate((op, sock, meta, nbytes)):
        p = p.at[i, host].set(v)
    mask = jnp.zeros(h, bool).at[host].set(True)
    return Popped(mask=mask, time=jnp.full(h, 10**9, jnp.int64),
                  kind=jnp.where(mask, K_APP, 0).astype(jnp.int32), p=p,
                  tb=jnp.zeros(h, jnp.int64))


@pytest.mark.parametrize("name,retries", [("tor20", "cell_retries"),
                                          ("bitcoin64", "msg_retries")])
def test_a_socket_at_msgq_cap_refuses_the_next_boundary_with_pool_room(
        name, retries):
    from shadow1_tpu.apps import bitcoin, tor

    app, op = {"tor20": (tor, tor.OP_TX_CELL),
               "bitcoin64": (bitcoin, bitcoin.OP_TX_MSG)}[name]
    exp, params = build(name, msgq_cap=4)
    eng = Engine(exp, params)
    pool = params.mq_pool
    assert pool >= 4 * 4
    host, sock = 5, 2
    for held, refused in ((4, True), (3, False)):
        st = eng.init_state()
        tcp = dict(st.model.tcp)
        # ``held`` boundaries of the socket and four of another, scattered.
        col = np.full(pool, -1, np.int32)
        col[1:2 * held:2] = sock
        col[pool - 4:] = sock + 1
        tcp["mq_sock"] = tcp["mq_sock"].at[:, host].set(jnp.asarray(col))
        tcp["mq_end"] = tcp["mq_end"].at[:, host].set(
            jnp.arange(1000, 1000 + pool, dtype=jnp.int32))
        st = st._replace(model=st.model._replace(tcp=tcp))
        ev = _app_event(eng, host, op, sock, meta=0x123, nbytes=64)
        assert bool(T.mq_room(tcp, ev.p[1], 4)[host]) is not refused
        st = app.on_wakeup(st, eng.ctx, ev, ev.mask)
        mine = int((np.asarray(st.model.tcp["mq_sock"])[:, host] == sock).sum())
        assert int(np.asarray(st.model.app[retries])[host]) == int(refused)
        assert mine == 4                    # refused at 4; 3 + the new one
        assert int(st.metrics.mq_overflow) == 0
        other = np.asarray(st.model.tcp["mq_sock"])[:, host] == sock + 1
        assert int(other.sum()) == 4


# ---- (5) the pool between sizes ---------------------------------------------------

def test_the_pool_migrates_as_a_set_and_refuses_a_shrink_below_its_fill():
    from shadow1_tpu.tune.resize import resize_mq_pool

    rng = np.random.default_rng(3)
    sock = np.full((2, 8, 5), -1, np.int32)         # a fleet of two lanes
    sock[:, [1, 4, 6], :] = rng.integers(0, 4, (2, 3, 5))
    tcp = {"mq_sock": sock, "mq_end": rng.integers(1, 99, sock.shape, np.int32),
           "mq_meta": rng.integers(1, 99, sock.shape, np.int32),
           "st": np.zeros((2, 4, 5), np.int32)}

    def entries(t, lane, h):
        live = t["mq_sock"][lane, :, h] >= 0
        return sorted(zip(*(t[k][lane, live, h].tolist()
                            for k in ("mq_sock", "mq_end", "mq_meta"))))

    for new in (12, 3):
        out = resize_mq_pool(tcp, new)
        assert out["mq_sock"].shape == (2, new, 5) and out["st"] is tcp["st"]
        assert all(entries(out, e, h) == entries(tcp, e, h)
                   for e in range(2) for h in range(5))
    assert (resize_mq_pool(tcp, 12)["mq_sock"][:, 8:] == -1).all()
    assert resize_mq_pool(tcp, 8) is tcp
    with pytest.raises(ValueError, match="a host holds 3 message boundaries"):
        resize_mq_pool(tcp, 2)


def test_a_snapshot_from_before_the_pool_is_refused_as_a_version_error(tmp_path):
    """Format 16 held the queue as three ``[MQ, S, H]`` leaves: such a file
    is turned away by its version, before any leaf is compared."""
    from shadow1_tpu import ckpt

    exp, params = build("bitcoin64")
    eng = Engine(exp, params)
    st = eng.init_state()
    path = str(tmp_path / "snap.npz")
    ckpt.save_state(st, path)
    assert ckpt.CKPT_FORMAT == 19
    with np.load(path) as d:
        arrs = {k: d[k].copy() for k in d.files}
    arrs["format"][0] = 16
    np.savez(path, **arrs)
    with pytest.raises(ValueError, match="format v16.*reads v19"):
        ckpt.load_state(st, path)


def test_a_snapshot_loads_onto_another_pool_with_its_boundaries(tmp_path,
                                                                small_pool):
    from shadow1_tpu import ckpt

    eng, st = small_pool
    st = _send(eng, _send(eng, st, [3, 9], 1), [9], 2)
    path = str(tmp_path / "snap.npz")
    ckpt.save_state(st, path)
    exp, params = build("bitcoin64", msgq_pool=12)
    got = ckpt.load_state(Engine(exp, params).init_state(), path)
    sock = np.asarray(got.model.tcp["mq_sock"])
    assert sock.shape == (12, exp.n_hosts)
    assert sorted(sock[:, 9].tolist()) == [-1] * 10 + [1, 2]
    assert sorted(sock[:, 3].tolist()) == [-1] * 11 + [1]
    # ... and not onto a pool its fullest host does not fit.
    exp1, params1 = build("bitcoin64", msgq_pool=1)
    with pytest.raises(ValueError, match="cannot migrate.*msgq_pool 2 -> 1"):
        ckpt.load_state(Engine(exp1, params1).init_state(), path)


# ---- (6) sizing the pool: captune and the controller --------------------------------

def test_captune_sizes_the_pool_from_an_oracle_run_s_final_record(capsys,
                                                                  tmp_path):
    """What ``python -m shadow1_tpu <file> --engine cpu`` prints last, handed
    to captune: a row for ``msgq_pool`` from ``mq_max_fill``, beside
    ``ev_cap``'s from ``ev_max_fill``."""
    import json

    from shadow1_tpu.tools import captune

    exp, params = build("bitcoin64", msgq_pool=16)
    cm = CpuEngine(exp, params).run()
    rec = tmp_path / "final.json"
    rec.write_text(json.dumps({
        "engine": "cpu", "metrics": {k: int(v) for k, v in cm.items()},
        "caps": {"ev_cap": params.ev_cap, "outbox_cap": params.outbox_cap,
                 "msgq_pool": params.mq_pool}}) + "\n")
    rows = {r["knob"]: r for r in captune.advise(*captune.peaks_from_records(
        captune.load_records([str(rec)])))}
    assert (rows["msgq_pool"]["peak"], rows["msgq_pool"]["cap"]) == (8, 16)
    assert rows["msgq_pool"]["recommended"] == 12 and not \
        rows["msgq_pool"]["overflowed"]
    assert rows["ev_cap"]["peak"] == cm["ev_max_fill"]
    assert captune.main([str(rec)]) == 0
    assert "msgq_pool: measured peak 8, cap 16" in capsys.readouterr().out


def test_the_cap_controller_grows_a_pool_that_fills_and_never_shrinks_one():
    from shadow1_tpu.ckpt import run_chunked
    from shadow1_tpu.tune.autocap import CapController

    exp, params = build("bitcoin64", msgq_pool=8)
    eng = Engine(exp, params)
    ctl = CapController(eng, lambda p: Engine(exp, p))
    st = run_chunked(eng, None, n_windows=20, chunk=4, retune=ctl)
    grown = [r["msgq_pool"] for r in ctl.resizes if "msgq_pool" in r]
    assert grown and grown[0][0] == 8 and grown[-1][1] >= 12
    assert st.model.tcp["mq_sock"].shape[0] == grown[-1][1]
    # A pool of 16 holds 8, twice the fill, and stays (under the same
    # headroom an ev_cap would be cut to 12).
    exp, params = build("bitcoin64", msgq_pool=16)
    eng = Engine(exp, params)
    ctl = CapController(eng, lambda p: Engine(exp, p))
    st = run_chunked(eng, None, n_windows=20, chunk=4, retune=ctl)
    assert not [r for r in ctl.resizes if "msgq_pool" in r]
    assert st.model.tcp["mq_sock"].shape[0] == 16
