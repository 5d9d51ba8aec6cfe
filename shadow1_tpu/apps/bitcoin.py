"""bitcoin — inv/getdata/tx gossip over a P2P graph (BASELINE rung 5).

The model-application analogue of the reference's bitcoin plugin
(shadow-plugin-bitcoin, SURVEY §2.4/§7.1: "Bitcoin = inv/getdata/tx gossip
state machine"). Nodes hold persistent TCP connections along the edges of a
configured peer graph; a transaction created at its origin is announced with
small INV messages, fetched with GETDATA, transferred as a tx-sized payload,
and re-announced by each node on first receipt — the classic flood. The
instrumented output is propagation: which nodes saw each tx, and when.

Wire model: INV/GETDATA/TX are message boundaries on the TCP byte stream
(meta = cmd<<20 | txid), so loss/recovery/queueing all ride the real virtual
TCP machinery.

Batched-engine shape note: fan-out (dial K neighbors, announce a tx on K
conns) is expressed as K self-scheduled events at the same timestamp rather
than K inline transport calls — the event core serializes them in
deterministic (time, tb) order, the CPU oracle schedules the identical
events, and the traced round body instantiates the TCP send path once
instead of K times (the SIMD analogue of the reference queueing work items
rather than deep call chains).

Phase scopes (under the engine's ``phase:h_app`` / ``phase:h_deliver``, read
by telemetry/phases.py): ``phase:btc_dial`` (dialing a neighbor, binding an
accepted conn), ``phase:btc_create`` (a tx's origin), ``phase:btc_msg`` (the
admission check and ``tcp_send`` of OP_TX_MSG), ``phase:btc_notify`` (a
received INV / GETDATA / TX and the announcements it queues).

model_cfg:
  peers      i32 [H, K] neighbor ids, -1 = unused slot (edges must be
             symmetric: n in peers[h] ⇔ h in peers[n])
  tx_origin  i32 [T] origin host per transaction — the one key the
             config generator draws from the seed (apps.LANE_TABLES): a
             traced per-lane table under the fleet engine, so it is only
             ever compared with ``ctx.hosts``, never read as a Python int
  tx_time    i64 [T] creation time per transaction (leave ≥ a few RTT after
             connect_time so the conn mesh is up)
  tx_size    int, payload bytes of a transaction (default 400)
  inv_size   int, bytes of INV/GETDATA messages (default 36)
  connect_time  int ns, when the conn mesh is dialed (default 0)
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from shadow1_tpu.core.dense import extract_col, get_col, read_sel, set_col
from shadow1_tpu.consts import (
    K_APP,
    N_ACCEPTED,
    N_MSG,
    NP,
    TCP_LISTEN,
)
from shadow1_tpu.core.engine import (
    any_host,
    lane_branch,
    pass_rows,
    push_local_event,
)
from shadow1_tpu.core.events import push_local
from shadow1_tpu.tcp import tcp as T

OP_CONNECT_ONE = 1   # p1 = neighbor slot j
OP_TX_CREATE = 2     # p1 = txid
OP_TX_MSG = 3        # p1 = socket, p2 = meta, p3 = nbytes

CMD_INV = 1
CMD_GET = 2
CMD_TX = 3

TXID_BITS = 20
TXID_MASK = (1 << TXID_BITS) - 1


def _meta(cmd, txid):
    return (cmd << TXID_BITS) | txid


def init(ctx, evbuf, tcpd):
    cfg = ctx.model_cfg
    peers = jnp.asarray(cfg["peers"], jnp.int32).T        # [K, H] host-minor
    tx_origin = jnp.asarray(cfg["tx_origin"], jnp.int32)  # [T], maybe traced
    tx_time = np.asarray(cfg["tx_time"], np.int64)        # [T] (host-side)
    n_tx = tx_origin.shape[0]
    assert n_tx <= TXID_MASK
    k_max, h = peers.shape
    app = {
        "peers": peers,
        # Socket reaching neighbor j (outbound = 1+j at dial time; inbound
        # learned on N_ACCEPTED); -1 = no conn yet.
        "nbr_sock": jnp.full((k_max, h), -1, jnp.int32),
        "seen": jnp.zeros((n_tx, h), bool),
        "req": jnp.zeros((n_tx, h), bool),
        "seen_time": jnp.zeros((n_tx, h), jnp.int64),
        "tx_rx": jnp.zeros(h, jnp.int64),   # tx payloads received
        "msg_retries": jnp.zeros(h, jnp.int64),
    }
    tcpd = dict(tcpd)
    tcpd["st"] = tcpd["st"].at[0].set(TCP_LISTEN)
    # Dial the conn mesh: one OP_CONNECT_ONE per outbound neighbor slot.
    connect_time = jnp.full(ctx.n_hosts, int(cfg.get("connect_time", 0)), jnp.int64)
    kk = jnp.full(ctx.n_hosts, K_APP, jnp.int32)
    n_over = jnp.zeros((), jnp.int64)
    for j in range(k_max):
        m = peers[j] > ctx.hosts
        p = jnp.zeros((NP, ctx.n_hosts), jnp.int32)
        p = p.at[0].set(OP_CONNECT_ONE).at[1].set(j)
        evbuf, over = push_local(evbuf, m, connect_time, kk, p)
        n_over = n_over + over.sum(dtype=jnp.int64)
    # Seed tx-creation wakeups, one masked push per transaction.
    for t in range(n_tx):
        mask = ctx.hosts == tx_origin[t]
        p = jnp.zeros((NP, ctx.n_hosts), jnp.int32)
        p = p.at[0].set(OP_TX_CREATE).at[1].set(t)
        evbuf, over = push_local(
            evbuf, mask, jnp.full(ctx.n_hosts, int(tx_time[t]), jnp.int64), kk, p
        )
        n_over = n_over + over.sum(dtype=jnp.int64)
    return app, evbuf, n_over, tcpd


def _peer_slots(ctx) -> int:
    return ctx.model_cfg["peers"].shape[-1]


def _push_msg(st, ctx, mask, sock, meta, nbytes, now):
    """Queue a protocol message send (admission-checked in OP_TX_MSG)."""
    return push_local_event(
        st, ctx, mask, now, K_APP, p0=OP_TX_MSG, p1=sock, p2=meta, p3=nbytes
    )


def _announce(st, ctx, mask, txid, skip_sock, now):
    """Queue one INV per live neighbor conn except ``skip_sock``."""
    inv_size = int(ctx.model_cfg.get("inv_size", 36))
    app = st.model.app
    for j in range(app["peers"].shape[0]):
        ns = app["nbr_sock"][j]
        m = mask & (ns >= 0) & (ns != skip_sock)
        st = _push_msg(st, ctx, m, ns, _meta(CMD_INV, txid), inv_size, now)
    return st


def _mark_seen(app, mask, txid, now):
    t_safe = jnp.where(mask, txid, 0)
    was = get_col(app["seen"], t_safe)
    new = mask & ~was
    # Dense one-hot writes, not .at[] scatters (core/dense.py: XLA
    # serializes dynamic-index scatters on TPU; this runs per gossip round).
    app["seen"] = set_col(app["seen"], t_safe, True, new)
    app["seen_time"] = set_col(app["seen_time"], t_safe, now, new)
    return app, new


# One INV a peer slot from ``_announce`` (a new transaction, created or
# received), beside the dial's flush, the message's send and its retry.
@pass_rows(lambda ctx: 5 + _peer_slots(ctx))
def on_wakeup(st, ctx, ev, mask):
    op = ev.p[0]
    app = st.model.app
    k_max = app["peers"].shape[0]
    zero = jnp.zeros(ctx.n_hosts, jnp.int32)

    # OP_CONNECT_ONE: dial neighbor slot j = p1 on socket 1+j. Startup-only
    # (one per neighbor edge) but carries a tcp_connect — lax.cond keeps it
    # out of steady-state gossip rounds (exact: all writes masked).
    conn = mask & (op == OP_CONNECT_ONE)

    def _op_conn(st):
        app = st.model.app
        j = jnp.where(conn, ev.p[1], 0)
        peer = get_col(app["peers"], j)
        sock = (1 + j).astype(jnp.int32)
        napp = dict(app)
        napp["nbr_sock"] = set_col(napp["nbr_sock"], j, sock, conn)
        st = st._replace(model=st.model._replace(app=napp))
        return T.tcp_connect(st, ctx, conn, sock, peer, zero, ev.time)

    with jax.named_scope("phase:btc_dial"):
        st = jax.lax.cond(any_host(ctx, conn), lane_branch(ctx, _op_conn),
                          lambda s: s, st)

    # OP_TX_CREATE: origin marks the tx seen and queues the announcements
    # (a few hundred per run — cond-gated).
    create = mask & (op == OP_TX_CREATE)

    def _op_create(st):
        txid = ev.p[1]
        app = dict(st.model.app)
        app, new = _mark_seen(app, create, txid, ev.time)
        st = st._replace(model=st.model._replace(app=app))
        none = jnp.full(ctx.n_hosts, -1, jnp.int32)
        return _announce(st, ctx, new, txid, none, ev.time)

    with jax.named_scope("phase:btc_create"):
        st = jax.lax.cond(any_host(ctx, create), lane_branch(ctx, _op_create),
                          lambda s: s, st)

    # OP_TX_MSG: the single transport-send site. Admission: the message must
    # fit the send buffer and a boundary slot must be free, else retry at the
    # next window start — a congested conn defers gossip instead of losing
    # its framing (same shape as tor.py's OP_TX_CELL).
    with jax.named_scope("phase:btc_msg"):
        tx = mask & (op == OP_TX_MSG)
        sock, meta, nbytes = ev.p[1], ev.p[2], ev.p[3]
        r = T.Sock(st.model.tcp, sock, tx)
        snd_una, app_end = r.g("snd_una"), r.g("app_end")
        buffered = (app_end - snd_una) - (snd_una == 0).astype(jnp.int32)
        fits = (ctx.params.sndbuf - buffered) >= nbytes
        mq_ok = T.mq_room(st.model.tcp, sock, ctx.params.msgq_cap)
        can = tx & fits & mq_ok
        retry = tx & ~can
        st, _acc = T.tcp_send(st, ctx, can, sock, nbytes, meta, ev.time)
        napp = dict(st.model.app)
        napp["msg_retries"] = napp["msg_retries"] + retry.astype(jnp.int64)
        st = st._replace(model=st.model._replace(app=napp))
        t_retry = (ev.time // ctx.window + 1) * ctx.window
        return push_local_event(
            st, ctx, retry, t_retry, K_APP,
            p0=OP_TX_MSG, p1=sock, p2=meta, p3=nbytes
        )


@pass_rows(lambda ctx: 1 + _peer_slots(ctx))
def on_notify(st, ctx, nf: T.Notif, now, mask):
    f = nf.flags
    sock = nf.sock
    tx_size = int(ctx.model_cfg.get("tx_size", 400))
    inv_size = int(ctx.model_cfg.get("inv_size", 36))

    # Inbound conn accepted: bind it to its neighbor slot (startup-only;
    # the k-slot scan is cond-gated out of steady-state gossip rounds).
    acc = mask & ((f & N_ACCEPTED) != 0)

    def _accepted(st):
        app = dict(st.model.app)
        peer = get_col(st.model.tcp["peer_host"], jnp.where(acc, sock, 0))
        for j in range(app["peers"].shape[0]):
            m = acc & (app["peers"][j] == peer) & (app["nbr_sock"][j] < 0)
            app["nbr_sock"] = app["nbr_sock"].at[j].set(
                jnp.where(m, sock, app["nbr_sock"][j])
            )
        return st._replace(model=st.model._replace(app=app))

    with jax.named_scope("phase:btc_dial"):
        st = jax.lax.cond(any_host(ctx, acc), lane_branch(ctx, _accepted),
                          lambda s: s, st)
    with jax.named_scope("phase:btc_notify"):
        return _on_msg(st, ctx, nf, now, mask, tx_size, inv_size)


def _on_msg(st, ctx, nf, now, mask, tx_size, inv_size):
    """Protocol messages (one boundary per host-round at most)."""
    sock = nf.sock
    msg = mask & ((nf.flags & N_MSG) != 0)
    cmd = nf.meta >> TXID_BITS
    txid = nf.meta & TXID_MASK
    app = st.model.app
    t_safe = jnp.where(msg, txid, 0)
    tsel = read_sel(t_safe, app["seen"].shape[0])
    seen = extract_col(tsel, app["seen"])
    req = extract_col(tsel, app["req"])

    # INV for an unknown tx → GETDATA back on the same conn.
    want = msg & (cmd == CMD_INV) & ~seen & ~req
    napp = dict(app)
    napp["req"] = set_col(napp["req"], t_safe, True, want)
    st = st._replace(model=st.model._replace(app=napp))

    # GETDATA for a tx we hold → send the payload. The two responses are
    # mutually exclusive per host-round, so they share one queued send.
    give = msg & (cmd == CMD_GET) & seen
    resp = want | give
    nbytes = jnp.where(give, tx_size, inv_size).astype(jnp.int32)
    rmeta = jnp.where(
        give, _meta(CMD_TX, txid), _meta(CMD_GET, jnp.where(msg, txid, 0))
    )
    st = _push_msg(st, ctx, resp, sock, rmeta, nbytes, now)

    # TX payload → first sight: record + queue announcements everywhere else.
    got = msg & (cmd == CMD_TX)
    app = dict(st.model.app)
    app["tx_rx"] = app["tx_rx"] + got.astype(jnp.int64)
    app, new = _mark_seen(app, got, txid, now)
    st = st._replace(model=st.model._replace(app=app))
    return _announce(st, ctx, new, txid, sock, now)


def summary(app) -> dict:
    seen = app["seen"]                        # [T, H] internally
    return {
        "seen": seen.T,                       # [H, T] — oracle orientation
        "seen_time": app["seen_time"].T,
        "tx_rx": app["tx_rx"],
        "reach": seen.sum(axis=1),            # nodes reached per tx
        "msg_retries": app["msg_retries"],
        # Run totals (0-dim: they ride heartbeat rows' ``model`` block,
        # telemetry/registry.MODEL_TOTALS).
        "total_seen": seen.sum(),
        "total_tx_rx": app["tx_rx"].sum(),
        "total_msg_retries": app["msg_retries"].sum(),
    }
