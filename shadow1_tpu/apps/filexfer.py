"""filexfer — bulk file transfer over the virtual TCP stack.

The model-application analogue of the reference's minimal tgen file-transfer
example (resource/examples/, BASELINE ladder rung 1): clients connect to a
server at a start time, stream ``flow_bytes`` with a FLOW_DONE message
boundary at the end, close, and optionally repeat. Servers listen on socket
0, count delivered bytes and completed flows.

model_cfg (numpy arrays, [H]):
  role        0=server 1=client 2=idle
  server      server host per client
  flow_bytes  bytes per flow
  start_time  first-connect time (ns)
  flow_count  sequential flows per client
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from shadow1_tpu.consts import (
    K_APP,
    N_CLOSED,
    N_DATA,
    N_ESTABLISHED,
    N_MSG,
    N_PEER_FIN,
    N_SPACE,
    NP,
    TCP_LISTEN,
)
from shadow1_tpu.core.engine import pass_rows
from shadow1_tpu.core.events import push_local
from shadow1_tpu.tcp import tcp as T

FLOW_DONE = 1
OP_START = 1


def init(ctx, evbuf, tcpd):
    cfg = ctx.model_cfg
    role = jnp.asarray(cfg["role"], jnp.int32)
    app = {
        "role": role,
        "server": jnp.asarray(cfg["server"], jnp.int32),
        "flow_bytes": jnp.asarray(cfg["flow_bytes"], jnp.int32),
        "remaining": jnp.zeros(ctx.n_hosts, jnp.int32),
        "flows_left": jnp.asarray(cfg["flow_count"], jnp.int32),
        "closed_sent": jnp.zeros(ctx.n_hosts, bool),
        "rx_bytes": jnp.zeros(ctx.n_hosts, jnp.int64),
        "flows_done": jnp.zeros(ctx.n_hosts, jnp.int32),
        "done_time": jnp.zeros(ctx.n_hosts, jnp.int64),
    }
    # Servers listen on socket 0 from t=0.
    tcpd = dict(tcpd)
    tcpd["st"] = tcpd["st"].at[0].set(
        jnp.where(role == 0, TCP_LISTEN, tcpd["st"][0])
    )
    # Clients wake up at their start time.
    is_client = role == 1
    p = jnp.zeros((NP, ctx.n_hosts), jnp.int32).at[0].set(OP_START)
    k = jnp.full(ctx.n_hosts, K_APP, jnp.int32)
    evbuf, over = push_local(
        evbuf, is_client, jnp.asarray(cfg["start_time"], jnp.int64), k, p
    )
    return app, evbuf, over.sum(dtype=jnp.int64), tcpd


def _client_pump(st, ctx, mask, now):
    """Queue as much of the current flow as the send buffer takes; attach
    FLOW_DONE on the final chunk; close once everything is queued."""
    app = st.model.app
    m = mask & (app["remaining"] > 0)
    meta = jnp.full(ctx.n_hosts, FLOW_DONE, jnp.int32)
    zero = jnp.zeros(ctx.n_hosts, jnp.int32)
    st, accepted = T.tcp_send(st, ctx, m, zero, app["remaining"], meta, now)
    app = dict(st.model.app)
    app["remaining"] = app["remaining"] - accepted
    # mask (not m) so zero-byte flows close right at establishment.
    done = mask & (app["remaining"] == 0) & ~app["closed_sent"]
    app["closed_sent"] = app["closed_sent"] | done
    st = st._replace(model=st.model._replace(app=app))
    return T.tcp_close(st, ctx, done, zero, now)


def _client_start(st, ctx, mask, now):
    app = dict(st.model.app)
    app["remaining"] = jnp.where(mask, app["flow_bytes"], app["remaining"])
    app["closed_sent"] = jnp.where(mask, False, app["closed_sent"])
    st = st._replace(model=st.model._replace(app=app))
    zero = jnp.zeros(ctx.n_hosts, jnp.int32)
    return T.tcp_connect(st, ctx, mask, zero, app["server"], zero, now)


@pass_rows(2)
def on_wakeup(st, ctx, ev, mask):
    start = mask & (ev.p[0] == OP_START)
    return _client_start(st, ctx, start, ev.time)


@pass_rows(8)
def on_notify(st, ctx, nf: T.Notif, now, mask):
    app = st.model.app
    is_client = app["role"] == 1
    is_server = app["role"] == 0
    f = nf.flags

    # Client: connection up or buffer space → pump bytes.
    pump = mask & is_client & (((f & N_ESTABLISHED) != 0) | ((f & N_SPACE) != 0))
    st = _client_pump(st, ctx, pump, now)

    # Server: count stream bytes and completed flows.
    app = dict(st.model.app)
    data = mask & is_server & ((f & N_DATA) != 0)
    app["rx_bytes"] = app["rx_bytes"] + jnp.where(data, nf.dlen.astype(jnp.int64), 0)
    msg = mask & is_server & ((f & N_MSG) != 0) & (nf.meta == FLOW_DONE)
    app["flows_done"] = app["flows_done"] + msg.astype(jnp.int32)
    st = st._replace(model=st.model._replace(app=app))

    # Server: peer finished → close our side (full teardown). Teardown-only
    # blocks run under lax.cond (tcp_close / tcp_connect are the heavy ops;
    # gating is exact since all writes are masked).
    peer_fin = mask & is_server & ((f & N_PEER_FIN) != 0)
    st = jax.lax.cond(
        peer_fin.any(),
        lambda s: T.tcp_close(s, ctx, peer_fin, nf.sock, now),
        lambda s: s, st,
    )

    # Client: connection fully closed → next flow or done.
    closed = mask & is_client & ((f & N_CLOSED) != 0)

    def _closed(st):
        app = dict(st.model.app)
        app["flows_left"] = app["flows_left"] - closed.astype(jnp.int32)
        again = closed & (app["flows_left"] > 0)
        app["done_time"] = jnp.where(
            closed & (app["flows_left"] == 0), now, app["done_time"]
        )
        st = st._replace(model=st.model._replace(app=app))
        return _client_start(st, ctx, again, now)

    return jax.lax.cond(closed.any(), _closed, lambda s: s, st)


def summary(app) -> dict:
    return {
        "rx_bytes": app["rx_bytes"],
        "flows_done": app["flows_done"],
        "done_time": app["done_time"],
        "total_rx_bytes": app["rx_bytes"].sum(),
        "total_flows_done": app["flows_done"].sum(),
    }
