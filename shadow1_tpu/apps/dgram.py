"""dgram — periodic datagram traffic (UDP-path exerciser).

Each sender emits ``count`` datagrams of ``payload`` bytes at ``interval``
spacing to a fixed destination; receivers count deliveries. The minimal
workload for the NIC + routing + loss path without TCP (reference analogue:
the UDP feature test plugins, SURVEY §4).

model_cfg ([H] numpy arrays): dst, payload, interval, count, start_time.
"""

from __future__ import annotations

import jax.numpy as jnp

from shadow1_tpu import net
from shadow1_tpu.consts import K_APP, N_DGRAM, NP
from shadow1_tpu.core.engine import pass_rows, push_local_event
from shadow1_tpu.core.events import push_local

OP_TICK = 1


def init(ctx, evbuf, tcpd):
    cfg = ctx.model_cfg
    app = {
        "dst": jnp.asarray(cfg["dst"], jnp.int32),
        "payload": jnp.asarray(cfg["payload"], jnp.int32),
        "interval": jnp.asarray(cfg["interval"], jnp.int64),
        "left": jnp.asarray(cfg["count"], jnp.int32),
        "rx_count": jnp.zeros(ctx.n_hosts, jnp.int64),
        "rx_bytes": jnp.zeros(ctx.n_hosts, jnp.int64),
    }
    sender = app["left"] > 0
    p = jnp.zeros((NP, ctx.n_hosts), jnp.int32).at[0].set(OP_TICK)
    k = jnp.full(ctx.n_hosts, K_APP, jnp.int32)
    evbuf, over = push_local(
        evbuf, sender, jnp.asarray(cfg["start_time"], jnp.int64), k, p
    )
    return app, evbuf, over.sum(dtype=jnp.int64), tcpd


@pass_rows(1)
def on_wakeup(st, ctx, ev, mask):
    m = mask & (ev.p[0] == OP_TICK)
    app = st.model.app
    send = m & (app["left"] > 0)
    zero = jnp.zeros(ctx.n_hosts, jnp.int32)
    st = net.udp_send(
        st, ctx, send, app["dst"], zero, app["payload"], zero + 1, zero, ev.time
    )
    app = dict(st.model.app)
    app["left"] = app["left"] - send.astype(jnp.int32)
    st = st._replace(model=st.model._replace(app=app))
    again = send & (app["left"] > 0)
    return push_local_event(st, ctx, again, ev.time + app["interval"], K_APP, p0=OP_TICK)


@pass_rows(0)
def on_notify(st, ctx, nf, now, mask):
    app = dict(st.model.app)
    dg = mask & ((nf.flags & N_DGRAM) != 0)
    app["rx_count"] = app["rx_count"] + dg.astype(jnp.int64)
    app["rx_bytes"] = app["rx_bytes"] + jnp.where(dg, nf.dlen.astype(jnp.int64), 0)
    return st._replace(model=st.model._replace(app=app))


def summary(app) -> dict:
    return {
        "rx_count": app["rx_count"],
        "rx_bytes": app["rx_bytes"],
        "total_rx": app["rx_count"].sum(),
    }
