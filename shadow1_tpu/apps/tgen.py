"""tgen — Markov/flow traffic-generator model over the virtual TCP stack.

The model-application analogue of the reference's tgen plugin
(shadow-plugin-tgen, SURVEY §2.4/§7.1: "tgen configs are literally
Markov/flow state machines — faithful to re-express"). Every host serves on
socket 0; hosts with ``active`` set additionally run a client loop on
socket 1: pick a uniform random peer, stream an exponentially-sized payload
with a STREAM_DONE message boundary, close, think an exponential pause,
repeat — the classic tgen mesh/bulk workload (BASELINE ladder rung 2).

All randomness is counter-based (R_APP, host, 3*stream + k): k=0 peer draw,
k=1 size draw, k=2 think draw — so the CPU oracle reproduces identical
streams in any execution order.

model_cfg (numpy arrays, [H] unless noted):
  active         1 = runs the client loop, 0 = serves only
  streams        sequential streams per active host
  mean_bytes     mean stream size (exponential, clipped to [1, 2^30])
  mean_think_ns  mean pause between streams (exponential, ≥ 1 ns)
  start_time     first-stream time (ns)
  fixed_size     (python bool, optional) stream size = mean_bytes exactly
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from shadow1_tpu import rng
from shadow1_tpu.consts import (
    K_APP,
    N_CLOSED,
    N_DATA,
    N_ESTABLISHED,
    N_MSG,
    N_PEER_FIN,
    N_SPACE,
    NP,
    R_APP,
    TCP_LISTEN,
)
from shadow1_tpu.core.engine import pass_rows, push_local_event
from shadow1_tpu.core.events import push_local
from shadow1_tpu.tcp import tcp as T

STREAM_DONE = 1
OP_START = 1
SIZE_MAX = 1 << 30


def init(ctx, evbuf, tcpd):
    cfg = ctx.model_cfg
    active = jnp.asarray(cfg["active"], jnp.int32)
    app = {
        "active": active,
        "streams_left": jnp.asarray(cfg["streams"], jnp.int32),
        "mean_bytes": jnp.asarray(cfg["mean_bytes"], jnp.float32),
        "mean_think": jnp.asarray(cfg["mean_think_ns"], jnp.float32),
        "remaining": jnp.zeros(ctx.n_hosts, jnp.int32),
        "closed_sent": jnp.zeros(ctx.n_hosts, bool),
        "ctr": jnp.zeros(ctx.n_hosts, jnp.int64),  # stream index
        "rx_bytes": jnp.zeros(ctx.n_hosts, jnp.int64),
        "streams_served": jnp.zeros(ctx.n_hosts, jnp.int32),
        "streams_done": jnp.zeros(ctx.n_hosts, jnp.int32),
        "done_time": jnp.zeros(ctx.n_hosts, jnp.int64),
    }
    # Every host serves on socket 0.
    tcpd = dict(tcpd)
    tcpd["st"] = tcpd["st"].at[0].set(TCP_LISTEN)
    starts = (active == 1) & (app["streams_left"] > 0)
    p = jnp.zeros((NP, ctx.n_hosts), jnp.int32).at[0].set(OP_START)
    k = jnp.full(ctx.n_hosts, K_APP, jnp.int32)
    evbuf, over = push_local(
        evbuf, starts, jnp.asarray(cfg["start_time"], jnp.int64), k, p
    )
    return app, evbuf, over.sum(dtype=jnp.int64), tcpd


def _draw(ctx, app, k_off):
    """One u32 per host for sub-draw ``k_off`` of the current stream index."""
    return rng.bits_v(ctx.key, R_APP, ctx.hosts, 3 * app["ctr"] + k_off)


def _start_stream(st, ctx, mask, now):
    """Draw (peer, size) for the next stream and connect socket 1 to it."""
    app = dict(st.model.app)
    draw_dst = rng.randint(_draw(ctx, app, 0), ctx.n_total - 1)
    dst = draw_dst + (draw_dst >= ctx.hosts).astype(jnp.int32)
    if ctx.model_cfg.get("fixed_size"):
        size = jnp.maximum(app["mean_bytes"].astype(jnp.int32), 1)
    else:
        size = jnp.clip(
            rng.exponential_ns(_draw(ctx, app, 1), app["mean_bytes"]), 1, SIZE_MAX
        ).astype(jnp.int32)
    app["remaining"] = jnp.where(mask, size, app["remaining"])
    app["closed_sent"] = jnp.where(mask, False, app["closed_sent"])
    app["ctr"] = app["ctr"] + mask.astype(jnp.int64)
    st = st._replace(model=st.model._replace(app=app))
    one = jnp.ones(ctx.n_hosts, jnp.int32)
    zero = jnp.zeros(ctx.n_hosts, jnp.int32)
    return T.tcp_connect(st, ctx, mask, one, dst, zero, now)


def _client_pump(st, ctx, mask, now):
    app = st.model.app
    m = mask & (app["remaining"] > 0)
    one = jnp.ones(ctx.n_hosts, jnp.int32)
    meta = jnp.full(ctx.n_hosts, STREAM_DONE, jnp.int32)
    st, accepted = T.tcp_send(st, ctx, m, one, app["remaining"], meta, now)
    app = dict(st.model.app)
    app["remaining"] = app["remaining"] - accepted
    done = mask & (app["remaining"] == 0) & ~app["closed_sent"]
    app["closed_sent"] = app["closed_sent"] | done
    st = st._replace(model=st.model._replace(app=app))
    return T.tcp_close(st, ctx, done, one, now)


@pass_rows(2)
def on_wakeup(st, ctx, ev, mask):
    start = mask & (ev.p[0] == OP_START)
    return _start_stream(st, ctx, start, ev.time)


@pass_rows(7)
def on_notify(st, ctx, nf: T.Notif, now, mask):
    f = nf.flags
    is_client_sock = nf.sock == 1

    # Client: connection up or buffer space → pump the stream.
    pump = mask & is_client_sock & (((f & N_ESTABLISHED) != 0) | ((f & N_SPACE) != 0))
    st = _client_pump(st, ctx, pump, now)

    # Server (listener children live on high sockets): count bytes/streams.
    app = dict(st.model.app)
    srv = mask & ~is_client_sock
    data = srv & ((f & N_DATA) != 0)
    app["rx_bytes"] = app["rx_bytes"] + jnp.where(data, nf.dlen.astype(jnp.int64), 0)
    msg = srv & ((f & N_MSG) != 0) & (nf.meta == STREAM_DONE)
    app["streams_served"] = app["streams_served"] + msg.astype(jnp.int32)
    st = st._replace(model=st.model._replace(app=app))

    # Server: peer finished → close our side. Teardown blocks are lax.cond-
    # gated out of steady-state rounds (exact: all writes masked).
    peer_fin = srv & ((f & N_PEER_FIN) != 0)
    st = jax.lax.cond(
        peer_fin.any(),
        lambda s: T.tcp_close(s, ctx, peer_fin, nf.sock, now),
        lambda s: s, st,
    )

    # Client: stream fully closed → think, then next stream (or done).
    closed = mask & is_client_sock & ((f & N_CLOSED) != 0)

    def _closed(st):
        app = dict(st.model.app)
        app["streams_left"] = app["streams_left"] - closed.astype(jnp.int32)
        app["streams_done"] = app["streams_done"] + closed.astype(jnp.int32)
        again = closed & (app["streams_left"] > 0)
        app["done_time"] = jnp.where(
            closed & (app["streams_left"] == 0), now, app["done_time"]
        )
        # Think draw belongs to the stream just completed: ctr was advanced
        # at start, so its index is ctr - 1.
        think_ctr = 3 * (app["ctr"] - 1) + 2
        think = rng.exponential_ns(
            rng.bits_v(ctx.key, R_APP, ctx.hosts, think_ctr), app["mean_think"]
        )
        st = st._replace(model=st.model._replace(app=app))
        return push_local_event(st, ctx, again, now + think, K_APP, p0=OP_START)

    return jax.lax.cond(closed.any(), _closed, lambda s: s, st)


def summary(app) -> dict:
    return {
        "rx_bytes": app["rx_bytes"],
        "streams_served": app["streams_served"],
        "streams_done": app["streams_done"],
        "done_time": app["done_time"],
        "total_rx_bytes": app["rx_bytes"].sum(),
        "total_streams_served": app["streams_served"].sum(),
        "total_streams_done": app["streams_done"].sum(),
    }
