"""The "net" workload model: NIC + TCP/UDP transport + model applications.

This composes the tensor equivalents of the reference's host stack
(SURVEY §2.3): NetworkInterface (net/nic.py), the descriptor/TCP subsystem
(tcp/tcp.py), and the application layer (apps/*) that replaces real plugin
binaries with state-machine traffic models (the sanctioned substitution,
SURVEY §2.4). Event flow per arrived packet mirrors the reference call
stack §3.4: K_PKT (NIC receive queue) → K_PKT_DELIVER (TCP/UDP processing)
→ app notification → app reaction (sends, closes) in the same round.

model_cfg: ``{"app": <name>, ...app-specific numpy arrays}``.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from shadow1_tpu.consts import (
    F_DGRAM,
    K_APP,
    K_NONE,
    K_PKT,
    K_PKT_DELIVER,
    K_TCP_TIMER,
    K_TX_RESUME,
    N_DGRAM,
    WIRE_OVERHEAD,
)
from shadow1_tpu.core.dense import payload
from shadow1_tpu.core.engine import pass_rows, rows_of
from shadow1_tpu.core.events import I64_MAX, push_local, tb_split
from shadow1_tpu.core.outbox import outbox_append
from shadow1_tpu.net.nic import (
    NicState,
    ctx_aqm,
    nic_init,
    rx_stamp,
    ser_delay,
    tx_stamp,
)
from shadow1_tpu.tcp import tcp as T


class NetState(NamedTuple):
    nic: NicState
    tcp: dict
    app: Any


def _app_module(name: str):
    if name == "filexfer":
        from shadow1_tpu.apps import filexfer

        return filexfer
    if name == "dgram":
        from shadow1_tpu.apps import dgram

        return dgram
    if name == "tgen":
        from shadow1_tpu.apps import tgen

        return tgen
    if name == "tor":
        from shadow1_tpu.apps import tor

        return tor
    if name == "bitcoin":
        from shadow1_tpu.apps import bitcoin

        return bitcoin
    raise ValueError(f"unknown app {name!r}")


def init(ctx, evbuf):
    pr = ctx.params
    nic = nic_init(ctx.n_hosts)
    tcpd = T.tcp_init(ctx.n_hosts, pr.sockets_per_host, pr.mq_pool)
    app_mod = _app_module(ctx.model_cfg["app"])
    app, evbuf, over, tcpd = app_mod.init(ctx, evbuf, tcpd)
    return NetState(nic=nic, tcp=tcpd, app=app), evbuf, over


def udp_send(st, ctx, mask, dst_host, dst_sock, length, meta, meta2, now):
    """Datagram send: NIC uplink stamp + outbox packet with F_DGRAM.

    The reference's UDP socket (src/main/host/descriptor/udp.c): no
    handshake, no reliability; loss/latency/bandwidth still apply.
    """
    p = payload(
        ctx.n_hosts, ctx.hosts, T.pack_meta(0, dst_sock, F_DGRAM), None, None,
        jnp.asarray(length, jnp.int32), None, None,
        jnp.asarray(meta, jnp.int32), jnp.asarray(meta2, jnp.int32),
    )
    wire = jnp.asarray(length, jnp.int64) + WIRE_OVERHEAD
    nic, depart, sent, red = tx_stamp(
        st.model.nic, mask, wire, now, ctx.bw_up,
        ctx.tx_qlen_ns if ctx.has_tx_qlen else None,
        aqm=ctx_aqm(ctx), ser=ctx.ser_up,
    )
    k = jnp.full(ctx.n_hosts, K_PKT, jnp.int32)
    outbox, ok = outbox_append(st.outbox, sent, dst_host, k, depart, p)
    m = st.metrics
    st = st._replace(
        model=st.model._replace(nic=nic),
        outbox=outbox,
        metrics=m._replace(
            ob_overflow=m.ob_overflow + (sent & ~ok).sum(dtype=jnp.int64),
            nic_tx_drops=m.nic_tx_drops
            + (mask & ~sent & ~red).sum(dtype=jnp.int64),
            nic_aqm_drops=m.nic_aqm_drops + red.sum(dtype=jnp.int64),
        ),
    )
    if st.links is not None:
        # Link plane: drop-tail losses never reach route_outbox, so their
        # egress-edge attribution happens here, at the tx site.
        from shadow1_tpu.telemetry.links import link_nic_drops

        st = st._replace(links=link_nic_drops(
            st.links, ctx, mask & ~sent & ~red, dst_host))
    return st


def make_pre_window(ctx):
    """Batched NIC-arrival processing — the K_PKT round eliminator.

    Packet arrivals dominated the inner-round count: every delivered packet
    cost its host one K_PKT round (NIC receive-queue stamp) before its
    K_PKT_DELIVER round, and a busy relay's round count is the per-window
    maximum. But the NIC rx chain depends ONLY on arrival order and the
    rx_free clock — never on interleaved app/timer events — and every
    K_PKT eligible in a window exists in the event buffer at window start
    (packets are created only by the window-end exchange). So one batched
    per-host pass computes the exact FIFO schedule the per-round handler
    would: sort each host's eligible K_PKT slots by (time, tb) — the
    packet-length plane rides that sort as a payload operand, so nothing is
    gathered back through the sort's index — run a
    max-plus associative scan ``free_j = max(free_{j-1}, arr_j) + ser_j``,
    and convert each slot IN PLACE to K_PKT_DELIVER at its queue-cleared
    time, keeping the packet's own tie-break (docs/SEMANTICS.md §packet
    path — the oracle mirrors this exactly, so parity is bit-identical).

    Returns None (keeping the per-round K_PKT handler) when the rx
    drop-tail queue is configured (its drop decisions feed back into the
    clock recurrence, which breaks the max-plus associativity) or when the
    virtual-CPU model is on (arrival events must charge per-event cpu time
    — round-3 advisor finding; the oracle mirrors both gates)."""
    if ctx.has_rx_qlen or ctx.has_cpu:
        return None
    neg = -(1 << 62)

    def pre_window(st, _ctx, win_end):
        buf = st.evbuf
        cap, h = buf.kind.shape
        # Absolute times join once per window (the buffer planes are i32 —
        # core/events.py EventBuf); writes below split back via tb_split.
        abs_t = buf.abs_time()
        sel = (buf.kind == K_PKT) & (abs_t < win_end)
        kind0, time0 = buf.kind, abs_t
        m = st.metrics
        if ctx.has_stop:
            from shadow1_tpu.fault.plane import hosts_down_at

            # A dead host discards arrivals unprocessed (run_round rule);
            # they must not reserve the downlink.
            down = sel & hosts_down_at(ctx.fault_down, ctx.fault_up, abs_t)
            sel = sel & ~down
            kind0 = jnp.where(down, K_NONE, kind0)
            time0 = jnp.where(down, I64_MAX, time0)
            m = m._replace(down_events=m.down_events
                           + down.sum(dtype=jnp.int64))
        t_key = jnp.where(sel, abs_t, I64_MAX)
        # Tie-break ordering over the pre-split (hi, lo) i32 planes
        # (core/events.py tb_split): lexicographic (time, tb_hi, tb_lo)
        # equals the (time, tb) i64 order.
        hi_key = jnp.where(sel, buf.tb_hi, jnp.iinfo(jnp.int32).max)
        lo_key = jnp.where(sel, buf.tb_lo, jnp.iinfo(jnp.int32).max)
        idx = jnp.broadcast_to(
            jnp.arange(cap, dtype=jnp.int32)[:, None], (cap, h)
        )
        # plen is a payload of the sort, not a take_along_axis through
        # idx_s (an element-serial fusion on the TPU: PERF.md §6, PR 34).
        t_s, _hi_s, _lo_s, idx_s, plen = jax.lax.sort(
            (t_key, hi_key, lo_key, idx, buf.p[4]), dimension=0, num_keys=3
        )
        valid = t_s < I64_MAX
        wire = jnp.where(valid, plen.astype(jnp.int64) + WIRE_OVERHEAD, 0)
        ser = jnp.where(
            valid, ser_delay(wire, ctx.bw_dn[None, :], ctx.ser_dn), 0)
        # Max-plus prefix: each packet is the affine map x ↦ max(x+p, q)
        # with p = ser, q = arr + ser; invalid slots are the identity.
        pq = (ser, jnp.where(valid, t_s + ser, neg))
        p_pre, q_pre = jax.lax.associative_scan(
            lambda a, b: (a[0] + b[0], jnp.maximum(a[1] + b[0], b[1])),
            pq, axis=0,
        )
        free0 = st.model.nic.rx_free[None, :]
        free = jnp.maximum(free0 + p_pre, q_pre)      # clock after packet j
        ready = free - ser                            # = max(free_{j-1}, arr)
        # Un-sort: order by slot index restores original positions.
        _i, ready_o, valid_o = jax.lax.sort(
            (idx_s, ready, valid.astype(jnp.int32)), dimension=0, num_keys=1
        )
        vo = valid_o != 0
        nic = st.model.nic._replace(
            # A static slice, under vmap too (free[-1, :] batches to a
            # gather).
            rx_free=jax.lax.index_in_dim(free, cap - 1, 0, keepdims=False),
            rx_bytes=st.model.nic.rx_bytes + wire.sum(axis=0),
        )
        new_time = jnp.where(vo, ready_o, time0)
        thi, tlo = tb_split(new_time)
        evbuf = buf._replace(
            kind=jnp.where(vo, K_PKT_DELIVER, kind0),
            time_hi=thi,
            time_lo=tlo,
        )
        return st._replace(
            evbuf=evbuf, model=st.model._replace(nic=nic), metrics=m
        )

    return pre_window


def make_handlers(ctx):
    app_mod = _app_module(ctx.model_cfg["app"])
    app_on_notify = app_mod.on_notify
    app_on_wakeup = app_mod.on_wakeup

    @pass_rows(1)
    def on_pkt(st, ev):
        """K_PKT: packet reached the dst NIC — model the receive queue
        (drop-tail when the downlink queue bound is exceeded)."""
        m = ev.mask & (ev.kind == K_PKT)
        wire = jnp.asarray(ev.p[4], jnp.int64) + WIRE_OVERHEAD
        nic, ready, okq = rx_stamp(
            st.model.nic, m, wire, ev.time, ctx.bw_dn,
            ctx.rx_qlen_ns if ctx.has_rx_qlen else None, ser=ctx.ser_dn,
        )
        st = st._replace(model=st.model._replace(nic=nic))
        k = jnp.full(ctx.n_hosts, K_PKT_DELIVER, jnp.int32)
        evbuf, over = push_local(st.evbuf, okq, ready, k, ev.p)
        met = st.metrics
        return st._replace(
            evbuf=evbuf,
            metrics=met._replace(
                ev_overflow=met.ev_overflow + over.sum(dtype=jnp.int64),
                nic_rx_drops=met.nic_rx_drops + (m & ~okq).sum(dtype=jnp.int64),
            ),
        )

    @pass_rows(T.tcp_rx.push_rows + rows_of(app_on_notify, ctx))
    def on_deliver(st, ev):
        """K_PKT_DELIVER: the packet cleared the NIC — run TCP/UDP, then app."""
        m = ev.mask & (ev.kind == K_PKT_DELIVER)
        flags = (ev.p[1] >> 16) & 0xFF
        is_dgram = (flags & F_DGRAM) != 0
        st, nf = T.tcp_rx(st, ctx, m & ~is_dgram, ev.p, ev.time)
        dg = m & is_dgram
        nf = T._notify(
            nf, dg, (ev.p[1] >> 8) & 0xFF, N_DGRAM,
            meta=ev.p[7], meta2=ev.p[8], dlen=ev.p[4],
        )
        return app_on_notify(st, ctx, nf, ev.time, nf.flags != 0)

    @pass_rows(T.on_tcp_timer.push_rows)
    def on_timer(st, ev):
        return T.on_tcp_timer(st, ctx, ev)

    @pass_rows(T.on_tx_resume.push_rows)
    def on_txr(st, ev):
        return T.on_tx_resume(st, ctx, ev)

    @pass_rows(rows_of(app_on_wakeup, ctx))
    def on_app(st, ev):
        m = ev.mask & (ev.kind == K_APP)
        return app_on_wakeup(st, ctx, ev, m)

    handlers = {
        K_PKT: on_pkt,
        K_PKT_DELIVER: on_deliver,
        K_TCP_TIMER: on_timer,
        K_TX_RESUME: on_txr,
        K_APP: on_app,
    }
    if not (ctx.has_rx_qlen or ctx.has_cpu):
        # Arrivals are batch-converted by make_pre_window — no K_PKT event
        # ever reaches a round, so the pass (and its cond) would be dead.
        del handlers[K_PKT]
    return handlers


def summary(model: NetState, ctx) -> dict:
    d = {
        "nic_tx_bytes": model.nic.tx_bytes,
        "nic_rx_bytes": model.nic.rx_bytes,
    }
    d.update(_app_module(ctx.model_cfg["app"]).summary(model.app))
    return d
