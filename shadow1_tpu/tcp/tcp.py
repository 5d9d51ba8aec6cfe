"""Vectorized virtual TCP — every socket of every host updated in SIMD.

The tensor re-expression of the reference's biggest state machine
(src/main/host/descriptor/tcp.c, SURVEY §2.3): 3-way handshake, sliding
window, Reno-style congestion control (slow start, AIMD, fast retransmit on
3 dup-ACKs, RTO with exponential backoff), RFC6298 integer RTT estimation,
FIN teardown. State lives in a dict of ``[S, H]`` arrays (socket-major,
host-minor — core/dense.py layout contract); every operation
is a masked gather/scatter over the (host, socket) plane — one packet per
host per round, all hosts in parallel.

Deliberate model simplifications vs the reference (docs/SEMANTICS.md §tcp):

* Go-Back-N loss recovery: the receiver accepts only in-order segments (no
  out-of-order reassembly buffer / SACK); on retransmit the sender rewinds
  ``snd_nxt`` to ``snd_una``. Identical in both engines, so parity is exact;
  fidelity differs from the reference only under loss.
* Immediate ACKs (no delayed-ACK timer).
* Byte counts only — payload contents are never materialized (apps are
  models); message boundaries ride packets as (end_seq, meta) pairs, at
  most one per segment. A host's pending boundaries live in ONE pool
  ``[P, H]`` (``mq_sock`` −1 = free slot, ``mq_end``, ``mq_meta``;
  P = ``EngineParams.mq_pool``): a socket's queue is the slots that name it,
  in no order (every use is a set operation). ``msgq_cap`` stays the
  reference's per-socket bound (``len(k.mq) < msgq_cap``); a boundary that
  finds the pool full is dropped and counted (``Metrics.mq_overflow``).

Sequence space: u32 wrapping (i32 arrays, natural overflow). ISN = 0: SYN
occupies seq 0, stream byte k is seq 1+k, FIN occupies the seq after the
last byte.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from shadow1_tpu.consts import (
    F_ACK,
    F_FIN,
    F_SYN,
    K_PKT,
    K_TCP_TIMER,
    K_TX_RESUME,
    N_ACCEPTED,
    N_CLOSED,
    N_DATA,
    N_ESTABLISHED,
    N_MSG,
    N_PEER_FIN,
    N_SPACE,
    NP,
    TCP_CLOSE_WAIT,
    TCP_CLOSING,
    TCP_ESTABLISHED,
    TCP_FIN_WAIT_1,
    TCP_FIN_WAIT_2,
    TCP_FREE,
    TCP_LAST_ACK,
    TCP_LISTEN,
    TCP_SYN_RCVD,
    TCP_SYN_SENT,
    WIRE_OVERHEAD,
)
from shadow1_tpu.consts import (  # noqa: F811 — shared tuning/state sets
    CWND_MAX,
    SSTHRESH_INIT,
    TCP_CONN_STATES,
    TCP_RCV_STATES,
    TCP_SENDABLE_STATES,
)
from shadow1_tpu.core.dense import (
    extract_col,
    payload,
    first_true_idx,
    last_true,
    onehot_col,
    read_sel,
    set_col,
)
from shadow1_tpu.core.events import tb_join, tb_split
from shadow1_tpu.core.outbox import outbox_append, outbox_space
from shadow1_tpu.net.nic import ctx_aqm, tx_stamp

# Fields of the TCP state dict, all [S, H] unless noted.
_FIELDS_I32 = (
    "st", "peer_host", "peer_sock",
    "snd_una", "snd_nxt", "snd_max", "rcv_nxt", "app_end",  # seq (u32 wrap)
    "fin_pend", "cwnd", "ssthresh", "peer_wnd",
    "dupacks", "recover", "ts_seq", "txr",
)
# ``snd_max`` is the highest sequence ever sent (RFC 793's SND.NXT before
# any Go-Back-N rewind). Cumulative-ACK acceptance must test against it,
# not the rewound snd_nxt: after an RTO rewinds snd_nxt to snd_una, the
# receiver may legitimately ACK data it got BEFORE the loss event — with
# random loss the window's every ACK dying is vanishingly rare, but a
# fault-plane link outage makes it certain, and rejecting that ACK
# deadlocks the connection (the retransmitted low segment is below the
# receiver's rcv_nxt forever). Found by the PR-4 outage tests.
# Time-valued fields with i64 SEMANTICS (RTT estimator state, retransmit
# deadline, RTT-sample stamp — values up to rto_max·backoff / absolute sim
# time). Stored as order-preserving i32 (hi, lo) plane pairs (core/events.py
# tb_split): the chip has no native i64, and these planes see a one-hot
# full-plane write per set (core/dense.py set_col) on the round path.
# Sock.g/Sock.s and the tcp_flush helpers join/split at the [H]-vector
# level, so all arithmetic still happens on exact i64 values.
_FIELDS_I64 = ("srtt", "rttvar", "rto", "rtx_t", "ts_time")
_I64_SET = frozenset(_FIELDS_I64)
_FIELDS_BOOL = ("timer_armed", "ts_act")


def tcp_init(n_hosts: int, n_socks: int, mq_pool: int) -> dict:
    zhi, zlo = tb_split(jnp.zeros((), jnp.int64))
    d = {}
    for f in _FIELDS_I32:
        d[f] = jnp.zeros((n_socks, n_hosts), jnp.int32)
    for f in _FIELDS_I64:
        d[f + "_hi"] = jnp.full((n_socks, n_hosts), zhi, jnp.int32)
        d[f + "_lo"] = jnp.full((n_socks, n_hosts), zlo, jnp.int32)
    for f in _FIELDS_BOOL:
        d[f] = jnp.zeros((n_socks, n_hosts), bool)
    d["mq_sock"] = jnp.full((mq_pool, n_hosts), -1, jnp.int32)
    d["mq_end"] = jnp.zeros((mq_pool, n_hosts), jnp.int32)
    d["mq_meta"] = jnp.zeros((mq_pool, n_hosts), jnp.int32)
    return d


def mq_of(tcp: dict, sock) -> jnp.ndarray:
    """bool [P, H]: the pool slots that hold a boundary of socket
    ``sock[h]`` (a real socket id, 0..S-1, wherever the caller's mask
    holds; free slots read −1 and match none)."""
    return tcp["mq_sock"] == sock[None, :]


def mq_room(tcp: dict, sock, msgq_cap: int) -> jnp.ndarray:
    """bool [H]: socket ``sock[h]`` holds fewer than ``msgq_cap``
    boundaries — the reference's ``len(k.mq) < msgq_cap``."""
    return mq_of(tcp, sock).sum(axis=0, dtype=jnp.int32) < msgq_cap


def mq_fill(tcp: dict) -> jnp.ndarray:
    """The busiest host's boundaries in the pool (vs ``mq_pool``)."""
    return (tcp["mq_sock"] >= 0).sum(axis=0, dtype=jnp.int64).max()


class Sock:
    """Masked (host → socket) view over the TCP dict: readable sequential
    code, functional updates underneath. All reads/writes are [H] vectors at
    [sock, h]; writes apply only where the (possibly narrowed) mask holds."""

    def __init__(self, tcp: dict, sock, mask):
        self.d = dict(tcp)
        self.S = tcp["st"].shape[0]
        self.sock = sock
        self.mask = mask
        self._sel = None  # read one-hot [S, H]; sock and mask never change

    def g(self, k):
        if self._sel is None:
            self._sel = read_sel(jnp.where(self.mask, self.sock, 0), self.S)
        if k in _I64_SET:
            return tb_join(extract_col(self._sel, self.d[k + "_hi"]),
                           extract_col(self._sel, self.d[k + "_lo"]))
        return extract_col(self._sel, self.d[k])

    def s(self, k, val, where=None):
        m = self.mask if where is None else (self.mask & where)
        if k in _I64_SET:
            hi, lo = tb_split(jnp.asarray(val, jnp.int64))
            self.d[k + "_hi"] = set_col(self.d[k + "_hi"], self.sock, hi, m)
            self.d[k + "_lo"] = set_col(self.d[k + "_lo"], self.sock, lo, m)
            return
        self.d[k] = set_col(self.d[k], self.sock, val, m)


class Notif(NamedTuple):
    """Per-round, per-host transport→app notification (descriptor status
    bits analogue)."""

    sock: jnp.ndarray   # i32 [H]
    flags: jnp.ndarray  # i32 [H] bitmask of N_*
    meta: jnp.ndarray   # i32 [H] message meta (N_MSG / N_DGRAM)
    meta2: jnp.ndarray  # i32 [H] second dgram meta
    dlen: jnp.ndarray   # i32 [H] stream/dgram bytes delivered
    space: jnp.ndarray  # i32 [H] send-buffer space (N_SPACE)


def notif_none(n_hosts: int) -> Notif:
    z = jnp.zeros(n_hosts, jnp.int32)
    return Notif(z, z, z, z, z, z)


def _notify(nf: Notif, mask, sock, flag, meta=None, meta2=None, dlen=None, space=None) -> Notif:
    upd = lambda cur, v: jnp.where(mask, jnp.asarray(v, jnp.int32), cur)
    return Notif(
        sock=upd(nf.sock, sock),
        flags=jnp.where(mask, nf.flags | flag, nf.flags),
        meta=nf.meta if meta is None else upd(nf.meta, meta),
        meta2=nf.meta2 if meta2 is None else upd(nf.meta2, meta2),
        dlen=nf.dlen if dlen is None else upd(nf.dlen, dlen),
        space=nf.space if space is None else upd(nf.space, space),
    )


# --------------------------------------------------------------------------
# Packet emission
# --------------------------------------------------------------------------
def pack_meta(src_sock, dst_sock, flags):
    return (
        jnp.asarray(src_sock, jnp.int32)
        | (jnp.asarray(dst_sock, jnp.int32) << 8)
        | (jnp.asarray(flags, jnp.int32) << 16)
    )


def _emit(st, ctx, r: Sock, mask, flags, seq, length, mend, mmeta, now):
    """Emit one segment per host where mask: NIC stamp + outbox append.

    Caller must have established outbox space. Returns engine state.
    """
    p = payload(
        ctx.n_hosts, ctx.hosts, pack_meta(r.sock, r.g("peer_sock"), flags),
        seq, r.g("rcv_nxt"), jnp.asarray(length, jnp.int32),
        ctx.params.rcvbuf, mend, mmeta,
    )
    wire = jnp.asarray(length, jnp.int64) + WIRE_OVERHEAD
    nic, depart, sent, red = tx_stamp(
        st.model.nic, mask, wire, now, ctx.bw_up,
        ctx.tx_qlen_ns if ctx.has_tx_qlen else None,
        aqm=ctx_aqm(ctx), ser=ctx.ser_up,
    )
    k = jnp.full(ctx.n_hosts, K_PKT, jnp.int32)
    # A queue-dropped segment (tail or RED) behaves exactly like path loss:
    # sequence state advanced, packet never routed — retransmission recovers.
    outbox, ok = outbox_append(st.outbox, sent, r.g("peer_host"), k, depart, p)
    m = st.metrics
    st = st._replace(
        model=st.model._replace(nic=nic), outbox=outbox,
        metrics=m._replace(
            nic_tx_drops=m.nic_tx_drops
            + (mask & ~sent & ~red).sum(dtype=jnp.int64),
            nic_aqm_drops=m.nic_aqm_drops + red.sum(dtype=jnp.int64),
            # tcp_flush checks outbox_space before every segment, so this
            # "cannot" fire — but a vanishing segment with no counter would
            # be the worst possible failure mode, and the oracle counts it.
            ob_overflow=m.ob_overflow + (sent & ~ok).sum(dtype=jnp.int64),
        ),
    )
    if st.links is not None:
        # Link plane: egress-edge attribution of the drop-tail losses that
        # never reach route_outbox (same rule as net.udp_send).
        from shadow1_tpu.telemetry.links import link_nic_drops

        st = st._replace(links=link_nic_drops(
            st.links, ctx, mask & ~sent & ~red, r.g("peer_host")))
    return st


from shadow1_tpu.core.engine import pass_rows  # noqa: E402
from shadow1_tpu.core.engine import push_local_event as _push_local  # noqa: E402


# --------------------------------------------------------------------------
# Flush: packetize [snd_nxt, limit) — data, SYN, FIN — up to send_burst segs.
# --------------------------------------------------------------------------
_SENDABLE = TCP_SENDABLE_STATES
_FAR = 2**31 - 1  # no boundary: farther than any segment reaches


def _state_in(state, states):
    m = jnp.zeros_like(state, dtype=bool)
    for s in states:
        m = m | (state == s)
    return m


def tcp_flush(st, ctx, mask, sock, now):
    """Send as many pending segments of ``sock`` as burst/window/outbox
    allow; schedule K_TX_RESUME to continue if still pending — annotated
    ``phase:tcp_flush`` for the performance attribution plane (the flush
    machine is the single largest source in the deliver-pass op census,
    docs/PERF.md; the scope makes it visible in device traces and in
    tools/opcensus.py's per-source table)."""
    with jax.named_scope("phase:tcp_flush"):
        return _tcp_flush(st, ctx, mask, sock, now)


def _tcp_flush(st, ctx, mask, sock, now):
    """Send as many pending segments of ``sock`` as burst/window/outbox
    allow; schedule K_TX_RESUME to continue if still pending.

    Bit-exact vectorization of the former per-segment loop (round-4 op-count
    trim): socket state is read ONCE (one-hot passes through one read_sel,
    not gathers — core/dense.py), the burst recurrence (sequence
    advance, window/outbox budget, message-boundary truncation, NIC clock,
    RED coins) runs as cheap [H]-vector arithmetic per lane, and every heavy
    tensor write — the outbox append, the TCP field writes, the timer-event
    push — happens ONCE for the whole burst. Per-segment outputs (depart
    stamps, packet counters, RNG draws, drop decisions) replicate the loop's
    order exactly, so results are identical to the reference per-segment
    semantics (src/main/host/descriptor/tcp.c tcp_flush, SURVEY §3.4) and to
    the CPU oracle.
    """
    pr = ctx.params
    B = pr.send_burst
    H = ctx.n_hosts
    tcp = st.model.tcp
    # One read one-hot [S, H] for all ~24 reads of this flush.
    rsel = read_sel(jnp.where(mask, sock, 0), tcp["st"].shape[0])

    def g(f):
        return extract_col(rsel, tcp[f])

    def g64(f):
        return tb_join(g(f + "_hi"), g(f + "_lo"))

    state = g("st")
    sendable = mask & _state_in(state, _SENDABLE)
    snd_una = g("snd_una")
    nxt0 = g("snd_nxt")
    app_end, fin_p = g("app_end"), g("fin_pend")
    limit = jnp.minimum(g("cwnd"), g("peer_wnd"))
    rcv_nxt = g("rcv_nxt")
    peer_host, peer_sock = g("peer_host"), g("peer_sock")
    rto = g64("rto")
    # The socket's boundaries as distances past the flush's first byte,
    # [P, H], built ONCE a flush (u32 wrap: a lane's distance is this less
    # its advance, exactly); a slot of another socket or none reads _FAR.
    mq_d0 = jnp.where(mq_of(tcp, jnp.where(mask, sock, 0)),
                      tcp["mq_end"] - nxt0[None, :], _FAR)
    mqm = tcp["mq_meta"]
    is_synrcvd = state == TCP_SYN_RCVD

    # --- burst recurrence: cheap per-lane arithmetic, heavy ops deferred ---
    nxt = nxt0
    space = outbox_space(st.outbox)
    nic_run = st.model.nic  # threaded through tx_stamp lane by lane
    aqm = ctx_aqm(ctx)
    qlen = ctx.tx_qlen_ns if ctx.has_tx_qlen else None
    now64 = jnp.asarray(now, jnp.int64)
    ts_taken = g("ts_act")
    rtx_armed = g64("rtx_t") != 0
    lanes = []  # per-lane (sent, depart, seq, length, flags, mend, mmeta)
    n_tx_drop = jnp.zeros((), jnp.int64)
    n_red = jnp.zeros((), jnp.int64)
    # Link plane: per-HOST drop-tail counts across the burst lanes (each
    # host flushes one sock per call, so peer_host is the egress edge for
    # every lane). None when the plane is off — zero extra traced ops.
    tx_drop_h = jnp.zeros(H, jnp.int64) if st.links is not None else None
    ts_seq = g("ts_seq")
    ts_time = g64("ts_time")
    ts_first = jnp.zeros(H, bool)  # any lane took the RTT sample
    arm_any = jnp.zeros(H, bool)
    for _ in range(B):
        pending = (nxt - (app_end + fin_p)) < 0
        flight = nxt - snd_una
        can = sendable & pending & (flight < limit) & (space > 0)
        seg_syn = can & (nxt == 0)
        seg_fin = can & ~seg_syn & (nxt == app_end) & (fin_p == 1)
        seg_data = can & ~seg_syn & ~seg_fin
        length = jnp.where(
            seg_data,
            jnp.minimum(jnp.minimum(pr.mss, app_end - nxt), limit - flight),
            0,
        )
        flags = jnp.where(
            seg_syn,
            jnp.where(is_synrcvd, F_SYN | F_ACK, F_SYN),
            jnp.where(seg_fin, F_FIN | F_ACK, F_ACK),
        )
        # Message boundary riding this segment (truncating segmentation —
        # see tcp_send): min mq end in (nxt, nxt+len].
        rel = mq_d0 - (nxt - nxt0)[None, :]
        inrange = (rel > 0) & (rel <= length[None, :])
        dist = jnp.where(inrange, rel, _FAR)
        # Nearest boundary via min-reduce + equality one-hot (no argmin —
        # core/dense.py). A socket's ends are distinct while valid, so
        # `near` is one-hot among inrange slots.
        dmin = dist.min(axis=0)
        has_m = seg_data & (dmin != _FAR)
        near = inrange & (dist == dmin[None, :])
        mend = jnp.where(has_m, nxt + dmin, 0)
        mmeta = jnp.where(has_m, extract_col(near, mqm), 0)
        length = jnp.where(has_m, dmin, length)
        # NIC uplink reservation per lane — tx_stamp itself (pure [H]-vector
        # arithmetic) threaded on a running NicState, so RED/drop-tail
        # semantics have exactly one source of truth (net/nic.py).
        wire = length.astype(jnp.int64) + WIRE_OVERHEAD
        nic_run, depart, sent, red = tx_stamp(
            nic_run, can, wire, now64, ctx.bw_up, qlen, aqm=aqm,
            ser=ctx.ser_up,
        )
        n_tx_drop = n_tx_drop + (can & ~sent & ~red).sum(dtype=jnp.int64)
        n_red = n_red + red.sum(dtype=jnp.int64)
        if tx_drop_h is not None:
            tx_drop_h = tx_drop_h + (can & ~sent & ~red)
        lanes.append((sent, depart, nxt, length, flags, mend, mmeta))
        new_nxt = nxt + length + jnp.where(seg_syn | seg_fin, 1, 0)
        # RTT sample (Karn): first sample-taking segment of the burst wins.
        take_ts = can & ~ts_taken
        ts_seq = jnp.where(take_ts, new_nxt, ts_seq)
        ts_time = jnp.where(take_ts, now64, ts_time)
        ts_taken = ts_taken | take_ts
        ts_first = ts_first | take_ts
        arm_any = arm_any | (can & ~rtx_armed)
        rtx_armed = rtx_armed | can
        nxt = jnp.where(can, new_nxt, nxt)
        space = space - sent.astype(jnp.int32)
    # NOTE: `sent` excludes RED/queue drops, but `can` advanced nxt — a
    # dropped segment behaves exactly like path loss (state advanced,
    # packet never routed; retransmission recovers), as before.

    # --- one batched outbox append for the whole burst -------------------
    ob = st.outbox
    cap = ob.dst.shape[0]
    sent_l = jnp.stack([l[0] for l in lanes])                 # [B, H]
    rank = jnp.cumsum(sent_l, axis=0) - sent_l.astype(jnp.int32)
    pos = ob.cnt[None, :] + rank                              # [B, H]
    ok_l = sent_l & (pos < cap)
    n_new = sent_l.sum(axis=0, dtype=jnp.int32)
    slots = jnp.arange(cap, dtype=jnp.int32)[None, :, None]   # [1, P, 1]
    sel = ok_l[:, None, :] & (pos[:, None, :] == slots)       # [B, P, H]
    written = sel.any(axis=0)                                 # [P, H]

    def merge(old, lane_vals, dt):
        lv = jnp.stack(lane_vals).astype(dt)                  # [B, (NP,) H]
        if lv.ndim == 2:
            new = (sel * lv[:, None, :]).sum(axis=0, dtype=dt)
            return jnp.where(written, new, old)
        # payload lanes [B, NP, H] -> [NP, P, H]
        new = (sel[:, None, :, :] * lv[:, :, None, :]).sum(axis=0, dtype=dt)
        return jnp.where(written[None], new, old)

    dstL = [jnp.where(l[0], peer_host, 0) for l in lanes]
    # Departure times split to the outbox's i32 (hi, lo) planes at the
    # [H]-vector level (core/outbox.py layout; lo is sign-flipped but the
    # one-hot masked-sum merge is sign-agnostic). The ctr plane is the low
    # word of pkt_ctr (exact below 2**31 pkts/host).
    depL = [tb_split(l[1]) for l in lanes]
    dhiL = [d[0] for d in depL]
    dloL = [d[1] for d in depL]
    ctrL = [ob.pkt_ctr.astype(jnp.int32) + rank[i] for i in range(B)]
    pL = []
    p1 = pack_meta(sock, peer_sock, 0)
    for (snt, dep, seq, length, flags, mend, mmeta) in lanes:
        pL.append(payload(
            H, ctx.hosts, p1 | (flags << 16), seq, rcv_nxt, length,
            pr.rcvbuf, mend, mmeta,
        ))
    ob = ob._replace(
        dst=merge(ob.dst, dstL, jnp.int32),
        kind=jnp.where(written, K_PKT, ob.kind),
        depart_hi=merge(ob.depart_hi, dhiL, jnp.int32),
        depart_lo=merge(ob.depart_lo, dloL, jnp.int32),
        ctr=merge(ob.ctr, ctrL, jnp.int32),
        p=merge(ob.p, pL, jnp.int32),
        cnt=ob.cnt + n_new,
        pkt_ctr=ob.pkt_ctr + n_new.astype(jnp.int64),
    )
    n_ob_over = (sent_l & ~ok_l).sum(dtype=jnp.int64)

    # --- one batched write-back of the TCP fields ------------------------
    # nxt advanced where any lane's `can` held — including RED/queue-dropped
    # segments (can & ~sent). Track it directly:
    adv = nxt != nxt0
    d = dict(tcp)
    d["snd_nxt"] = set_col(d["snd_nxt"], sock, nxt, mask & adv)
    smax0 = g("snd_max")
    d["snd_max"] = set_col(
        d["snd_max"], sock, jnp.where((nxt - smax0) > 0, nxt, smax0),
        mask & adv,
    )
    d["ts_act"] = set_col(d["ts_act"], sock, True, mask & ts_first)
    d["ts_seq"] = set_col(d["ts_seq"], sock, ts_seq, mask & ts_first)
    tshi, tslo = tb_split(ts_time)
    d["ts_time_hi"] = set_col(d["ts_time_hi"], sock, tshi, mask & ts_first)
    d["ts_time_lo"] = set_col(d["ts_time_lo"], sock, tslo, mask & ts_first)
    rthi, rtlo = tb_split(now64 + rto)
    d["rtx_t_hi"] = set_col(d["rtx_t_hi"], sock, rthi, mask & arm_any)
    d["rtx_t_lo"] = set_col(d["rtx_t_lo"], sock, rtlo, mask & arm_any)
    timer_armed0 = g("timer_armed")
    need_ev = arm_any & ~timer_armed0
    d["timer_armed"] = set_col(d["timer_armed"], sock, True, mask & need_ev)

    m = st.metrics
    st = st._replace(
        model=st.model._replace(tcp=d, nic=nic_run),
        outbox=ob,
        metrics=m._replace(
            nic_tx_drops=m.nic_tx_drops + n_tx_drop,
            nic_aqm_drops=m.nic_aqm_drops + n_red,
            ob_overflow=m.ob_overflow + n_ob_over,
        ),
    )
    if tx_drop_h is not None:
        from shadow1_tpu.telemetry.links import link_nic_drops

        st = st._replace(links=link_nic_drops(
            st.links, ctx, tx_drop_h, peer_host))
    st = _push_local(st, ctx, need_ev, now64 + rto, K_TCP_TIMER, p0=sock)

    # Still pending but couldn't send → one TX_RESUME per sock (deduped).
    total_end = app_end + fin_p
    pending = (nxt - total_end) < 0
    wnd_ok = (nxt - snd_una) < limit
    blocked_outbox = outbox_space(st.outbox) <= 0
    txr0 = extract_col(rsel, st.model.tcp["txr"])
    more = sendable & pending & wnd_ok & (txr0 == 0)
    # Outbox-blocked sends resume at the next window start (after drain);
    # burst-limited sends resume immediately (same timestamp, next round).
    t_resume = jnp.where(
        blocked_outbox, (now // ctx.window + 1) * ctx.window, now
    )
    d2 = dict(st.model.tcp)
    d2["txr"] = set_col(d2["txr"], sock, 1, more)
    st = st._replace(model=st.model._replace(tcp=d2))
    return _push_local(st, ctx, more, t_resume, K_TX_RESUME, p0=sock)


def _ack_now(st, ctx, mask, sock, now):
    """Emit an immediate pure ACK (no data, no seq consumption)."""
    r = Sock(st.model.tcp, sock, mask)
    can = mask & (outbox_space(st.outbox) > 0)
    z = jnp.zeros(ctx.n_hosts, jnp.int32)
    return _emit(st, ctx, r, can, jnp.full(ctx.n_hosts, F_ACK, jnp.int32),
                 r.g("snd_nxt"), z, z, z, now)


# --------------------------------------------------------------------------
# App-facing API (vectorized, masked)
# --------------------------------------------------------------------------
def tcp_listen(st, ctx, mask, sock):
    r = Sock(st.model.tcp, sock, mask)
    r.s("st", TCP_LISTEN)
    return st._replace(model=st.model._replace(tcp=r.d))


def _init_conn(r: Sock, ctx, mask, peer_host, peer_sock, state, rcv_nxt):
    pr = ctx.params
    r.s("st", state, mask)
    r.s("peer_host", peer_host, mask)
    r.s("peer_sock", peer_sock, mask)
    r.s("snd_una", 0, mask)
    r.s("snd_nxt", 0, mask)
    r.s("snd_max", 0, mask)
    r.s("rcv_nxt", rcv_nxt, mask)
    r.s("app_end", 1, mask)
    r.s("fin_pend", 0, mask)
    r.s("cwnd", pr.init_cwnd_mss * pr.mss, mask)
    r.s("ssthresh", SSTHRESH_INIT, mask)
    r.s("peer_wnd", pr.mss, mask)  # lets the SYN go out; real wnd learned on first ACK
    r.s("srtt", 0, mask)
    r.s("rttvar", 0, mask)
    r.s("rto", pr.rto_init, mask)
    r.s("rtx_t", 0, mask)
    r.s("dupacks", 0, mask)
    r.s("recover", 0, mask)
    r.s("ts_act", False, mask)
    r.s("txr", 0, mask)
    r.d["mq_sock"] = jnp.where(
        mq_of(r.d, r.sock) & mask[None, :], -1, r.d["mq_sock"])


def tcp_connect(st, ctx, mask, sock, dst_host, dst_sock, now):
    r = Sock(st.model.tcp, sock, mask)
    _init_conn(r, ctx, mask, dst_host, dst_sock, TCP_SYN_SENT, 0)
    st = st._replace(model=st.model._replace(tcp=r.d))
    return tcp_flush(st, ctx, mask, sock, now)


def tcp_send(st, ctx, mask, sock, nbytes, meta, now):
    """Queue up to ``nbytes`` on the socket (clamped to send-buffer space);
    attach ``meta`` as a message boundary at the end iff fully queued and
    meta != 0. Returns (st, accepted[H])."""
    pr = ctx.params
    r = Sock(st.model.tcp, sock, mask)
    snd_una, app_end = r.g("snd_una"), r.g("app_end")
    buffered = (app_end - snd_una) - (snd_una == 0).astype(jnp.int32)
    space = jnp.maximum(pr.sndbuf - buffered, 0)
    accepted = jnp.clip(jnp.asarray(nbytes, jnp.int32), 0, space)
    accepted = jnp.where(mask, accepted, 0)
    new_end = app_end + accepted
    r.s("app_end", new_end, accepted > 0)
    # Message boundary bookkeeping.
    want_meta = mask & (accepted > 0) & (accepted == nbytes) & (jnp.asarray(meta, jnp.int32) != 0)
    # The reference's rule is per socket (len(k.mq) < msgq_cap); the slot
    # is the host pool's first free one. A boundary that has the socket's
    # leave and finds the pool full is dropped and counted, as a full
    # ev_cap drops an event (mq_pool is a capacity: docs/SEMANTICS.md).
    want_meta = want_meta & mq_room(r.d, r.sock, pr.msgq_cap)
    has_free, slot = first_true_idx(r.d["mq_sock"] < 0)
    sel = onehot_col(slot, r.d["mq_sock"].shape[0], want_meta & has_free)
    r.d["mq_sock"] = jnp.where(
        sel, jnp.asarray(r.sock, jnp.int32)[None, :], r.d["mq_sock"])
    r.d["mq_end"] = jnp.where(sel, new_end[None, :], r.d["mq_end"])
    r.d["mq_meta"] = jnp.where(
        sel, jnp.asarray(meta, jnp.int32)[None, :], r.d["mq_meta"]
    )
    met = st.metrics
    st = st._replace(
        model=st.model._replace(tcp=r.d),
        metrics=met._replace(mq_overflow=met.mq_overflow
                             + (want_meta & ~has_free).sum(dtype=jnp.int64)))
    st = tcp_flush(st, ctx, mask & (accepted > 0), sock, now)
    return st, accepted


def tcp_close(st, ctx, mask, sock, now):
    r = Sock(st.model.tcp, sock, mask)
    state = r.g("st")
    est = mask & (state == TCP_ESTABLISHED)
    cw = mask & (state == TCP_CLOSE_WAIT)
    r.s("st", TCP_FIN_WAIT_1, est)
    r.s("st", TCP_LAST_ACK, cw)
    r.s("fin_pend", 1, est | cw)
    st = st._replace(model=st.model._replace(tcp=r.d))
    return tcp_flush(st, ctx, est | cw, sock, now)


# --------------------------------------------------------------------------
# Receive path — one packet per host per round, all hosts in parallel.
# Mirrors the sequencing of the reference's tcp_processPacket (SURVEY §3.4):
# connection demux → ACK processing (cwnd/RTT/retransmit) → payload →
# FIN → immediate ACK, then app notifications.
# --------------------------------------------------------------------------
_CONN_STATES = TCP_CONN_STATES
_RCV_STATES = TCP_RCV_STATES


# Push sites (core/engine.pass_rows): a flush traces two, the retransmit
# timer's event and TX_RESUME's. tcp_rx flushes in ``_accept`` and after the ACK
# processing; the callers that go on into an app add the app's.
@pass_rows(4)
def tcp_rx(st, ctx, mask, p, now):
    """Process one arrived TCP segment per host where ``mask``.

    Returns (st, Notif). ``now`` is the per-host event time vector.
    """
    pr = ctx.params
    H = ctx.n_hosts
    src = p[0]
    packed = p[1]
    ss = packed & 0xFF
    ds = (packed >> 8) & 0xFF
    flags = (packed >> 16) & 0xFF
    seq, ackno, length = p[2], p[3], p[4]
    wnd, mend, mmeta = p[5], p[6], p[7]
    is_syn = (flags & F_SYN) != 0
    is_ack = (flags & F_ACK) != 0
    is_fin = (flags & F_FIN) != 0
    nf = notif_none(H)

    # ---- passive open: SYN → LISTEN socket spawns a child (tcp.c accept
    # path). Whole block under lax.cond: bare SYNs exist only while
    # connections open, and the block carries a full tcp_flush (the SYN|ACK
    # emit) — dead weight in every steady-state deliver round otherwise.
    tcp = st.model.tcp
    r0 = Sock(tcp, ds, mask)
    syn_to_listen = mask & is_syn & ~is_ack & (r0.g("st") == TCP_LISTEN)

    def _accept(st):
        tcp = st.model.tcp
        dup = (
            (tcp["peer_host"] == src[None, :])
            & (tcp["peer_sock"] == ss[None, :])
            & (tcp["st"] != TCP_FREE)
            & (tcp["st"] != TCP_LISTEN)
        ).any(axis=0)
        free = tcp["st"] == TCP_FREE
        # Children take the HIGHEST free slot: low slots are app-owned (0 =
        # listener, 1 = client socket on dual-role hosts) and may be
        # TCP_FREE between uses — allocating from the top keeps them
        # unclobbered. Max-reduce, not argmax (core/dense.py).
        new_conn0, child = last_true(free)
        new_conn = syn_to_listen & ~dup & new_conn0
        rc = Sock(tcp, child, new_conn)
        _init_conn(rc, ctx, new_conn, src, ss, TCP_SYN_RCVD, 1)
        rc.s("peer_wnd", wnd, new_conn)
        st = st._replace(model=st.model._replace(tcp=rc.d))
        return tcp_flush(st, ctx, new_conn, child, now)  # emits SYN|ACK

    # (The lane's own predicate, not ``any_host``: on a fleet some lane is in
    # this block in nearly every deliver round, and a real conditional here
    # only splits the pass's fused writes of the event planes into separate
    # sweeps — PERF.md §6, PR 38.)
    st = jax.lax.cond(syn_to_listen.any(), _accept, lambda s: s, st)

    # ---- established-path demux: peer must match (guards stale/reused slots)
    r = Sock(st.model.tcp, ds, mask)
    state = r.g("st")
    # A client in SYN_SENT connected to the *listener*; the SYN|ACK arrives
    # from the freshly-spawned child socket — accept it by host only and
    # learn the true peer socket from it.
    learn_peer = (state == TCP_SYN_SENT) & is_syn & is_ack
    v = (
        mask
        & ~syn_to_listen
        & _state_in(state, _CONN_STATES)
        & (r.g("peer_host") == src)
        & ((r.g("peer_sock") == ss) | learn_peer)
    )
    r.s("peer_sock", ss, v & learn_peer)
    r.s("peer_wnd", jnp.maximum(wnd, 1), v & is_ack)

    # ---- ACK processing. Acceptance tests against snd_max (highest ever
    # sent), NOT the possibly-rewound snd_nxt — see the snd_max note at
    # _FIELDS_I32 (outage-recovery deadlock otherwise).
    a = v & is_ack
    snd_una, snd_nxt = r.g("snd_una"), r.g("snd_nxt")
    snd_max = r.g("snd_max")
    new_ack = a & ((ackno - snd_una) > 0) & ((ackno - snd_max) <= 0)
    # RTT sample (RFC6298, integer ns; err>>3 is floor division by 8).
    ts_ok = new_ack & r.g("ts_act") & ((ackno - r.g("ts_seq")) >= 0)
    rtt = jnp.maximum(now - r.g("ts_time"), 1)
    first = r.g("srtt") == 0
    err = rtt - r.g("srtt")
    srtt_n = jnp.where(first, rtt, r.g("srtt") + (err >> 3))
    rttvar_n = jnp.where(first, rtt // 2, r.g("rttvar") + ((jnp.abs(err) - r.g("rttvar")) >> 2))
    rto_n = jnp.clip(srtt_n + jnp.maximum(4 * rttvar_n, 1_000_000), pr.rto_min, pr.rto_max)
    r.s("srtt", srtt_n, ts_ok)
    r.s("rttvar", rttvar_n, ts_ok)
    r.s("rto", rto_n, ts_ok)
    r.s("ts_act", False, ts_ok)
    # cwnd growth: slow start below ssthresh, else AIMD (tcp_cong_reno.c).
    cwnd = r.g("cwnd")
    grow = jnp.where(
        cwnd < r.g("ssthresh"), pr.mss, jnp.maximum((pr.mss * pr.mss) // jnp.maximum(cwnd, 1), 1)
    )
    r.s("cwnd", jnp.minimum(cwnd + grow, CWND_MAX), new_ack)
    r.s("snd_una", ackno, new_ack)
    # An ACK beyond the rewound snd_nxt pulls it forward: those bytes were
    # sent (snd_max proves it) and are now acked — never resend them.
    r.s("snd_nxt", ackno, new_ack & ((ackno - snd_nxt) > 0))
    r.s("dupacks", 0, new_ack)
    # Retire message boundaries the peer has fully acked.
    acked = (mq_of(r.d, ds) & new_ack[None, :]
             & ((r.d["mq_end"] - ackno[None, :]) <= 0))
    r.d["mq_sock"] = jnp.where(acked, -1, r.d["mq_sock"])
    # Restart (or clear) the retransmit deadline.
    outstanding = (snd_max - ackno) > 0
    r.s("rtx_t", jnp.where(outstanding, now + r.g("rto"), 0), new_ack)

    # State transitions driven by this ACK.
    est_sr = new_ack & (state == TCP_SYN_RCVD)
    r.s("st", TCP_ESTABLISHED, est_sr)
    nf = _notify(nf, est_sr, ds, N_ACCEPTED)
    est_ss = a & is_syn & (state == TCP_SYN_SENT) & (ackno == 1)
    r.s("st", TCP_ESTABLISHED, est_ss)
    r.s("rcv_nxt", 1, est_ss)
    nf = _notify(nf, est_ss, ds, N_ESTABLISHED)
    total_end = r.g("app_end") + r.g("fin_pend")
    fin_acked = new_ack & (r.g("fin_pend") == 1) & (ackno == total_end)
    r.s("st", TCP_FIN_WAIT_2, fin_acked & (state == TCP_FIN_WAIT_1))
    closed_by_ack = fin_acked & ((state == TCP_CLOSING) | (state == TCP_LAST_ACK))
    nf = _notify(nf, closed_by_ack, ds, N_CLOSED)
    sp = new_ack & ((state == TCP_ESTABLISHED) | (state == TCP_CLOSE_WAIT)) & ~closed_by_ack
    space = pr.sndbuf - (r.g("app_end") - ackno)
    nf = _notify(nf, sp, ds, N_SPACE, space=space)

    # Duplicate ACKs → fast retransmit (Go-Back-N rewind) at the threshold.
    # A scope of its own (``phase:tcp_fast_rtx``, inside the pass's) so the
    # attribution plane shows the episode as a row; the flush that resends
    # is ``phase:tcp_flush``'s, below.
    with jax.named_scope("phase:tcp_fast_rtx"):
        dup_a = a & ~new_ack & (ackno == snd_una) & outstanding & (length == 0) & ~is_syn & ~is_fin
        dp = r.g("dupacks") + 1
        r.s("dupacks", dp, dup_a)
        frx = dup_a & (dp == pr.dupack_thresh) & ((snd_una - r.g("recover")) >= 0)
        flight = snd_nxt - snd_una
        ssth = jnp.maximum(flight // 2, 2 * pr.mss)
        r.s("ssthresh", ssth, frx)
        r.s("cwnd", ssth, frx)
        r.s("recover", snd_nxt, frx)
        r.s("snd_nxt", snd_una, frx)
        r.s("ts_act", False, frx)

        st = st._replace(model=st.model._replace(tcp=r.d))
        met = st.metrics
        st = st._replace(metrics=met._replace(
            tcp_fast_rtx=met.tcp_fast_rtx + frx.sum(dtype=jnp.int64)))
    st = tcp_flush(st, ctx, new_ack | frx, ds, now)

    # ---- payload (in-order only: Go-Back-N receiver) and FIN
    r = Sock(st.model.tcp, ds, mask)
    state2 = r.g("st")
    can_rcv = v & _state_in(state2, _RCV_STATES)
    has_data = can_rcv & (length > 0)
    in_order = has_data & (seq == r.g("rcv_nxt"))
    r.s("rcv_nxt", r.g("rcv_nxt") + length, in_order)
    nf = _notify(nf, in_order, ds, N_DATA, dlen=length)
    msg = in_order & (mend != 0)
    nf = _notify(nf, msg, ds, N_MSG, meta=mmeta)
    # FIN: in order once preceding data (if any) is consumed. The teardown
    # block runs under lax.cond — FINs appear only at stream close, and
    # every deliver round otherwise paid its state machinery for nothing.
    def _fin(rd, nf):
        r2 = Sock(rd, ds, mask)
        fin_here = v & is_fin & ((seq + length) == r2.g("rcv_nxt")) & _state_in(
            state2, (TCP_ESTABLISHED, TCP_FIN_WAIT_1, TCP_FIN_WAIT_2)
        )
        r2.s("rcv_nxt", r2.g("rcv_nxt") + 1, fin_here)
        to_cw = fin_here & (state2 == TCP_ESTABLISHED)
        r2.s("st", TCP_CLOSE_WAIT, to_cw)
        nf2 = _notify(nf, to_cw, ds, N_PEER_FIN)
        to_closing = fin_here & (state2 == TCP_FIN_WAIT_1)
        r2.s("st", TCP_CLOSING, to_closing)
        closed_by_fin = fin_here & (state2 == TCP_FIN_WAIT_2)
        nf2 = _notify(nf2, closed_by_fin, ds, N_CLOSED)
        return r2.d, nf2, closed_by_fin

    rd, nf, closed_by_fin = jax.lax.cond(
        (v & is_fin).any(), _fin,
        lambda rd, nf: (rd, nf, jnp.zeros_like(v)),
        dict(r.d), nf,
    )
    r = Sock(rd, ds, mask)

    # Free fully-closed sockets (slot reuse; stale packets are dropped by the
    # peer-match guard above).
    freed = closed_by_ack | closed_by_fin
    r.s("st", TCP_FREE, freed)
    r.s("rtx_t", 0, freed)

    # Immediate ACK policy: ack any data (dup-ACK for OOO), any FIN (in or
    # out of order), and the final step of the client handshake.
    need_ack = has_data | (v & is_fin) | est_ss
    st = st._replace(model=st.model._replace(tcp=r.d))
    st = _ack_now(st, ctx, need_ack, ds, now)
    met = st.metrics
    st = st._replace(metrics=met._replace(
        tcp_ooo_drops=met.tcp_ooo_drops + (has_data & ~in_order).sum(dtype=jnp.int64)))
    return st, nf


# --------------------------------------------------------------------------
# Timer + TX-resume event handlers
# --------------------------------------------------------------------------
@pass_rows(3)
def on_tcp_timer(st, ctx, ev):
    """K_TCP_TIMER: lazy single-event-per-socket retransmit timer.

    The event is a *check*: if the deadline moved into the future (ACKs
    restarted it) the event re-arms itself at the new deadline; if the
    deadline is gone it dies; else → RTO: multiplicative backoff, cwnd to
    one segment, Go-Back-N rewind, retransmit (tcp.c retransmit timer).
    """
    pr = ctx.params
    m = ev.mask & (ev.kind == K_TCP_TIMER)
    sock = ev.p[0]
    now = ev.time
    r = Sock(st.model.tcp, sock, m)
    r.s("timer_armed", False, m)
    deadline = r.g("rtx_t")
    live = m & (deadline != 0)
    future = live & (now < deadline)
    r.s("timer_armed", True, future)
    fire = live & ~future
    outstanding = (r.g("snd_max") - r.g("snd_una")) > 0
    # The timeout's rewind under a scope of its own (``phase:tcp_rto``,
    # inside ``phase:h_timer``): a row of the attribution plane.
    with jax.named_scope("phase:tcp_rto"):
        rto_fire = fire & outstanding & _state_in(r.g("st"), _SENDABLE)
        flight = r.g("snd_nxt") - r.g("snd_una")
        r.s("ssthresh", jnp.maximum(flight // 2, 2 * pr.mss), rto_fire)
        r.s("cwnd", pr.mss, rto_fire)
        rto_n = jnp.minimum(r.g("rto") * 2, pr.rto_max)
        r.s("rto", rto_n, rto_fire)
        r.s("snd_nxt", r.g("snd_una"), rto_fire)
        r.s("ts_act", False, rto_fire)
        r.s("dupacks", 0, rto_fire)
        r.s("recover", r.g("snd_una"), rto_fire)
        r.s("rtx_t", now + rto_n, rto_fire)
        r.s("timer_armed", True, rto_fire)
        r.s("rtx_t", 0, fire & ~rto_fire)
        st = st._replace(model=st.model._replace(tcp=r.d))
        met = st.metrics
        st = st._replace(metrics=met._replace(
            tcp_rto=met.tcp_rto + rto_fire.sum(dtype=jnp.int64)))
    # One pending event per socket: re-push at whichever deadline applies.
    repush = future | rto_fire
    t_ev = jnp.where(future, deadline, now + rto_n)
    st = _push_local(st, ctx, repush, t_ev, K_TCP_TIMER, p0=sock)
    return tcp_flush(st, ctx, rto_fire, sock, now)


@pass_rows(2)
def on_tx_resume(st, ctx, ev):
    """K_TX_RESUME: continue a burst- or outbox-bounded flush."""
    m = ev.mask & (ev.kind == K_TX_RESUME)
    sock = ev.p[0]
    r = Sock(st.model.tcp, sock, m)
    r.s("txr", 0, m)
    st = st._replace(model=st.model._replace(tcp=r.d))
    return tcp_flush(st, ctx, m, sock, ev.time)
