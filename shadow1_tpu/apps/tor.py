"""tor — circuit-layer Tor model over the virtual TCP stack (BASELINE 3/4).

The model-application analogue of the reference's Tor plugin
(shadow-plugin-tor, SURVEY §2.4/§7.1: "Tor = circuit-layer message model:
client builds circuits over relays, fixed-size cells, per-hop queueing").
What is modeled:

* bootstrap — each client fetches a consensus document from a dirauth over
  TCP before building circuits (the dirauth role of rung 4);
* weighted path selection — guard/middle/exit drawn ∝ consensus bandwidth
  weight from the configured relay sets (real Tor's bandwidth-weighted
  sampling), via shared counter-based draws;
* telescoping circuit build — CREATE/CREATED, EXTEND/EXTENDED relayed
  through the partial circuit; relays open (or reuse) onward TCP conns on
  demand and multiplex circuits over them with per-conn circuit ids, the
  real link-protocol shape;
* streams — BEGIN to the exit, a cell-stream reply (one message of
  n_cells × 512 B), END; client thinks, then next stream/circuit.

Cells are 512-byte message boundaries on TCP (meta = circ<<18|aux<<4|cmd);
all loss/retransmit/queueing rides the virtual TCP machinery. Deliberate
model simplifications (docs/SEMANTICS.md): no DESTROY (circuits persist;
table capacity `ct_cap` must cover all circuits built), DATA streams are
store-and-forwarded per hop as whole messages (no circuit-level sendme flow
control yet), one circuit at a time per client.

Fan-out (dialing, cell sends, pending-CREATE drains) is expressed as
self-scheduled events so the traced round body instantiates the TCP send
path once (see apps/bitcoin.py note). The OP_TX_CELL site admission-checks
send-buffer space and a free message-boundary slot and retries next window
otherwise, so a congested conn defers cells instead of losing framing.

Phase scopes (under the engine's ``phase:h_app`` / ``phase:h_deliver``, read
by telemetry/phases.py; every op this module traces in a handler pass sits
under one of them, and the TCP calls inside keep their own rows, e.g.
``tor_relay/tcp_flush``): ``phase:tor_dir`` (the bootstrap: OP_START's dial
of a dirauth, the consensus request and reply with the guard drawn and
dialed on it, the dirauth's side), ``phase:tor_build`` (circuit
construction: the first CREATE once the guard conn is up, CREATE / CREATED /
EXTEND / EXTENDED at a relay and at the client, OP_CONNECT_RELAY),
``phase:tor_relay`` (a cell crossing a hop: the decode of a notification's
cell header, the circuit-table lookup, forwarding BEGIN / DATA / END, the
exit serving a stream, OP_TX_CELL's admission check and ``tcp_send``,
OP_DRAIN), ``phase:tor_stream`` (the client's streams: BEGIN, DATA / END
received, completion, the think timer). Two sites nest — the first stream
begun from the last EXTENDED (``tor_build/tor_stream``) and the next circuit
begun from a think timer (``tor_stream/tor_build``) — and count under the
inner scope.

model_cfg:
  role           i32 [H]: 0=relay 1=client 2=dirauth 3=idle
  relay_weight   i64 [H] consensus weight (>0 for relays; Σ < 2^31)
  is_guard       bool [H], is_exit: bool [H] (subsets of relays)
  n_circuits     i32 [H] circuits per client (sequential)
  n_streams      i32 [H] streams per circuit (sequential)
  mean_stream_cells  f [H] mean cells per stream (exp, clip [1, cells_max])
  mean_think_ns  f [H]
  start_time     i64 [H]
  consensus_bytes  int (default 2048)
  cells_max      int (default 120; 120·512 B ≪ sndbuf)
  ct_cap         int (default 64) circuit-table slots per relay
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from shadow1_tpu import rng
from shadow1_tpu.consts import (
    K_APP,
    N_ESTABLISHED,
    N_MSG,
    N_PEER_FIN,
    R_TOR_PATH,
    TCP_ESTABLISHED,
    TCP_FREE,
    TCP_LISTEN,
)
from shadow1_tpu.core.dense import (
    add_col,
    extract_col,
    first_true_idx,
    get_col,
    read_sel,
    set_col,
)
from shadow1_tpu.core.engine import (
    any_host,
    lane_branch,
    pass_rows,
    push_local_event,
)
from shadow1_tpu.core.events import push_local
from shadow1_tpu.consts import NP as NPCOLS
from shadow1_tpu.tcp import tcp as T

CELL = 512

# meta = circ<<18 | aux<<4 | cmd  (circ ≤ 8191, aux ≤ 16383, cmd ≤ 15)
C_CREATE = 1
C_CREATED = 2
C_EXTEND = 3
C_EXTENDED = 4
C_BEGIN = 5
C_DATA = 6
C_END = 7
C_DIRREQ = 8
C_DIRRESP = 9

# K_APP opcodes
OP_START = 1
OP_TX_CELL = 2        # p1=sock p2=meta p3=nbytes
OP_CONNECT_RELAY = 3  # p1=sock p2=peer relay id
OP_DRAIN = 4          # p1=sock
OP_THINK = 5

# Client bootstrap/circuit states
CL_IDLE = 0
CL_DIR_CONN = 1
CL_DIR_FETCH = 2
CL_GUARD_CONN = 3
CL_BUILDING = 4
CL_STREAM = 5
CL_DONE = 7


def _meta(circ, aux, cmd):
    return (jnp.asarray(circ, jnp.int32) << 18) | (jnp.asarray(aux, jnp.int32) << 4) | cmd


def _decode(meta):
    return meta >> 18, (meta >> 4) & 0x3FFF, meta & 0xF


def tables(cfg) -> dict:
    """Static path-selection tables from the config (memoized; numpy).

    The equivalent of the consensus the reference's dirauths serve: member
    id lists + cumulative bandwidth weights for guard/middle/exit sampling.
    Kept out of engine state — they are compile-time constants.
    """
    t = cfg.get("_tor_tables")
    if t is None:
        role = np.asarray(cfg["role"], np.int32)
        weight = np.asarray(cfg["relay_weight"], np.int64)
        is_relay = role == 0

        def cum_ids(member):
            ids = np.nonzero(member)[0].astype(np.int32)
            w = weight[ids]
            assert len(ids) > 0 and (w > 0).all()
            cum = np.cumsum(w)
            assert cum[-1] < 2**31, "total weight must fit i31 for exact randint"
            return ids, cum

        g_ids, g_cum = cum_ids(is_relay & np.asarray(cfg["is_guard"], bool))
        e_ids, e_cum = cum_ids(is_relay & np.asarray(cfg["is_exit"], bool))
        r_ids, r_cum = cum_ids(is_relay)
        dir_ids = np.nonzero(role == 2)[0].astype(np.int32)
        assert len(dir_ids) > 0, "need at least one dirauth"
        t = cfg["_tor_tables"] = {
            "guard_ids": g_ids, "guard_cum": g_cum,
            "exit_ids": e_ids, "exit_cum": e_cum,
            "relay_ids": r_ids, "relay_cum": r_cum,
            "dir_ids": dir_ids,
        }
    return t


def init(ctx, evbuf, tcpd):
    cfg = ctx.model_cfg
    tables(cfg)  # validate config early
    role = np.asarray(cfg["role"], np.int32)
    h = ctx.n_hosts
    s = ctx.params.sockets_per_host
    ct = int(cfg.get("ct_cap", 64))
    app = {
        # Per-host config columns live in app state (NOT read from
        # ctx.model_cfg inside handlers) so they shard with the host axis —
        # a handler reading a global [n_total] cfg array inside the
        # shard-local block is a trace-time shape error (round-1 advisor
        # finding; same pattern as apps/tgen.py).
        "role": jnp.asarray(cfg["role"], jnp.int32),
        "cfg_n_streams": jnp.asarray(cfg["n_streams"], jnp.int32),
        "cfg_mean_cells": jnp.asarray(cfg["mean_stream_cells"], jnp.float32),
        "cfg_mean_think": jnp.asarray(cfg["mean_think_ns"], jnp.float32),
        # client
        "cl_state": jnp.zeros(h, jnp.int32),
        "cl_guard": jnp.full(h, -1, jnp.int32),
        "cl_circ": jnp.zeros(h, jnp.int32),
        "cl_hop": jnp.zeros(h, jnp.int32),
        "cl_mid": jnp.zeros(h, jnp.int32),
        "cl_exit": jnp.zeros(h, jnp.int32),
        "cl_circs_left": jnp.asarray(cfg["n_circuits"], jnp.int32),
        "cl_streams_left": jnp.zeros(h, jnp.int32),
        "cl_cells_want": jnp.zeros(h, jnp.int32),
        "ctr": jnp.zeros(h, jnp.int64),
        "streams_done": jnp.zeros(h, jnp.int32),
        "cells_rx": jnp.zeros(h, jnp.int64),
        "bootstrap_time": jnp.zeros(h, jnp.int64),
        "done_time": jnp.zeros(h, jnp.int64),
        # relay link conns + circuit table
        "rc_peer": jnp.full((s, h), -1, jnp.int32),
        "rc_next_circ": jnp.ones((s, h), jnp.int32),
        "ct_used": jnp.zeros((ct, h), bool),
        "ct_in_sock": jnp.zeros((ct, h), jnp.int32),
        "ct_in_circ": jnp.zeros((ct, h), jnp.int32),
        "ct_out_sock": jnp.full((ct, h), -1, jnp.int32),
        "ct_out_circ": jnp.zeros((ct, h), jnp.int32),
        "ct_pend": jnp.zeros((ct, h), bool),
        "cells_fwd": jnp.zeros(h, jnp.int64),
        "ct_overflow": jnp.zeros(h, jnp.int64),
        "cell_retries": jnp.zeros(h, jnp.int64),
    }
    tcpd = dict(tcpd)
    listeners = (role == 0) | (role == 2)
    tcpd["st"] = tcpd["st"].at[0].set(
        jnp.where(jnp.asarray(listeners), TCP_LISTEN, tcpd["st"][0])
    )
    starts = (role == 1) & (np.asarray(cfg["n_circuits"]) > 0)
    p = jnp.zeros((NPCOLS, h), jnp.int32).at[0].set(OP_START)
    kk = jnp.full(h, K_APP, jnp.int32)
    evbuf, over = push_local(
        evbuf, jnp.asarray(starts), jnp.asarray(cfg["start_time"], jnp.int64), kk, p
    )
    return app, evbuf, over.sum(dtype=jnp.int64), tcpd


# -- draws -----------------------------------------------------------------
def _draw_bits(ctx, app, mask):
    """One u32 per host from the host's R_TOR_PATH stream; advances ctr
    where ``mask``."""
    bits = rng.bits_v(ctx.key, R_TOR_PATH, ctx.hosts, app["ctr"])
    app["ctr"] = app["ctr"] + mask.astype(jnp.int64)
    return bits


def _pick_weighted(bits, ids, cum):
    """Bandwidth-weighted relay pick: u ∈ [0, Σw) via multiply-shift, then
    first cumulative bucket exceeding u (identical ints in both engines)."""
    u = rng.randint(bits, int(cum[-1]))
    idx = jnp.searchsorted(jnp.asarray(cum), u.astype(jnp.int64), side="right")
    jids = jnp.asarray(ids)
    return jids[jnp.minimum(idx, jids.shape[0] - 1)]


def _push_cell(st, ctx, mask, sock, meta, nbytes, now):
    return push_local_event(
        st, ctx, mask, now, K_APP, p0=OP_TX_CELL, p1=sock, p2=meta, p3=nbytes
    )


# -- client steps ----------------------------------------------------------
def _client_begin_circuit(st, ctx, mask, now):
    """Draw middle+exit, CREATE on the guard conn (sock 1)."""
    t = tables(ctx.model_cfg)
    app = dict(st.model.app)
    mid = _pick_weighted(_draw_bits(ctx, app, mask), t["relay_ids"], t["relay_cum"])
    ext = _pick_weighted(_draw_bits(ctx, app, mask), t["exit_ids"], t["exit_cum"])
    circ = app["cl_circ"] + 1
    app["cl_circ"] = jnp.where(mask, circ, app["cl_circ"])
    app["cl_mid"] = jnp.where(mask, mid, app["cl_mid"])
    app["cl_exit"] = jnp.where(mask, ext, app["cl_exit"])
    app["cl_hop"] = jnp.where(mask, 1, app["cl_hop"])
    app["cl_state"] = jnp.where(mask, CL_BUILDING, app["cl_state"])
    app["cl_streams_left"] = jnp.where(
        mask, app["cfg_n_streams"], app["cl_streams_left"]
    )
    st = st._replace(model=st.model._replace(app=app))
    one = jnp.ones(ctx.n_hosts, jnp.int32)
    return _push_cell(st, ctx, mask, one, _meta(circ, 0, C_CREATE), CELL, now)


def _client_begin_stream(st, ctx, mask, now):
    """Draw the stream size and BEGIN it on the current circuit."""
    cells_max = int(ctx.model_cfg.get("cells_max", 120))
    app = dict(st.model.app)
    want = jnp.clip(
        rng.exponential_ns(_draw_bits(ctx, app, mask), app["cfg_mean_cells"]),
        1, cells_max,
    ).astype(jnp.int32)
    app["cl_cells_want"] = jnp.where(mask, want, app["cl_cells_want"])
    app["cl_state"] = jnp.where(mask, CL_STREAM, app["cl_state"])
    circ = app["cl_circ"]
    st = st._replace(model=st.model._replace(app=app))
    one = jnp.ones(ctx.n_hosts, jnp.int32)
    return _push_cell(st, ctx, mask, one, _meta(circ, want, C_BEGIN), CELL, now)


def _client_think(st, ctx, mask, now):
    app = dict(st.model.app)
    think = rng.exponential_ns(_draw_bits(ctx, app, mask), app["cfg_mean_think"])
    st = st._replace(model=st.model._replace(app=app))
    return push_local_event(st, ctx, mask, now + think, K_APP, p0=OP_THINK)


# -- relay machinery -------------------------------------------------------
def _ct_find(app, sock, circ, side):
    """First circuit-table slot matching (sock, circ) on ``side`` ∈
    {'in', 'out'}. Returns (found[H], idx[H])."""
    m = (
        app["ct_used"]
        & (app[f"ct_{side}_sock"] == sock[None, :])
        & (app[f"ct_{side}_circ"] == circ[None, :])
    )
    return first_true_idx(m)


def _relay_on_cell(st, ctx, m, sock, meta, now):
    """The relay cell machine: one cell per host per round."""
    with jax.named_scope("phase:tor_build"):
        circ, aux, cmd = _decode(meta)
        app = dict(st.model.app)

        # --- C_CREATE: allocate a table entry, reply CREATED on the same leg.
        cr = m & (cmd == C_CREATE)
        has_free, slot = first_true_idx(~app["ct_used"])
        ok = cr & has_free
        app["ct_overflow"] = (
            app["ct_overflow"] + (cr & ~has_free).astype(jnp.int64)
        )
        # Dense one-hot writes, not .at[] scatters — XLA serializes
        # dynamic-index scatters on TPU and this block runs in every relay
        # cell round (core/dense.py; the round-2 scatter postmortem applies
        # here too).
        app["ct_used"] = set_col(app["ct_used"], slot, True, ok)
        app["ct_in_sock"] = set_col(app["ct_in_sock"], slot, sock, ok)
        app["ct_in_circ"] = set_col(app["ct_in_circ"], slot, circ, ok)
        app["ct_out_sock"] = set_col(app["ct_out_sock"], slot, -1, ok)
        app["ct_pend"] = set_col(app["ct_pend"], slot, False, ok)
        st = st._replace(model=st.model._replace(app=app))
        st = _push_cell(st, ctx, ok, sock, _meta(circ, 0, C_CREATED), CELL, now)

    with jax.named_scope("phase:tor_relay"):
        # --- locate the entry for every other cell, by in-side then out-side.
        app = dict(st.model.app)
        other = m & (cmd != C_CREATE)
        f_in, i_in = _ct_find(app, sock, circ, "in")
        f_out, i_out = _ct_find(app, sock, circ, "out")
        from_in = other & f_in
        from_out = other & ~f_in & f_out
        idx = jnp.where(from_in, i_in, jnp.where(from_out, i_out, 0))
        # One read one-hot [ct_cap, H] for the five table reads at this entry.
        isel = read_sel(idx, app["ct_used"].shape[0])
        out_sock0 = extract_col(isel, app["ct_out_sock"])

    with jax.named_scope("phase:tor_build"):
        # --- C_EXTEND from the in-side with no out leg yet: open/reuse the
        # onward conn and queue its CREATE.
        ext = from_in & (cmd == C_EXTEND) & (out_sock0 < 0)
        target = aux
        # reuse: first outbound conn already dialed to this relay
        reuse_m = app["rc_peer"] == target[None, :]
        any_reuse, r_sock = first_true_idx(reuse_m)
        has_reuse = ext & any_reuse
        # else: lowest FREE socket ≥ 1 (children take the top; see tcp.py)
        tcp_free = st.model.tcp["st"] == TCP_FREE
        tcp_free = tcp_free.at[0].set(False)
        need_dial = ext & ~has_reuse
        any_free, d_sock = first_true_idx(tcp_free)
        can_dial = need_dial & any_free
        app["ct_overflow"] = (
            app["ct_overflow"] + (need_dial & ~can_dial).astype(jnp.int64)
        )
        osock = jnp.where(has_reuse, r_sock, d_sock)
        oks = has_reuse | can_dial
        # allocate the out-circ id from the conn's counter
        ocirc = get_col(app["rc_next_circ"], osock)
        app["rc_next_circ"] = add_col(app["rc_next_circ"], osock, 1, oks)
        app["rc_peer"] = set_col(app["rc_peer"], d_sock, target, can_dial)
        app["ct_out_sock"] = set_col(app["ct_out_sock"], idx, osock, oks)
        app["ct_out_circ"] = set_col(app["ct_out_circ"], idx, ocirc, oks)
        # CREATE goes out now if the conn is up, else when it establishes.
        conn_up = has_reuse & (
            get_col(st.model.tcp["st"], osock) == TCP_ESTABLISHED
        )
        app["ct_pend"] = set_col(app["ct_pend"], idx, ~conn_up, oks)
        st = st._replace(model=st.model._replace(app=app))
        st = _push_cell(
            st, ctx, conn_up, osock, _meta(ocirc, 0, C_CREATE), CELL, now
        )
        st = push_local_event(
            st, ctx, can_dial, now, K_APP,
            p0=OP_CONNECT_RELAY, p1=d_sock, p2=target,
        )

        # --- C_CREATED arriving on an out leg: translate to EXTENDED inward.
        app = st.model.app
        created = from_out & (cmd == C_CREATED)
        in_sock = extract_col(isel, app["ct_in_sock"])
        in_circ = extract_col(isel, app["ct_in_circ"])
        st = _push_cell(
            st, ctx, created, in_sock, _meta(in_circ, 0, C_EXTENDED), CELL, now
        )

    with jax.named_scope("phase:tor_relay"):
        # --- C_BEGIN landing at the exit (in-side entry, no out leg): serve the
        # stream — one DATA message of aux cells, then END.
        at_exit = from_in & (cmd == C_BEGIN) & (out_sock0 < 0)
        st = _push_cell(
            st, ctx, at_exit, sock, _meta(circ, aux, C_DATA), aux * CELL, now
        )
        st = _push_cell(st, ctx, at_exit, sock, _meta(circ, 0, C_END), CELL, now)

        # --- forwarding: everything else crosses the relay.
        app = st.model.app
        out_sock = extract_col(isel, app["ct_out_sock"])
        out_circ = extract_col(isel, app["ct_out_circ"])
        # EXTEND with an existing out leg telescopes onward (the next relay does
        # the extending); only the ext-handled case (fresh out leg this round)
        # must not also forward.
        fwd_in = (
            from_in & ~ext & (cmd != C_CREATED) & ~at_exit & (out_sock >= 0)
        )
        fwd_out = from_out & (cmd != C_CREATED)
        nbytes = jnp.where(cmd == C_DATA, aux * CELL, CELL)
        napp = dict(app)
        napp["cells_fwd"] = napp["cells_fwd"] + (fwd_in | fwd_out).astype(jnp.int64)
        st = st._replace(model=st.model._replace(app=napp))
        st = _push_cell(
            st, ctx, fwd_in, out_sock, _meta(out_circ, aux, cmd), nbytes, now
        )
        st = _push_cell(
            st, ctx, fwd_out, in_sock, _meta(in_circ, aux, cmd), nbytes, now
        )
        return st


# -- event handlers --------------------------------------------------------
@pass_rows(11)
def on_wakeup(st, ctx, ev, mask):
    with jax.named_scope("phase:tor_dir"):
        op = ev.p[0]
        now = ev.time
        zero = jnp.zeros(ctx.n_hosts, jnp.int32)
        t = tables(ctx.model_cfg)

        # OP_START: client dials a dirauth on sock 2. Rare (one per client
        # bootstrap) but carries a tcp_connect — lax.cond keeps it out of every
        # steady-state K_APP round (same for the other rare opcodes below; a
        # cond whose block is fully masked is a no-op by construction, so the
        # gating is exact).
        start = mask & (op == OP_START)
        two = jnp.full(ctx.n_hosts, 2, jnp.int32)

        def _op_start(st):
            app = dict(st.model.app)
            b = _draw_bits(ctx, app, start)
            d_idx = rng.randint(b, len(t["dir_ids"]))
            dirauth = jnp.asarray(t["dir_ids"])[d_idx]
            app["cl_state"] = jnp.where(start, CL_DIR_CONN, app["cl_state"])
            st = st._replace(model=st.model._replace(app=app))
            return T.tcp_connect(st, ctx, start, two, dirauth, zero, now)

        st = jax.lax.cond(any_host(ctx, start), lane_branch(ctx, _op_start),
                          lambda s: s, st)

    with jax.named_scope("phase:tor_relay"):
        # OP_TX_CELL: the single transport-send site. Admission: the full
        # message must fit the send buffer and a boundary slot must be free;
        # otherwise retry at the next window start (deterministic backoff).
        tx = mask & (op == OP_TX_CELL)
        sock, meta, nbytes = ev.p[1], ev.p[2], ev.p[3]
        r = T.Sock(st.model.tcp, sock, tx)
        snd_una, app_end = r.g("snd_una"), r.g("app_end")
        buffered = (app_end - snd_una) - (snd_una == 0).astype(jnp.int32)
        fits = (ctx.params.sndbuf - buffered) >= nbytes
        mq_ok = T.mq_room(st.model.tcp, sock, ctx.params.msgq_cap)
        can = tx & fits & mq_ok
        retry = tx & ~can
        st, _acc = T.tcp_send(st, ctx, can, sock, nbytes, meta, now)
        app = dict(st.model.app)
        app["cell_retries"] = app["cell_retries"] + retry.astype(jnp.int64)
        st = st._replace(model=st.model._replace(app=app))
        t_retry = (now // ctx.window + 1) * ctx.window
        st = push_local_event(
            st, ctx, retry, t_retry, K_APP,
            p0=OP_TX_CELL, p1=sock, p2=meta, p3=nbytes,
        )

    with jax.named_scope("phase:tor_build"):
        # OP_CONNECT_RELAY: dial an onward relay conn.
        dial = mask & (op == OP_CONNECT_RELAY)
        st = jax.lax.cond(
            any_host(ctx, dial),
            lane_branch(ctx, lambda s: T.tcp_connect(
                s, ctx, dial, ev.p[1], ev.p[2], zero, now)),
            lambda s: s, st,
        )

    with jax.named_scope("phase:tor_relay"):
        # OP_DRAIN: send one pending CREATE on an established conn; loop while
        # more remain.
        drain = mask & (op == OP_DRAIN)

        def _op_drain(st):
            sock = ev.p[1]
            app = dict(st.model.app)
            pend = (
                app["ct_used"] & app["ct_pend"]
                & (app["ct_out_sock"] == sock[None, :])
            )
            any_p, idx = first_true_idx(pend)
            has = drain & any_p
            ocirc = get_col(app["ct_out_circ"], idx)
            app["ct_pend"] = set_col(app["ct_pend"], idx, False, has)
            more = drain & (pend.sum(axis=0) > 1)
            st = st._replace(model=st.model._replace(app=app))
            st = _push_cell(
                st, ctx, has, sock, _meta(ocirc, 0, C_CREATE), CELL, now
            )
            return push_local_event(
                st, ctx, more, now, K_APP, p0=OP_DRAIN, p1=sock
            )

        st = jax.lax.cond(any_host(ctx, drain), lane_branch(ctx, _op_drain),
                          lambda s: s, st)

    with jax.named_scope("phase:tor_stream"):
        # OP_THINK: next stream on this circuit, or next circuit.
        think = mask & (op == OP_THINK)

        def _op_think(st):
            app = st.model.app
            next_stream = think & (app["cl_streams_left"] > 0)
            st2 = _client_begin_stream(st, ctx, next_stream, now)
            with jax.named_scope("phase:tor_build"):
                next_circ = (
                    think & ~next_stream & (st2.model.app["cl_circs_left"] > 0)
                )
                return _client_begin_circuit(st2, ctx, next_circ, now)

        return jax.lax.cond(any_host(ctx, think), lane_branch(ctx, _op_think),
                          lambda s: s, st)


@pass_rows(22)
def on_notify(st, ctx, nf: T.Notif, now, mask):
    with jax.named_scope("phase:tor_relay"):
        f = nf.flags
        sock = nf.sock
        role = st.model.app["role"]
        is_client = role == 1
        est = (f & N_ESTABLISHED) != 0
        msg = (f & N_MSG) != 0
        circ, aux, cmd = _decode(nf.meta)
        one = jnp.ones(ctx.n_hosts, jnp.int32)
        two = jnp.full(ctx.n_hosts, 2, jnp.int32)
        t = tables(ctx.model_cfg)
        app = st.model.app

    with jax.named_scope("phase:tor_dir"):
        # Client bootstrap and circuit-build blocks run under lax.cond: each
        # fires a handful of times per client ever, but carries tcp_connect /
        # tcp_close / weighted-draw machinery that every notify round would
        # otherwise pay for (the gating is exact — all writes are masked).

        # Client: dirauth conn up → request the consensus.
        dir_up = (
            mask & is_client & est & (sock == 2)
            & (app["cl_state"] == CL_DIR_CONN)
        )

        def _dir_up(st):
            napp = dict(st.model.app)
            napp["cl_state"] = jnp.where(dir_up, CL_DIR_FETCH, napp["cl_state"])
            st = st._replace(model=st.model._replace(app=napp))
            return _push_cell(
                st, ctx, dir_up, two, _meta(0, 0, C_DIRREQ), CELL, now
            )

        st = jax.lax.cond(any_host(ctx, dir_up), lane_branch(ctx, _dir_up),
                          lambda s: s, st)

        # Client: consensus received → close dir conn, dial the drawn guard.
        app = st.model.app
        got_dir = (
            mask & is_client & msg & (sock == 2) & (cmd == C_DIRRESP)
            & (app["cl_state"] == CL_DIR_FETCH)
        )

        def _got_dir(st):
            napp = dict(st.model.app)
            guard = _pick_weighted(
                _draw_bits(ctx, napp, got_dir), t["guard_ids"], t["guard_cum"]
            )
            napp["cl_guard"] = jnp.where(got_dir, guard, napp["cl_guard"])
            napp["bootstrap_time"] = jnp.where(got_dir, now, napp["bootstrap_time"])
            napp["cl_state"] = jnp.where(got_dir, CL_GUARD_CONN, napp["cl_state"])
            st = st._replace(model=st.model._replace(app=napp))
            st = T.tcp_close(st, ctx, got_dir, two, now)
            zero = jnp.zeros(ctx.n_hosts, jnp.int32)
            return T.tcp_connect(st, ctx, got_dir, one, guard, zero, now)

        st = jax.lax.cond(any_host(ctx, got_dir), lane_branch(ctx, _got_dir),
                          lambda s: s, st)

    with jax.named_scope("phase:tor_build"):
        # Client: guard conn up → first circuit.
        app = st.model.app
        guard_up = (
            mask & is_client & est & (sock == 1)
            & (app["cl_state"] == CL_GUARD_CONN)
        )
        st = jax.lax.cond(
            any_host(ctx, guard_up),
            lane_branch(ctx, lambda s: _client_begin_circuit(
                s, ctx, guard_up, now)),
            lambda s: s, st,
        )

        # Client: circuit-build and stream cells on the guard conn.
        app = st.model.app
        cl_msg = mask & is_client & msg & (sock == 1) & (circ == app["cl_circ"])
        hop = app["cl_hop"]
        creatd = cl_msg & (cmd == C_CREATED) & (hop == 1)
        ext2 = cl_msg & (cmd == C_EXTENDED) & (hop == 2)
        ext3 = cl_msg & (cmd == C_EXTENDED) & (hop == 3)

        def _circ_build(st):
            app = st.model.app
            napp = dict(app)
            napp["cl_hop"] = jnp.where(creatd | ext2, hop + 1, napp["cl_hop"])
            st = st._replace(model=st.model._replace(app=napp))
            st = _push_cell(
                st, ctx, creatd, one,
                _meta(app["cl_circ"], app["cl_mid"], C_EXTEND), CELL, now,
            )
            st = _push_cell(
                st, ctx, ext2, one,
                _meta(app["cl_circ"], app["cl_exit"], C_EXTEND), CELL, now,
            )
            with jax.named_scope("phase:tor_stream"):
                return _client_begin_stream(st, ctx, ext3, now)

        st = jax.lax.cond(
            any_host(ctx, creatd | ext2 | ext3), lane_branch(ctx, _circ_build),
            lambda s: s, st,
        )

    with jax.named_scope("phase:tor_stream"):
        # Client: stream data/end.
        app = st.model.app
        data = cl_msg & (cmd == C_DATA) & (app["cl_state"] == CL_STREAM)
        napp = dict(app)
        napp["cells_rx"] = (
            napp["cells_rx"] + jnp.where(data, aux, 0).astype(jnp.int64)
        )
        ended = cl_msg & (cmd == C_END) & (napp["cl_state"] == CL_STREAM)
        napp["streams_done"] = napp["streams_done"] + ended.astype(jnp.int32)
        napp["cl_streams_left"] = (
            napp["cl_streams_left"] - ended.astype(jnp.int32)
        )
        circ_done = ended & (napp["cl_streams_left"] == 0)
        napp["cl_circs_left"] = (
            napp["cl_circs_left"] - circ_done.astype(jnp.int32)
        )
        all_done = circ_done & (napp["cl_circs_left"] == 0)
        napp["done_time"] = jnp.where(all_done, now, napp["done_time"])
        napp["cl_state"] = jnp.where(all_done, CL_DONE, napp["cl_state"])
        st = st._replace(model=st.model._replace(app=napp))
        st = _client_think(st, ctx, ended & ~all_done, now)

    with jax.named_scope("phase:tor_dir"):
        # Dirauth: serve consensus requests; reap disconnected clients.
        consensus_bytes = int(ctx.model_cfg.get("consensus_bytes", 2048))
        dreq = mask & (role == 2) & msg & (cmd == C_DIRREQ)
        d_fin = mask & (role == 2) & ((f & N_PEER_FIN) != 0)

        def _dirauth(st):
            st = _push_cell(
                st, ctx, dreq, sock, _meta(0, 0, C_DIRRESP), consensus_bytes, now
            )
            return T.tcp_close(st, ctx, d_fin, sock, now)

        st = jax.lax.cond(any_host(ctx, dreq | d_fin),
                          lane_branch(ctx, _dirauth), lambda s: s, st)

    with jax.named_scope("phase:tor_relay"):
        # Relay: onward conn established → drain pending CREATEs.
        app = st.model.app
        dialed = get_col(app["rc_peer"], sock) >= 0
        r_est = mask & (role == 0) & est & dialed
        st = push_local_event(st, ctx, r_est, now, K_APP, p0=OP_DRAIN, p1=sock)

        # Relay: the cell machine.
        r_msg = mask & (role == 0) & msg
    return _relay_on_cell(st, ctx, r_msg, sock, nf.meta, now)


def summary(app) -> dict:
    # The 0-dim entries are the run totals that ride heartbeat rows' ``model``
    # block and ``fleet_exp`` records (telemetry/registry.MODEL_TOTALS).
    return {
        "streams_done": app["streams_done"],
        "cells_rx": app["cells_rx"],
        "bootstrap_time": app["bootstrap_time"],
        "done_time": app["done_time"],
        "cells_fwd": app["cells_fwd"],
        "ct_overflow": app["ct_overflow"],
        "cell_retries": app["cell_retries"],
        "total_streams_done": app["streams_done"].sum(),
        "total_cells_rx": app["cells_rx"].sum(),
        "total_cells_fwd": app["cells_fwd"].sum(),
        "total_ct_overflow": app["ct_overflow"].sum(),
        "total_cell_retries": app["cell_retries"].sum(),
        "clients_done": (app["done_time"] > 0).sum(),
    }
