"""Active-host compaction — a window's rounds run on its active hosts only,
a bucket of ``compact_cap`` columns at a time.

The batched engine pays every inner round as a full [C, H] tensor pass no
matter how few hosts execute events; on the sparse ladder rungs that is the
dominant waste (rung-3 Tor: mean 47 of 1000 hosts active per window,
p99 = 284 — tools/activeprobe.py; rung 4 on the fleet: 598 of 10,000,
PERF.md §5, PR 43). The reference's eager scheduler gets sparsity for free
by only visiting queued events
(src/main/core/scheduler/scheduler-policy-host-steal.c steals only
non-empty host queues); this module is the batched equivalent.

Exactness argument: within a window no host reads another's columns —
handlers only self-push (timers, app wakeups, TX resume all target the
executing host) and cross-host packets defer to the window-end exchange by
the conservative-window construction. So (a) a window's active-host set is
CLOSED under round execution: hosts with no eligible event at window start
stay event-free all window; and (b) any partition of the active set may run
its parts' round loops apart, one after the other — what the sharded engine
does with its shards. A window therefore takes ⌈n_active / cap⌉ **trips**:
each moves the next ``cap`` active columns into a bucket, runs the identical
round program at bucket width to quiescence, and puts the columns back; a
window with no event takes none. That is the identity on every inactive
host and the identical computation on every active one: pops, handler
order, RNG draws (keyed by GLOBAL host id) and metric sums are bit-equal to
the full-width path, whatever the cap — the knob is purely a performance
choice. What differs is what the PROGRAM counts of itself: ``rounds`` is
the round loop's iterations summed over the trips (equal to the full-width
count in a one-trip window, larger otherwise), ``fires_*`` / ``runs_*``
likewise, and ``SimState.compact_buckets`` counts the trips.
``round_cap_hits`` is unchanged: a host needs more than ``max_rounds`` pops
in its bucket exactly when it does at full width.

There is no full-width branch: a program with a cap holds the round loop
once, ``cap`` columns wide. Under a fleet's lane axis the trip loop's
predicate is reduced over the lanes like every guard's
(``core/engine.any_lane``); a lane with nothing left rides a trip with an
all-padding bucket, which is the identity on its state.

Padding lanes (bucket wider than what is left) clone the last host's
columns but are forced event-free, so they never pop, masked handlers never
write them, and the put-back leaves them out.

Columns move by one-hot contraction on the MXU, never by ``gather`` or
``scatter`` (ISSUE 44's census, tests/test_tor10k.py): a leaf's words are
split into byte planes, each ``dot``ted with the one-hot as ``bfloat16``
with ``float32`` accumulation — one non-zero term a sum, a byte, so exact on
any backend (``rng._log_tbl_read``'s arithmetic). The leaves' words are
stacked so that the state moves in a few matmuls a byte plane
(``move_leaves``). At rung 4's shapes the two directions, a leaf at a
time, read 33 + 32 ms a trip on a v5e alone and 20 + 22 inside the
program; a ``jnp.take`` along the host axis — one index for a whole
column, not PR 41's index per element — read 10 + 20 ms alone (PERF.md §6,
PR 44): the next step for whoever takes the mover up.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from shadow1_tpu.consts import K_NONE
from shadow1_tpu.core.events import I32_FREE

# Ctx fields indexed by LOCAL host lane (everything else — vertex tables,
# host_vertex (global-id-indexed), scalars, static flags — stays as is).
_CTX_HOST_FIELDS = (
    "hosts", "bw_up", "bw_dn", "ser_up", "ser_dn", "fault_down", "fault_up",
    "cpu_cost",
    "tx_qlen_ns", "rx_qlen_ns", "aqm_min_ns", "aqm_span_ns", "aqm_pmax_thr",
)


def next_bucket(remaining: jnp.ndarray, cap: int):
    """The first ``cap`` hosts of ``remaining`` (bool [H]), lowest id first.

    Returns (idx [cap], lane_pad [cap], taken [H]):
    * ``idx``  — host id occupying each bucket lane; ``H`` (no host) on the
      padding lanes, which come last,
    * ``lane_pad`` — True on padding lanes,
    * ``taken`` — the hosts this bucket holds (a subset of ``remaining``).
    """
    h = remaining.shape[0]
    iota = jnp.arange(h, dtype=jnp.int32)
    (key_s,) = jax.lax.sort((jnp.where(remaining, iota, h),))
    idx = key_s[:cap]
    lane_pad = idx >= h
    last = jnp.max(jnp.where(lane_pad, -1, idx))
    return idx, lane_pad, remaining & (iota <= last)


def _words(x):
    """``x`` as int32 (or bool) arrays of its shape, and the way back."""
    dt = x.dtype
    if dt in (jnp.bool_, jnp.int32):
        return [x], lambda ws: ws[0]
    cast = jax.lax.bitcast_convert_type
    if dt.itemsize == 4:
        return [cast(x, jnp.int32)], lambda ws: cast(ws[0], dt)
    if dt.itemsize == 8:
        v = x if dt == jnp.int64 else cast(x, jnp.int64)

        def join(ws):
            v = (ws[1].astype(jnp.int64) << 32) \
                | (ws[0].astype(jnp.int64) & 0xFFFFFFFF)
            return v if dt == jnp.int64 else cast(v, dt)

        return [v.astype(jnp.int32), (v >> 32).astype(jnp.int32)], join
    if jnp.issubdtype(dt, jnp.integer):
        return [x.astype(jnp.int32)], lambda ws: ws[0].astype(dt)
    raise TypeError(f"compaction cannot move a {dt} leaf")


def _move_word(w, sel):
    """``w`` [R, N] (int32 or bool) through the one-hot ``sel`` [N, M]
    (bfloat16): column m of the result is the column of ``w`` that ``sel``'s
    column m marks, zeros where it marks none. Bit-exact: a sum has at most
    one non-zero term, a byte, which ``bfloat16`` holds. (A ``bfloat16``
    result is as exact and as fast, and the compiled program's scratch is
    160 MB larger with it at rung 4's shapes: PERF.md §6, PR 44.)"""
    def dot(p):
        return jnp.dot(p.astype(jnp.bfloat16), sel,
                       preferred_element_type=jnp.float32)

    if w.dtype == jnp.bool_:
        return dot(w) != 0
    # Three unsigned bytes and the signed top one: each exact in bfloat16.
    b0, b1, b2 = (dot((w >> s) & 0xFF).astype(jnp.int32) for s in (0, 8, 16))
    b3 = dot(w >> 24).astype(jnp.int32)
    return b0 | (b1 << 8) | (b2 << 16) | (b3 << 24)


# A leaf of this many rows or more is moved by matmuls of its own; the
# smaller ones share theirs (``move_leaves``).
_OWN_ROWS = 4096


def move_leaves(xs, sel):
    """The host (last) axis of every array of ``xs`` ([..., N]) through
    ``sel`` [N, M]; a list of arrays [..., M].

    The words of the leaves are stacked row-wise into ONE matrix a kind of
    word (int32; bool), so the whole state moves in a few matmuls a byte
    plane, not in four a leaf: the v5e's code for ~660 small convolutions
    was 75–130 MB of a 500 MB program, and the program's code is what
    ``peak_hbm_mb`` reads on top of arguments and results (PERF.md §6,
    PR 44). Stacking copies the leaves; those of ``_OWN_ROWS`` rows or more
    (the message-queue planes: two thirds of a Tor state) go unstacked."""
    m = sel.shape[1]
    parts = [_words(x) for x in xs]
    groups: dict = {}
    for i, (ws, _) in enumerate(parts):
        for j, w in enumerate(ws):
            rows = w.reshape((-1, w.shape[-1]))
            own = (i, j) if rows.shape[0] >= _OWN_ROWS else None
            groups.setdefault((w.dtype == jnp.bool_, own), []).append(
                (i, j, rows, w.shape[:-1]))
    moved = {}
    for members in groups.values():
        out = _move_word(jnp.concatenate([r for _, _, r, _ in members]), sel)
        at = 0
        for i, j, rows, lead in members:
            moved[i, j] = out[at:at + rows.shape[0]].reshape(lead + (m,))
            at += rows.shape[0]
    return [rebuild([moved[i, j] for j in range(len(ws))])
            for i, (ws, rebuild) in enumerate(parts)]


def move_cols(x, sel):
    """One array's host (last) axis through ``sel`` [N, M]."""
    return move_leaves([x], sel)[0]


def _is_host_leaf(x, h: int) -> bool:
    return hasattr(x, "ndim") and x.ndim >= 1 and x.shape[-1] == h


def take_cols(tree, sel, h: int):
    """Every [*, H] leaf of ``tree`` down to the bucket: [*, cap]."""
    leaves, treedef = jax.tree.flatten(tree)
    host = [i for i, x in enumerate(leaves) if _is_host_leaf(x, h)]
    for i, x in zip(host, move_leaves([leaves[i] for i in host], sel)):
        leaves[i] = x
    return treedef.unflatten(leaves)


def put_cols(full, comp, sel_t, taken, h: int):
    """Inverse of ``take_cols``: the ``taken`` hosts read their bucket lane
    (``sel_t`` [cap, H] marks it), every other column stays; a leaf with no
    host axis takes the round loop's value."""
    old, treedef = jax.tree.flatten(full)
    new = jax.tree.leaves(comp)
    host = [i for i, x in enumerate(old) if _is_host_leaf(x, h)]
    for i, back in zip(host, move_leaves([new[i] for i in host], sel_t)):
        new[i] = jnp.where(taken, back, old[i])
    return treedef.unflatten(new)


def ctx_tables(ctx) -> dict:
    """The per-host tables of a Ctx, by field."""
    return {f: jnp.asarray(getattr(ctx, f)) for f in _CTX_HOST_FIELDS
            if getattr(ctx, f) is not None}


def compact_window_rounds(st, ctx, make_handlers, win_end, cap: int):
    """Run one window's inner rounds on its active hosts, a bucket a trip.

    The engine's round loop (``core/engine.run_rounds``) is traced here
    once, at bucket width. ``make_handlers(ctx)`` builds the handler
    closures over the bucket's ctx tensors (model handler builders are pure
    trace-time functions)."""
    from shadow1_tpu.core.engine import any_lane, run_rounds

    h = ctx.n_hosts
    iota = jnp.arange(h, dtype=jnp.int32)
    # (The demanded-fill gauge ``compact_max_fill`` is recorded by
    # window_step for every window, compaction on or off.)

    def trip(carry):
        st, remaining, cap_hit = carry
        idx, lane_pad, taken = next_bucket(remaining, cap)
        host_state = (st.evbuf, st.outbox, st.model, st.cpu_busy)
        with jax.named_scope("phase:compact_gather"):
            # Padding lanes clone host H−1 (a real host's tables and state,
            # so no handler meets a value no host could hold) ...
            sel = (iota[:, None] == jnp.minimum(idx, h - 1)[None, :]) \
                .astype(jnp.bfloat16)
            # (the Ctx's per-host tables ride the state's matmuls)
            (evbuf_c, outbox_c, model_c, busy_c), tables_c = take_cols(
                (host_state, ctx_tables(ctx)), sel, h)
            ctx_c = dataclasses.replace(ctx, n_hosts=cap, **tables_c)
            # ... and must never pop: force them event-free (a clone with a
            # live n_elig copy would spin the round loop: it can never pop,
            # its count never drains).
            evbuf_c = evbuf_c._replace(
                kind=jnp.where(lane_pad[None, :], K_NONE, evbuf_c.kind),
                t32=jnp.where(lane_pad[None, :], I32_FREE, evbuf_c.t32),
                n_elig=jnp.where(lane_pad, 0, evbuf_c.n_elig),
            )
        st_c = st._replace(evbuf=evbuf_c, outbox=outbox_c, model=model_c,
                           cpu_busy=busy_c)
        st_c, hit = run_rounds(st_c, ctx_c, make_handlers(ctx_c), win_end)
        with jax.named_scope("phase:compact_scatter"):
            # Unclipped ``idx``: a padding lane marks no host.
            sel_t = (idx[:, None] == iota[None, :]).astype(jnp.bfloat16)
            evbuf_f, outbox_f, model_f, busy_f = put_cols(
                host_state,
                (st_c.evbuf, st_c.outbox, st_c.model, st_c.cpu_busy),
                sel_t, taken, h)
        st = st_c._replace(
            evbuf=evbuf_f, outbox=outbox_f, model=model_f, cpu_busy=busy_f,
            # The lane's own trips: one that rode another lane's counts 0.
            compact_buckets=st.compact_buckets
            + remaining.any().astype(jnp.int64))
        return st, remaining & ~taken, cap_hit | hit

    st, _, cap_hit = jax.lax.while_loop(
        lambda c: any_lane(ctx, c[1].any()), trip,
        (st, st.evbuf.n_elig > 0, jnp.zeros((), bool)))
    return st, cap_hit
