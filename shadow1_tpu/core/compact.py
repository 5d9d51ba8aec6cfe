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

Columns move by a ROW-UNIFORM gather along the host axis: ONE index for a
whole column of rows, ``cap`` indices a leaf on the way out (``take_cols``:
``x[..., idx]``, ``idx`` ascending out of ``next_bucket``'s sort) and ``H``
on the way back (``put_cols``: a taken host reads the lane of its rank among
the taken hosts, every other host keeps its column) — not the index per
ELEMENT that PR 41 priced at 7 ns each and ISSUE 44's census forbade. A
gather copies words, so it is exact on any backend for any dtype, and a
leaf moves as it is: no byte planes, no stacking. At rung 4's shapes the
two directions read 10 + 23 ms a trip on a v5e alone where PR 44's one-hot
``bfloat16`` matmuls read 27 + 29, and 0.9 + 11.8 inside the window program
where they read 20 + 23: XLA keeps the loop's carry host-major for the
gather's sake, so the way out transposes nothing (PERF.md §5, §6, PR 45).
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


def _is_host_leaf(x, h: int) -> bool:
    return hasattr(x, "ndim") and x.ndim >= 1 and x.shape[-1] == h


def _cols(x, idx):
    """``x[..., idx]``: ONE index for a whole column of rows (a row-uniform
    gather along the host axis), ``idx`` ascending and in bounds."""
    return x.at[..., idx].get(indices_are_sorted=True,
                              mode="promise_in_bounds")


def _as_bits(x):
    """A float leaf as the integers of its bits, and the way back: XLA:TPU
    may lower a float's gather or select as arithmetic, which flushes a
    denormal and quiets a NaN (PERF.md §6, PR 45); integers move as they
    are."""
    if not jnp.issubdtype(x.dtype, jnp.floating):
        return x, lambda y: y
    cast = jax.lax.bitcast_convert_type
    bits = {2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}[x.dtype.itemsize]
    return cast(x, bits), lambda y: cast(y, x.dtype)


def take_cols(tree, idx, h: int):
    """Every [*, H] leaf of ``tree`` down to the bucket, [*, cap]: lane m
    holds host ``idx[m]``; a padding lane (``idx`` = H) clones host H − 1."""
    at = jnp.minimum(idx, h - 1)

    def take(x):
        if not _is_host_leaf(x, h):
            return x
        bits, back = _as_bits(x)
        return back(_cols(bits, at))

    return jax.tree.map(take, tree)


def put_cols(full, comp, taken):
    """Inverse of ``take_cols``: a ``taken`` host reads its bucket lane — its
    rank among the taken hosts, the bucket being filled lowest id first —
    and every other column stays, whatever lane its position names; a leaf
    with no host axis takes the round loop's value."""
    h = taken.shape[0]
    rank = jnp.cumsum(taken.astype(jnp.int32)) - 1

    def put(old, new):
        if not _is_host_leaf(old, h):
            return new
        pos = jnp.clip(rank, 0, new.shape[-1] - 1)
        (old, back), (new, _) = _as_bits(old), _as_bits(new)
        return back(jnp.where(taken, _cols(new, pos), old))

    return jax.tree.map(put, full, comp)


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
    # (The demanded-fill gauge ``compact_max_fill`` is recorded by
    # window_step for every window, compaction on or off.)

    def trip(carry):
        st, remaining, cap_hit = carry
        idx, lane_pad, taken = next_bucket(remaining, cap)
        host_state = (st.evbuf, st.outbox, st.model, st.cpu_busy)
        with jax.named_scope("phase:compact_gather"):
            # Padding lanes clone host H−1 (a real host's tables and state,
            # so no handler meets a value no host could hold) ...
            (evbuf_c, outbox_c, model_c, busy_c), tables_c = take_cols(
                (host_state, ctx_tables(ctx)), idx, h)
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
            # A padding lane is no host's rank: it is left out.
            evbuf_f, outbox_f, model_f, busy_f = put_cols(
                host_state,
                (st_c.evbuf, st_c.outbox, st_c.model, st_c.cpu_busy), taken)
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
