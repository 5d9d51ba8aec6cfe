"""Active-host compaction — sparse windows run on a narrow static bucket.

The batched engine pays every inner round as a full [C, H] tensor pass no
matter how few hosts execute events; on the sparse ladder rungs that is the
dominant waste (rung-3 Tor: mean 47 of 1000 hosts active per window,
p99 = 284 — tools/activeprobe.py). The reference's eager scheduler gets
sparsity for free by only visiting queued events
(src/main/core/scheduler/scheduler-policy-host-steal.c steals only
non-empty host queues); this module is the batched equivalent.

Exactness argument: a window's active-host set is CLOSED under round
execution — handlers only self-push (timers, app wakeups, TX resume all
target the executing host) and cross-host packets defer to the window-end
exchange by the conservative-window construction — so hosts with no
eligible event at window start stay event-free all window. Gathering the
active columns, running the identical round program at bucket width, and
scattering back is therefore the identity on every inactive host and the
identical computation on every active one: pops, handler order, RNG draws
(keyed by GLOBAL host id), and metric sums are bit-equal to the full-width
path. Windows whose active count exceeds the bucket run the full-width
branch (a ``lax.cond``), so the knob is purely a performance choice.

Padding lanes (bucket wider than the active count) clone the last host's
columns but are forced event-free, so they never pop, and masked handlers
never write them; duplicate-clone lanes are excluded from the scatter-back
(``pos`` maps each host to its FIRST lane). All gathers ride
``take``/``searchsorted``; the scatter-back is a lane-axis gather by
inverse permutation + ``where`` — no dynamic scatter (core/dense.py).
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


def active_mask(evbuf, win_end) -> jnp.ndarray:
    """bool [H]: host has ≥1 eligible event this window (= will pop).

    Runs after the window-start rebase (core/engine.py window_step), so the
    maintained per-host eligible counters are current — an [H]-vector read,
    no [C, H] plane scan (core/events.py n_elig)."""
    del win_end  # pinned at rebase time (evbuf.u32)
    return evbuf.n_elig > 0


def compact_perm(active: jnp.ndarray, cap: int):
    """Bucket permutation for the active set.

    Returns (idx [cap], pos [H], lane_pad [cap]):
    * ``idx``  — host id occupying each bucket lane (clipped into range;
      padding lanes clone host H−1),
    * ``pos``  — bucket lane of each host (valid where ``active``; for a
      cloned host it is the FIRST — real — lane),
    * ``lane_pad`` — True on padding lanes (no real host).
    """
    h = active.shape[0]
    iota = jnp.arange(h, dtype=jnp.int32)
    (key_s,) = jax.lax.sort((jnp.where(active, iota, h),))
    pos = jnp.searchsorted(key_s, iota).astype(jnp.int32)   # first occurrence
    idx = key_s[:cap]
    lane_pad = idx >= h
    return jnp.minimum(idx, h - 1), pos, lane_pad


def _gather_tree(tree, idx, h: int):
    """Gather the host (last) axis of every [*, H] leaf down to the bucket."""
    def g(x):
        if hasattr(x, "ndim") and x.ndim >= 1 and x.shape[-1] == h:
            return jnp.take(x, idx, axis=-1)
        return x
    return jax.tree.map(g, tree)


def _scatter_tree(full, comp, pos, active, h: int):
    """Inverse of ``_gather_tree``: active hosts read their bucket lane."""
    def s(xf, xc):
        if hasattr(xf, "ndim") and xf.ndim >= 1 and xf.shape[-1] == h:
            back = jnp.take(xc, pos, axis=-1)
            am = active.reshape((1,) * (xf.ndim - 1) + (h,))
            return jnp.where(am, back, xf)
        return xc  # scalars/metrics: the round loop's value wins
    return jax.tree.map(s, full, comp)


def compact_ctx(ctx, idx, cap: int):
    """The bucket-width view of a Ctx: per-host tables gathered, n_hosts=cap."""
    repl = {"n_hosts": cap}
    for f in _CTX_HOST_FIELDS:
        v = getattr(ctx, f)
        if v is not None:
            repl[f] = jnp.take(v, idx, axis=-1)
    return dataclasses.replace(ctx, **repl)


def compact_window_rounds(st, ctx, handlers, make_handlers, run_rounds,
                          win_end, cap: int):
    """Run one window's inner rounds, compacted when the active set fits.

    ``run_rounds(st, ctx, handlers, win_end) -> (st, cap_hit)`` is the
    engine's full-width round loop; it is reused verbatim at bucket width.
    ``handlers`` is the engine's existing full-width handler dict (the
    fallback branch); ``make_handlers(ctx)`` rebuilds the handler closures
    over the gathered ctx tensors (model handler builders are pure
    trace-time functions)."""
    h = ctx.n_hosts
    active = active_mask(st.evbuf, win_end)
    n_active = active.sum(dtype=jnp.int32)
    # (The demanded-fill gauge ``compact_max_fill`` is recorded by
    # window_step for every window, compaction on or off — keeping the
    # compacted and plain engines' states bit-identical.)

    def full_branch(st):
        return run_rounds(st, ctx, handlers, win_end)

    def compact_branch(st):
        idx, pos, lane_pad = compact_perm(active, cap)
        ctx_c = compact_ctx(ctx, idx, cap)
        handlers_c = make_handlers(ctx_c)
        host_state = (st.evbuf, st.outbox, st.model, st.cpu_busy)
        evbuf_c, outbox_c, model_c, busy_c = _gather_tree(host_state, idx, h)
        # Padding/clone lanes must never pop: force them event-free.
        evbuf_c = evbuf_c._replace(
            kind=jnp.where(lane_pad[None, :], K_NONE, evbuf_c.kind),
            t32=jnp.where(lane_pad[None, :], I32_FREE, evbuf_c.t32),
            # A clone lane with a live n_elig copy would spin the round
            # loop (it can never pop, its count never drains).
            n_elig=jnp.where(lane_pad, 0, evbuf_c.n_elig),
        )
        st_c = st._replace(evbuf=evbuf_c, outbox=outbox_c, model=model_c,
                           cpu_busy=busy_c)
        st_c, cap_hit = run_rounds(st_c, ctx_c, handlers_c, win_end)
        comp = (st_c.evbuf, st_c.outbox, st_c.model, st_c.cpu_busy)
        evbuf_f, outbox_f, model_f, busy_f = _scatter_tree(
            host_state, comp, pos, active, h
        )
        st = st_c._replace(evbuf=evbuf_f, outbox=outbox_f, model=model_f,
                           cpu_busy=busy_f)
        return st, cap_hit

    return jax.lax.cond(n_active <= cap, compact_branch, full_branch, st)
