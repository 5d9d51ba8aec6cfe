"""Traced fault-plane helpers — the device side of the fault schedule.

Every function here is pure jnp over the dense tables built by
``fault/schedule.py`` and rides inside the jitted window loop: the down
predicates cost K (intervals/host) compares, the link/ramp gates L/R
broadcast compares over the window's flat packet axis — all at window or
round granularity, never a host sync. The CPU oracle mirrors the identical
integer predicates from the same numpy tables (cpu_engine/engine.py), so
the decisions are bit-equal by construction.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def hosts_down_at(down, up, t) -> jnp.ndarray:
    """bool down-mask for times ``t`` of shape [..., H] (per-host last
    axis), against the [K, H] interval tensors."""
    k, h = down.shape
    shape = (k,) + (1,) * (t.ndim - 1) + (h,)
    d, u = down.reshape(shape), up.reshape(shape)
    tt = t[None]
    return ((tt >= d) & (tt < u)).any(axis=0)


def hosts_down_at_idx(down, up, idx, t) -> jnp.ndarray:
    """Per-packet down-mask: ``idx`` [N] host indices, ``t`` [N] times."""
    d, u = down[:, idx], up[:, idx]
    tt = t[None, :]
    return ((tt >= d) & (tt < u)).any(axis=0)


def link_down_mask(link_fault, vs, vd, dep) -> jnp.ndarray:
    """bool [N]: packet's (src vertex, dst vertex, departure) hits an
    outage window. ``link_fault`` is the (src, dst, t0, t1) table."""
    src, dst, t0, t1 = link_fault
    m = (
        (vs[None, :] == src[:, None])
        & (vd[None, :] == dst[:, None])
        & (dep[None, :] >= t0[:, None])
        & (dep[None, :] < t1[:, None])
    )
    return m.any(axis=0)


def ramp_loss_thr(loss_ramp, vs, vd, dep, thr) -> jnp.ndarray:
    """Apply the timed loss ramps: where a packet's path+departure matches
    an entry, its u64 Bernoulli threshold is replaced (entries in order —
    later entries win, same rule as the oracle). Static unroll: R is a
    handful of config lines."""
    src, dst, t0, t1, rthr = loss_ramp
    for i in range(src.shape[0]):
        m = (vs == src[i]) & (vd == dst[i]) & (dep >= t0[i]) & (dep < t1[i])
        thr = jnp.where(m, rthr[i], thr)
    return thr


def restart_mask(up, win_start) -> jnp.ndarray:
    """bool [H]: hosts whose (window-quantized) up time IS this window's
    start — their restart reset applies before this window's rounds."""
    return (up == win_start).any(axis=0)


def reset_host_columns(tree, init_tree, mask, n_hosts: int):
    """Restore the masked hosts' columns of every per-host leaf to its
    initial value (the post-init model capture). The host axis is the LAST
    axis by the state layout contract (shard/engine._spec_for,
    compact.take_cols uses the same rule); leaves of other shapes —
    scalars, config tables — pass through untouched."""
    def r(cur, ini):
        if hasattr(cur, "ndim") and cur.ndim >= 1 and cur.shape[-1] == n_hosts:
            m = mask.reshape((1,) * (cur.ndim - 1) + (n_hosts,))
            return jnp.where(m, jnp.asarray(ini, cur.dtype), cur)
        return cur
    return jax.tree.map(r, tree, init_tree)
