"""NIC model: per-host token-bucket-style serialization on both directions.

The reference's NetworkInterface (src/main/host/network-interface.c) gives
each host token-bucket up/down bandwidth with a FIFO send queue. The tensor
model keeps one "link free at" timestamp per direction per host: a packet of
wire length L departs at ``max(now, tx_free)`` and occupies the link for
``ceil(8·L / bw)`` ns; the receive side delays packet *processing* the same
way (SURVEY §3.3–3.4). This reproduces serialization/queueing delay exactly
for FIFO order, which is how both engines process packets.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from shadow1_tpu import rng
from shadow1_tpu.consts import R_AQM, SEC


class NicState(NamedTuple):
    tx_free: jnp.ndarray   # i64 [H] uplink busy until
    rx_free: jnp.ndarray   # i64 [H] downlink busy until
    tx_bytes: jnp.ndarray  # i64 [H]
    rx_bytes: jnp.ndarray  # i64 [H]
    aqm_ctr: jnp.ndarray   # i64 [H] uplink enqueue-attempt counter (RED coin)


def nic_init(n_hosts: int) -> NicState:
    z = lambda: jnp.zeros(n_hosts, jnp.int64)
    return NicState(z(), z(), z(), z(), z())


def ctx_aqm(ctx):
    """The ``aqm`` argument for tx_stamp from an engine Ctx (None = off)."""
    if not ctx.has_aqm:
        return None
    return (ctx.key, ctx.hosts, ctx.aqm_min_ns, ctx.aqm_span_ns,
            ctx.aqm_pmax_thr)


_RED_CERTAIN = np.uint64(1) << np.uint64(32)  # threshold meaning "always"


def ser_delay(wire_bytes, bw_bits, ns_per_byte=None):
    """ceil(8e9 · bytes / bw) ns — identical integer math in both engines.

    ``ns_per_byte`` (``Ctx.ser_up`` / ``ser_dn``) is ``8e9 // bw`` where
    every link's bits/s divides 8e9, else None. Then the ceiling is exactly
    ``bytes × ns_per_byte`` and no division is traced: the TPU has no 64-bit
    divider, each ``//`` by a per-host rate is ~2,000 emulated instructions,
    and they were 57 % of the TCP window program (PERF.md §6, PR 28)."""
    w = jnp.asarray(wire_bytes, jnp.int64)
    if ns_per_byte is not None:
        return w * ns_per_byte
    return (w * (8 * SEC) + bw_bits - 1) // bw_bits


def tx_stamp(nic: NicState, mask, wire_bytes, now, bw_up, qlen_ns=None,
             aqm=None, ser=None):
    """Reserve the uplink: returns (nic', depart_time[H], ok[H], red[H]).

    Two drop gates, in order (both off by default):

    * **RED early drop** (``aqm`` from ctx_aqm — router.c's upstream AQM):
      with instantaneous backlog q, drop probability ramps linearly 0→pmax
      over [min, min+span) and is 1 at ≥ min+span. The coin is the shared
      counter RNG at (R_AQM, host, per-host attempt counter) — the counter
      advances on EVERY masked attempt (enabled or not, dropped or not), so
      both engines see identical streams. Integer pipeline: Q16 backlog
      ratio × the u64 pmax threshold, compared against the raw 32 coin bits.
    * **drop-tail** (``qlen_ns``, the bound expressed as serialization
      backlog time — router.c's queue bound): a packet is DROPPED (ok=False,
      link not reserved) when the backlog already exceeds the bound.
    """
    red = jnp.zeros_like(mask)
    if aqm is not None:
        key, hosts, min_ns, span_ns, pmax_thr = aqm
        coin = rng.bits(key, R_AQM, hosts, nic.aqm_ctr)
        nic = nic._replace(aqm_ctr=nic.aqm_ctr + mask.astype(jnp.int64))
        backlog = jnp.maximum(nic.tx_free - jnp.asarray(now, jnp.int64), 0)
        delta = jnp.clip(backlog - min_ns, 0, span_ns)
        ratio_q16 = (
            (delta.astype(jnp.uint64) << np.uint64(16))
            // span_ns.astype(jnp.uint64)
        )
        thr = (pmax_thr * ratio_q16) >> np.uint64(16)
        thr = jnp.where(delta >= span_ns, _RED_CERTAIN, thr)
        thr = jnp.where(pmax_thr > np.uint64(0), thr, np.uint64(0))
        red = mask & rng.uniform_lt(coin, thr)
        mask = mask & ~red
    if qlen_ns is not None:
        mask = mask & ((nic.tx_free - jnp.asarray(now, jnp.int64)) <= qlen_ns)
    depart = jnp.maximum(now, nic.tx_free)
    busy = depart + ser_delay(wire_bytes, bw_up, ser)
    w = jnp.asarray(wire_bytes, jnp.int64)
    return (
        nic._replace(
            tx_free=jnp.where(mask, busy, nic.tx_free),
            tx_bytes=nic.tx_bytes + jnp.where(mask, w, 0),
        ),
        depart,
        mask,
        red,
    )


def rx_stamp(nic: NicState, mask, wire_bytes, now, bw_dn, qlen_ns=None,
             ser=None):
    """Reserve the downlink: returns (nic', ready_time[H], ok[H]) — the time
    the packet clears the receive queue; drop-tail like tx_stamp."""
    if qlen_ns is not None:
        mask = mask & ((nic.rx_free - jnp.asarray(now, jnp.int64)) <= qlen_ns)
    ready = jnp.maximum(now, nic.rx_free)
    busy = ready + ser_delay(wire_bytes, bw_dn, ser)
    w = jnp.asarray(wire_bytes, jnp.int64)
    return (
        nic._replace(
            rx_free=jnp.where(mask, busy, nic.rx_free),
            rx_bytes=nic.rx_bytes + jnp.where(mask, w, 0),
        ),
        ready,
        mask,
    )
