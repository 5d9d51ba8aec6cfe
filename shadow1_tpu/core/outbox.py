"""Per-host per-window packet outboxes.

In the reference, a packet send walks NIC → topology path lookup → a locked
push onto the destination host's queue (SURVEY §3.3, src/main/routing/
topology.c + core/scheduler). Conservative windows guarantee every
cross-host event lands at least one window in the future, so the batched
engine buffers all sends of a window here and performs routing (path
latency, loss draws) plus the destination scatter once per window — and, when
sharded, exactly one all_to_all per window over ICI (SURVEY §2.5).

Layout: slot-major, host-minor ([P, H]; payload [NP, P, H]) — see
core/dense.py for the tiling rationale. All [P, H] planes are i32 (the chip
has no native i64; docs/PERF.md): departure times ride the same
order-preserving (hi, lo) split as the event buffer (core/events.py
tb_split), joined once per window in route_outbox; the per-packet counter
plane holds the low 32 bits of the i64 ``pkt_ctr`` lifetime counter —
exact while no single host sends ≥ 2**31 packets in one run, which is far
outside the design envelope (the largest ladder rung totals 33M packets
across 5,000 hosts).
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from shadow1_tpu.consts import NP
from shadow1_tpu.core.dense import set_col
from shadow1_tpu.core.events import tb_join, tb_split


class Outbox(NamedTuple):
    dst: jnp.ndarray        # i32 [P, H]
    kind: jnp.ndarray       # i32 [P, H] event kind to deliver at dst
    depart_hi: jnp.ndarray  # i32 [P, H] src-NIC departure time, high word
    depart_lo: jnp.ndarray  # i32 [P, H] low word (sign-flipped; tb_split)
    ctr: jnp.ndarray        # i32 [P, H] per-src packet counter (low word)
    p: jnp.ndarray          # i32 [NP, P, H]
    cnt: jnp.ndarray        # i32 [H] entries used this window
    pkt_ctr: jnp.ndarray    # i64 [H] lifetime per-src packet counter

    def abs_depart(self) -> jnp.ndarray:
        """i64 [P, H] departure times (window-granularity readers only)."""
        return tb_join(self.depart_hi, self.depart_lo)


def outbox_init(n_hosts: int, cap: int) -> Outbox:
    return Outbox(
        dst=jnp.zeros((cap, n_hosts), jnp.int32),
        kind=jnp.zeros((cap, n_hosts), jnp.int32),
        depart_hi=jnp.zeros((cap, n_hosts), jnp.int32),
        depart_lo=jnp.zeros((cap, n_hosts), jnp.int32),
        ctr=jnp.zeros((cap, n_hosts), jnp.int32),
        p=jnp.zeros((NP, cap, n_hosts), jnp.int32),
        cnt=jnp.zeros(n_hosts, jnp.int32),
        pkt_ctr=jnp.zeros(n_hosts, jnp.int64),
    )


def outbox_space(ob: Outbox) -> jnp.ndarray:
    return ob.dst.shape[0] - ob.cnt


def outbox_fill(ob: Outbox) -> jnp.ndarray:
    """Occupancy gauge: this window's fill on the busiest host, i64 scalar.
    Reads the maintained [H] counter — free; read before ``outbox_clear``."""
    return ob.cnt.max().astype(jnp.int64)


def outbox_append(ob: Outbox, mask, dst, kind, depart, p) -> tuple[Outbox, jnp.ndarray]:
    """Append one packet per host where ``mask``. Returns (ob, ok_mask).

    Callers that cannot tolerate drops (TCP) must check ``outbox_space``
    first and defer to the next window instead (K_TX_RESUME). Dense one-hot
    write — no scatter (core/dense.py). ``p`` is [NP, H].
    """
    cap = ob.dst.shape[0]
    ok = mask & (ob.cnt < cap)
    dhi, dlo = tb_split(jnp.asarray(depart, jnp.int64))
    ob = ob._replace(
        dst=set_col(ob.dst, ob.cnt, dst, ok),
        kind=set_col(ob.kind, ob.cnt, kind, ok),
        depart_hi=set_col(ob.depart_hi, ob.cnt, dhi, ok),
        depart_lo=set_col(ob.depart_lo, ob.cnt, dlo, ok),
        ctr=set_col(ob.ctr, ob.cnt, ob.pkt_ctr.astype(jnp.int32), ok),
        p=set_col(ob.p, ob.cnt, p, ok),
        cnt=ob.cnt + ok.astype(jnp.int32),
        pkt_ctr=ob.pkt_ctr + ok.astype(jnp.int64),
    )
    return ob, ok


def outbox_clear(ob: Outbox) -> Outbox:
    return ob._replace(cnt=jnp.zeros_like(ob.cnt))
