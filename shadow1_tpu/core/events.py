"""Batched event buffers — the tensorized per-host priority queues.

The reference gives every host a binary-heap event queue and a locked async
queue for cross-thread pushes (src/main/core/scheduler/*,
src/main/utility/priority-queue.c). Here all H queues live in one set of
fixed-capacity SoA tensors ``[C, H]`` (slot-major, host-minor — see
core/dense.py for why); pop-min is a chain of masked min-reductions, local
push writes the first free slot, and cross-host delivery is a sorted batch
merge performed once per conservative window (SURVEY §7.1).

Total event order matches the reference's (time, host, seq) comparator
(src/main/core/work/event.c): within a host, events pop by (time, tb) where
``tb`` is a deterministic tie-break assigned at creation — local pushes use
the host's own monotone counter, delivered packets use
``consts.packet_tb(src_host, src_pkt_counter)``. Both engines compute the
same keys, so event order is engine-independent.

int32 round path (round-5 rewrite): the chip has no native int64 — every
i64 op is a 3-6x-cost emulation (docs/PERF.md) — and the inner round loop
used to run ~15 full-plane i64 passes per pop/push. The buffer therefore
carries the pop keys twice:

* ``time``  i64 [C, H] — the authoritative absolute event time, written on
  push/delivery, READ ONLY at window granularity (rebase, pre_window);
* ``t32``   i32 [C, H] — ``clamp(time - epoch, 0, I32_HORIZON)`` where
  ``epoch`` advances to the window start each window (``rebase``). Pop
  eligibility/ordering runs entirely on t32: exact for every eligible
  event because eligible means ``time < win_end = epoch + W`` and the
  engine validates ``W < 2**31`` ns, so eligible rebased times never
  clamp; far-future events saturate at I32_HORIZON ≥ W and stay
  ineligible until the epoch catches up;
* ``tb_hi``/``tb_lo`` i32 [C, H] — the i64 tie-break split into an
  order-preserving (hi, lo) pair (``lo`` is sign-flipped so SIGNED i32
  comparison matches the unsigned low-word order). Pop's tie-break is a
  2-step lexicographic min over these planes: no i64 anywhere per round.

Pop-min exploits that the (time, tb) key pair is UNIQUE per host — tb
values never repeat within a host (local pushes consume a monotone counter;
packet tbs embed the unique (src, src_ctr); the two ranges are disjoint via
TB_PACKET_BASE) — so "the" minimum slot is an equality one-hot against the
reduced min keys, and payload extraction is a masked sum. No dynamic
scatters, no per-slot argmin/cumsum in the round path (core/dense.py).
"""

from __future__ import annotations

import sys
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from shadow1_tpu.consts import K_NONE, NP
from shadow1_tpu.core.dense import extract_col, first_true

I64_MAX = jnp.iinfo(jnp.int64).max
I32_MAX = jnp.iinfo(jnp.int32).max
I32_MIN = jnp.iinfo(jnp.int32).min
# Free/ineligible sentinel for the t32 plane; live far-future events clamp
# to I32_HORIZON. Both are ≥ any valid until32 (window < 2**31 — validated
# by the engine), so neither can pop.
I32_FREE = I32_MAX
I32_HORIZON = I32_MAX - 1
# Lower clamp: PAST-DUE events (left eligible by a max_rounds cap-hit
# window) rebase to NEGATIVE t32 so their (time, tb) order and exact
# reconstructed times survive into the next window — they sort before
# every in-window event, as the i64 semantics require. Only a backlog
# older than ~2.1 s would hit this clamp (and lose exactness); a cap-hit
# run that deep is already flagged by the round_cap_hits metric.
I32_PASTDUE = -I32_HORIZON
_SIGN = jnp.int32(-0x80000000)  # == 1 << 31 as a signed bit pattern


def tb_split(tb) -> tuple[jnp.ndarray, jnp.ndarray]:
    """i64 tie-break → (hi, lo) i32 planes, SIGNED-order-preserving.

    tb is always ≥ 0 and < 2**62 (consts.packet_tb / self_ctr), so
    hi = tb >> 32 fits positive i32 and orders first; lo is the low 32 bits
    with the sign bit flipped so signed i32 comparison equals unsigned
    low-word comparison."""
    hi = (tb >> 32).astype(jnp.int32)
    lo = (tb & 0xFFFFFFFF).astype(jnp.uint32).astype(jnp.int32) ^ _SIGN
    return hi, lo


def tb_join(hi, lo) -> jnp.ndarray:
    """Inverse of tb_split."""
    lo_u = (lo ^ _SIGN).astype(jnp.uint32).astype(jnp.int64)
    return (hi.astype(jnp.int64) << 32) | lo_u


def _t32_of(time, epoch) -> jnp.ndarray:
    """Rebased saturating pop key; exact (and order-exact) for times within
    (epoch - 2**31 + 2, epoch + 2**31 - 1)."""
    return jnp.clip(time - epoch, I32_PASTDUE, I32_HORIZON).astype(jnp.int32)


class EventBuf(NamedTuple):
    """Every [C, H] plane is i32 — the chip has no native i64. Absolute
    event times live as a tb_split-encoded (hi, lo) pair, reassembled only
    at window granularity (rebase, pre_window)."""

    time_hi: jnp.ndarray   # i32 [C, H] absolute time, high word
    time_lo: jnp.ndarray   # i32 [C, H] absolute time, low word (sign-flip)
    t32: jnp.ndarray       # i32 [C, H] rebased pop key (I32_FREE = empty)
    tb_hi: jnp.ndarray     # i32 [C, H] tie-break high word
    tb_lo: jnp.ndarray     # i32 [C, H] tie-break low word (sign-flipped)
    kind: jnp.ndarray      # i32 [C, H] (K_NONE = free slot)
    p: jnp.ndarray         # i32 [NP, C, H] payload columns
    self_ctr: jnp.ndarray  # i64 [H] counter for locally-pushed tb keys
    epoch: jnp.ndarray     # i64 scalar — t32 = clamp(time - epoch)
    # Running per-host count of events eligible before ``u32`` — maintained
    # incrementally by push/pop (cheap [H]-vector arithmetic) so the round
    # loop's continue-condition and the compaction active mask read a
    # vector instead of re-scanning the [C, H] planes every round. Only
    # valid between a ``rebase`` (which recomputes it and pins ``u32``)
    # and the next window-granularity mutation (deliver_batch/pre_window
    # rewrites leave it stale, exactly like t32).
    n_elig: jnp.ndarray    # i32 [H]
    u32: jnp.ndarray       # i32 scalar eligibility bound of n_elig
    # A round's staged local pushes (``Stage``, below), or None: None between
    # rounds, in every carry, snapshot and digest — no pytree leaf — and in a
    # round whose pushes write the planes directly (``push_local``).
    stage: Any = None

    def abs_time(self) -> jnp.ndarray:
        """i64 [C, H] absolute times (window-granularity readers only)."""
        return tb_join(self.time_hi, self.time_lo)


class Popped(NamedTuple):
    mask: jnp.ndarray   # bool [H] — host had an eligible event this round
    time: jnp.ndarray   # i64 [H] absolute
    kind: jnp.ndarray   # i32 [H] (K_NONE where ~mask)
    p: jnp.ndarray      # i32 [NP, H]
    tb: jnp.ndarray     # i64 [H] original tie-break (for cpu-model requeue)


def evbuf_init(n_hosts: int, cap: int) -> EventBuf:
    thi, tlo = tb_split(jnp.asarray(I64_MAX, jnp.int64))
    return EventBuf(
        time_hi=jnp.full((cap, n_hosts), thi, jnp.int32),
        time_lo=jnp.full((cap, n_hosts), tlo, jnp.int32),
        t32=jnp.full((cap, n_hosts), I32_FREE, jnp.int32),
        tb_hi=jnp.zeros((cap, n_hosts), jnp.int32),
        tb_lo=jnp.zeros((cap, n_hosts), jnp.int32),
        kind=jnp.full((cap, n_hosts), K_NONE, jnp.int32),
        p=jnp.zeros((NP, cap, n_hosts), jnp.int32),
        self_ctr=jnp.zeros(n_hosts, jnp.int64),
        epoch=jnp.zeros((), jnp.int64),
        n_elig=jnp.zeros(n_hosts, jnp.int32),
        u32=jnp.asarray(I32_HORIZON, jnp.int32),
    )


def rebase(buf: EventBuf, epoch, until=None) -> EventBuf:
    """Advance the t32 plane's epoch (once per window, off the round path).

    Recomputes t32 from the authoritative absolute times — this is also
    what makes window-end ``deliver_batch`` and pre-window event rewrites
    free to skip t32 maintenance: any staleness is repaired here before the
    next round loop reads it. ``until`` (default: the saturation horizon)
    pins the eligibility bound the ``n_elig`` counters are maintained
    against — the engine passes win_end."""
    epoch = jnp.asarray(epoch, jnp.int64)
    t32 = jnp.where(
        buf.kind != K_NONE, _t32_of(buf.abs_time(), epoch), I32_FREE
    )
    u32 = (jnp.asarray(I32_HORIZON, jnp.int32) if until is None
           else jnp.clip(jnp.asarray(until, jnp.int64) - epoch, 0,
                         I32_HORIZON).astype(jnp.int32))
    n_elig = (t32 < u32).sum(axis=0, dtype=jnp.int32)
    return buf._replace(t32=t32, epoch=epoch, n_elig=n_elig, u32=u32)


def push_local(buf: EventBuf, mask, time, kind, p) -> tuple[EventBuf, jnp.ndarray]:
    """Push one event per host where ``mask``; tb from the host's own counter.

    Returns (buf, overflow_mask). Overflowing events are dropped and must be
    surfaced as a metric — capacity is an experiment knob (SURVEY §7.3.2).
    Where the round has a stage open (``stage_open``) the event is staged
    and the planes are written at the round's one ``push_commit``: the same
    slot, keys and counters either way.
    """
    hi, lo = tb_split(buf.self_ctr)
    buf, ok, over = _push(buf, mask, time, hi, lo, kind, p)
    return buf._replace(self_ctr=buf.self_ctr + ok.astype(jnp.int64)), over


def push_back(buf: EventBuf, mask, time, tb, kind, p) -> tuple[EventBuf, jnp.ndarray]:
    """Re-insert a popped event with its ORIGINAL tie-break key.

    Used by the virtual-CPU model when a busy host's event execution slips
    past the window boundary (docs/SEMANTICS.md §cpu): the event re-enters
    at (eff_time, original tb), so its order among same-time events is
    preserved. Does not advance self_ctr."""
    hi, lo = tb_split(jnp.asarray(tb, jnp.int64))
    buf, _ok, over = _push(buf, mask, time, hi, lo, kind, p)
    return buf, over


def _push(buf: EventBuf, mask, time, tb_hi, tb_lo, kind, p):
    """One event per host where ``mask`` and a slot is free, under the given
    tie-break words: (buf, ok, overflow_mask). Staged where a stage is open,
    else written into the host's first free slot."""
    time = jnp.asarray(time, jnp.int64)
    thi, tlo = tb_split(time)
    t32v = _t32_of(time, buf.epoch)
    kind = jnp.asarray(kind, jnp.int32)
    p = jnp.asarray(p, jnp.int32)
    if buf.stage is not None:
        stage, ok, over = buf.stage.push(
            mask, (thi, tlo, t32v, tb_hi, tb_lo, kind), p)
        buf = buf._replace(stage=stage)
    else:
        has_free, first = first_true(buf.kind == K_NONE)
        ok = mask & has_free
        over = mask & ~has_free
        w = first & ok[None, :]
        buf = buf._replace(
            time_hi=jnp.where(w, thi[None, :], buf.time_hi),
            time_lo=jnp.where(w, tlo[None, :], buf.time_lo),
            t32=jnp.where(w, t32v[None, :], buf.t32),
            tb_hi=jnp.where(w, tb_hi[None, :], buf.tb_hi),
            tb_lo=jnp.where(w, tb_lo[None, :], buf.tb_lo),
            kind=jnp.where(w, kind[None, :], buf.kind),
            p=jnp.where(w[None], p[:, None, :], buf.p),
        )
    return buf._replace(
        n_elig=buf.n_elig + (ok & (t32v < buf.u32)).astype(jnp.int32)), ok, over


# --------------------------------------------------------------------------
# A round's local pushes, staged and committed once.
#
# A push writes ONE row a host, and as a select over the planes it reads and
# writes all 16 * C * H words to do so. Straight-line pushes fuse into one
# sweep, but a ``conditional`` is a fusion wall and a TCP round is built of
# them (the pass guards, tcp_rx's accept and FIN blocks, the apps' guarded
# blocks): five or six sweeps a round in the Tor cells (PERF.md §6, PR 49).
# With a stage open a push site touches no plane: it writes its event into a
# row of [H]-vectors, and ``push_commit`` writes the round's events into the
# planes in one sweep after the last pass.
#
# It is the same buffer to the bit, layout included: a host's r-th successful
# push of a round takes its then-first free slot, which is its r-th free slot
# after the pop in ascending slot order — the slot the commit gives rank r.
# ``self_ctr``, ``n_elig`` and the overflow mask are decided at the site from
# [H] counts, exactly as the direct push decides them.
# --------------------------------------------------------------------------

# Ranks the commit writes a trip: RB compares and RB * 16 selects an element
# of the planes. A Tor or tgen round stages at most one or two events on its
# busiest host (a timer, a TX_RESUME, an app wakeup) and two rounds in three
# stage none; a Bitcoin node that first sees a transaction announces it to
# each of its K peers in one round (PERF.md §6, PR 49).
# ``Metrics.push_commit_trips`` against ``rounds`` says how many rounds
# needed a second trip.
PUSH_RB = 4
# A staged row: the host's rank + 1 (0 = the row holds nothing of this
# host's), the six head planes' values, the payload.
_ROW_FIELDS = 7 + NP


class PushRowsError(Exception):
    """A pass traced a push the stage has no row for (or from inside a
    loop, where one site would run more than once a round)."""


class PushSites:
    """The trace-time count of push sites behind a round's stage.

    A site pushes at most one event a host and a host executes one event —
    one pass — a round, so the sites traced in a pass bound what a host can
    stage there, and the passes share the rows. ``enter`` opens a pass (the
    count restarts, since each trace of a pass traces its sites again),
    ``take`` hands a site its row and refuses one past the rows the pass
    declared (``core/engine.pass_rows``) or inside a loop's body.
    ``PushSites.log``, where a dict, collects the sites each pass traced
    (``core/engine.count_push_sites``)."""

    log = None

    def __init__(self, rows: int):
        self.rows = rows
        self.frame = None
        self.leave()

    def enter(self, name: str, limit: int):
        """Open pass ``name`` of ``limit`` declared rows, from the frame
        whose callees trace its sites."""
        self.name, self.limit, self.n = name, min(limit, self.rows), 0
        self.frame = sys._getframe(1)

    def leave(self):
        if PushSites.log is not None and self.frame is not None:
            PushSites.log[self.name] = max(PushSites.log.get(self.name, 0),
                                           self.n)
        self.name, self.limit, self.n, self.frame = "round", self.rows, 0, None

    def take(self) -> int:
        # A loop's body is traced once and runs many times: a site inside
        # one could push twice a round into its one row. jax traces the
        # bodies of while_loop, fori_loop, scan and map from loops.py; the
        # frames above the pass's own are the round loop's.
        f = sys._getframe(1)
        while f is not None and f is not self.frame:
            if f.f_code.co_filename.endswith("control_flow/loops.py"):
                raise PushRowsError(
                    f"pass {self.name!r} pushes an event from inside a "
                    "loop's body: a site must run once a round")
            f = f.f_back
        if self.n >= self.limit:
            raise PushRowsError(
                f"pass {self.name!r} traces more than the {self.limit} push "
                "sites it declares (core/engine.pass_rows): count them "
                "(core/engine.count_push_sites) and raise its rows")
        self.n += 1
        return self.n - 1


@jax.tree_util.register_pytree_node_class
class Stage:
    """A round's staged pushes: ``free`` i32 [H] the host's free slots after
    the pop, ``cnt`` i32 [H] the events it has staged, ``rows`` a tuple of
    i32 [_ROW_FIELDS, H], one a push site of a pass (``PushSites``)."""

    def __init__(self, free, cnt, rows, sites):
        self.free, self.cnt, self.rows, self.sites = free, cnt, rows, sites

    def tree_flatten(self):
        return (self.free, self.cnt, self.rows), self.sites

    @classmethod
    def tree_unflatten(cls, sites, children):
        return cls(*children, sites)

    def push(self, mask, heads, p):
        """Stage one event a host where ``mask`` and a slot is left:
        (stage, ok, overflow_mask). O(fields * H): a masked write of the
        site's own row."""
        r = self.sites.take()
        ok = mask & (self.cnt < self.free)
        new = jnp.concatenate([jnp.stack([self.cnt + 1, *heads]), p])
        rows = list(self.rows)
        rows[r] = jnp.where(ok[None, :], new, rows[r])
        stage = Stage(self.free, self.cnt + ok.astype(jnp.int32),
                      tuple(rows), self.sites)
        return stage, ok, mask & ~ok


def free_slots(buf: EventBuf) -> jnp.ndarray:
    """i32 [H]: the free slots each host has (one reduction over the ``kind``
    plane, the plane ``pop_until``'s own first reduction reads)."""
    return (buf.kind == K_NONE).sum(axis=0, dtype=jnp.int32)


def stage_open(buf: EventBuf, rows: int, free) -> EventBuf:
    """Open a round's stage of ``rows`` rows on a just-popped buffer whose
    hosts have ``free`` slots left."""
    h = buf.kind.shape[1]
    zero = jnp.zeros((_ROW_FIELDS, h), jnp.int32)
    return buf._replace(stage=Stage(
        free, jnp.zeros(h, jnp.int32), (zero,) * rows, PushSites(rows)))


def free_slot_rank(kind) -> jnp.ndarray:
    """i32 [C, H]: a free slot's rank among its host's free slots in
    ascending slot order, -1 on an occupied slot.

    An exclusive prefix count down the slot axis, as a product with the
    strictly lower triangle: 0/1 operands are exact in bfloat16 and the sums
    (at most C) in float32, on any backend, and the TPU runs it on the MXU —
    where ``cumsum`` down the sublane axis lowers to a slow sequence
    (core/dense.py) and PUSH_RB successive first-free reductions read the
    plane PUSH_RB times."""
    cap = kind.shape[0]
    free = kind == K_NONE
    slot = jnp.arange(cap, dtype=jnp.int32)
    below = (slot[None, :] < slot[:, None]).astype(jnp.bfloat16)
    rank = jnp.dot(below, free.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    return jnp.where(free, rank.astype(jnp.int32), -1)


def push_commit(buf: EventBuf, any_lane=lambda hit: hit):
    """Write the round's staged events into the planes and close the stage:
    (buf, trips, n_max) — the trips THIS buffer needed and the most events
    one of its hosts staged.

    A host's rank-r event goes to its r-th free slot in ascending slot
    order (``free_slot_rank``, taken once: a slot's rank does not move as
    lower ranks fill), PUSH_RB ranks a trip: each rank's event by a select
    over the rows, the write as dense compare-selects over the sixteen
    planes — ``deliver_batch``'s fill body without its gather. No trip
    where nothing was staged (the caller guards the ranking too:
    ``core/engine._commit_pushes``). ``any_lane`` reduces the loop's
    predicate over a fleet's lanes (``core/engine``): ``vmap`` of a loop
    whose trip count differs by lane selects the whole carry every trip."""
    stage = buf.stage
    n = stage.cnt
    n_max = n.max()
    slot_rank = free_slot_rank(buf.kind)

    def trip(carry):
        i, heads, pay = carry
        for j in range(PUSH_RB):
            k = i * PUSH_RB + j
            ev = jnp.zeros((_ROW_FIELDS - 1, n.shape[0]), jnp.int32)
            for row in stage.rows:
                ev = jnp.where((row[0] == k + 1)[None, :], row[1:], ev)
            # A rank no host staged selects no slot: a free slot of that
            # rank stays free.
            sel = (slot_rank == k) & (n > k)[None, :]
            heads = [jnp.where(sel, ev[f][None, :], x)
                     for f, x in enumerate(heads)]
            pay = jnp.where(sel[None], ev[6:][:, None, :], pay)
        return i + 1, heads, pay

    heads = [buf.time_hi, buf.time_lo, buf.t32, buf.tb_hi, buf.tb_lo,
             buf.kind]
    _, heads, pay = jax.lax.while_loop(
        lambda c: any_lane(c[0] * PUSH_RB < n_max), trip,
        (jnp.zeros((), jnp.int32), heads, buf.p))
    buf = buf._replace(
        time_hi=heads[0], time_lo=heads[1], t32=heads[2], tb_hi=heads[3],
        tb_lo=heads[4], kind=heads[5], p=pay, stage=None)
    return buf, (n_max + (PUSH_RB - 1)) // PUSH_RB, n_max


# An ``until`` that no event is before, past-due ones (negative t32)
# included: ``until32`` clamps it below every key a slot can hold.
NEVER = -(1 << 62)


def until32(buf: EventBuf, until) -> jnp.ndarray:
    """Rebased eligibility bound. Exact when until - epoch <= I32_HORIZON
    = 2**31 - 2 (the engine's window-size validation guarantees it for
    win_end bounds: window < 2**31 - 1, config/compiled.py). ``NEVER``
    comes out below every key, so it admits nothing."""
    return jnp.clip(until - buf.epoch, I32_MIN, I32_HORIZON).astype(jnp.int32)


def pop_until(buf: EventBuf, until) -> tuple[EventBuf, Popped]:
    """Per-host pop of the minimum-(time, tb) event with time < until
    (none at all, and ``buf`` back as it went in, for ``until = NEVER``).

    A 3-step lexicographic masked min over the slot (sublane) axis — t32,
    then tb_hi among time-ties, then tb_lo — ending in an equality one-hot;
    exact because (time, tb) is unique per host (module docstring). All
    i32: the only i64 work is the [H]-vector reconstruction of the popped
    absolute time/tb. Kind and payload leave the buffer as a masked sum
    over the one-hot."""
    u32 = until32(buf, until)
    elig = (buf.kind != K_NONE) & (buf.t32 < u32)
    t_masked = jnp.where(elig, buf.t32, I32_FREE)
    min_t = t_masked.min(axis=0)
    mask = min_t < u32
    tie = elig & (t_masked == min_t[None, :])
    hi_masked = jnp.where(tie, buf.tb_hi, I32_MAX)
    min_hi = hi_masked.min(axis=0)
    tie2 = tie & (hi_masked == min_hi[None, :])
    lo_masked = jnp.where(tie2, buf.tb_lo, I32_MAX)
    min_lo = lo_masked.min(axis=0)
    sel = tie2 & (lo_masked == min_lo[None, :])    # one-hot per active host
    kind = extract_col(sel, buf.kind)
    pay = extract_col(sel, buf.p)
    ev = Popped(
        mask=mask,
        time=jnp.where(mask, buf.epoch + min_t.astype(jnp.int64), 0),
        kind=kind,
        p=pay,
        tb=jnp.where(mask, tb_join(min_hi, min_lo), 0),
    )
    buf = buf._replace(
        kind=jnp.where(sel, K_NONE, buf.kind),
        t32=jnp.where(sel, I32_FREE, buf.t32),
        n_elig=buf.n_elig - mask.astype(jnp.int32),
    )
    return buf, ev


def any_eligible(buf: EventBuf, until) -> jnp.ndarray:
    """True if any host still has an eligible event. Reads the maintained
    [H] counters, NOT the [C, H] planes — exact whenever ``until`` matches
    the bound pinned by the last ``rebase`` (the engine always passes
    win_end to both; arbitrary other ``until`` values are not supported
    here and must scan the planes directly)."""
    del until  # pinned at rebase time (buf.u32)
    return (buf.n_elig > 0).any()


def evbuf_fill(buf: EventBuf) -> jnp.ndarray:
    """Occupancy gauge: pending events on the busiest host, i64 scalar.

    One [C, H] plane pass — read at WINDOW granularity only (the engine's
    window-end gauge update and the telemetry ring share one evaluation),
    never in the round loop. Slot-layout-independent: it counts occupied
    slots, so a cap migration (tune/resize.py) cannot change it."""
    return (buf.kind != K_NONE).sum(axis=0, dtype=jnp.int32).max().astype(jnp.int64)


# Arriving ranks deliver_batch places per pass of its fill loop. A pass costs
# RB * H gathered packet rows plus one fused select over the event planes, so
# a larger block trades plane passes for rows fetched past the busiest host's
# last packet (dense PHOLD on the v5e: 54.0 / 54.9 / 61.0 / 79.4 ms a merge
# at 2 / 4 / 8 / 16; PERF.md §6, PR 29).
RB = 4


def deliver_batch(
    buf: EventBuf, dst, time, tb, kind, p, mask
) -> tuple[EventBuf, jnp.ndarray, jnp.ndarray]:
    """Merge N externally-created events into their hosts' buffers.

    The tensor analogue of the reference's locked cross-thread event push
    (src/main/utility/async-priority-queue.c). Packets are sorted by
    destination (masked ones to the end); packet r of a host — the r-th in
    flat source order — goes to that host's r-th free slot in ascending
    slot index, and when free slots run out the highest ranks drop. Slot
    ASSIGNMENT is an engine-internal layout choice; pop order is decided
    purely by the (time, tb) keys, so it is engine- and layout-independent.
    Returns (buf, n_overflow, n_ranks). ``p`` is [NP, N]; ``n_ranks`` is
    the fill loop's trips * RB (``Metrics.deliver_ranks``).

    Runs at window granularity only, so it writes the authoritative i64
    time plane and leaves t32 stale — the window-start ``rebase`` repairs
    it before any round reads it.

    Overflow-victim selection is layout-defined: when a destination's free
    slots run out, which packets drop depends on flat source order (since
    the [C, H] rewrite: slot-major), so it differs across engines and
    layout revisions. Cross-engine parity is guaranteed only for runs with
    ``ev_overflow == 0`` — the oracle harness asserts this
    (docs/SEMANTICS.md "Bounds and overflow").

    TPU tuning: the sort key packs (dst, flat index) into one integer so an
    *unstable* single-key sort is deterministic (keys are distinct and the
    packing preserves source order within a destination); segment bounds
    come from one H+1-point searchsorted. The chip runs a gather one index
    at a time (7-11 ns an index, 20-25 ns a 15-field row: PERF.md §6,
    PR 29), so the fill fetches by ARRIVING RANK, not by slot: a
    ``while_loop`` over blocks of RB ranks, each trip gathering the
    block's RB * H packet rows (time split into i32 halves, the pre-split
    tb planes, kind, p: one stacked gather) and writing them with dense
    compare-selects against each slot's free rank. The trip count is data
    — ceil(busiest host's placed packets / RB): zero in a window that sent
    nothing — where a gather per slot costs C * H indices whatever
    arrived. Under ``vmap`` the loop runs to the busiest lane's count.
    """
    n_hosts = buf.kind.shape[1]
    n = dst.shape[0]
    nb = max((n - 1).bit_length(), 1)
    wide = (n_hosts + 1) << nb > 2**31 - 1
    kdt = jnp.int64 if wide else jnp.int32
    key = (jnp.where(mask, dst, n_hosts).astype(kdt) << nb) | jnp.arange(n, dtype=kdt)
    (key_s,) = jax.lax.sort((key,), is_stable=False)
    dst_s = (key_s >> nb).astype(jnp.int32)
    idx_s = (key_s & ((1 << nb) - 1)).astype(jnp.int32)      # [N] flat idx
    hs = jnp.arange(n_hosts + 1, dtype=jnp.int32)
    seg = jnp.searchsorted(dst_s, hs, side="left").astype(jnp.int32)
    first = seg[:-1]                                         # [H] rank 0's
    n_in = seg[1:] - first                                   # [H]
    free = buf.kind == K_NONE                                # [C, H]
    free_rank = (jnp.cumsum(free, axis=0) - free).astype(jnp.int32)
    # The arriving rank each slot receives, -1 where it receives none.
    slot_rank = jnp.where(free & (free_rank < n_in[None, :]), free_rank, -1)
    n_take = jnp.minimum(n_in, free.sum(axis=0, dtype=jnp.int32))
    trips = (n_take.max() + (RB - 1)) // RB
    thi, tlo = tb_split(jnp.asarray(time, jnp.int64))
    bhi, blo = tb_split(jnp.asarray(tb, jnp.int64))
    stacked = jnp.concatenate(
        [
            jnp.stack([thi, tlo, bhi, blo, kind]),
            p,
        ]
    )                                                        # [5+NP, N] i32

    def fill(carry):
        i, heads, pay = carry
        r = i * RB + jnp.arange(RB, dtype=jnp.int32)
        src = jnp.minimum(first[None, :] + r[:, None], n - 1)
        g = stacked[:, idx_s[src]]                           # [5+NP, RB, H]
        for j in range(RB):
            sel = slot_rank == r[j]
            heads = [jnp.where(sel, g[k, j][None, :], x)
                     for k, x in enumerate(heads)]
            pay = jnp.where(sel[None], g[5:, j][:, None, :], pay)
        return i + 1, heads, pay

    heads = [buf.time_hi, buf.time_lo, buf.tb_hi, buf.tb_lo, buf.kind]
    _, heads, pay = jax.lax.while_loop(
        lambda carry: carry[0] < trips, fill,
        (jnp.zeros((), jnp.int32), heads, buf.p))
    buf = buf._replace(
        time_hi=heads[0], time_lo=heads[1], tb_hi=heads[2], tb_lo=heads[3],
        kind=heads[4], p=pay,
    )
    n_over = mask.sum() - n_take.sum()
    return buf, n_over, (trips * RB).astype(jnp.int64)


def _lo(x):
    return (x & 0xFFFFFFFF).astype(jnp.uint32).astype(jnp.int32)


def _hi(x):
    return ((x >> 32) & 0xFFFFFFFF).astype(jnp.uint32).astype(jnp.int32)


def _join(lo, hi):
    return (
        lo.astype(jnp.uint32).astype(jnp.uint64)
        | (hi.astype(jnp.uint32).astype(jnp.uint64) << jnp.uint64(32))
    ).astype(jnp.int64)
