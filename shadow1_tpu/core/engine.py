"""The TPU engine: conservative-window batched discrete-event execution.

This is the tensor re-expression of the reference's scheduler stack
(src/main/core/master.c runahead loop + src/main/core/scheduler/*.c barrier
rounds + src/main/core/worker.c event loop, SURVEY §3.1–3.2):

* outer loop  — one iteration per conservative window [T, T+W), W = min
  topology latency, exactly the reference's Master round loop;
* inner loop  — rounds: every host pops its minimum-(time, tb) event and the
  masked vectorized handlers run; a round is the SIMD analogue of "each
  worker runs the next event of one host"; hosts interact only through
  packets, which conservative lookahead guarantees land ≥ one window later;
* window end  — the buffered packet outboxes are routed (path latency from
  the vertex matrix, Bernoulli loss draws) and scattered into destination
  event buffers: the one cross-host exchange per window.

The whole run is a single jitted program (fori over windows, while over
rounds); there is no host↔device traffic until metrics are fetched.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from shadow1_tpu import rng
from shadow1_tpu.config.compiled import CompiledExperiment
from shadow1_tpu.consts import (
    KIND_METRIC_FIELDS,
    KIND_NAMES,
    K_NONE,
    R_JITTER,
    R_LOSS,
    EngineParams,
    packet_tb,
)
from shadow1_tpu.core.dense import pick_row, table_rows
from shadow1_tpu.core.events import (
    NEVER,
    EventBuf,
    Popped,
    PushSites,
    any_eligible,
    deliver_batch,
    evbuf_init,
    free_slots,
    pop_until,
    push_back,
    push_commit,
    stage_open,
)
from shadow1_tpu.core.outbox import Outbox, outbox_clear, outbox_init
from shadow1_tpu.telemetry.profiler import PH_ARGS, PH_CALL, run_span


class Metrics(NamedTuple):
    events: jnp.ndarray          # events executed
    rounds: jnp.ndarray          # inner rounds run
    windows: jnp.ndarray         # windows completed
    pkts_sent: jnp.ndarray
    pkts_delivered: jnp.ndarray
    pkts_lost: jnp.ndarray       # dropped by path loss
    ev_overflow: jnp.ndarray     # events dropped: full event buffer
    ob_overflow: jnp.ndarray     # packets dropped: full outbox
    round_cap_hits: jnp.ndarray  # windows that hit the max_rounds safety cap
    tcp_fast_rtx: jnp.ndarray    # fast-retransmit (3 dup-ACK) episodes
    tcp_rto: jnp.ndarray         # retransmit-timeout episodes
    tcp_ooo_drops: jnp.ndarray   # out-of-order segments dropped (GBN receiver)
    x2x_overflow: jnp.ndarray    # packets dropped: all_to_all bucket full
                                 # (sharded engine only; parity needs 0)
    x2x_max_fill: jnp.ndarray    # high-water DEMANDED per-destination bucket
                                 # fill across the run (sharded exchange;
                                 # pmax-replicated, so excluded from the
                                 # cross-shard psum like ``windows``) — the
                                 # quantity that rationally pins x2x_cap
    # Capacity high-water gauges (shadow1_tpu/tune/): run-max window-end
    # fill of each bounded structure, maintained inside the jitted window
    # path at one ``max`` per already-computed fill count. These are what
    # the between-chunk cap controller (tune/autocap.py) and the offline
    # tuner (tools/captune.py) size caps from. Window-END samples are a
    # LOWER bound on the true mid-window peak (like tools/occprobe.py) —
    # the overflow counters stay the authoritative guard. Under sharding
    # they are max-globalized at chunk end (shard/engine.py), so they match
    # the single-device values bit-exactly; ``compact_max_fill`` is the one
    # exception (per-shard bucket demand, like ``rounds``).
    ev_max_fill: jnp.ndarray      # busiest host's event-slot fill (vs ev_cap)
    ob_max_fill: jnp.ndarray      # busiest host's per-window outbox fill
                                  # (vs outbox_cap)
    compact_max_fill: jnp.ndarray # busiest window's active-host count — the
                                  # demanded compaction-bucket lanes (vs
                                  # compact_cap), recorded compaction on OR
                                  # off so the knob can be sized before it
                                  # is enabled
    mq_max_fill: jnp.ndarray      # busiest host's message boundaries in its
                                  # pool (vs mq_pool; tcp/tcp.py), 0 without
                                  # TCP
    mq_overflow: jnp.ndarray      # boundaries dropped: full pool (parity
                                  # needs 0, like ev_overflow)
    down_events: jnp.ndarray     # events discarded: host stopped (churn)
    down_pkts: jnp.ndarray       # packets dropped: destination host stopped
    nic_tx_drops: jnp.ndarray    # packets dropped: NIC uplink queue full
    nic_rx_drops: jnp.ndarray    # packets dropped: NIC downlink queue full
    nic_aqm_drops: jnp.ndarray   # packets dropped: RED early-drop (uplink)
    # Per-kind pop occupancy (performance observability: which handler
    # passes the rounds actually feed; parity-exact like events).
    pops_pkt: jnp.ndarray        # K_PKT (rx drop-tail path only)
    pops_deliver: jnp.ndarray    # K_PKT_DELIVER
    pops_timer: jnp.ndarray      # K_TCP_TIMER
    pops_txr: jnp.ndarray        # K_TX_RESUME
    pops_app: jnp.ndarray        # K_APP
    # Rounds in which each handler pass FIRED (its lax.cond took the real
    # branch) — the per-round cost drivers. Batch-engine-only counters,
    # excluded from parity like ``rounds``.
    fires_pkt: jnp.ndarray
    fires_deliver: jnp.ndarray
    fires_timer: jnp.ndarray
    fires_txr: jnp.ndarray
    fires_app: jnp.ndarray
    # Rounds in which the lane's PROGRAM ran each pass: the guard's
    # predicate as the ``lax.cond`` saw it (``any_host``), which on a fleet
    # is "some lane has the kind". Solo: equal to fires_*. Fleet: one number
    # in every lane (every lane rides every iteration of the one loop), >=
    # each lane's fires_*. Batch-engine-only like fires_*, and the one family
    # a lane-against-solo comparison leaves out (registry.LANE_PROGRAM_FIELDS).
    runs_pkt: jnp.ndarray
    runs_deliver: jnp.ndarray
    runs_timer: jnp.ndarray
    runs_txr: jnp.ndarray
    runs_app: jnp.ndarray
    # Windows in which the program ran the window end (``deliver_window``'s
    # guard: some host of some lane had sent): ``1 - runs_window_end /
    # windows`` is the share skipped. The same family as runs_*; the sharded
    # engine, whose window end is unguarded, counts every window.
    runs_window_end: jnp.ndarray
    # Arriving ranks the window-end merge swept (events.deliver_batch: its
    # fill loop's trips * RB, summed over windows) — against windows * ev_cap
    # it says what a fill by slot would sweep. Batch-engine-only like fires_*.
    deliver_ranks: jnp.ndarray
    # Outbox rows the window ends' ``route_outbox`` looked up: ``outbox_cap
    # × hosts`` in each window whose end the program ran (a skipped one adds
    # 0), filled or not — against ``pkts_sent`` the share of the lookups
    # that had a packet. The program's count like ``runs_window_end`` (one
    # number in every lane of a fleet), summed over shards.
    route_rows: jnp.ndarray
    # The round's one commit of its staged pushes (events.push_commit):
    # trips the lane's own rounds needed, PUSH_RB ranks a trip (0 in a round
    # that staged nothing; against ``rounds`` it says how often one trip did
    # not do), and the most events one host staged in one round (against the
    # rows a pass declares, ``pass_rows``: how far the proven bound is from
    # use). 0 where the round pushes directly (one handler kind).
    # Batch-engine-only like fires_*.
    push_commit_trips: jnp.ndarray
    push_stage_max: jnp.ndarray
    # Fault plane (shadow1_tpu/fault/): deterministic link outages and host
    # restarts (docs/SEMANTICS.md §"Fault plane").
    link_down_pkts: jnp.ndarray  # packets dropped: link outage window
    host_restarts: jnp.ndarray   # host restart resets applied (churn up)
    # Wasted-work accounting (performance attribution plane): per-window
    # boundary samples accumulated as running sums so the telemetry ring's
    # delta columns recover the per-window values. All three sample
    # engine-independent boundary quantities (the window-start pending set
    # and the per-window send set are identical on every engine — the state
    # digest's argument), so they are parity-exact cpu↔tpu↔sharded: each
    # shard counts its local host block and the psum is the global value.
    # active_hosts/n_hosts per window is exactly the ROADMAP's "rung-3/4
    # rounds touch ~0.1% of hosts but pay full [cap, H] plane passes"
    # pathology as a live signal.
    active_hosts: jnp.ndarray    # Σ_w hosts with ≥1 eligible event at start
    elig_events: jnp.ndarray     # Σ_w events eligible at window start
    outbox_hosts: jnp.ndarray    # Σ_w hosts with ≥1 outbox slot used


def _metrics_init() -> Metrics:
    z = jnp.zeros((), jnp.int64)
    return Metrics(*([z] * len(Metrics._fields)))


def compact_cap_of(params: EngineParams, n_hosts: int) -> int:
    """The bucket width in force for a block of ``n_hosts`` columns:
    ``params.compact_cap`` where it is set and narrower than the block,
    else 0 (the plain full-width round loop)."""
    cap = params.compact_cap
    return cap if cap and cap < n_hosts else 0


def compact_buckets_init(params: EngineParams, n_hosts: int):
    """``SimState.compact_buckets`` at t = 0: a counter where a cap is in
    force, no leaf where none is."""
    if compact_cap_of(params, n_hosts):
        return jnp.zeros((), jnp.int64)
    return None


class SimState(NamedTuple):
    win_start: jnp.ndarray  # i64 scalar
    evbuf: EventBuf
    outbox: Outbox
    model: Any              # workload-model pytree
    metrics: Metrics
    cpu_busy: jnp.ndarray   # i64 [H] virtual CPU free-at (host/cpu.c model)
    # On-device telemetry ring (telemetry/ring.TelemetryRing) or None when
    # EngineParams.metrics_ring == 0 — None contributes no pytree leaves,
    # so a ring-less state keeps the historic leaf layout.
    telem: Any = None
    # Flow-probe ring (telemetry/probes.ProbeRing, [W, K, F]) or None when
    # EngineParams.probes is empty — same None-leaf rule as the telemetry
    # ring, so a probe-less state keeps the historic layout.
    probes: Any = None
    # Link-telemetry accumulator (telemetry/links.LinkAccum, [V, V, F]) or
    # None when EngineParams.link_telem == 0 — same None-leaf rule again;
    # never digested, so carrying it is digest-neutral by construction.
    links: Any = None
    # Trips of the compacted round loop, summed over windows (i64 scalar;
    # core/compact.py), or None where no ``compact_cap`` is in force — the
    # None-leaf rule once more, so a program without a cap is the program it
    # was. A count the PROGRAM makes of itself, like ``Metrics.rounds`` and
    # ``runs_*``: no parity or lane-against-solo comparison reads it.
    compact_buckets: Any = None


@dataclasses.dataclass(frozen=True)
class Ctx:
    """Trace-time context handed to model handler builders.

    Shard-awareness: the engine state lives on a (possibly sharded) host
    axis. ``n_hosts`` is the LOCAL block size (every [H, ...] tensor shape),
    ``hosts`` holds the GLOBAL host ids of this block (a contiguous range),
    and ``n_total`` is the global host count. Anything semantic — RNG
    streams keyed by host, packet src/dst fields, random destination draws —
    uses global ids; anything shape-like uses ``n_hosts``. On a single
    device the two views coincide (hosts == arange(n_hosts)).
    """

    n_hosts: int            # local host-axis block size
    n_total: int            # global host count
    params: EngineParams
    window: int
    key: jax.Array          # base PRNG key (device)
    lat_vv: jax.Array       # i64 [V, V]
    loss_vv: jax.Array      # f32 [V, V]
    host_vertex: jax.Array  # i32 [n_total] — indexed by GLOBAL host id
    bw_up: jax.Array        # i64 [H] local
    bw_dn: jax.Array        # i64 [H] local
    model_cfg: dict
    hosts: jax.Array = None  # i32 [H] global host ids of this block
    loss_thr_vv: jax.Array = None  # u64 [V, V] Bernoulli thresholds
    # Fidelity knobs (all local [H] / [V,V]; see CompiledExperiment). The
    # has_* flags are TRACE-TIME booleans so disabled features compile to
    # nothing.
    jitter_vv: jax.Array = None    # i64 [V, V]
    # Host churn intervals (fault/schedule.host_interval_tensors): host h is
    # DOWN at time t iff any k has fault_down[k,h] <= t < fault_up[k,h].
    # The legacy single stop_time compiles to one [stop, NO_STOP) interval.
    fault_down: jax.Array = None   # i64 [K, H]
    fault_up: jax.Array = None     # i64 [K, H] (window-quantized; NO_STOP)
    link_fault: Any = None         # (src, dst, t0, t1) [L] tables or None
    loss_ramp: Any = None          # (src, dst, t0, t1, thr) [R] or None
    init_model: Any = None         # post-init model pytree (restart target)
    cpu_cost: jax.Array = None     # i64 [H] virtual CPU ns per event
    # ns per wire byte, 8e9 // bw, where EVERY link's bits/s divides 8e9
    # (then serialization is bytes × this, exactly, and no 64-bit division
    # is traced: net/nic.ser_delay); None as soon as one link's does not.
    ser_up: Any = None             # i64 [H] (numpy until traced)
    ser_dn: Any = None             # i64 [H]
    tx_qlen_ns: jax.Array = None   # i64 [H] uplink queue bound (ns of backlog)
    rx_qlen_ns: jax.Array = None   # i64 [H]
    aqm_min_ns: jax.Array = None   # i64 [H] RED min threshold (backlog ns)
    aqm_span_ns: jax.Array = None  # i64 [H] RED max − min (≥1 where enabled)
    aqm_pmax_thr: jax.Array = None # u64 [H] Bernoulli threshold at pmax
    has_jitter: bool = False
    has_stop: bool = False         # any host down interval exists
    has_restart: bool = False      # any finite up time (restart resets)
    has_link_fault: bool = False
    has_loss_ramp: bool = False
    has_cpu: bool = False
    has_tx_qlen: bool = False
    has_rx_qlen: bool = False
    has_aqm: bool = False
    # The name of the vmap axis the window step runs under (FleetEngine
    # sets it: its lanes), None where there is none (solo, sharded). Read
    # by ``any_host`` and by nothing else.
    lane_axis: str | None = None
    # ``host_vertex`` as its runs of equal vertex over contiguous global
    # ids — ((first id, vertex), …), read off the concrete map below —
    # where they are few; None where they are many (``vertex: spread``).
    # Read by ``vertex_of`` and by nothing else.
    vertex_runs: tuple | None = None

    def __post_init__(self):
        if self.vertex_runs is None:
            object.__setattr__(self, "vertex_runs",
                               _vertex_runs(self.host_vertex))
        if self.hosts is None:
            # Single-device default: the block IS the whole host range.
            object.__setattr__(self, "hosts", jnp.arange(self.n_hosts, dtype=jnp.int32))
        if self.loss_thr_vv is None:
            # Integer loss thresholds, computed host-side once (numpy) so no
            # float op survives in the per-window path (round-2 postmortem).
            object.__setattr__(
                self,
                "loss_thr_vv",
                jnp.asarray(rng.prob_threshold(np.asarray(self.loss_vv))),
            )


# route_outbox's three static bounds, on what it can see when it is traced.
# A read of ``host_vertex`` or of a [V, V] path table with one index per
# outbox row is an element-serial gather on the TPU, 7–14 ns a row and half
# word whatever the outbox holds (PERF.md §6, PRs 34, 42 and 50); the same
# read as compares and selects over the rows fuses into the arrival add and
# the loss compare. What bounds each dense form:
# - a host → vertex map costs one compare and select per run and row, so it
#   is dense up to this many runs (one per host group attached in order; the
#   largest config under configs/ has 6) and a gather beyond (``vertex:
#   spread`` has one run per host): the traced program's size, not the
#   chip's time;
MAX_VERTEX_RUNS = 32
# - a path table is read in two steps, the table's row per host ([V, H]: every
#   slot of an outbox column has the column's source) and a pick over the
#   destination's vertex per slot. As selects, the per-host step is
#   V · (V − 1) of them over [H] and the pick V − 1 per row, for each of up
#   to three tables: 720 + 45 equations at 16 vertices, four times that at
#   32 — again the program's size;
MAX_DENSE_VERTICES = 16
# - past that the per-host step is ONE read of H table rows (a product of the
#   table's byte planes with a one-hot of the hosts' vertices,
#   core/dense.table_rows) and the pick a masked sum (core/dense.pick_row):
#   a handful of equations whatever V, whose time grows with V — the pick
#   does cap · H · V compare-selects where the gather pays cap · H indices,
#   and the rows' byte planes are 32 · V · H bytes a table and lane. On the
#   v5e, one table of two lanes of 5,000 hosts and 64 slots (PERF.md §6, PR
#   51): 0.26 ms at 200 vertices, the planes held on the chip, against the
#   gather's 15.2; 3.4 ms at 512 and 6.9 at 1,024 against 16.4–18.1, the
#   planes by then 0.33 and 0.66 GB of HBM scratch. At twice that the
#   scratch alone is past what a window end may ask for, and the link
#   plane's own [V, V] bound is 1,024 too. A larger topology keeps
#   ``table[vs, vd]``.
MAX_ROW_VERTICES = 1024


def _vertex_runs(host_vertex):
    """((first global id, vertex), …) of the map's runs, or None where it
    has more than MAX_VERTEX_RUNS of them."""
    hv = np.asarray(host_vertex)
    starts = np.flatnonzero(np.diff(hv, prepend=hv[:1] - 1))
    if len(starts) > MAX_VERTEX_RUNS:
        return None
    return tuple((int(s), int(hv[s])) for s in starts)


def vertex_of(ctx: Ctx, ids):
    """``ctx.host_vertex[ids]`` for in-range GLOBAL host ids: a chain of
    compares against the runs' first ids where the map has few runs (vertex
    ids need not rise with host id: a later run overwrites), the gather
    where it has many."""
    if ctx.vertex_runs is None:
        return ctx.host_vertex[ids]
    (_, v), *runs = ctx.vertex_runs
    out = jnp.full(ids.shape, v, ctx.host_vertex.dtype)
    for start, v in runs:
        out = jnp.where(ids >= start, jnp.asarray(v, out.dtype), out)
    return out


Handler = Callable[[SimState, Popped], SimState]


def push_local_event(st: SimState, ctx: Ctx, mask, time, kind,
                     p0=None, p1=None, p2=None, p3=None) -> SimState:
    """Push one local event per host where ``mask``, counting overflow.

    The engine-state-level convenience over events.push_local used by all
    handler layers (transport timers, app wakeups)."""
    from shadow1_tpu.core.dense import payload
    from shadow1_tpu.core.events import push_local

    p = payload(ctx.n_hosts, p0, p1, p2, p3)
    k = jnp.full(ctx.n_hosts, kind, jnp.int32)
    evbuf, over = push_local(st.evbuf, mask, time, k, p)
    m = st.metrics
    return st._replace(
        evbuf=evbuf,
        metrics=m._replace(ev_overflow=m.ev_overflow + over.sum(dtype=jnp.int64)),
    )


# --------------------------------------------------------------------------
# Window-step building blocks, shared by the single-device Engine and the
# sharded engine (shard/engine.py). All take the local-block view: state
# tensors sized [ctx.n_hosts, ...], global host ids in ctx.hosts.
# --------------------------------------------------------------------------

class FlatPackets(NamedTuple):
    """One window's routed packets, flattened to a single axis.

    ``dst`` is a GLOBAL host id; ``keep`` marks packets that survived the
    loss draw. Flat order is slot-major over the [P, H] outbox — an
    engine-internal layout detail: per-(src,dst) pair it equals send order,
    and event pop order is decided by the (time, tb) keys alone, so results
    are layout-independent. Under sharding the per-window all_to_all
    concatenates received buckets in source-shard order.
    """

    dst: jnp.ndarray      # i32 [N] global dst host
    arrival: jnp.ndarray  # i64 [N]
    tb: jnp.ndarray       # i64 [N]
    kind: jnp.ndarray     # i32 [N]
    p: jnp.ndarray        # i32 [NP, N]
    keep: jnp.ndarray     # bool [N]


def any_host(ctx: Ctx, mask) -> jnp.ndarray:
    """The predicate of a "some host has this" guard: ``mask.any()``, and
    under a fleet's lane axis that, reduced over the lanes.

    ``vmap`` turns a ``lax.cond`` whose predicate differs by lane into both
    branches and a select of every leaf they carry. ``pmax`` over the named
    axis is one value for all lanes, so the ``cond`` stays a conditional and
    a pass (or a block inside one) that no lane needs is skipped, as on the
    solo engine. Use it for a guard's predicate and for nothing else: what
    it returns says nothing about this lane's hosts (``run_round``'s
    docstring has the contract the guarded code must keep)."""
    return any_lane(ctx, jnp.any(mask))


def any_lane(ctx: Ctx, hit) -> jnp.ndarray:
    """A lane's scalar ``hit``, or-ed over the fleet's lanes; ``hit`` itself
    where no lane axis is bound (``any_host`` of an already reduced mask)."""
    if ctx.lane_axis is None:
        return hit
    return jax.lax.pmax(hit.astype(jnp.int32), ctx.lane_axis) > 0


def lane_branch(ctx: Ctx, fn):
    """``fn`` as the taken branch of a guard on ``any_host``: itself where no
    lane axis is bound; under a fleet's, one call of a jitted callee.

    ``vmap``'s rule for a ``cond`` whose predicate is one value for all lanes
    batches each branch twice (once to learn which outputs come out batched,
    once to match the branches), and a handler pass is seconds of tracing: a
    fleet's warm set-up grew by a third (PERF.md §6, PR 38). A jitted callee
    is batched through a cache keyed on the callee and its operands' axes
    alone, so it is batched once however often the branch is; XLA inlines
    the call. The callee returns only the leaves ``fn`` replaced and the
    rest are handed back as they came in — what ``lax.cond`` itself does
    for a branch it can see into, and what keeps a guard's outputs (and the
    round loop's carry) to the leaves its block writes."""
    if ctx.lane_axis is None:
        return fn

    def branch(*args):
        fwd = []    # set while ``written`` is traced, i.e. by the call below

        def written(*a):
            came = {id(x): i for i, x in enumerate(jax.tree.leaves(a))}
            out, tree = jax.tree.flatten(fn(*a))
            fwd[:] = tree, [came.get(id(o)) for o in out]
            return [o for o in out if id(o) not in came]

        new = iter(jax.jit(written)(*args))
        tree, src = fwd
        old = jax.tree.leaves(args)
        return tree.unflatten(
            [next(new) if i is None else old[i] for i in src])

    return branch


def pass_rows(rows):
    """Declare the push sites a handler pass traces: ``run_round`` sizes the
    round's stage by the largest declaration and the trace fails, naming the
    pass, where a pass traces one more (``events.PushSites``). A site is one
    traced ``push_local`` / ``push_local_event``, whatever its mask; a
    handler that declares nothing may push nothing. ``rows`` is a number,
    or a function of the ctx where the experiment decides (Bitcoin announces
    to each of a node's K peers: K sites); ``rows_of`` reads it."""
    def declare(fn):
        fn.push_rows = rows
        return fn

    return declare


def rows_of(fn, ctx: Ctx) -> int:
    """The push sites ``fn`` declares (``pass_rows``) under ``ctx``."""
    rows = getattr(fn, "push_rows", 0)
    return rows(ctx) if callable(rows) else rows


def count_push_sites(st: SimState, ctx: Ctx, handlers: dict) -> dict:
    """{pass: push sites one trace of it counts} for a round of ``handlers``
    (shapes only, nothing runs): what ``pass_rows`` has to cover."""
    PushSites.log = log = {}
    try:
        jax.eval_shape(
            lambda s: run_round(s, ctx, handlers, s.win_start + ctx.window),
            st)
    finally:
        PushSites.log = None
    return log


def run_round(st: SimState, ctx: Ctx, handlers: dict, win_end) -> SimState:
    """One inner round: per-host pop-min + the handler passes.

    Two fidelity gates apply between pop and dispatch (both compile to
    nothing when the knobs are off):

    * **churn** (fault plane / config host stop times): an event whose
      time falls inside a down interval of its host is discarded (counted
      in ``down_events``) — the batch analogue of the reference halting a
      host's processes; events timed after a restart execute against the
      reset state (docs/SEMANTICS.md §"Fault plane");
    * **virtual CPU** (src/main/host/cpu.c): execution time is
      ``eff = max(time, cpu_busy[h])``; if eff crosses the window boundary
      the event re-queues at (eff, original tb) unexecuted, else it
      executes with ``now = eff`` and charges ``cpu_busy = eff + cost``.
      Both engines apply the identical rule in identical per-host order,
      so the busy clocks evolve identically (docs/SEMANTICS.md).

    Each kind's pass is wrapped in ``lax.cond`` on "any host popped this
    kind this round" (``any_host``) — most rounds touch 1–2 of the 5 kinds,
    so skipping the dead passes cuts the round cost correspondingly
    (handlers draw RNG and advance counters only where masked, so an
    all-false pass is a no-op by construction and skipping it is exact).

    On a fleet the same contract carries one axis up: the predicate is
    "any host of ANY lane", so a lane with no event of the kind runs the
    pass whenever another lane has one — exactly what happens to host 7
    when only host 3 popped a timer. A handler (or a guarded block below
    one, in tcp/ or apps/) that writes, draws or counts outside its mask
    therefore breaks fleets, lane against solo run, not only speed.
    (Below a pass, ``any_host`` guards the blocks a run leaves behind —
    Tor's bootstrap and build, bitcoin's dial; the per-stream guards of
    tcp/, tgen and filexfer keep the lane's own ``mask.any()``: some lane
    is in them in nearly every round, and a conditional inside a running
    pass splits its fused writes of the event planes. PERF.md §6, PR 38.)
    ``fires_*`` counts the lane's own ``present``; ``runs_*`` counts what
    the ``cond`` was handed.

    With more than one kind every guard is a ``conditional``, a wall no
    fusion crosses, so each piece of a pass that pushed an event swept the
    whole event plane on its own. Such a round stages its pushes as
    [H]-vectors (``events.stage_open``: the field is None outside a round)
    and writes them in ONE commit after the last pass, under
    ``phase:push_commit`` and one more guard on ``any_host``. A host runs
    one pass a round and a site pushes once a host, so the sites a pass
    declares (``pass_rows``) bound what it stages; the passes share the
    rows. With one kind (PHOLD) there is no conditional, the pass's pushes
    fuse as they are, and the round pushes directly."""
    items = sorted(handlers.items())
    staged = len(items) > 1
    with jax.named_scope("phase:pop"):
        evbuf, ev = pop_until(st.evbuf, win_end)
        if staged:
            # A pop frees its slot: counted on the plane the pop reads.
            evbuf = stage_open(
                evbuf,
                max([int(ctx.has_cpu)] + [rows_of(fn, ctx) for _, fn in items]),
                free_slots(st.evbuf) + ev.mask.astype(jnp.int32))
    st = st._replace(evbuf=evbuf)
    m = st.metrics
    n_down = jnp.zeros((), jnp.int64)
    if ctx.has_stop:
        from shadow1_tpu.fault.plane import hosts_down_at

        supp = ev.mask & hosts_down_at(ctx.fault_down, ctx.fault_up, ev.time)
        n_down = supp.sum(dtype=jnp.int64)
        ev = ev._replace(mask=ev.mask & ~supp,
                         kind=jnp.where(supp, 0, ev.kind))
    if ctx.has_cpu:
        eff = jnp.maximum(ev.time, st.cpu_busy)
        defer = ev.mask & (eff >= win_end)
        run = ev.mask & ~defer
        if staged:
            # A deferred host runs no pass: its one row is any pass's first.
            st.evbuf.stage.sites.enter("cpu_defer", 1)
        evbuf, over = push_back(
            st.evbuf, defer, eff, ev.tb, ev.kind, ev.p
        )
        if staged:
            evbuf.stage.sites.leave()
        st = st._replace(
            evbuf=evbuf,
            cpu_busy=jnp.where(run, eff + ctx.cpu_cost, st.cpu_busy),
        )
        m = m._replace(ev_overflow=m.ev_overflow + over.sum(dtype=jnp.int64))
        ev = ev._replace(mask=run, time=jnp.where(run, eff, ev.time),
                         kind=jnp.where(defer, 0, ev.kind))
    pops = {
        f[0]: getattr(m, f[0]) + (ev.mask & (ev.kind == k)).sum(dtype=jnp.int64)
        for k, f in KIND_METRIC_FIELDS.items() if k in handlers
    }
    st = st._replace(
        metrics=m._replace(
            events=m.events + ev.mask.sum(dtype=jnp.int64),
            rounds=m.rounds + 1,
            down_events=m.down_events + n_down,
            **pops,
        ),
    )
    for kind, fn in items:
        scope = f"phase:h_{KIND_NAMES.get(kind, kind)}"
        if not staged:
            with jax.named_scope(scope):
                st = fn(st, ev)
        else:
            fn = _counted_pass(fn, scope[len("phase:"):], rows_of(fn, ctx))
            present = (ev.mask & (ev.kind == kind)).any()
            runs = any_lane(ctx, present)
            if kind in KIND_METRIC_FIELDS:
                _, f_fires, f_runs = KIND_METRIC_FIELDS[kind]
                m2 = st.metrics
                st = st._replace(metrics=m2._replace(**{
                    f_fires: getattr(m2, f_fires) + present.astype(jnp.int64),
                    f_runs: getattr(m2, f_runs) + runs.astype(jnp.int64),
                }))
            with jax.named_scope(scope):
                st = jax.lax.cond(runs, lane_branch(ctx, fn),
                                  lambda s, _e: s, st, ev)
    if staged:
        with jax.named_scope("phase:push_commit"):
            st = _commit_pushes(st, ctx)
    return st


def _counted_pass(fn, name: str, rows: int):
    """``fn`` with its push sites counted against the ``rows`` it declares."""
    def run(st, ev):
        sites = st.evbuf.stage.sites
        sites.enter(name, rows)
        st = fn(st, ev)
        sites.leave()
        return st

    return run


def _commit_pushes(st: SimState, ctx: Ctx) -> SimState:
    """The round's one write of its staged events (``events.push_commit``)
    and the stage's end: one more guard on ``any_host``, so a round in which
    no host of any lane pushed (two in three of a Tor lane's) ranks no slot
    and sweeps no plane."""
    def commit(evbuf):
        return push_commit(evbuf, lambda hit: any_lane(ctx, hit))

    def skip(evbuf):
        none = jnp.zeros((), jnp.int32)
        return evbuf._replace(stage=None), none, none

    evbuf, trips, n_max = jax.lax.cond(
        any_host(ctx, st.evbuf.stage.cnt > 0), lane_branch(ctx, commit), skip,
        st.evbuf)
    m = st.metrics
    return st._replace(evbuf=evbuf, metrics=m._replace(
        push_commit_trips=m.push_commit_trips + trips.astype(jnp.int64),
        push_stage_max=jnp.maximum(m.push_stage_max,
                                   n_max.astype(jnp.int64))))


def route_outbox(ctx: Ctx, ob: Outbox, links=None, win_start=None):
    """Route this block's outbox: path latency + fault gates + loss draws.

    The tensor analogue of the reference's topology path lookup at send time
    (src/main/routing/topology.c getLatency/getReliability, SURVEY §3.3),
    plus the fault plane's source-side gates (docs/SEMANTICS.md §"Fault
    plane"), in canonical order: a packet departing inside a link-outage
    window is dropped deterministically (counted ``link_down_pkts``, never
    in ``pkts_lost``); otherwise the Bernoulli loss draw applies at the
    path's threshold — replaced by an active timed loss ramp's, same coin
    bits either way. Returns (flat_packets, n_sent, n_lost, n_linkdown).

    Every outbox row needs ``table[vs, vd]`` of each path table, and no
    read has an index per row where the network lets it be arithmetic: a
    gather over the rows is an element-serial fusion on the TPU, paid for
    every slot, filled or not (PERF.md §6, PR 34 and PR 42). On a one-vertex
    network (static table shape [1, 1]: every ``configs/`` file but the
    GraphML ones) each read is the table's single element broadcast over the
    rows and ``host_vertex`` is not looked at. With more vertices ``vs`` is
    the block's slice of ``host_vertex`` broadcast down the slot axis
    (``src`` is ``ctx.hosts`` in every slot, a contiguous range of ids);
    ``vd`` is ``vertex_of(dst)``: compares against the runs of
    ``host_vertex`` where they are few, the lookup where they are many
    (``vertex: spread``); and a path table is read in two steps, the
    table's row per host and a pick over ``vd`` per slot, the table's own
    integers bit for bit: both steps selects up to MAX_DENSE_VERTICES
    vertices, then up to MAX_ROW_VERTICES the per-host step one read of H
    table rows (``core/dense.table_rows``) and the pick a masked sum
    (``core/dense.pick_row``) — only a larger table by the lookup
    ``table[vs, vd]``. The rows are transients of the window end, [V, H] a
    half word, never a leaf of the state or a constant of the program.
    Which form is traced depends on the map and the tables' shape alone,
    the same on every engine.

    With the link plane on (``links`` a LinkAccum, ``win_start`` the window
    start), every offered packet's edge contribution — counts, wire bytes,
    drop partition, NIC queueing ns (depart − win_start) — is scatter-added
    here, at the routing attribution point, and the updated accumulator is
    returned as a fifth element (docs/SEMANTICS.md §"Link telemetry
    contract")."""
    cap, h = ob.dst.shape
    mask = jnp.arange(cap)[:, None] < ob.cnt[None, :]
    src = jnp.broadcast_to(ctx.hosts[None, :], (cap, h))

    def flat(x):
        return x.reshape(x.shape[:-2] + (cap * h,))

    fmask, fsrc, fdst = flat(mask), flat(src), flat(ob.dst)
    fdst_safe = jnp.where(fmask, fdst, 0)
    # The i32 outbox planes widen once here, at window granularity
    # (core/outbox.py layout note); fctr is exact below 2**31 pkts/host.
    fdep = flat(ob.abs_depart())
    fctr = flat(ob.ctr).astype(jnp.int64)
    if ctx.lat_vv.shape == (1, 1):
        vs = vd = jnp.zeros_like(fdst_safe)

        def vv(table):
            # Inside the fleet's vmap a per-lane table is [1, 1] too.
            return jnp.broadcast_to(table.reshape(()), fmask.shape)
    else:
        # The two kinds of read a V > 1 network pays per outbox row, each
        # under a scope of its own so that a trace prices them apart
        # (docs/OBSERVABILITY.md "Phase spans, device traces ..."), and
        # neither with an index per row where the bounds above allow: the
        # block's hosts are a contiguous range of ids, and every slot of a
        # column has the column's source.
        n_v = ctx.lat_vv.shape[0]
        with jax.named_scope("phase:route_vertex"):
            vs_h = ctx.host_vertex   # of the whole range, or of this block
            if h != ctx.n_total:
                vs_h = jax.lax.dynamic_slice_in_dim(vs_h, ctx.hosts[0], h)
            vs = flat(jnp.broadcast_to(vs_h[None, :], (cap, h)))
            vd_ch = vertex_of(ctx, jnp.where(mask, ob.dst, 0))
            vd = flat(vd_ch)

        def vv(table):
            with jax.named_scope("phase:route_path"):
                if n_v > MAX_ROW_VERTICES:
                    return table[vs, vd]
                if n_v > MAX_DENSE_VERTICES:
                    # The two steps below with no equation per vertex pair.
                    lo, hi = pick_row(table_rows(table, vs_h), vd_ch)
                    return flat((hi.astype(jnp.uint64) << jnp.uint64(32)
                                 | lo.astype(jnp.uint64)).astype(table.dtype))
                # table[vs, vd], the table's own integers: per host the row
                # table[vs_h, :] by a select over the source vertex (a
                # constant for a closed-over table, [H] a lane for the
                # fleet's thresholds), then per slot a select over vd, the
                # row broadcast down the slot axis; flattened once.
                def col(j):
                    c = table[0, j]
                    for i in range(1, n_v):
                        c = jnp.where(vs_h == i, table[i, j], c)
                    return c

                out = jnp.broadcast_to(col(0)[None, :], (cap, h))
                for j in range(1, n_v):
                    c = col(j)
                    out = jnp.where(vd_ch == j, c[None, :], out)
                return flat(out)

    arrival = fdep + vv(ctx.lat_vv)
    if ctx.has_jitter:
        # Per-packet edge jitter in [-J, +J] (reference: topology edge
        # jitter attribute); J < lat so the conservative window holds.
        jit = vv(ctx.jitter_vv)
        jbits = rng.bits_v(ctx.key, R_JITTER, fsrc, fctr)
        arrival = arrival + rng.randint(jbits, 2 * jit + 1).astype(jnp.int64) - jit
    linkdown = jnp.zeros_like(fmask)
    if ctx.has_link_fault:
        from shadow1_tpu.fault.plane import link_down_mask

        linkdown = fmask & link_down_mask(ctx.link_fault, vs, vd, fdep)
    thr = vv(ctx.loss_thr_vv)
    if ctx.has_loss_ramp:
        from shadow1_tpu.fault.plane import ramp_loss_thr

        thr = ramp_loss_thr(ctx.loss_ramp, vs, vd, fdep, thr)
    bits = rng.bits_v(ctx.key, R_LOSS, fsrc, fctr)
    # Integer Bernoulli on precomputed thresholds (rng.prob_threshold) —
    # shared with the CPU oracle, backend-exact by construction.
    lost = fmask & ~linkdown & rng.uniform_lt(bits, thr)
    keep = fmask & ~lost & ~linkdown
    tb = packet_tb(fsrc.astype(jnp.int64), fctr)
    fp = FlatPackets(
        dst=fdst_safe, arrival=arrival, tb=tb, kind=flat(ob.kind), p=flat(ob.p),
        keep=keep,
    )
    out = (fp, fmask.sum(dtype=jnp.int64), lost.sum(dtype=jnp.int64),
           linkdown.sum(dtype=jnp.int64))
    if links is None:
        return out
    from shadow1_tpu.consts import WIRE_OVERHEAD
    from shadow1_tpu.telemetry.links import link_route_accum

    links = link_route_accum(
        links, vs, vd, fmask, lost, linkdown,
        queued=fdep - win_start,
        wire=flat(ob.p)[4].astype(jnp.int64) + WIRE_OVERHEAD,
    )
    return out + (links,)


def deliver_flat(evbuf, ctx: Ctx, fp: FlatPackets):
    """Scatter (possibly gathered) packets into this block's event buffers.

    Maps global dst ids onto the local block (contiguous range starting at
    ctx.hosts[0]); packets for other blocks are masked out; packets whose
    arrival falls inside a down interval of the destination are dropped
    here (churn — counted, never delivered, so a dead host's buffers stay
    clean). Returns (evbuf, n_delivered, n_overflow, n_down, n_ranks)
    counting only this block's packets; n_ranks is deliver_batch's."""
    base = ctx.hosts[0].astype(fp.dst.dtype)
    local = fp.dst - base
    mine = fp.keep & (local >= 0) & (local < ctx.n_hosts)
    local = jnp.where(mine, local, 0)
    n_down = jnp.zeros((), jnp.int64)
    if ctx.has_stop:
        from shadow1_tpu.fault.plane import hosts_down_at_idx

        to_down = mine & hosts_down_at_idx(
            ctx.fault_down, ctx.fault_up, local, fp.arrival
        )
        n_down = to_down.sum(dtype=jnp.int64)
        mine = mine & ~to_down
    evbuf, n_over, n_ranks = deliver_batch(
        evbuf, local, fp.arrival, fp.tb, fp.kind, fp.p, mine
    )
    return evbuf, mine.sum(dtype=jnp.int64) - n_over, n_over, n_down, n_ranks


def deliver_window(st: SimState, ctx: Ctx, exchange=None) -> SimState:
    """Window-end packet exchange: route, (all_to_all under sharding), scatter.

    ``exchange`` maps FlatPackets → (FlatPackets, n_dropped, fill_high_water)
    across the mesh (identity on a single device; a bucketed all_to_all over
    the host axis when sharded — the one collective per window, SURVEY §2.5).

    Without an ``exchange`` the window end is one more guard on ``any_host``:
    it runs only when some host (of some lane, on a fleet) sent this window.
    With every ``outbox.cnt`` 0 it is the identity on the state — no row is
    routed or drawn for (the RNG is counter-based, so a draw not made changes
    no later one), the fill loop makes no trip, the clear writes the zeros
    ``cnt`` holds, every counter gets ``+ 0`` or ``max(·, 0)`` — so skipping
    it is exact, and an empty window costs a test of a maintained ``[H]``
    counter, not a sort of the outbox's capacity (PERF.md §6, PR 40). A lane
    that sent nothing while another did runs it, as that identity
    (``run_round``'s contract, one axis up). ``runs_window_end`` counts the
    windows the program ran it, ``route_rows`` the outbox rows it looked up
    in them (every row of the block, filled or not).

    The sharded engine keeps its window end unguarded: ``exchange`` is a
    collective that every shard must enter, and a predicate that differs by
    shard would hang it (reducing the predicate over the mesh axis as well is
    the follow-up for whoever times that engine)."""
    if exchange is not None:
        runs = jnp.ones((), bool)
        st = _window_end(st, ctx, exchange)
    else:
        runs = any_host(ctx, st.outbox.cnt > 0)
        st = jax.lax.cond(runs,
                          lane_branch(ctx, lambda s: _window_end(s, ctx)),
                          lambda s: s, st)
    m = st.metrics
    ran = runs.astype(jnp.int64)
    cap, h = st.outbox.dst.shape
    return st._replace(metrics=m._replace(
        runs_window_end=m.runs_window_end + ran,
        route_rows=m.route_rows + ran * (cap * h)))


def _window_end(st: SimState, ctx: Ctx, exchange=None) -> SimState:
    """``deliver_window``'s taken branch: route the outbox, exchange, merge
    into the event buffers, clear, count."""
    from shadow1_tpu.core.outbox import outbox_fill

    with jax.named_scope("phase:route"):
        links = st.links
        if links is not None:
            fp, n_sent, n_lost, n_linkdown, links = route_outbox(
                ctx, st.outbox, links=links, win_start=st.win_start)
        else:
            fp, n_sent, n_lost, n_linkdown = route_outbox(ctx, st.outbox)
    # Maintained [H] counters — read before the window-end clear. ob_hosts
    # is the wasted-work gauge's numerator: hosts that actually used the
    # [P, H] outbox planes this window (the oracle mirrors per-window send
    # sets exactly, so this is parity-exact).
    ob_fill = outbox_fill(st.outbox)
    ob_hosts = (st.outbox.cnt > 0).sum(dtype=jnp.int64)
    n_x2x = x2x_hw = jnp.zeros((), jnp.int64)
    if exchange is not None:
        with jax.named_scope("phase:exchange"):
            fp, n_x2x, x2x_hw = exchange(fp)
    with jax.named_scope("phase:deliver"):
        evbuf, n_deliv, n_over, n_down, n_ranks = deliver_flat(
            st.evbuf, ctx, fp)
    m = st.metrics
    return st._replace(
        evbuf=evbuf,
        outbox=outbox_clear(st.outbox),
        links=links,
        metrics=m._replace(
            pkts_sent=m.pkts_sent + n_sent,
            pkts_delivered=m.pkts_delivered + n_deliv,
            pkts_lost=m.pkts_lost + n_lost,
            ev_overflow=m.ev_overflow + n_over,
            x2x_overflow=m.x2x_overflow + n_x2x,
            x2x_max_fill=jnp.maximum(m.x2x_max_fill, x2x_hw),
            ob_max_fill=jnp.maximum(m.ob_max_fill, ob_fill),
            down_pkts=m.down_pkts + n_down,
            link_down_pkts=m.link_down_pkts + n_linkdown,
            outbox_hosts=m.outbox_hosts + ob_hosts,
            deliver_ranks=m.deliver_ranks + n_ranks,
        ),
    )


def run_rounds(st: SimState, ctx: Ctx, handlers: dict, win_end):
    """The inner round loop to quiescence (or the safety cap).

    Returns (st, cap_hit). Traced once: at full width, or at bucket width
    by the compacted path (core/compact.py) where a cap is set. On a fleet the
    loop's own predicate is reduced over the lanes like every guard's
    (``any_lane``), and a lane whose own loop has ended rides the remaining
    iterations as the identity: its ``rounds``, ``r`` and ``cap_hit`` are
    what its solo run's are."""
    max_rounds = ctx.params.max_rounds

    def live(carry):
        s, r = carry
        return (r < max_rounds) & any_eligible(s.evbuf, win_end)

    def body(carry):
        s, r = carry
        if ctx.lane_axis is None:
            return run_round(s, ctx, handlers, win_end), r + 1
        # A fleet's loop is one loop: it runs while ANY lane's own would
        # (its predicate is one more guard's). vmap would freeze a lane
        # whose loop has ended by selecting every leaf of the carry, every
        # iteration, old against new, which keeps both whole; here the round
        # itself is the identity for such a lane: it runs to an end that no
        # event is before (past-due ones of a capped window included), so
        # nothing pops and every mask in run_round is false.
        on = live(carry)
        s2 = run_round(s, ctx, handlers, jnp.where(on, win_end, NEVER))
        return (s2._replace(metrics=s2.metrics._replace(
            rounds=s.metrics.rounds + on.astype(jnp.int64))),
            r + on.astype(r.dtype))

    st, r = jax.lax.while_loop(lambda c: any_lane(ctx, live(c)), body,
                               (st, jnp.zeros((), jnp.int32)))
    return st, (r >= max_rounds) & any_eligible(st.evbuf, win_end)


class WindowFrame(NamedTuple):
    """The intra-window carry threaded through the ``window_phases`` stages.

    ``window_step`` is the composition of the stage list over this frame;
    ``tools/phaseprobe.py`` jits the stages INDIVIDUALLY (on frames captured
    from a real run) so wall time attributes per phase instead of per
    window. Every leaf is a jittable array, so a frame crosses a jit
    boundary unchanged."""

    st: SimState
    m_entry: Metrics        # metrics at window entry (ring delta baseline)
    win_end: jnp.ndarray    # i64 scalar
    cap_hit: jnp.ndarray    # bool scalar (set by the rounds phase)
    dg_ob: jnp.ndarray      # i64 outbox digest word (digest runs only)
    l_entry: Any = None     # link accumulator at window entry (the sharded
                            # per-window psum's delta baseline; link runs only)


def window_frame(st: SimState, ctx: Ctx) -> WindowFrame:
    """The entry frame of one conservative window."""
    return WindowFrame(
        st=st,
        m_entry=st.metrics,
        win_end=st.win_start + ctx.window,
        cap_hit=jnp.zeros((), bool),
        dg_ob=jnp.zeros((), jnp.int64),
        l_entry=st.links,
    )


def window_phases(ctx: Ctx, handlers: dict, exchange=None, pre_window=None,
                  make_handlers=None, telem_reduce=None, probe_reduce=None,
                  link_reduce=None):
    """The ordered (name, frame → frame) stage list of one window.

    The phase decomposition of the jitted ``window_step`` (performance
    attribution plane): ``prepare`` (restart resets, work gauges, the net
    model's NIC arrival batch, rebase/clear), ``rounds`` (the pop + handler
    while-loop — sub-annotated ``phase:pop`` / ``phase:h_<kind>`` in
    run_round), ``deliver`` (route + optional exchange collective + the
    destination scatter + outbox clear — sub-annotated in deliver_window),
    ``telem`` (occupancy gauges, window counters, the telemetry-ring row).
    Each stage is wrapped in ``jax.named_scope("phase:<name>")`` by
    window_step: the scopes reach every instruction of the compiled program
    (telemetry/phases.py joins a device trace's ops to them), and
    ``tools/opcensus.py`` censuses each stage's jaxpr separately."""
    from shadow1_tpu.core.events import rebase

    digest_on = bool(ctx.params.state_digest)

    def ph_prepare(fr: WindowFrame) -> WindowFrame:
        st, win_end = fr.st, fr.win_end
        if ctx.has_restart:
            # Host restart (fault plane): hosts whose window-quantized up
            # time IS this window's start get their model columns (tcp
            # socks, nic clocks/counters, app state) restored to the
            # post-init capture and their virtual-CPU clock zeroed — BEFORE
            # this window's rounds, so events timed at/after the restart
            # execute against fresh state. The event buffer is deliberately
            # untouched: stale events are a pure function of time
            # (dead-interval ones discard at pop), so the oracle's eager
            # heap and this batched reset stay bit-equal.
            from shadow1_tpu.fault.plane import (
                reset_host_columns,
                restart_mask,
            )

            rs = restart_mask(ctx.fault_up, st.win_start)
            mr = st.metrics
            st = st._replace(
                model=reset_host_columns(st.model, ctx.init_model, rs,
                                         ctx.n_hosts),
                cpu_busy=jnp.where(rs, 0, st.cpu_busy),
                metrics=mr._replace(
                    host_restarts=mr.host_restarts + rs.sum(dtype=jnp.int64)),
            )
        n_act = n_el = None
        if pre_window is not None:
            # Wasted-work gauges BEFORE the NIC arrival batch rewrites
            # event times: the RAW window-start pending set (events with
            # time < win_end) is the engine-independent quantity the CPU
            # oracle mirrors from its heap at the same boundary — the
            # post-conversion eligibility (queue-cleared times) is not.
            abs_t = st.evbuf.abs_time()
            live = (st.evbuf.kind != K_NONE) & (abs_t < win_end)
            n_act = live.any(axis=0).sum(dtype=jnp.int64)
            n_el = live.sum(dtype=jnp.int64)
            st = pre_window(st, ctx, win_end)
        # Advance the i32 pop-key epoch to this window's start
        # (core/events.py: the round loop runs i64-free; pre_window and
        # last window's delivery write absolute times only, repaired here).
        st = st._replace(evbuf=rebase(st.evbuf, st.win_start, win_end))
        # Compaction-bucket demand gauge: this window's active-host count
        # (the lanes compact_cap must cover), read off the just-rebased [H]
        # eligibility counters — recorded whether or not compaction is on,
        # so the knob can be sized BEFORE enabling it, and the compacted
        # and plain engines stay bit-identical (tests/test_compact.py).
        # Local-block count under sharding (the per-shard bucket is the
        # resource), like rounds.
        n_active = (st.evbuf.n_elig > 0).sum(dtype=jnp.int64)
        if n_act is None:
            # No pre-window time rewrite: the just-rebased eligibility
            # counters ARE the raw window-start set — the work gauges cost
            # zero extra plane passes on this path.
            n_act = n_active
            n_el = st.evbuf.n_elig.sum(dtype=jnp.int64)
        m0 = st.metrics
        st = st._replace(metrics=m0._replace(
            compact_max_fill=jnp.maximum(m0.compact_max_fill, n_active),
            active_hosts=m0.active_hosts + n_act,
            elig_events=m0.elig_events + n_el,
        ))
        return fr._replace(st=st)

    def ph_rounds(fr: WindowFrame) -> WindowFrame:
        st = fr.st
        ccap = compact_cap_of(ctx.params, ctx.n_hosts)
        if ccap and make_handlers is not None:
            from shadow1_tpu.core.compact import compact_window_rounds

            st, cap_hit = compact_window_rounds(
                st, ctx, make_handlers, fr.win_end, ccap)
        else:
            st, cap_hit = run_rounds(st, ctx, handlers, fr.win_end)
        return fr._replace(st=st, cap_hit=cap_hit)

    def ph_deliver(fr: WindowFrame) -> WindowFrame:
        st, dg_ob = fr.st, fr.dg_ob
        if digest_on and st.telem is not None:
            # The outbox still holds this window's sends here — the
            # delivery below routes and clears it, so its digest word is
            # taken first.
            from shadow1_tpu.core.digest import digest_outbox

            dg_ob = digest_outbox(st.outbox, ctx.hosts)
        st = deliver_window(st, ctx, exchange)
        return fr._replace(st=st, dg_ob=dg_ob)

    def ph_telem(fr: WindowFrame) -> WindowFrame:
        # Window-end event-slot occupancy: computed ONCE here (one [C, H]
        # pass per window, off the round path) and shared by the run-max
        # gauge and the telemetry ring's per-window column.
        from shadow1_tpu.core.events import evbuf_fill

        st = fr.st
        ev_fill = evbuf_fill(st.evbuf)
        m = st.metrics
        tcp = getattr(st.model, "tcp", None)
        if tcp is not None:
            # The boundary pool's fill, sampled with the event slots' (the
            # pending boundaries at a window's end are engine-independent
            # as the pending events are: the oracle mirrors the gauge).
            from shadow1_tpu.tcp.tcp import mq_fill

            m = m._replace(mq_max_fill=jnp.maximum(m.mq_max_fill,
                                                   mq_fill(tcp)))
        st = st._replace(
            win_start=fr.win_end,
            metrics=m._replace(
                windows=m.windows + 1,
                round_cap_hits=m.round_cap_hits
                + fr.cap_hit.astype(jnp.int64),
                ev_max_fill=jnp.maximum(m.ev_max_fill, ev_fill),
            ),
        )
        if st.telem is not None:
            from shadow1_tpu.telemetry.ring import ring_record

            digests = None
            if digest_on:
                # Everything but the outbox digests the post-delivery
                # window-boundary state — exactly the pending/live sets the
                # CPU oracle sees when its next event crosses this boundary.
                from shadow1_tpu.core.digest import state_digests

                digests = state_digests(st, ctx, fr.dg_ob)
            st = st._replace(telem=ring_record(
                st.telem, fr.m_entry, st.metrics, ev_fill, telem_reduce,
                digests=digests,
            ))
        if st.probes is not None:
            # Flow-probe samples: the same post-delivery window-boundary
            # state the digests hash, gathered per watched entity
            # (telemetry/probes.py). ``fr.win_end`` anchors the NIC backlog
            # columns; the window-entry metrics pick the ring slot.
            # ``probe_reduce`` psums the owned-shard one-hot rows under
            # sharding; identity elsewhere.
            from shadow1_tpu.telemetry.probes import probe_record, probe_sample

            row = probe_sample(st, ctx, fr.win_end, ctx.params.probes)
            if probe_reduce is not None:
                row = probe_reduce(row)
            st = st._replace(probes=probe_record(st.probes, fr.m_entry, row))
        if st.links is not None and link_reduce is not None:
            # Link-accumulator globalization (sharded runs only): psum this
            # window's per-shard counter deltas onto the entry baseline and
            # pmax the high-water column, so every shard carries the exact
            # single-device tensor at the boundary (shard/engine.py
            # link_reduce; identity is link_reduce=None elsewhere — zero
            # per-window overhead off the sharded path).
            st = st._replace(links=link_reduce(fr.l_entry, st.links))
        return fr._replace(st=st)

    return [("prepare", ph_prepare), ("rounds", ph_rounds),
            ("deliver", ph_deliver), ("telem", ph_telem)]


def window_step(st: SimState, ctx: Ctx, handlers: dict, exchange=None,
                pre_window=None, make_handlers=None,
                telem_reduce=None, probe_reduce=None,
                link_reduce=None) -> SimState:
    """One conservative window: inner rounds to quiescence, then delivery.

    The batched form of the reference's barrier round
    (scheduler_continueNextRound in src/main/core/scheduler/scheduler.c):
    the while_loop plays the worker event loop, the delivery plays the
    cross-thread event push that the barrier makes safe.

    ``pre_window(st, ctx, win_end)`` is an optional model hook that runs
    before the rounds — the net model uses it to batch-process every NIC
    arrival of the window in one scan instead of one round per packet.

    When ``params.compact_cap`` is set (and ``make_handlers`` provided),
    a window's rounds run on its active hosts only, a bucket of that many
    columns a trip (core/compact.py) — bit-identical results, narrow
    tensors, and no full-width copy of the round program.

    When the state carries a telemetry ring (``st.telem``), the window's
    metric deltas are recorded into it here, still inside the trace —
    ``telem_reduce`` globalizes the row under sharding (telemetry/ring.py).

    Structured as the composition of the ``window_phases`` stage list, each
    under a ``jax.named_scope("phase:<name>")`` — the performance
    attribution plane's decomposition (tools/phaseprobe.py times the stages
    individually; tools/opcensus.py censuses their jaxprs; telemetry/
    phases.py reads them off the compiled program for a device trace)."""
    fr = window_frame(st, ctx)
    for name, fn in window_phases(ctx, handlers, exchange, pre_window,
                                  make_handlers, telem_reduce, probe_reduce,
                                  link_reduce):
        with jax.named_scope(f"phase:{name}"):
            fr = fn(fr)
    return fr.st


_QLEN_INF = 1 << 62


def qlen_ns_np(qlen_bytes: np.ndarray, bw_bits: np.ndarray) -> np.ndarray:
    """NIC queue bound in serialization-time ns (0 bytes = unbounded)."""
    from shadow1_tpu.consts import SEC

    q = np.asarray(qlen_bytes, np.int64)
    bw = np.asarray(bw_bits, np.int64)
    return np.where(q > 0, (q * 8 * SEC + bw - 1) // bw, _QLEN_INF)


def aqm_tables_np(exp) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """RED per-host tables (min_ns, span_ns, pmax_thr) — computed ONCE here,
    in numpy, and consumed by both engines so their drop decisions compare
    identical integers. Thresholds convert bytes → uplink backlog-time ns
    (same ceil math as the drop-tail bound, but 0 bytes means 0 ns here, not
    "unbounded"); span is clamped ≥1 so the traced division is always safe;
    pmax_thr is 0 wherever AQM is off."""
    from shadow1_tpu.consts import SEC

    bw = np.asarray(exp.bw_up, np.int64)

    def to_ns(b):
        return (np.asarray(b, np.int64) * 8 * SEC + bw - 1) // bw

    min_ns = to_ns(exp.aqm_min_bytes)
    max_ns = to_ns(exp.aqm_max_bytes)
    on = np.asarray(exp.aqm_max_bytes) > 0
    min_ns = np.where(on, min_ns, 0)
    span_ns = np.maximum(np.where(on, max_ns - min_ns, 1), 1)
    from shadow1_tpu import rng

    pmax_thr = np.where(on, rng.prob_threshold(exp.aqm_pmax), np.uint64(0))
    return min_ns.astype(np.int64), span_ns.astype(np.int64), pmax_thr


def ser_tables_np(exp):
    """(ns per wire byte up, down), i64 [H] each, when 8e9 is a whole
    multiple of every host's uplink AND downlink bits/s; else (None, None)
    and serialization keeps its per-packet division (net/nic.ser_delay)."""
    from shadow1_tpu.consts import SEC

    bw = [np.asarray(b, np.int64) for b in (exp.bw_up, exp.bw_dn)]
    if any(((8 * SEC) % b).any() for b in bw):
        return None, None
    return tuple((8 * SEC) // b for b in bw)


def fidelity_ctx_kwargs(exp) -> dict:
    """The Ctx fidelity fields + static has_* flags from a CompiledExperiment
    (shared by Engine and ShardedEngine; everything numpy → device const).
    The fault plane compiles here too: host down/up intervals (legacy
    stop_time merged in), link-outage and loss-ramp tables — one builder
    (fault/schedule.py) shared with the CPU oracle."""
    from shadow1_tpu.config.compiled import NO_STOP
    from shadow1_tpu.fault.schedule import (
        host_interval_tensors,
        link_tables,
        ramp_tables,
    )

    aqm_min_ns, aqm_span_ns, aqm_pmax_thr = aqm_tables_np(exp)
    ser_up, ser_dn = ser_tables_np(exp)
    fault_down, fault_up = host_interval_tensors(exp)
    lf = link_tables(exp)
    rt = ramp_tables(exp)
    return dict(
        jitter_vv=jnp.asarray(exp.jitter_vv, jnp.int64),
        fault_down=jnp.asarray(fault_down),
        fault_up=jnp.asarray(fault_up),
        link_fault=(tuple(jnp.asarray(a) for a in lf)
                    if lf is not None else None),
        loss_ramp=(tuple(jnp.asarray(a) for a in rt)
                   if rt is not None else None),
        cpu_cost=jnp.asarray(exp.cpu_ns_per_event, jnp.int64),
        # Host-side (numpy): a model without a NIC never touches them, and
        # then they take no device memory (phold65k: 2 x 512 KiB).
        ser_up=ser_up,
        ser_dn=ser_dn,
        tx_qlen_ns=jnp.asarray(qlen_ns_np(exp.tx_qlen_bytes, exp.bw_up)),
        rx_qlen_ns=jnp.asarray(qlen_ns_np(exp.rx_qlen_bytes, exp.bw_dn)),
        aqm_min_ns=jnp.asarray(aqm_min_ns),
        aqm_span_ns=jnp.asarray(aqm_span_ns),
        aqm_pmax_thr=jnp.asarray(aqm_pmax_thr),
        has_jitter=bool(exp.jitter_vv.max() > 0),
        has_stop=bool(fault_down.min() < NO_STOP),
        has_restart=bool((fault_up < NO_STOP).any()),
        has_link_fault=lf is not None,
        has_loss_ramp=rt is not None,
        has_cpu=bool(exp.cpu_ns_per_event.max() > 0),
        has_tx_qlen=bool(exp.tx_qlen_bytes.max() > 0),
        has_rx_qlen=bool(exp.rx_qlen_bytes.max() > 0),
        has_aqm=bool(np.asarray(exp.aqm_max_bytes).max() > 0),
    )


def build_base_ctx(exp: CompiledExperiment, params: EngineParams,
                   window: int | None = None) -> Ctx:
    """The single-device Ctx for a CompiledExperiment — topology constants,
    fidelity tables, fault plane, per-experiment RNG key. Shared by Engine
    below and the batched-experiment FleetEngine (shadow1_tpu/fleet/),
    which swaps the per-experiment leaves (key, loss thresholds, fault
    tables) per vmapped lane."""
    return Ctx(
        n_hosts=exp.n_hosts,
        n_total=exp.n_hosts,
        params=params,
        window=window if window is not None else exp.window,
        key=rng.base_key(exp.seed),
        lat_vv=jnp.asarray(exp.lat_vv, jnp.int64),
        loss_vv=jnp.asarray(exp.loss_vv, jnp.float32),
        host_vertex=jnp.asarray(exp.host_vertex, jnp.int32),
        bw_up=jnp.asarray(exp.bw_up, jnp.int64),
        bw_dn=jnp.asarray(exp.bw_dn, jnp.int64),
        model_cfg=exp.model_cfg,
        **fidelity_ctx_kwargs(exp),
    )


def check_digest_params(params: EngineParams) -> None:
    """state_digest needs a telemetry ring to carry the words on the
    batched engines (the CPU oracle keeps its own rows and has no ring)."""
    if params.state_digest and params.metrics_ring <= 0:
        raise ValueError(
            "state_digest=1 requires metrics_ring > 0 on the batched "
            "engines — the per-window digest words are ring columns "
            "(CLI --state-digest sets a ring automatically)"
        )


def check_probe_params(params: EngineParams) -> None:
    """The probe ring reuses the telemetry ring's depth knob: watched
    flows need metrics_ring > 0 on the batched engines (the CPU oracle
    keeps its own probe_rows and has no ring)."""
    if params.probes and params.metrics_ring <= 0:
        raise ValueError(
            "probes require metrics_ring > 0 on the batched engines — "
            "the [W, K, F] probe ring depth is the metrics_ring window "
            "count (CLI --watch sets a ring automatically)"
        )


def _model_module(name: str):
    if name == "phold":
        from shadow1_tpu.core import phold

        return phold
    if name == "net":
        from shadow1_tpu import net

        return net
    raise ValueError(f"unknown model {name!r}")


class Engine:
    """Batched engine for one CompiledExperiment.

    The model module supplies ``init(ctx) -> (model_state, evbuf)`` (initial
    events seeded) and ``make_handlers(ctx) -> dict[kind, Handler]``.
    """

    def __init__(self, exp: CompiledExperiment, params: EngineParams | None = None):
        exp.validate()
        self.exp = exp
        self.params = params or EngineParams()
        check_digest_params(self.params)
        check_probe_params(self.params)
        from shadow1_tpu.telemetry.links import check_link_params

        check_link_params(self.params, np.asarray(exp.lat_vv).shape[0])
        self.window = exp.window
        self.n_windows = int(-(-exp.end_time // self.window))
        self.ctx = build_base_ctx(exp, self.params, window=self.window)
        self._model = _model_module(exp.model)
        if self.ctx.has_restart:
            # Restart target: the model pytree exactly as init() builds it
            # (tcp listen sockets included), materialized once and closed
            # over as device constants — window_step restores restarted
            # hosts' columns from it (fault/plane.reset_host_columns).
            model0, _, _ = self._model.init(
                self.ctx, evbuf_init(exp.n_hosts, self.params.ev_cap)
            )
            self.ctx = dataclasses.replace(
                self.ctx,
                init_model=jax.tree.map(lambda x: jnp.asarray(np.asarray(x)),
                                        model0),
            )
        self._handlers = self._model.make_handlers(self.ctx)
        self._pre_window = getattr(self._model, "make_pre_window", lambda c: None)(
            self.ctx
        )
        # No donation: the initial state contains aliased zero-buffers (XLA
        # rejects donating one buffer twice) and run() is called once per sim,
        # so the single input copy is negligible. n_windows is a TRACED
        # argument (fori_loop lowers to while_loop): engine round bodies take
        # minutes to compile, and a dynamic bound means one compiled program
        # serves every chunk size / heartbeat / resume window count.
        self._run_jit = jax.jit(self._make_run())

    # -- state ------------------------------------------------------------
    def init_state(self) -> SimState:
        from shadow1_tpu.telemetry.links import link_init
        from shadow1_tpu.telemetry.probes import probe_init
        from shadow1_tpu.telemetry.ring import ring_init

        evbuf = evbuf_init(self.exp.n_hosts, self.params.ev_cap)
        model, evbuf, seed_over = self._model.init(self.ctx, evbuf)
        metrics = _metrics_init()
        return SimState(
            win_start=jnp.zeros((), jnp.int64),
            evbuf=evbuf,
            outbox=outbox_init(self.exp.n_hosts, self.params.outbox_cap),
            model=model,
            metrics=metrics._replace(ev_overflow=metrics.ev_overflow + seed_over),
            cpu_busy=jnp.zeros(self.exp.n_hosts, jnp.int64),
            telem=ring_init(self.params.metrics_ring),
            probes=probe_init(self.params.metrics_ring, self.params.probes),
            links=link_init(self.params.link_telem,
                            np.asarray(self.exp.lat_vv).shape[0]),
            compact_buckets=compact_buckets_init(self.params,
                                                 self.exp.n_hosts),
        )

    def place_state(self, st: SimState) -> SimState:
        """Put a (host-built) state pytree on this engine's devices — the
        hook the cap controller uses after a tune/resize.py migration.
        Single-device: a plain transfer."""
        return jax.device_put(st)

    # -- window step pieces ----------------------------------------------
    def _window_step(self, st: SimState) -> SimState:
        return window_step(st, self.ctx, self._handlers,
                           pre_window=self._pre_window,
                           make_handlers=self._model.make_handlers)

    def _make_run(self):
        def run(st: SimState, n_windows) -> SimState:
            return jax.lax.fori_loop(
                0, n_windows, lambda _, s: self._window_step(s), st
            )

        return run

    # -- public -----------------------------------------------------------
    def run(self, st: SimState | None = None, n_windows: int | None = None) -> SimState:
        if st is None:
            st = self.init_state()
        n = n_windows if n_windows is not None else self.n_windows
        # dispatch ⊃ args, call (telemetry/profiler.py): what the call is
        # handed, made; then the jitted call returning.
        with run_span(PH_ARGS):
            n = jnp.asarray(n, jnp.int32)
        with run_span(PH_CALL):
            return self._run_jit(st, n)

    def hlo_text(self, st: SimState | None = None, n_windows: int = 0) -> str:
        """The optimized HLO text of the one window program ``run`` drives
        (``n_windows`` is a traced argument: any count is the same program),
        for ``telemetry.phases.phase_table``. Tracing consumers only, after
        the run, never on the hot path: it lowers and compiles again (the
        persistent cache serves it where it holds the program)."""
        if st is None:
            st = jax.eval_shape(self.init_state)
        return self._run_jit.lower(
            st, jnp.asarray(n_windows, jnp.int32)).compile().as_text()

    @staticmethod
    def metrics_dict(st: SimState) -> dict[str, int]:
        return {k: int(v) for k, v in st.metrics._asdict().items()}

    def model_summary(self, st: SimState) -> dict[str, Any]:
        return jax.tree.map(np.asarray, self._model.summary(st.model, self.ctx))

    def model_totals(self, st: SimState) -> dict[str, int]:
        """The model summary's run totals (its 0-dim entries), fetched
        without its per-host tables: a heartbeat row's ``model`` block."""
        return {k: int(v) for k, v in
                self._model.summary(st.model, self.ctx).items()
                if jnp.ndim(v) == 0}
