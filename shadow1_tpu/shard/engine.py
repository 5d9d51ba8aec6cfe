"""Multi-device engine: the host axis sharded over a JAX mesh.

The reference scales by partitioning hosts across worker threads
(src/main/core/scheduler/scheduler-policy-host-steal.c et al., SURVEY §2.5);
the TPU-native equivalent shards the host axis of every state tensor over a
``jax.sharding.Mesh`` with ``jax.shard_map``. Inside a window each device
runs its local block's rounds completely independently (the conservative
lookahead guarantees no mid-window cross-host interaction — the same
invariant the reference's barrier rounds rely on); at the window end the
shard buckets its routed packets by destination shard and ONE
``lax.all_to_all`` over the mesh axis delivers every bucket to its owner;
each shard then scatters the packets addressed to its hosts. That single
collective per window is the entire communication schedule — it rides ICI
within a slice and DCN across slices, replacing the reference's locked
cross-thread event push (src/main/utility/async-priority-queue.c).
Exchanged bytes scale with the per-destination bucket capacity
(``EngineParams.x2x_cap``, auto-sized to 2× the uniform-traffic
expectation), NOT with ×n_dev as the earlier all_gather did. Bucket-full
drops are counted in ``x2x_overflow``. When the cap was auto-sized and a
bucket overflows — which the flagship *convergent* workloads (every
client → one server; Tor clients → few relays) can always do, since one
bucket may need the shard's entire outbox — ``run()`` retries the same
run from the same (immutable) input state at the guaranteed-fit cap
``h_local·outbox_cap``, so results are exact and never silently lossy;
an explicitly-set cap that overflows raises instead (the user's knob is
a contract). The retry costs one recompile; pass an explicit cap to
pin the exchange size for perf-critical runs. Caps beyond
``h_local·outbox_cap`` are clamped to it — a bucket physically cannot
hold more than the shard's whole outbox, so larger values only waste
exchange bytes.

Determinism across shardings: within a shard's outbound, the bucket sort is
stable in flat source order and received buckets concatenate in
source-shard order, so each destination sees its packets in shard-major ×
host-major = global host-major order — exactly the single-device flatten
order — and all event/tie-break keys are computed from global host ids, so the
delivered event streams are identical for any device count. The
``rounds``/``round_cap_hits``/``deliver_ranks`` metrics are the one exception
(each shard counts its own inner rounds and its own merge's trips; they are
summed), so they are performance counters, not semantic invariants.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from shadow1_tpu import rng
from shadow1_tpu.config.compiled import CompiledExperiment
from shadow1_tpu.consts import EngineParams
from shadow1_tpu.core.engine import (
    Ctx,
    Engine,
    FlatPackets,
    SimState,
    _metrics_init,
    _model_module,
    compact_buckets_init,
    fidelity_ctx_kwargs,
    window_step,
)
from shadow1_tpu.core.events import _hi, _join, _lo, evbuf_init
from shadow1_tpu.core.outbox import outbox_init
from shadow1_tpu.telemetry.profiler import PH_ARGS, PH_CALL, run_span


class ShardedEngine:
    """Engine running one CompiledExperiment over an n-device host-axis mesh.

    API mirrors core.engine.Engine: init_state() → run() → metrics_dict /
    model_summary. n_hosts must divide evenly by the device count.
    """

    def __init__(
        self,
        exp: CompiledExperiment,
        params: EngineParams | None = None,
        devices=None,
        axis: str = "hosts",
    ):
        exp.validate()
        self.exp = exp
        self.params = params or EngineParams()
        from shadow1_tpu.core.engine import (check_digest_params,
                                             check_probe_params)

        check_digest_params(self.params)
        check_probe_params(self.params)
        from shadow1_tpu.telemetry.links import check_link_params

        check_link_params(self.params, np.asarray(exp.lat_vv).shape[0])
        devices = list(devices if devices is not None else jax.devices())
        self.n_dev = len(devices)
        if exp.n_hosts % self.n_dev:
            raise ValueError(
                f"n_hosts={exp.n_hosts} not divisible by {self.n_dev} devices"
            )
        self.h_local = exp.n_hosts // self.n_dev
        self.axis = axis
        self.mesh = jax.make_mesh((self.n_dev,), (axis,), devices=devices)
        self.window = exp.window
        self.n_windows = int(-(-exp.end_time // self.window))
        # Global-view ctx: used for state init (which runs unsharded) and for
        # model summaries. Semantically identical to the single-device ctx.
        self.global_ctx = Ctx(
            n_hosts=exp.n_hosts,
            n_total=exp.n_hosts,
            params=self.params,
            window=self.window,
            key=rng.base_key(exp.seed),
            lat_vv=jnp.asarray(exp.lat_vv, jnp.int64),
            loss_vv=jnp.asarray(exp.loss_vv, jnp.float32),
            host_vertex=jnp.asarray(exp.host_vertex, jnp.int32),
            bw_up=jnp.asarray(exp.bw_up, jnp.int64),
            bw_dn=jnp.asarray(exp.bw_dn, jnp.int64),
            model_cfg=exp.model_cfg,
            **fidelity_ctx_kwargs(exp),
        )
        self._model = _model_module(exp.model)
        # Restart target for the fault plane (mirrors Engine.__init__): the
        # post-init model pytree, kept as a HOST-side numpy tree here and
        # passed through shard_map with the state's specs so each block
        # restores from its own host columns.
        self._init_model = None
        if self.global_ctx.has_restart:
            model0, _, _ = self._model.init(
                self.global_ctx,
                evbuf_init(exp.n_hosts, self.params.ev_cap),
            )
            self._init_model = jax.tree.map(np.asarray, model0)
        # Per-(src→dst shard) bucket capacity. The worst case is convergent
        # traffic: ONE bucket holding the shard's entire outbox, so
        # ``_full_cap`` always fits by construction. The auto default is 2×
        # the uniform-traffic expectation (cheap exchange); run() escalates
        # to _full_cap on overflow.
        self._full_cap = self.h_local * self.params.outbox_cap
        auto = max(16, -(-2 * self._full_cap // self.n_dev))
        self._x2x_cap = min(self.params.x2x_cap or auto, self._full_cap)
        # n_windows traced: one compiled program for every window count.
        # Keyed by bucket cap (the overflow-retry path recompiles once).
        self._run_jits: dict[int, object] = {}

    # -- sharding specs ----------------------------------------------------
    def _spec_for(self, leaf) -> P:
        # Every rank≥1 state tensor is host-MINOR by design (the host axis
        # is the last/lane axis — core/dense.py layout contract); scalars
        # are replicated. (Guarded by the n_hosts match so aux leaves of
        # other shapes would fail loudly in shard_map rather than mis-shard.)
        if (hasattr(leaf, "ndim") and leaf.ndim >= 1
                and leaf.shape[-1] == self.exp.n_hosts):
            return P(*([None] * (leaf.ndim - 1)), self.axis)
        return P()

    def _state_specs(self, st: SimState):
        # The telemetry ring is [W, F] with NO host axis — replicated like
        # win_start (window_step globalizes each row via telem_reduce).
        # Spec'd explicitly so a ring whose trailing dim happens to equal
        # n_hosts can never be mis-sharded by the shape heuristic.
        specs = jax.tree.map(self._spec_for, st._replace(telem=None,
                                                         probes=None,
                                                         links=None))
        if st.telem is not None:
            specs = specs._replace(telem=jax.tree.map(lambda _: P(), st.telem))
        # The probe ring is [W, K, F] — replicated for the same reason (the
        # one-hot psum in probe_reduce makes every shard carry the owning
        # shard's rows), and spec'd explicitly for the same shape-collision
        # safety.
        if st.probes is not None:
            specs = specs._replace(
                probes=jax.tree.map(lambda _: P(), st.probes))
        # The link accumulator is [V, V, F] vertex-keyed — no host axis, so
        # it is replicated; link_reduce globalizes each window's deltas.
        if st.links is not None:
            specs = specs._replace(
                links=jax.tree.map(lambda _: P(), st.links))
        return specs

    # -- state -------------------------------------------------------------
    def init_state(self) -> SimState:
        from shadow1_tpu.telemetry.links import link_init
        from shadow1_tpu.telemetry.probes import probe_init
        from shadow1_tpu.telemetry.ring import ring_init

        evbuf = evbuf_init(self.exp.n_hosts, self.params.ev_cap)
        model, evbuf, seed_over = self._model.init(self.global_ctx, evbuf)
        metrics = _metrics_init()
        st = SimState(
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
            compact_buckets=compact_buckets_init(self._block_params(),
                                                 self.h_local),
        )
        return self.place_state(st)

    def place_state(self, st: SimState) -> SimState:
        """Shard a (host-built) state pytree over the mesh — used at init
        and after a tune/resize.py cap migration (the migrated planes are
        plain numpy; the specs are shape-derived, so a new cap reshards
        correctly)."""
        shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), self._state_specs(st)
        )
        return jax.device_put(st, shardings)

    # -- the sharded program ----------------------------------------------
    def _get_run(self, x2x_cap: int):
        f = self._run_jits.get(x2x_cap)
        if f is None:
            f = self._run_jits[x2x_cap] = jax.jit(self._make_run(x2x_cap))
        return f

    def _block_params(self) -> EngineParams:
        """``params`` as a shard's block runs them: ``compact_cap`` is sized
        against the GLOBAL active set (configs, tools/activeprobe.py) and
        each shard block sees ~1/n_dev of it, so the per-shard bucket is
        that share, rounded up to a lane tile (128) so that it stays
        tiling-friendly. A shard whose active count overflows its bucket
        takes more trips that window, each shard its own number (no
        collective inside the trip loop; exact either way —
        core/compact.py)."""
        pr = self.params
        if not pr.compact_cap:
            return pr
        local_cap = -(-pr.compact_cap // self.n_dev)
        tile = 128 if local_cap >= 128 else 8
        local_cap = min(-(-local_cap // tile) * tile, self.h_local)
        return dataclasses.replace(pr, compact_cap=local_cap)

    def _make_run(self, x2x_cap: int):
        exp, pr, axis = self.exp, self.params, self.axis
        n_dev, h_local = self.n_dev, self.h_local
        pr = self._block_params()
        window, model = self.window, self._model
        key = self.global_ctx.key
        lat_vv = self.global_ctx.lat_vv
        loss_vv = self.global_ctx.loss_vv
        loss_thr_vv = self.global_ctx.loss_thr_vv
        host_vertex = self.global_ctx.host_vertex  # full, replicated
        gctx = self.global_ctx
        # Per-host columns sharded alongside the state (host-minor last
        # axis, P(..., axis) each — fault_down/fault_up are [K, H]).
        cols_g = dict(
            hosts=gctx.hosts, bw_up=gctx.bw_up, bw_dn=gctx.bw_dn,
            fault_down=gctx.fault_down, fault_up=gctx.fault_up,
            cpu_cost=gctx.cpu_cost, ser_up=gctx.ser_up, ser_dn=gctx.ser_dn,
            tx_qlen_ns=gctx.tx_qlen_ns, rx_qlen_ns=gctx.rx_qlen_ns,
            aqm_min_ns=gctx.aqm_min_ns, aqm_span_ns=gctx.aqm_span_ns,
            aqm_pmax_thr=gctx.aqm_pmax_thr,
        )
        flags = dict(
            has_jitter=gctx.has_jitter, has_stop=gctx.has_stop,
            has_restart=gctx.has_restart,
            has_link_fault=gctx.has_link_fault,
            has_loss_ramp=gctx.has_loss_ramp,
            has_cpu=gctx.has_cpu, has_tx_qlen=gctx.has_tx_qlen,
            has_rx_qlen=gctx.has_rx_qlen, has_aqm=gctx.has_aqm,
        )
        jitter_vv = gctx.jitter_vv
        # Vertex-keyed fault tables are tiny and host-free: replicated
        # closure constants, like lat_vv.
        link_fault, loss_ramp = gctx.link_fault, gctx.loss_ramp
        init_model_g = self._init_model

        def block(st: SimState, cols, imodel, n_windows) -> SimState:
            ctx = Ctx(
                n_hosts=h_local,
                n_total=exp.n_hosts,
                params=pr,
                window=window,
                key=key,
                lat_vv=lat_vv,
                loss_vv=loss_vv,
                host_vertex=host_vertex,
                bw_up=cols["bw_up"],
                bw_dn=cols["bw_dn"],
                model_cfg=exp.model_cfg,
                hosts=cols["hosts"],
                loss_thr_vv=loss_thr_vv,
                jitter_vv=jitter_vv,
                fault_down=cols["fault_down"],
                fault_up=cols["fault_up"],
                link_fault=link_fault,
                loss_ramp=loss_ramp,
                init_model=imodel,
                cpu_cost=cols["cpu_cost"],
                ser_up=cols["ser_up"],
                ser_dn=cols["ser_dn"],
                tx_qlen_ns=cols["tx_qlen_ns"],
                rx_qlen_ns=cols["rx_qlen_ns"],
                aqm_min_ns=cols["aqm_min_ns"],
                aqm_span_ns=cols["aqm_span_ns"],
                aqm_pmax_thr=cols["aqm_pmax_thr"],
                **flags,
            )
            handlers = model.make_handlers(ctx)
            pre_window = getattr(model, "make_pre_window", lambda c: None)(ctx)

            def exchange(fp: FlatPackets):
                # The one collective per window (SURVEY §2.5): bucket local
                # packets by destination shard (stable in flat source order),
                # all_to_all the fixed-capacity buckets, concatenate received
                # buckets in source-shard order. All fields ride one stacked
                # i32 tensor (i64 halves split like core/events.deliver_batch).
                n = fp.dst.shape[0]
                nb = max((n - 1).bit_length(), 1)
                wide = (n_dev + 1) << nb > 2**31 - 1
                kdt = jnp.int64 if wide else jnp.int32
                dshard = jnp.where(fp.keep, fp.dst // h_local, n_dev)
                skey = (dshard.astype(kdt) << nb) | jnp.arange(n, dtype=kdt)
                (skey_s,) = jax.lax.sort((skey,), is_stable=False)
                dshard_s = (skey_s >> nb).astype(jnp.int32)
                idx_s = (skey_s & ((1 << nb) - 1)).astype(jnp.int32)
                seg = jnp.searchsorted(
                    dshard_s, jnp.arange(n_dev + 1, dtype=jnp.int32), side="left"
                )
                pos = seg[:-1, None] + jnp.arange(x2x_cap, dtype=jnp.int32)[None, :]
                valid = pos < seg[1:, None]                   # [n_dev, K]
                src = idx_s[jnp.minimum(pos, n - 1)]          # [n_dev, K]
                dropped = (
                    fp.keep.sum(dtype=jnp.int64) - valid.sum(dtype=jnp.int64)
                )
                # Occupancy: the DEMANDED fill of this shard's busiest
                # outbound bucket this window (can exceed x2x_cap — that is
                # exactly when overflow happens), reduced so every shard
                # carries the same global high-water mark. The max is
                # carried by a psum'd one-hot [n_dev] vector — bit-identical
                # to lax.pmax with a sum-only collective (ROADMAP Design 6
                # decides whether pmax replaces it).
                local_fill = (seg[1:] - seg[:-1]).max().astype(jnp.int64)
                slot = jnp.arange(n_dev) == jax.lax.axis_index(axis)
                fill_vec = jax.lax.psum(
                    jnp.where(slot, local_fill, 0), axis
                )
                fill_hw = fill_vec.max()
                stacked = jnp.concatenate(
                    [
                        jnp.stack(
                            [
                                fp.dst,
                                _lo(fp.arrival), _hi(fp.arrival),
                                _lo(fp.tb), _hi(fp.tb),
                                fp.kind,
                            ],
                            axis=1,
                        ),
                        fp.p.T,
                    ],
                    axis=1,
                )                                             # [N, 6+NP] i32
                send = jnp.where(valid[:, :, None], stacked[src], 0)
                send = jnp.concatenate(
                    [send, valid[:, :, None].astype(jnp.int32)], axis=2
                )                                             # [n_dev, K, 7+NP]
                recv = jax.lax.all_to_all(
                    send, axis, split_axis=0, concat_axis=0
                )                                             # row s = from shard s
                r = recv.reshape(n_dev * x2x_cap, recv.shape[2])
                keep = r[:, -1] != 0
                out = FlatPackets(
                    dst=jnp.where(keep, r[:, 0], 0),
                    arrival=_join(r[:, 1], r[:, 2]),
                    tb=_join(r[:, 3], r[:, 4]),
                    kind=r[:, 5],
                    p=r[:, 6:-1].T,
                    keep=keep,
                )
                return out, dropped, fill_hw

            def pmax_(x):
                # max across shards of a scalar or [G] vector, carried by a
                # psum'd one-hot [n_dev, ...] (sum-only collectives;
                # ROADMAP Design 6).
                slot = jnp.arange(n_dev) == jax.lax.axis_index(axis)
                x = jnp.asarray(x)
                shaped = slot.reshape((n_dev,) + (1,) * x.ndim)
                vec = jax.lax.psum(jnp.where(shaped, x[None], 0), axis)
                return vec.max(axis=0)

            def telem_reduce(counters, gauges):
                # Globalize one ring row: counter deltas are additive across
                # shards (psum); the occupancy gauge vector needs an
                # elementwise max. The state-digest words (appended to the
                # counter vector by ring_record) are per-shard partial sums
                # of globally-host-keyed element hashes, so the same psum
                # yields the exact single-device digest on every shard.
                return jax.lax.psum(counters, axis), pmax_(gauges)

            def probe_reduce(row):
                # Globalize one [K, F] probe row: probe_sample zeroes every
                # probe another shard's block owns, so the psum IS the
                # owning shard's row — every shard then carries the
                # identical replicated ring (same one-hot-sum trick as
                # pmax_, sum-only collectives).
                return jax.lax.psum(row, axis)

            def link_reduce(entry, cur):
                # Globalize the [V, V, F] link accumulator at a window
                # boundary. route_outbox runs per-shard PRE-exchange, so
                # every offered packet is scattered exactly once (on its
                # source shard) and the NIC drop sites hit the source shard
                # only — the per-window counter deltas partition across
                # shards and their psum, added back onto the replicated
                # entry baseline, is bit-identical to the single-device
                # tensor. The queued_ns_max column is a high-water gauge:
                # cross-shard max via the one-hot psum (sum-only
                # collectives, see pmax_).
                from shadow1_tpu.telemetry.links import LINK_MAX_COL
                d = cur.buf - entry.buf
                ctr = entry.buf[..., :LINK_MAX_COL] + jax.lax.psum(
                    d[..., :LINK_MAX_COL], axis)
                mx = pmax_(cur.buf[..., LINK_MAX_COL])
                return cur._replace(buf=jnp.concatenate(
                    [ctr, mx[..., None]], axis=-1))

            init_metrics = st.metrics
            st = jax.lax.fori_loop(
                0, n_windows,
                lambda _, s: window_step(s, ctx, handlers, exchange, pre_window,
                                         make_handlers=model.make_handlers,
                                         telem_reduce=telem_reduce,
                                         probe_reduce=probe_reduce,
                                         link_reduce=link_reduce),
                st,
            )
            # Each shard accumulated its own partials on top of the (replicated)
            # input metrics; psum then re-subtract the duplicated baseline.
            mfin = jax.tree.map(
                lambda f, i: jax.lax.psum(f, axis) - (n_dev - 1) * i,
                st.metrics,
                init_metrics,
            )
            # ``windows`` advances identically on every shard (replicated, like
            # win_start) — keep the local count rather than the 8× sum; same
            # for ``runs_window_end`` (the window end is unguarded here: every
            # shard counts every window) and for the pmax-replicated exchange
            # high-water mark. The capacity
            # gauges accumulated per-shard LOCAL maxima inside the loop; one
            # cross-shard max here makes them the global run high-water —
            # bit-identical to the single-device values (max of per-window
            # maxes commutes). compact_max_fill stays a per-shard bucket
            # gauge semantically (like ``rounds``), but the max over shards
            # is exactly the number that sizes the per-shard bucket.
            # ``compact_buckets`` (where a cap is in force) is each shard's
            # own trip count under a replicated spec: the slowest shard's.
            if st.compact_buckets is not None:
                st = st._replace(compact_buckets=pmax_(st.compact_buckets))
            return st._replace(metrics=mfin._replace(
                windows=st.metrics.windows,
                runs_window_end=st.metrics.runs_window_end,
                x2x_max_fill=st.metrics.x2x_max_fill,
                ev_max_fill=pmax_(st.metrics.ev_max_fill),
                ob_max_fill=pmax_(st.metrics.ob_max_fill),
                compact_max_fill=pmax_(st.metrics.compact_max_fill),
                mq_max_fill=pmax_(st.metrics.mq_max_fill),
                push_stage_max=pmax_(st.metrics.push_stage_max),
            ))

        def run(st: SimState, n_windows) -> SimState:
            specs = self._state_specs(st)
            col_specs = {
                k: None if v is None else P(*([None] * (v.ndim - 1)), axis)
                for k, v in cols_g.items()
            }
            imodel_specs = jax.tree.map(self._spec_for, init_model_g)
            # Replication checking off: the metrics psum pattern
            # intentionally returns locally-diverged values under
            # replicated specs.
            f = jax.shard_map(
                block,
                mesh=self.mesh,
                in_specs=(specs, col_specs, imodel_specs, P()),
                out_specs=specs,
                check_vma=False,
            )
            return f(st, cols_g, init_model_g, n_windows)

        return run

    # -- public ------------------------------------------------------------
    def run(self, st: SimState | None = None, n_windows: int | None = None,
            check_x2x: bool = True) -> SimState:
        if st is None:
            st = self.init_state()
        n = n_windows if n_windows is not None else self.n_windows
        base = int(st.metrics.x2x_overflow)
        with run_span(PH_ARGS):
            n = jnp.asarray(n, jnp.int32)
        with run_span(PH_CALL):
            out = self._get_run(self._x2x_cap)(st, n)
        if not check_x2x:
            # A supervising OverflowGuard passes check_x2x=False (through
            # ckpt.run_chunked): the chunk-boundary policy then owns the
            # response — retry grows the bucket via grow_x2x() and replays
            # the chunk transactionally, halt raises the structured
            # CapacityExceededError — so the eager escalate/raise below
            # must not preempt it. The psum'd metrics already carry the
            # global x2x_overflow count every shard agrees on. Guard-LESS
            # callers keep this eager safety net no matter what
            # params.on_overflow says: a policy nobody supervises must
            # never mean silent loss.
            return out
        drops = int(out.metrics.x2x_overflow) - base
        if (drops and not base and not self.params.x2x_cap
                and self._x2x_cap < self._full_cap):
            # Auto-sized cap overflowed (convergent traffic). The input
            # state is immutable, so re-running it at the guaranteed-fit
            # cap is exact — results bit-match a single-device run. The
            # larger cap sticks for subsequent chunks of this engine.
            import warnings

            warnings.warn(
                f"x2x bucket overflow ({drops} pkts) at auto cap "
                f"{self._x2x_cap}; retrying at worst-case cap "
                f"{self._full_cap} (one recompile) — set "
                f"EngineParams.x2x_cap to pin the exchange size",
                RuntimeWarning,
                stacklevel=2,
            )
            self._x2x_cap = self._full_cap
            with run_span(PH_CALL):
                out = self._get_run(self._x2x_cap)(st, n)
        total = int(out.metrics.x2x_overflow)
        if total:
            # Loud failure beats silently-wrong results: a full all_to_all
            # bucket means packets vanished and single-device parity is
            # gone. Cumulative on purpose: a state carrying drops from an
            # earlier check_x2x=False run (or a lossy checkpoint) is
            # already divergent and must not pass a checked run silently.
            raise RuntimeError(
                f"{total} packets dropped by full all_to_all buckets "
                f"(x2x_cap too small for this traffic pattern) — results "
                f"diverge from the single-device engine; raise "
                f"EngineParams.x2x_cap or pass check_x2x=False"
            )
        return out

    def hlo_text(self, st: SimState | None = None, n_windows: int = 0) -> str:
        """``Engine.hlo_text`` for the program ``run`` drives now: the one
        compiled for the exchange bucket's current cap (per device: SPMD)."""
        if st is None:
            st = jax.eval_shape(self.init_state)
        return self._get_run(self._x2x_cap).lower(
            st, jnp.asarray(n_windows, jnp.int32)).compile().as_text()

    def grow_x2x(self) -> bool:
        """Escalate the exchange bucket to its guaranteed-fit cap (the
        overflow-retry hook, txn.OverflowGuard._grow). The bucket is not a
        state shape, so no plane migration is involved — the grown cap
        simply selects a different compiled program for the replay and all
        subsequent chunks. Returns False when already at the fit cap (a
        bucket physically cannot need more than the shard's whole outbox,
        so a False here means the overflow is not bucket-sized — the guard
        raises with that diagnosis)."""
        if self._x2x_cap >= self._full_cap:
            return False
        self._x2x_cap = self._full_cap
        return True

    metrics_dict = staticmethod(Engine.metrics_dict)

    def model_summary(self, st: SimState):
        return jax.tree.map(np.asarray, self._model.summary(st.model, self.global_ctx))
