"""FleetEngine — E experiment variants as one vmapped device program.

The paper's round economics say a conservative window costs roughly
kernel-count × fixed per-kernel launch cost, so a second experiment riding
the same jitted window loop is nearly free. This engine makes that the
serving shape: E experiments of ONE topology shape class (same host count,
same latencies, same capacities — docs/SEMANTICS.md §"Fleet contract")
stack onto a leading experiment axis and run through ``jax.vmap`` of the
exact single-device ``window_step``:

* every ``SimState`` leaf grows a leading ``[E, ...]`` axis (event
  buffers ``[E, C, H]``, metrics ``[E]``, telemetry rings ``[E, W, F]``);
* the per-experiment *variants* — RNG key, loss thresholds, fault tables,
  ``max_rounds``, the app's seed-drawn ``model_cfg`` tables — ride a
  batched pytree zipped with the state, so lane e executes with exactly
  the constants a solo run of experiment e would close over;
* everything trace-structural is shared: one compiled program, one launch
  per chunk, per-window cost = the max lane's round count.

**Per-experiment determinism contract**: lane e's digest stream, metrics
and model state are bit-identical to running experiment e alone (solo tpu
engine or the cpu oracle) — ``tools/fleetprobe.py`` verifies it, and
``tests/test_fleet.py`` asserts it per PR. The mechanism: ``vmap`` batches
the identical integer ops (RNG is counter-based per (key, host, ctr), so
lanes cannot interact). The one thing lanes share is the PREDICATE of
each "any host has this" guard and of the round loop itself, reduced over
the named lane axis (``LANE_AXIS``, ``core/engine.any_host`` /
``any_lane``) so that each stays a conditional (a loop) under ``vmap``:
a pass that some other lane needs runs here too, over masks that are all
false, which is the identity by the handlers' contract (``run_round``),
and a lane whose own round loop has ended pops nothing in the rounds the
slower lanes still need, so even per-lane ``rounds`` counts stay exact.
Only ``Metrics.runs_*`` and ``runs_window_end`` can tell which lanes rode
along.

**Recovery plane** (docs/SEMANTICS.md §"Fleet recovery contract"): the
``[E, ...]`` state pytree is a well-defined transaction unit, so
``--on-overflow retry`` rolls the WHOLE fleet back to the chunk-start
state, grows the (fleet-uniform) cap one ladder step via the leading-axis-
aware ``tune/resize.py`` migration and replays the chunk bit-exactly;
``--auto-caps`` feeds the CapController fleet-global gauges (max fill over
lanes, summed overflow); and failing/finished lanes are sliced out
mid-sweep (``select_lanes`` / ``lane_done`` — fleet/run.py drives the
policy). What the fleet plane still rejects (structured FleetConfigError,
``kind="mode"``): the sharded engine (vmap-over-shard_map composition is a
follow-up). A ``compact_cap`` is in force as on the solo engine: the
lanes' rounds run a bucket a trip, the trip loop's predicate reduced over
the lanes (core/compact.py).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from shadow1_tpu import rng
from shadow1_tpu.config.compiled import NO_STOP
from shadow1_tpu.consts import EngineParams
from shadow1_tpu.core.engine import (
    Ctx,
    SimState,
    _metrics_init,
    _model_module,
    build_base_ctx,
    check_digest_params,
    check_probe_params,
    compact_buckets_init,
    window_step,
)
from shadow1_tpu.core.events import evbuf_init
from shadow1_tpu.core.outbox import outbox_init
from shadow1_tpu.telemetry.profiler import PH_ARGS, PH_CALL, run_span
from shadow1_tpu.fleet.expand import (
    FleetConfigError,
    check_uniform,
    lane_table_keys,
    same_shape_class,
)


# The name of the vmap axis every per-lane function here runs under. A lane's
# Ctx carries it (``Ctx.lane_axis``), so a guard's predicate can be reduced
# over the lanes and its ``lax.cond`` stay a conditional (core/engine.any_host).
LANE_AXIS = "lane"


def slice_experiment(st: SimState, e: int) -> SimState:
    """Lane e of a fleet state as a standalone solo SimState (leading
    experiment axis stripped from every leaf). The per-experiment resume
    slice: saved via ckpt.save_state it loads into a solo Engine of the
    same config and continues bit-identically (tests/test_fleet.py)."""
    return jax.tree.map(lambda x: x[e], st)


def select_lanes(st: SimState, keep) -> SimState:
    """The sub-fleet state holding only lanes ``keep`` (local indices, in
    order): every leaf's leading experiment axis gathered down to E'. Lanes
    are vmap-independent — the batched program applies the identical
    per-lane computation to whatever rides the axis — so a kept lane's
    continuation from a selected state is bit-identical to its continuation
    in the full fleet (the quarantine / early-finalize repack primitive;
    tests/test_fleet_recover.py proves it against E-1-from-scratch runs)."""
    idx = np.asarray(list(keep), np.int32)
    return jax.tree.map(lambda x: x[idx], st)


def fleet_metrics_per_exp(st: SimState) -> list[dict[str, int]]:
    """Per-experiment metric dicts from a fleet state ([E] leaves)."""
    arrs = {k: np.asarray(v) for k, v in st.metrics._asdict().items()}
    n = len(next(iter(arrs.values())))
    return [{k: int(v[e]) for k, v in arrs.items()} for e in range(n)]


def drain_fleet_rings(st: SimState, window_ns: int, start: int = 0,
                      exp_base: int = 0, exp_ids=None) -> list[dict]:
    """Per-experiment telemetry-ring drain: the solo ``drain_ring`` per
    lane, each record tagged with its experiment id (``exp``) — the shape
    tools/heartbeat_report.py and captune group by (docs/OBSERVABILITY.md
    §fleet). ``exp_base`` offsets the ids: a memory-downshifted sub-batch
    (cli --on-oom downshift) runs lanes [base, base+k) of the sweep, and
    its ring records must carry the SWEEP-global experiment ids;
    ``exp_ids`` (explicit per-lane global ids, wins over exp_base) is the
    same need after mid-sweep quarantine/finalize leaves the surviving ids
    non-contiguous. TWO device→host fetches total (the [E, W, F] ring and
    the window counters), then pure numpy lane views — never a per-lane
    slice of the whole fleet state."""
    from types import SimpleNamespace

    from shadow1_tpu.telemetry.ring import drain_ring

    if getattr(st, "telem", None) is None:
        return []
    buf = np.asarray(st.telem.buf)               # [E, W, F]
    windows = np.asarray(st.metrics.windows)     # [E]
    recs: list[dict] = []
    for e in range(buf.shape[0]):
        lane = SimpleNamespace(
            telem=SimpleNamespace(buf=buf[e]),
            metrics=SimpleNamespace(windows=int(windows[e])),
        )
        gid = exp_ids[e] if exp_ids is not None else e + exp_base
        for r in drain_ring(lane, window_ns, start=start):
            recs.append({**r, "exp": int(gid)})
    return recs


def drain_fleet_probes(st: SimState, window_ns: int, probes: tuple,
                       start: int = 0, exp_base: int = 0,
                       exp_ids=None) -> list[dict]:
    """Per-experiment probe-ring drain: the solo ``drain_probes`` per lane
    over the [E, W, K, F] fleet ring, each ``flow`` record tagged with its
    sweep-global experiment id (``exp``) — same id rules and same
    two-fetch-then-numpy-views discipline as ``drain_fleet_rings``."""
    from types import SimpleNamespace

    from shadow1_tpu.telemetry.probes import drain_probes

    if getattr(st, "probes", None) is None:
        return []
    buf = np.asarray(st.probes.buf)              # [E, W, K, F]
    windows = np.asarray(st.metrics.windows)     # [E]
    recs: list[dict] = []
    for e in range(buf.shape[0]):
        lane = SimpleNamespace(
            probes=SimpleNamespace(buf=buf[e]),
            metrics=SimpleNamespace(windows=int(windows[e])),
        )
        gid = exp_ids[e] if exp_ids is not None else e + exp_base
        for r in drain_probes(lane, window_ns, probes, start=start):
            recs.append({**r, "exp": int(gid)})
    return recs


def drain_fleet_links(st: SimState, window_ns: int, start: int = 0,
                      exp_base: int = 0, exp_ids=None) -> list[dict]:
    """Per-experiment link drain: the solo ``drain_links`` per lane over
    the [E, V, V, F] fleet accumulator, each ``link`` record tagged with
    its sweep-global experiment id (``exp``) — same id rules and same
    two-fetch-then-numpy-views discipline as ``drain_fleet_rings``. A
    fresh lane bound mid-sweep (recovery-plane rebind) whose window count
    sits below the sweep cursor yields that lane's ``link_gap`` rebase
    marker, exactly as the solo drain would."""
    from types import SimpleNamespace

    from shadow1_tpu.telemetry.links import drain_links

    if getattr(st, "links", None) is None:
        return []
    buf = np.asarray(st.links.buf)               # [E, V, V, F]
    windows = np.asarray(st.metrics.windows)     # [E]
    recs: list[dict] = []
    for e in range(buf.shape[0]):
        lane = SimpleNamespace(
            links=SimpleNamespace(buf=buf[e]),
            metrics=SimpleNamespace(windows=int(windows[e])),
        )
        gid = exp_ids[e] if exp_ids is not None else e + exp_base
        for r in drain_links(lane, window_ns, start=start):
            recs.append({**r, "exp": int(gid)})
    return recs


def _stack_host_intervals(exps) -> tuple[np.ndarray, np.ndarray]:
    """Per-experiment [K_i, H] down/up interval tensors → [E, Kmax, H],
    padded with the empty [NO_STOP, NO_STOP) interval no time satisfies."""
    from shadow1_tpu.fault.schedule import host_interval_tensors

    tabs = [host_interval_tensors(e) for e in exps]
    h = exps[0].n_hosts
    kmax = max((d.shape[0] for d, _ in tabs), default=0)
    kmax = max(kmax, 1)  # keep a well-formed [E, 1, H] even when fault-free
    downs, ups = [], []
    for d, u in tabs:
        pad = kmax - d.shape[0]
        if pad:
            filler = np.full((pad, h), NO_STOP, np.int64)
            d = np.concatenate([d, filler]) if d.size else filler
            u = np.concatenate([u, filler]) if u.size else filler
        downs.append(d)
        ups.append(u)
    return np.stack(downs), np.stack(ups)


def _stack_link_tables(exps):
    """[E, Lmax] (src, dst, t0, t1) link-outage tables, or None when no
    experiment has any. Padding rows use t0 == t1 == 0 — an empty outage
    window no departure time can hit."""
    from shadow1_tpu.fault.schedule import link_tables

    tabs = [link_tables(e) for e in exps]
    if all(t is None for t in tabs):
        return None
    lmax = max(len(t[0]) for t in tabs if t is not None)

    def pad(t):
        if t is None:
            t = (np.zeros(0, np.int32), np.zeros(0, np.int32),
                 np.zeros(0, np.int64), np.zeros(0, np.int64))
        n = lmax - len(t[0])
        return tuple(np.concatenate([np.asarray(a), np.zeros(n, a.dtype)])
                     for a in t)

    cols = [pad(t) for t in tabs]
    return tuple(np.stack([c[i] for c in cols]) for i in range(4))


def _stack_ramp_tables(exps):
    """[E, Rmax] (src, dst, t0, t1, thr) loss-ramp tables, or None.
    Padding rows are inert the same way (t0 == t1)."""
    from shadow1_tpu.fault.schedule import ramp_tables

    tabs = [ramp_tables(e) for e in exps]
    if all(t is None for t in tabs):
        return None
    rmax = max(len(t[0]) for t in tabs if t is not None)

    def pad(t):
        if t is None:
            t = (np.zeros(0, np.int32), np.zeros(0, np.int32),
                 np.zeros(0, np.int64), np.zeros(0, np.int64),
                 np.zeros(0, np.uint64))
        n = rmax - len(t[0])
        return tuple(np.concatenate([np.asarray(a), np.zeros(n, a.dtype)])
                     for a in t)

    cols = [pad(t) for t in tabs]
    return tuple(np.stack([c[i] for c in cols]) for i in range(5))


class FleetEngine:
    """Batched engine over E CompiledExperiments of one shape class.

    API mirrors core.engine.Engine where the chunk runners need it
    (``init_state`` / ``run`` / ``place_state`` / ``n_windows`` /
    ``window`` / ``params``), plus the per-experiment accessors
    (``metrics_per_exp`` / ``slice_experiment`` / ``drain_rings``).
    ``metrics_dict`` returns the FLEET AGGREGATE (counters summed, gauges
    maxed) so generic chunk plumbing keeps working; anything that needs
    the real contract reads the per-experiment dicts."""

    def __init__(self, exps: list, params: EngineParams | None = None,
                 max_rounds: list[int] | None = None):
        if not exps:
            raise FleetConfigError("fleet needs >= 1 experiment")
        for exp in exps:
            exp.validate()
        self.params = params or EngineParams()
        check_uniform(exps, [self.params] * len(exps))
        check_digest_params(self.params)
        check_probe_params(self.params)
        from shadow1_tpu.telemetry.links import check_link_params

        check_link_params(self.params, np.asarray(exps[0].lat_vv).shape[0])
        self.exps = list(exps)
        self.exp = exps[0]
        self.n_exp = len(exps)
        self.window = self.exp.window
        self.n_windows = int(-(-self.exp.end_time // self.window))
        self.max_rounds = [int(m) for m in
                           (max_rounds or [self.params.max_rounds]
                            * self.n_exp)]
        if len(self.max_rounds) != self.n_exp:
            raise FleetConfigError(
                f"max_rounds list ({len(self.max_rounds)}) != experiment "
                f"count ({self.n_exp})")
        if len(set(self.max_rounds)) == 1 \
                and self.max_rounds[0] != self.params.max_rounds:
            # A UNIFORM list never becomes a variant leaf (the per-lane
            # substitution in _lane_ctx fires only for non-uniform lists),
            # so the compiled program would silently run params.max_rounds
            # instead — normalize params to the list before anything is
            # traced.
            self.params = dataclasses.replace(
                self.params, max_rounds=self.max_rounds[0])
        # Sweep-global id of lane 0 — nonzero only for a memory-downshifted
        # sub-batch (cli --on-oom downshift), so records keep global ids.
        self.exp_base = 0
        # Explicit per-lane sweep-global ids (wins over exp_base when set):
        # after a mid-sweep quarantine/finalize the surviving ids are
        # non-contiguous, and every ring record must still carry the id the
        # lane had in the original sweep (fleet/run.py sets this on repack).
        self.exp_ids: list[int] | None = None
        self._model = _model_module(self.exp.model)
        self._base_ctx = build_base_ctx(self.exp, self.params,
                                        window=self.window)
        self._variants, self._has = self._build_variants()
        self._base_ctx = dataclasses.replace(
            self._base_ctx,
            has_stop=self._has["stop"], has_restart=self._has["restart"],
            has_link_fault=self._has["link"],
            has_loss_ramp=self._has["ramp"],
        )
        if self._has["restart"]:
            # Per-experiment restart target: the model pytree exactly as
            # init() builds it under each lane's constants, captured once
            # (eager vmap) and carried as a batched variant leaf —
            # window_step restores restarted hosts' columns from lane e's
            # capture, same as the solo engine's device constant.
            cap = jax.vmap(self._lane_init_model,
                           axis_name=LANE_AXIS)(self._variants)
            self._variants["init_model"] = jax.tree.map(
                lambda x: jnp.asarray(np.asarray(x)), cap)
        self._run_jit = jax.jit(self._make_run())

    # -- construction ------------------------------------------------------
    def _build_variants(self) -> tuple[dict, dict]:
        exps = self.exps
        variants: dict[str, Any] = {
            "key": jnp.stack([rng.base_key(e.seed) for e in exps]),
            "loss_thr_vv": jnp.stack([
                jnp.asarray(rng.prob_threshold(np.asarray(e.loss_vv)))
                for e in exps]),
        }
        down, up = _stack_host_intervals(exps)
        variants["fault_down"] = jnp.asarray(down)
        variants["fault_up"] = jnp.asarray(up)
        lf = _stack_link_tables(exps)
        if lf is not None:
            variants["link_fault"] = tuple(jnp.asarray(a) for a in lf)
        rt = _stack_ramp_tables(exps)
        if rt is not None:
            variants["loss_ramp"] = tuple(jnp.asarray(a) for a in rt)
        if len(set(self.max_rounds)) > 1:
            variants["max_rounds"] = jnp.asarray(self.max_rounds, jnp.int32)
        tables = lane_table_keys(self.exp)
        if tables:
            variants["model_tables"] = {
                k: jnp.stack([jnp.asarray(e.model_cfg[k]) for e in exps])
                for k in tables}
        has = {
            "stop": bool(down.min() < NO_STOP),
            "restart": bool((up < NO_STOP).any()),
            "link": lf is not None,
            "ramp": rt is not None,
        }
        return variants, has

    def _lane_ctx(self, var: dict) -> Ctx:
        """The solo Ctx lane e would close over, with this lane's variant
        leaves substituted (traced under vmap)."""
        params = self._base_ctx.params
        if "max_rounds" in var:
            params = dataclasses.replace(params, max_rounds=var["max_rounds"])
        return dataclasses.replace(
            self._base_ctx,
            params=params,
            model_cfg={**self._base_ctx.model_cfg,
                       **var.get("model_tables", {})},
            key=var["key"],
            loss_thr_vv=var["loss_thr_vv"],
            fault_down=var["fault_down"],
            fault_up=var["fault_up"],
            link_fault=var.get("link_fault"),
            loss_ramp=var.get("loss_ramp"),
            init_model=var.get("init_model"),
            lane_axis=LANE_AXIS,
        )

    def _lane_init_model(self, var: dict):
        ctx = self._lane_ctx(var)
        model0, _, _ = self._model.init(
            ctx, evbuf_init(self.exp.n_hosts, self.params.ev_cap))
        return model0

    # -- state -------------------------------------------------------------
    def _lane_init_state(self, var: dict) -> SimState:
        from shadow1_tpu.telemetry.links import link_init
        from shadow1_tpu.telemetry.probes import probe_init
        from shadow1_tpu.telemetry.ring import ring_init

        ctx = self._lane_ctx(var)
        evbuf = evbuf_init(self.exp.n_hosts, self.params.ev_cap)
        model, evbuf, seed_over = self._model.init(ctx, evbuf)
        metrics = _metrics_init()
        return SimState(
            win_start=jnp.zeros((), jnp.int64),
            evbuf=evbuf,
            outbox=outbox_init(self.exp.n_hosts, self.params.outbox_cap),
            model=model,
            metrics=metrics._replace(
                ev_overflow=metrics.ev_overflow + seed_over),
            cpu_busy=jnp.zeros(self.exp.n_hosts, jnp.int64),
            telem=ring_init(self.params.metrics_ring),
            probes=probe_init(self.params.metrics_ring, self.params.probes),
            links=link_init(self.params.link_telem,
                            np.asarray(self.exp.lat_vv).shape[0]),
            compact_buckets=compact_buckets_init(self.params,
                                                 self.exp.n_hosts),
        )

    def init_state(self) -> SimState:
        return jax.vmap(self._lane_init_state,
                        axis_name=LANE_AXIS)(self._variants)

    def place_state(self, st: SimState) -> SimState:
        return jax.device_put(st)

    # -- run ---------------------------------------------------------------
    def _lane_window_step(self, st: SimState, var: dict) -> SimState:
        ctx = self._lane_ctx(var)
        handlers = self._model.make_handlers(ctx)
        pre = getattr(self._model, "make_pre_window",
                      lambda c: None)(ctx)
        return window_step(st, ctx, handlers, pre_window=pre,
                           make_handlers=self._model.make_handlers)

    def _make_run(self):
        # The per-lane variants ride as a TRACED ARGUMENT, not closure
        # constants: the compiled program is then a pure function of the
        # state/variant SHAPES, so a later experiment set of the same shape
        # class (new seeds, loss rates, fault schedules) reuses the
        # already-compiled executable via ``rebind`` — the serving plane's
        # hot-engine cache (shadow1_tpu/serve/cache.py) rests on this.
        def run(st: SimState, n_windows, variants) -> SimState:
            def body(_, s):
                return jax.vmap(self._lane_window_step,
                                axis_name=LANE_AXIS)(s, variants)

            return jax.lax.fori_loop(0, n_windows, body, st)

        return run

    def run(self, st: SimState | None = None,
            n_windows: int | None = None) -> SimState:
        if st is None:
            st = self.init_state()
        n = n_windows if n_windows is not None else self.n_windows
        with run_span(PH_ARGS):
            n = jnp.asarray(n, jnp.int32)
        with run_span(PH_CALL):
            return self._run_jit(st, n, self._variants)

    def hlo_text(self, st: SimState | None = None, n_windows: int = 0) -> str:
        """The optimized HLO text of the fleet's one window program, as
        ``Engine.hlo_text`` (the phases sit under ``vmap(phase:...)``)."""
        if st is None:
            st = jax.eval_shape(self.init_state)
        return self._run_jit.lower(
            st, jnp.asarray(n_windows, jnp.int32),
            self._variants).compile().as_text()

    @staticmethod
    def _signature(variants: dict, has: dict) -> tuple:
        leaves, treedef = jax.tree_util.tree_flatten(variants)
        return (tuple(sorted(has.items())), str(treedef),
                tuple((tuple(l.shape), str(l.dtype)) for l in leaves))

    def variant_signature(self) -> tuple:
        """Trace-structure fingerprint of the variant pytree + has-flags:
        two experiment sets whose signatures match run through the SAME
        compiled program after ``rebind`` (jit keys on treedef + leaf
        shapes/dtypes; the has-flags are Python-level trace gates)."""
        return self._signature(self._variants, self._has)

    def rebind(self, exps: list, max_rounds: list[int] | None = None
               ) -> "FleetEngine":
        """Swap a NEW experiment set of the same shape class into this
        already-compiled engine — no re-jit, no re-trace.

        The new set may differ in exactly the fleet-variable knobs (seed,
        loss, fault schedules, legacy stop_time, per-lane max_rounds, the
        app's lane tables — ``expand.shape_class`` is the rule):
        those ride the variant pytree, which ``run`` takes as a traced
        argument. Everything the base ctx closes over (topology, window,
        caps, model config) must be identical — the serve-plane engine
        cache guarantees it by keying on the shape-class fingerprint
        (serve/cache.py); this method re-checks the cheap invariants and
        raises FleetConfigError (kind="mode") when the new set's trace
        structure (lane count, has-flags, variant table shapes) would
        force a recompile, so a caller can fall back to a fresh build."""
        if len(exps) != self.n_exp:
            raise FleetConfigError(
                f"rebind: lane count {len(exps)} != compiled {self.n_exp} "
                f"(state shapes differ — build a fresh engine)",
                kind="mode", knob="n_exp")
        for exp in exps:
            exp.validate()
        check_uniform(exps, [self.params] * len(exps))
        # The compiled program closed over the OLD exps' shared constants
        # (topology tables, horizon, model config): the new set must be of
        # the compiled set's shape class, not just uniform within itself —
        # the serve cache's fingerprint guarantees this, but a direct
        # caller gets the same wall.
        f = same_shape_class(self.exp, exps[0])
        if f is not None:
            raise FleetConfigError(
                f"rebind: {f!r} differs from the compiled engine's — "
                f"it is closed over as a device constant (or picks "
                f"shapes); a different shape class needs a fresh "
                f"engine", kind="shape", knob=f)
        new_mr = [int(m) for m in
                  (max_rounds or [self.params.max_rounds] * self.n_exp)]
        if len(set(new_mr)) == 1 and new_mr[0] != self.params.max_rounds:
            # A uniform list is baked into the compiled program as
            # params.max_rounds (no variant leaf) — a different uniform
            # value cannot ride a rebind.
            raise FleetConfigError(
                f"rebind: uniform max_rounds {new_mr[0]} != compiled "
                f"{self.params.max_rounds} — baked into the traced round "
                f"loop; build a fresh engine", kind="mode",
                knob="max_rounds")
        old_exps, old_mr = self.exps, self.max_rounds
        old_variants, old_has = self._variants, self._has
        self.exps = list(exps)
        self.exp = exps[0]
        self.max_rounds = new_mr
        try:
            variants, has = self._build_variants()
            if has != old_has:
                raise FleetConfigError(
                    f"rebind: fault-plane trace gates changed "
                    f"({old_has} -> {has}) — the compiled program was "
                    f"traced without those passes; build a fresh engine",
                    kind="mode", knob="faults")
            if has["restart"]:
                cap = jax.vmap(self._lane_init_model,
                               axis_name=LANE_AXIS)(variants)
                variants["init_model"] = jax.tree.map(
                    lambda x: jnp.asarray(np.asarray(x)), cap)
            if self._signature(variants, has) \
                    != self._signature(old_variants, old_has):
                raise FleetConfigError(
                    "rebind: variant table shapes changed (fault schedule "
                    "sizes / per-lane max_rounds presence) — a same-shape "
                    "program cannot serve them; build a fresh engine",
                    kind="mode", knob="variants")
        except Exception:
            self.exps, self.exp = old_exps, old_exps[0]
            self.max_rounds = old_mr
            raise
        self._variants, self._has = variants, has
        self.exp_base = 0
        self.exp_ids = None
        return self

    # -- accessors ---------------------------------------------------------
    @staticmethod
    def metrics_per_exp(st: SimState) -> list[dict[str, int]]:
        return fleet_metrics_per_exp(st)

    @staticmethod
    def metrics_dict(st: SimState) -> dict[str, int]:
        """Fleet AGGREGATE: counters sum across experiments, gauges max —
        keeps generic chunk plumbing (progress display, normalize)
        working; per-experiment truth is metrics_per_exp."""
        from shadow1_tpu.telemetry.registry import (
            LANE_PROGRAM_FIELDS,
            gauge_names,
        )

        # runs_* is one number in every lane (the program's), not a sum.
        maxed = set(gauge_names()) | set(LANE_PROGRAM_FIELDS)
        out = {}
        for k, v in st.metrics._asdict().items():
            a = np.asarray(v)
            out[k] = int(a.max()) if k in maxed else int(a.sum())
        # windows advance in lockstep across lanes — report one fleet
        # window count, not E× it.
        out["windows"] = int(np.asarray(st.metrics.windows).max())
        out["rounds"] = int(np.asarray(st.metrics.rounds).max())
        return out

    def drain_rings(self, st: SimState, start: int = 0) -> list[dict]:
        recs = drain_fleet_rings(st, self.window, start=start,
                                 exp_base=self.exp_base,
                                 exp_ids=self.exp_ids)
        recs += drain_fleet_probes(st, self.window, self.params.probes,
                                   start=start, exp_base=self.exp_base,
                                   exp_ids=self.exp_ids)
        recs += drain_fleet_links(st, self.window, start=start,
                                  exp_base=self.exp_base,
                                  exp_ids=self.exp_ids)
        return recs

    @staticmethod
    def lane_done(st: SimState) -> np.ndarray:
        """Per-lane "nothing can ever happen again" flags ([E] bool).

        A lane is DONE when its event buffer holds no live event: all event
        creation flows from handling events (model init seeds the first
        ones; restart resets restore model columns but never push), and
        chunk boundaries always see an empty outbox (cleared at window
        end), so an empty buffer means every further window is a pure
        no-op on the lane's model/digest state. The basis of --lane-finalize
        (fleet/run.py slices such lanes out and emits their final record
        immediately). One [E, C, H] host fetch — call at chunk boundaries
        only, and only when the policy is on."""
        from shadow1_tpu.consts import K_NONE

        kind = np.asarray(st.evbuf.kind)          # [E, C, H]
        return ~(kind != K_NONE).any(axis=(-2, -1))

    def model_totals(self, st: SimState) -> list[dict[str, int]]:
        """Per lane, the model summary's run totals (its 0-dim entries),
        fetched without the per-host tables or a lane slice: the
        heartbeat's ``fleet.model_per_exp``."""
        summ = jax.vmap(lambda m: self._model.summary(m, self._base_ctx))(
            st.model)
        tot = {k: np.asarray(v) for k, v in summ.items() if v.ndim == 1}
        return [{k: int(v[e]) for k, v in tot.items()}
                for e in range(self.n_exp)]

    def model_summary(self, st: SimState, e: int) -> dict[str, Any]:
        lane = slice_experiment(st, e)
        return jax.tree.map(
            np.asarray, self._model.summary(lane.model, self._base_ctx))
