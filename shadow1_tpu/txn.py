"""Transactional overflow plane — recoverable capacity overflow.

The determinism contract (docs/SEMANTICS.md "Capacities") holds only for
overflow-free runs: WHICH events drop when a bounded buffer fills is
layout-defined, so one burst past ``ev_cap`` silently forks a run away
from its big-cap truth. This module turns that counted-but-corrupting
condition into a *policy* applied at chunk boundaries, where state is
already fetched to host (``ckpt.run_chunked``):

* ``drop`` (default) — today's behavior: overflow is counted, the run
  continues, parity claims are void for the lossy stretch.
* ``retry`` — chunk execution becomes **transactional**. The chunk runner
  keeps the chunk-start state pytree (immutable — jax arrays, never
  donated); when a chunk's fresh overflow deltas are non-zero the tainted
  result is discarded, the offending cap grows one ladder step
  (tune/ladder.py; bit-exact plane migration via tune/resize.py; the
  sharded exchange bucket escalates to its guaranteed-fit cap), and the
  SAME chunk re-runs from the saved state on the re-jitted engine.
  Counter-based RNG and window-indexed fault tables make the replay
  exact, so a retried run's digest stream bit-matches a straight run at
  the final (grown) caps — every *committed* chunk is overflow-free, and
  overflow-free execution is cap-independent (the tune/resize.py
  exactness argument). Caveat: growing ``outbox_cap`` restores
  bit-exactness only for models whose outbox use is drop-counted rather
  than flow-controlled (TCP paces on ``outbox_space`` and never drops —
  same boundary as ``tune.autocap.CapPolicy.tune_outbox``).
* ``halt`` — raise :class:`CapacityExceededError`, a structured error
  carrying the offending knob, window range, and paste-ready cap advice
  (the captune idiom). The CLI maps it to :data:`EXIT_CAPACITY` and the
  supervisor classifies that exit as deterministic — it never burns the
  respawn budget replaying a config-capacity condition.

Also here: the **in-run self-check** (``--selfcheck``) — churnprobe's
drop-accounting identity lifted into a reusable boundary check
(:func:`check_boundary_identity`) that ``run_chunked`` applies to every
committed chunk and the CPU oracle to every window boundary, so the
identity guards every run instead of only probe invocations.

Deliberately light: numpy/jax are imported lazily inside the retry path,
so report tools can import the error types without an accelerator runtime.
"""

from __future__ import annotations

# CLI exit code for a CapacityExceededError halt (distinct from generic
# crashes so cli._supervise can classify it without parsing stderr).
# Canonically defined in the consts.py exit-code taxonomy; re-exported here
# for the existing importers.
from shadow1_tpu.consts import EXIT_CAPACITY  # noqa: F401

# Overflow counter → the capacity knob whose growth recovers it.
OVERFLOW_KNOBS: dict[str, str] = {
    "ev_overflow": "ev_cap",
    "ob_overflow": "outbox_cap",
    "mq_overflow": "msgq_pool",
    "x2x_overflow": "x2x_cap",
}

# knob → the high-water gauge that lower-bounds the demanded capacity.
_KNOB_GAUGE = {
    "ev_cap": "ev_max_fill",
    "outbox_cap": "ob_max_fill",
    "msgq_pool": "mq_max_fill",
    "x2x_cap": "x2x_max_fill",
}

# (overflow counter, knob, gauge) of the caps that are SHAPES of the state
# (every engine has them; the exchange bucket is the sharded engine's own):
# what the per-lane halt (fleet/run.py) and the oracle's boundary check
# (cpu_engine/engine.py) walk. A knob's value in force is
# ``EngineParams.cap(knob)`` (msgq_pool is derived where 0).
STATE_CAP_CHECKS = tuple((c, k, _KNOB_GAUGE[k])
                         for c, k in OVERFLOW_KNOBS.items() if k != "x2x_cap")


class CapacityExceededError(RuntimeError):
    """A capacity knob overflowed under a policy that forbids silent loss.

    Structured: ``knob`` (the EngineParams field), ``counter`` (the
    overflow metric), ``cap`` (the value that overflowed), ``overflow``
    (fresh drops attributed to it), ``window_range`` (``[w0, w1)`` window
    indices of the tainted chunk), ``recommended`` (ladder-quantized cap
    that would have held, from the measured gauge when available) and
    ``advice`` (a paste-ready ``engine:`` YAML block)."""

    def __init__(self, knob: str, counter: str, cap: int, overflow: int,
                 window_range: tuple[int, int], recommended: int | None = None,
                 detail: str = "", remedy: str | None = None,
                 lanes: list[int] | None = None):
        self.knob = knob
        self.counter = counter
        self.cap = int(cap)
        self.overflow = int(overflow)
        self.window_range = (int(window_range[0]), int(window_range[1]))
        # Fleet attribution: LOCAL lane indices whose counter overflowed
        # (None on solo engines) — what --on-lane-fail quarantine slices.
        self.lanes = list(lanes) if lanes is not None else None
        if recommended is None:
            from shadow1_tpu.tune.ladder import next_step

            recommended = next_step(cap)
        self.recommended = int(recommended)
        self.advice = f"engine:\n  {knob}: {self.recommended}"
        super().__init__(
            f"{counter}: {self.overflow} overflow drop(s) in windows "
            f"[{self.window_range[0]}, {self.window_range[1]}) at "
            f"{knob}={self.cap}{detail} — which items drop on overflow is "
            f"layout-defined, so the run has forked from its big-cap truth "
            f"(docs/SEMANTICS.md 'Capacities'). Paste-ready fix:\n"
            f"{self.advice}\n"
            + (remedy if remedy is not None else
               "or rerun with --on-overflow retry (transactional "
               "grow+replay) / --auto-caps; size precisely from a recorded "
               "run: python -m shadow1_tpu.tools.captune <run.log>")
        )


class SelfCheckError(RuntimeError):
    """The drop-accounting identity failed at a chunk/window boundary.

    Structured: ``terms`` (every counter in the identity with its value),
    ``gap`` (signed packets unaccounted: positive = ``pkts_sent`` exceeds
    every accounted sink, negative = the sinks over-explain), ``where``
    (boundary description). A violation means a routing/drop path changed
    without its counter — the probe-only invariant churnprobe checked now
    guards every ``--selfcheck`` run."""

    IDENTITY = ("pkts_sent == pkts_delivered + pkts_lost + link_down_pkts "
                "+ down_pkts + x2x_overflow (+ delivery share of ev_overflow)")
    lanes = None    # a fleet's violating lanes (fleet/run._check_identity)

    def __init__(self, terms: dict, gap: int, where: str = ""):
        self.terms = {k: int(v) for k, v in terms.items()}
        self.gap = int(gap)
        self.where = where
        if gap > 0:
            culprit = (f"pkts_sent exceeds every accounted sink by {gap} — "
                       f"a drop/delivery path went uncounted")
        else:
            culprit = (f"the accounted sinks exceed pkts_sent by {-gap} — "
                       f"a packet was counted twice")
        span = f" at {where}" if where else ""
        super().__init__(
            f"drop-accounting self-check violated{span}: {culprit}. "
            f"Identity: {self.IDENTITY}. Terms: {self.terms}. "
            f"Bisect the window with tools/paritytrace.py; "
            f"cross-engine verdict: tools/churnprobe.py"
        )


def accounting(m: dict) -> dict:
    """The drop-accounting identity: where every sent packet went.
    ``ev_overflow`` counts event-buffer drops from both local pushes and
    deliveries; only the delivery share belongs here, so the identity is
    checked as sent ≤ explained ≤ sent + ev_overflow (exact when
    ev_overflow == 0 — overflow-free runs are the parity contract).
    Shared by tools/churnprobe.py and the ``--selfcheck`` boundary check."""
    explained = (m["pkts_delivered"] + m["pkts_lost"] + m["link_down_pkts"]
                 + m["down_pkts"] + m.get("x2x_overflow", 0))
    lo, hi = explained, explained + m["ev_overflow"]
    return {
        "pkts_sent": m["pkts_sent"],
        "explained": explained,
        "ev_overflow": m["ev_overflow"],
        "closes": lo <= m["pkts_sent"] <= hi,
    }


_IDENTITY_TERMS = ("pkts_sent", "pkts_delivered", "pkts_lost",
                   "link_down_pkts", "down_pkts", "x2x_overflow",
                   "ev_overflow")


def check_boundary_identity(metrics: dict, where: str = "") -> None:
    """Raise :class:`SelfCheckError` if the cumulative counters in
    ``metrics`` fail the drop-accounting identity. Missing counters read
    as 0 (engine field subsets — same tolerance as registry.normalize)."""
    m = {k: int(metrics.get(k, 0)) for k in _IDENTITY_TERMS}
    acc = accounting(m)
    if acc["closes"]:
        return
    raise SelfCheckError(m, m["pkts_sent"] - acc["explained"], where=where)


class OverflowGuard:
    """The chunk-boundary transactional brain (``--on-overflow``).

    Construct with the running engine, a ``params -> engine`` factory
    (sibling engines at grown caps), and the policy mode. ``run_chunked``
    calls :meth:`bind` once (overflow baselines from the possibly-resumed
    state) and :meth:`commit` after every chunk; commit either accepts the
    chunk (no fresh overflow), replays it at grown caps (``retry``), or
    raises :class:`CapacityExceededError` (``halt``, ladder exhaustion, or
    the repeated-overflow classifier).

    When a ``tune.autocap.CapController`` is attached, the guard shares
    its engine cache and reports every retry-driven grow via
    ``controller.note_lossy`` — the controller's lossless floor then
    ratchets above the proven-overflowing cap, so the two planes can never
    double-grow or oscillate against each other.
    """

    COUNTERS = OVERFLOW_KNOBS

    def __init__(self, engine, make_engine=None, mode: str = "retry",
                 controller=None, log=None, max_cap: int = 1 << 20,
                 max_retries_per_chunk: int = 12):
        assert mode in ("retry", "halt"), mode
        self.mode = mode
        self.engine = engine
        self._make_engine = make_engine
        self._controller = controller
        self._engines: dict = {}
        self._seen: dict[str, int] | None = None
        self._log = log
        self.max_cap = max_cap
        self.max_retries = max_retries_per_chunk
        # Counters (host-side; ride the registry namespace — HOST_FIELDS).
        self.chunk_retries = 0
        self.retry_windows_rerun = 0
        self.resizes: list[dict] = []  # audit log (CLI retries block / tests)
        self.on_engine_swap = None     # hook: heartbeat tracks the live engine

    # -- lifecycle ---------------------------------------------------------
    def bind(self, engine, st) -> None:
        """Baseline the overflow counters from ``st`` (resume-aware: a
        resumed state carries its pre-snapshot history — old losses must
        not read as a fresh lossy chunk, mirroring CapController)."""
        self.engine = engine
        self._seen = self._counters(st)

    @staticmethod
    def _counters(st) -> dict:
        """Cumulative overflow counters as numpy arrays — 0-d on solo
        engines, [E] on a FleetEngine state, so one guard serves both: the
        fleet's psum-equivalent is the lane sum, and per-lane deltas stay
        available for attribution (fresh_by_lane)."""
        import numpy as np

        return {c: np.asarray(getattr(st.metrics, c)).astype(np.int64)
                for c in OVERFLOW_KNOBS}

    @staticmethod
    def run_guarded(engine, st, n_windows: int):
        """Run one chunk under guard supervision. The sharded engine's
        eager x2x escalate/raise (its guard-less safety net) must stand
        down — the guard owns the overflow response — so it is told a
        guard is watching via check_x2x=False. ``ckpt.run_chunked`` and
        the retry replay both go through here."""
        if hasattr(engine, "grow_x2x"):
            return engine.run(st, n_windows=n_windows, check_x2x=False)
        return engine.run(st, n_windows=n_windows)

    def _fresh(self, st) -> dict[str, int]:
        cur = self._counters(st)
        out = {}
        for c, v in cur.items():
            d = int((v - self._seen[c]).sum())
            if d > 0:
                out[c] = d
        return out

    def fresh_by_lane(self, st) -> dict[str, list[int]]:
        """Fleet attribution: LOCAL lane indices with fresh overflow per
        counter since the last bind/commit ({} on solo engines — 0-d
        counters carry no lane axis)."""
        import numpy as np

        cur = self._counters(st)
        out: dict[str, list[int]] = {}
        for c, v in cur.items():
            if v.ndim == 0:
                continue
            lanes = np.nonzero(v - self._seen[c] > 0)[0]
            if lanes.size:
                out[c] = [int(e) for e in lanes]
        return out

    # -- the transaction ---------------------------------------------------
    def commit(self, engine, st0, st, done: int, step: int):
        """Accept / replay / refuse one chunk. ``st0`` is the chunk-start
        state (the rollback point), ``st`` the just-produced result.
        Returns the committed ``(engine, state)``."""
        if self._seen is None:
            self.bind(engine, st0)
        fresh = self._fresh(st)
        attempts = 0
        while fresh:
            import numpy as np

            # max over lanes == the scalar on solo engines; fleet lanes
            # advance in lockstep, so any lane's clock is the chunk's.
            w0 = int(np.asarray(st0.win_start).max()) // engine.window
            if self.mode == "halt":
                raise self._error(engine, fresh, w0, w0 + step, st)
            attempts += 1
            if attempts > self.max_retries:
                raise self._error(
                    engine, fresh, w0, w0 + step, st,
                    detail=(f" after {attempts - 1} grow+replay attempts at "
                            f"the same chunk — growing caps is not fixing "
                            f"it; diagnose with tools/occprobe.py or "
                            f"tools/paritytrace.py"))
            self.chunk_retries += 1
            self.retry_windows_rerun += step
            engine, st0 = self._grow(engine, st0, fresh, w0, w0 + step, st,
                                     lanes=self.fresh_by_lane(st) or None)
            st = self.run_guarded(engine, st0, step)
            fresh = self._fresh(st)
        self._seen = self._counters(st)
        self.engine = engine
        return engine, st

    # -- growth ------------------------------------------------------------
    def _engine_for(self, params):
        if self._controller is not None:
            return self._controller.engine_for(params)
        key = (params.ev_cap, params.outbox_cap, params.mq_pool)
        eng = self._engines.get(key)
        if eng is None:
            if self._make_engine is None:
                raise ValueError(
                    "OverflowGuard(mode='retry') needs a make_engine "
                    "factory (or an attached CapController) to re-jit at "
                    "grown caps"
                )
            eng = self._engines[key] = self._make_engine(params)
        return eng

    def _grow(self, engine, st0, fresh, w0, w1, st_tainted, lanes=None):
        import dataclasses

        from shadow1_tpu.tune.ladder import next_step

        params = engine.params
        repl: dict[str, int] = {}
        rec: dict = {"windows": [w0, w1], "retry": self.chunk_retries}
        if lanes:
            # Fleet retry audit: which lanes' counters tainted this chunk
            # (heartbeat_report's per-lane retry table reads these).
            rec["lanes"] = lanes
        for ctr, knob in OVERFLOW_KNOBS.items():
            if ctr not in fresh:
                continue
            if knob == "x2x_cap":
                # The exchange bucket is not a state shape: escalate to the
                # engine's guaranteed-fit cap (a bucket physically cannot
                # need more than the shard's whole outbox — shard/engine.py)
                # and replay; no plane migration involved.
                old = getattr(engine, "_x2x_cap", None)
                if not getattr(engine, "grow_x2x", lambda: False)():
                    raise self._error(engine, {ctr: fresh[ctr]}, w0, w1,
                                      st_tainted,
                                      detail=" (exchange bucket already at "
                                             "its guaranteed-fit cap)")
                rec["x2x_cap"] = [old, engine._x2x_cap]
                continue
            cap = params.cap(knob)
            new = next_step(cap)
            if new > self.max_cap:
                raise self._error(
                    engine, {ctr: fresh[ctr]}, w0, w1, st_tainted,
                    detail=f" (ladder top: cannot grow past {self.max_cap})")
            repl[knob] = new
            rec[knob] = [cap, new]
            if self._controller is not None:
                self._controller.note_lossy(knob, new)
        if repl:
            import jax
            import numpy as np

            from shadow1_tpu.tune.resize import resize_state

            new_params = dataclasses.replace(params, **repl)
            engine = self._engine_for(new_params)
            host_st = jax.tree.map(np.asarray, st0)
            host_st = resize_state(host_st, ev_cap=new_params.ev_cap,
                                   outbox_cap=new_params.outbox_cap,
                                   msgq_pool=new_params.mq_pool)
            st0 = engine.place_state(host_st)
        self.resizes.append(rec)
        if self.on_engine_swap is not None:
            self.on_engine_swap(engine)
        if self._log is not None:
            self._log("overflow retry: chunk discarded, caps grown", **rec)
        return engine, st0

    def _error(self, engine, fresh, w0, w1, st, detail=""):
        import numpy as np

        from shadow1_tpu.tune.ladder import next_step, recommend_cap

        counter = max(fresh, key=lambda c: fresh[c])
        knob = OVERFLOW_KNOBS[counter]
        cap = (getattr(engine, "_x2x_cap", 0) if knob == "x2x_cap"
               else engine.params.cap(knob))
        # max over lanes == the scalar on solo engines (gauges are maxes).
        peak = int(np.asarray(getattr(st.metrics, _KNOB_GAUGE[knob], 0)).max())
        rec = max(next_step(cap), recommend_cap(peak) if peak else 0)
        lanes = self.fresh_by_lane(st).get(counter) if self._seen else None
        if lanes:
            detail = f" (fleet lane(s) {lanes})" + detail
        return CapacityExceededError(
            knob=knob, counter=counter, cap=cap, overflow=fresh[counter],
            window_range=(w0, w1), recommended=rec, detail=detail,
            lanes=lanes)

    # -- reporting ---------------------------------------------------------
    @property
    def final_caps(self) -> dict:
        caps = {"ev_cap": self.engine.params.ev_cap,
                "outbox_cap": self.engine.params.outbox_cap}
        if self.engine.params.msgq_pool:    # grown (or set): not the derived
            caps["msgq_pool"] = self.engine.params.msgq_pool
        x2x = getattr(self.engine, "_x2x_cap", None)
        if x2x:
            caps["x2x_cap"] = x2x
        return caps

    def report(self) -> dict:
        """The ``retries`` block (heartbeat / final JSON —
        docs/OBSERVABILITY.md)."""
        return {
            "policy": self.mode,
            "chunk_retries": self.chunk_retries,
            "retry_windows_rerun": self.retry_windows_rerun,
            "caps": self.final_caps,
        }
