"""Between-chunk adaptive capacity controller (the ``--auto-caps`` brain).

At every chunk boundary — where state is already fetched to host for the
heartbeat/ring drain — the controller compares the run-max fill gauges
(``Metrics.ev_max_fill`` / ``ob_max_fill``) against the current caps:

* **grow before overflow**: a high-water above ``grow_frac · cap`` means the
  buffer is nearing its ceiling; the next chunk runs at the ladder step
  covering ``high_water × headroom``. Because the gauges are window-end
  samples, growth triggers while headroom still exists — on workloads whose
  occupancy ramps over windows (TCP slow-start), the controller stays ahead
  of the curve and the overflow counters stay 0 where a static cap would
  have dropped events.
* **shrink after sustained low occupancy**: once the quantized target
  ``quantize(high_water × headroom)`` has sat below the current cap for
  ``shrink_patience`` consecutive chunks (the high-water is cumulative, so
  early chunks cannot trigger a premature cut), the cap drops to the
  target. The floor is the RUN-MAX fill — never the current fill — so a
  workload's past peak keeps its headroom and the controller cannot
  oscillate (caps form a monotone-convergent sequence per direction).

Resizes migrate the state planes bit-exactly (tune/resize.py) and swap to
an engine compiled at the new static shape. Engines are cached per cap
pair and caps are ladder-quantized (tune/ladder.py), so total recompiles
are bounded by the ladder span, not the chunk count.

``msgq_pool`` (the host's message-boundary pool, tcp/tcp.py) is GROWN the
same way, from ``mq_max_fill`` and the ``mq_overflow`` backstop, and never
shrunk: its default is derived from two widths the file states, a smaller
pool is an offline choice (tools/captune.py), and a run without TCP has no
pool (gauge 0: no decision).

``outbox_cap`` tuning is OFF by default: outbox space is a semantic knob
for TCP (tcp_flush paces sends on ``outbox_space``; the CPU oracle honours
the same bound), so resizing it mid-run changes the event stream. Enable
``CapPolicy(tune_outbox=True)`` only for models whose outbox use is
drop-counted rather than flow-controlled (e.g. PHOLD).
"""

from __future__ import annotations

import dataclasses

from shadow1_tpu.tune.ladder import HEADROOM, next_step, quantize_cap


def _peak(x) -> int:
    """A gauge as one fleet-global int: max over the lane axis (identity on
    solo 0-d gauges). Caps are fleet-uniform, so the controller sizes for
    the BUSIEST lane — any smaller cap would overflow it."""
    import numpy as np

    return int(np.asarray(x).max())


def _total(x) -> int:
    """A counter as one fleet-global int: sum over the lane axis (identity
    on solo 0-d counters) — the psum idiom the sharded engine uses."""
    import numpy as np

    return int(np.asarray(x).sum())


@dataclasses.dataclass(frozen=True)
class CapPolicy:
    grow_frac: float = 0.75    # grow when high_water > grow_frac * cap
    headroom: float = HEADROOM # target cap = quantize(high_water * headroom)
    shrink_patience: int = 2   # consecutive low chunks before a shrink
    min_cap: int = 8           # never shrink below (ladder anchor)
    max_cap: int = 1 << 20     # never grow beyond (runaway guard)
    tune_outbox: bool = False  # semantic for TCP — see module docstring


class CapController:
    """The ``retune`` hook for ckpt.run_chunked: ``(engine, st) -> (engine,
    st)``. Construct with the running engine and a factory that builds its
    sibling at different caps (``params -> engine``)."""

    def __init__(self, engine, make_engine, policy: CapPolicy | None = None,
                 log=None, initial_state=None):
        self.policy = policy or CapPolicy()
        self._make_engine = make_engine
        self._engines = {self._key(engine.params): engine}
        self._low_chunks = {"ev_cap": 0, "outbox_cap": 0, "msgq_pool": 0}
        # Overflow backstop baselines (cumulative counters at last check).
        # A RESUMED state carries its pre-snapshot history in the cumulative
        # counters; baseline from it (``initial_state``) so a respawn does
        # not mistake old losses for a fresh lossy chunk and force a
        # spurious grow + re-jit on every restart. Counters sum over the
        # lane axis on a FleetEngine state (_total) — caps are
        # fleet-uniform, so ANY lane's loss is the fleet's loss.
        self._overflow_seen = {
            "ev_cap": (_total(initial_state.metrics.ev_overflow)
                       if initial_state is not None else 0),
            "outbox_cap": (_total(initial_state.metrics.ob_overflow)
                           if initial_state is not None else 0),
            "msgq_pool": (_total(initial_state.metrics.mq_overflow)
                          if initial_state is not None else 0),
        }
        # Lossless floor: once a cap has overflowed, shrinking back to it
        # would just re-drop events — the shrink target ratchets above the
        # largest cap ever seen lossy (prevents grow/shrink oscillation on
        # workloads whose mid-window bursts hide from the window-end gauge).
        self._floor = {"ev_cap": self.policy.min_cap,
                       "outbox_cap": self.policy.min_cap,
                       "msgq_pool": self.policy.min_cap}
        self.resizes: list[dict] = []   # audit log (CLI output / tests)
        self._log = log

    @staticmethod
    def _key(params):
        return (params.ev_cap, params.outbox_cap, params.mq_pool)

    def _engine_for(self, params):
        k = self._key(params)
        eng = self._engines.get(k)
        if eng is None:
            eng = self._engines[k] = self._make_engine(params)
        return eng

    # Public alias: the overflow-retry guard (txn.OverflowGuard) builds its
    # grown-cap engines through the controller's cache so the two planes
    # share one jit cache per (ev_cap, outbox_cap) instead of compiling the
    # same program twice.
    engine_for = _engine_for

    def note_lossy(self, knob: str, grown_cap: int) -> None:
        """Absorb a retry-driven grow (txn.OverflowGuard): the pre-grow cap
        is PROVEN lossy — the tainted chunk overflowed it — so the shrink
        floor ratchets to the grown cap and the low-occupancy streak
        resets. The controller can then never shrink back into a cap the
        retry plane just had to grow away from (grow/retry/shrink
        oscillation). The ``_overflow_seen`` baseline is deliberately NOT
        advanced: the tainted chunk's counters were discarded with its
        state, so the committed stream shows no fresh overflow and the
        backstop cannot double-grow on top of the guard's grow."""
        self._floor[knob] = max(self._floor[knob], int(grown_cap))
        self._low_chunks[knob] = 0

    def _decide(self, knob: str, high_water: int, cap: int) -> int:
        import math

        p = self.policy
        if high_water <= 0:
            return cap
        target = min(max(quantize_cap(math.ceil(high_water * p.headroom)),
                         p.min_cap, self._floor[knob]), p.max_cap)
        if high_water > p.grow_frac * cap:
            self._low_chunks[knob] = 0
            return max(target, min(next_step(cap), p.max_cap))
        if target < cap:
            self._low_chunks[knob] += 1
            if self._low_chunks[knob] >= p.shrink_patience:
                self._low_chunks[knob] = 0
                return target
            return cap
        self._low_chunks[knob] = 0
        return cap

    def _overflow_grow(self, knob: str, total: int, cap: int, decided: int) -> int:
        """Backstop on the authoritative guard: the fill gauges are
        window-END samples, so a buffer can overflow mid-window (burst push
        that drains before the sample) while the gauge sits below the grow
        threshold. Any NEW overflow since the last check forces at least one
        ladder step up — lossy chunks must never go unanswered."""
        fresh = total - self._overflow_seen[knob]
        self._overflow_seen[knob] = total
        if fresh <= 0:
            return decided
        self._low_chunks[knob] = 0
        grown = min(next_step(cap), self.policy.max_cap)
        self._floor[knob] = max(self._floor[knob], grown)  # ``cap`` is lossy
        return max(decided, grown)

    def __call__(self, engine, st):
        import dataclasses as _dc

        import jax
        import numpy as np

        params = engine.params
        # The gauges ride the metrics fetch the chunk drain already paid.
        # On a FleetEngine state they are [E] vectors: the controller is fed
        # the FLEET-GLOBAL view — max fill across lanes (caps are uniform,
        # so the busiest lane sets the floor), summed overflow counters.
        ev_hw = _peak(st.metrics.ev_max_fill)
        ob_hw = _peak(st.metrics.ob_max_fill)
        new_ev = self._decide("ev_cap", ev_hw, params.ev_cap)
        new_ev = self._overflow_grow("ev_cap", _total(st.metrics.ev_overflow),
                                     params.ev_cap, new_ev)
        new_ob = (self._decide("outbox_cap", ob_hw, params.outbox_cap)
                  if self.policy.tune_outbox else params.outbox_cap)
        if self.policy.tune_outbox:
            new_ob = self._overflow_grow("outbox_cap",
                                         _total(st.metrics.ob_overflow),
                                         params.outbox_cap, new_ob)
        # The boundary pool only grows (module docstring): the decision's
        # shrink side is cut off at the pool in force.
        pool = params.mq_pool
        mq_hw = _peak(st.metrics.mq_max_fill)
        new_mq = max(pool, self._decide("msgq_pool", mq_hw, pool))
        new_mq = self._overflow_grow(
            "msgq_pool", _total(st.metrics.mq_overflow), pool, new_mq)
        if (new_ev, new_ob, new_mq) == (params.ev_cap, params.outbox_cap,
                                        pool):
            return engine, st
        from shadow1_tpu.tune.resize import resize_state

        host_st = jax.tree.map(np.asarray, st)
        host_st = resize_state(host_st, ev_cap=new_ev, outbox_cap=new_ob,
                               msgq_pool=new_mq)
        new_params = _dc.replace(params, ev_cap=new_ev, outbox_cap=new_ob)
        rec = {
            "windows_done": _peak(st.metrics.windows),
            "ev_cap": [params.ev_cap, new_ev],
            "outbox_cap": [params.outbox_cap, new_ob],
            "ev_max_fill": ev_hw,
            "ob_max_fill": ob_hw,
        }
        if new_mq != pool:
            new_params = _dc.replace(new_params, msgq_pool=new_mq)
            rec.update(msgq_pool=[pool, new_mq], mq_max_fill=mq_hw)
        new_engine = self._engine_for(new_params)
        self.resizes.append(rec)
        if self._log is not None:
            self._log("auto-caps resize", **rec)
        return new_engine, new_engine.place_state(host_st)

    @property
    def final_caps(self) -> dict:
        last = self.resizes[-1] if self.resizes else None
        return ({"ev_cap": last["ev_cap"][1], "outbox_cap": last["outbox_cap"][1]}
                if last else {})
