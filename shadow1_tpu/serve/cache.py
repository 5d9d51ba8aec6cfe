"""Hot compiled-engine cache — the daemon's reason to stay alive.

Engine trace + compile dominates short-job wall time (BENCH_r06: 3.3 s
compile once vs 25.3 s paid per-seed across 16 sequential solos). A
long-lived daemon amortizes it by keeping compiled ``FleetEngine``
programs hot, keyed by everything that affects the TRACE:

* the **shape class** of the experiment — ``fleet.expand.shape_class``:
  every ``CompiledExperiment`` field outside the fleet-variable set (host
  count, topology latency / jitter / bandwidth tables, model + model_cfg
  less the values of the app's lane tables, fidelity knobs, horizon):
  what ``check_uniform`` pins, because it either picks tensor shapes or
  is closed over as a device constant;
* the **EngineParams** (caps, ring width, policies, kernel impls) — a
  frozen dataclass, hashable as-is;
* the **lane count** E (state shapes carry the leading [E] axis);
* the **backend** (compiled executables are device-specific).

A hit REBINDS the new batch's per-job variants (seed keys, loss
thresholds, fault tables) into the cached engine — ``FleetEngine.rebind``
— and runs through the already-compiled executable: the jitted ``run``
takes the variant pytree as a traced ARGUMENT, so same shapes ⇒ zero new
traces (``tests/test_serve.py`` asserts ``_run_jit._cache_size()`` stays
flat across a hit). A rebind the trace structure can't absorb (fault
table shapes / has-flags changed) falls back to a fresh build and counts
as a miss: the contract is "a hit never recompiles", not "equal keys
never miss".

Capacity is a small LRU (compiled fleet programs hold device constants;
an unbounded cache would leak HBM across tenants). Hit/miss/evict
counters feed the daemon's Prometheus ledger (SERVE_SPECS).
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict

import numpy as np

from shadow1_tpu.fleet.expand import FleetConfigError, shape_class


def _fold(h, x) -> None:
    """Feed one config value into the fingerprint hash."""
    if isinstance(x, np.ndarray):
        h.update(f"nd{x.shape}{x.dtype}".encode())
        h.update(np.ascontiguousarray(x).tobytes())
    elif isinstance(x, dict):
        for k in sorted(x):
            h.update(str(k).encode())
            _fold(h, x[k])
    elif isinstance(x, (list, tuple)):
        for v in x:
            _fold(h, v)
    else:
        h.update(repr(x).encode())


def shape_class_key(exp, params, n_exp: int, backend: str = "cpu") -> tuple:
    """The engine-cache key for a batch of ``n_exp`` lanes of ``exp``'s
    shape class under ``params``. Two batches with equal keys differ at
    most in the fleet-variable knobs (seed / loss / faults / stop_time /
    per-lane max_rounds / the app's lane tables), which ride the variant
    pytree — never the compiled program."""
    h = hashlib.sha256()
    _fold(h, shape_class(exp))
    return (h.hexdigest(), params, int(n_exp), backend)


class EngineCache:
    """LRU cache of compiled FleetEngines with hit/miss/evict counters."""

    def __init__(self, capacity: int = 4):
        assert capacity >= 1, capacity
        self.capacity = capacity
        self._entries: OrderedDict[tuple, object] = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._entries)

    def counters(self) -> dict[str, int]:
        return {"cache_hits": self.hits, "cache_misses": self.misses,
                "cache_evictions": self.evictions,
                "cache_entries": len(self._entries)}

    def get(self, exps: list, params, max_rounds=None,
            backend: str = "cpu"):
        """(engine, "hit"|"miss") for a batch of experiments.

        On a hit the cached engine is rebound to the new experiment set
        (no re-jit); a rebind refused by the trace structure rebuilds and
        REPLACES the entry (counted as a miss — the old program could not
        serve this batch, so keeping it would just pin dead HBM)."""
        from shadow1_tpu.fleet.engine import FleetEngine

        key = shape_class_key(exps[0], params, len(exps), backend)
        eng = self._entries.get(key)
        if eng is not None:
            try:
                eng.rebind(exps, max_rounds)
            except FleetConfigError:
                del self._entries[key]
            else:
                self._entries.move_to_end(key)
                self.hits += 1
                return eng, "hit"
        eng = FleetEngine(exps, params, max_rounds)
        self._entries[key] = eng
        self._entries.move_to_end(key)
        self.misses += 1
        while len(self._entries) > self.capacity:
            self._entries.popitem(last=False)
            self.evictions += 1
        return eng, "miss"
