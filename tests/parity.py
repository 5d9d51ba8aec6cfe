"""Shared end-of-run parity harness for the oracle-vs-batched test files.

Every parity test used to copy-paste the same block: run both engines,
assert the overflow guards, compare the semantic counters, compare summary
vectors. This module is the single implementation — and on a mismatch it
prints the ``tools/paritytrace.py`` invocation that would localize the
divergence to an exact (window, subsystem) instead of leaving a bare
end-of-run key mismatch (the determinism flight recorder,
docs/SEMANTICS.md §"State digest").

Not a test file itself (no ``test_`` prefix): pytest collects nothing here.
"""

from __future__ import annotations

import numpy as np

from shadow1_tpu.consts import EngineParams

# Counters that must be bit-identical between the CPU oracle and the
# batched engines (per-kind pops included: they guard the rx fast-path
# split staying symmetric between engines).
PARITY_KEYS = [
    "events", "pkts_sent", "pkts_delivered", "pkts_lost",
    "ev_overflow", "ob_overflow", "mq_overflow", "mq_max_fill",
    "tcp_fast_rtx", "tcp_rto", "tcp_ooo_drops",
    "pops_pkt", "pops_deliver", "pops_timer", "pops_txr", "pops_app",
]

_HINT = (
    "\nlocalize the first divergent (window, subsystem) with the "
    "determinism flight recorder:\n"
    "    python -m shadow1_tpu.tools.paritytrace <experiment.yaml> "
    "{a} {b}\n"
    "(write the in-test experiment as a YAML config, or call "
    "shadow1_tpu.tools.paritytrace.make_side/bisect directly on the "
    "CompiledExperiment)"
)


def run_both(exp, params: EngineParams | None = None):
    """Run ``exp`` on the CPU oracle and the single-device batched engine.

    Returns (cpu_metrics, cpu_summary, tpu_metrics, tpu_summary)."""
    from shadow1_tpu.core.engine import Engine
    from shadow1_tpu.cpu_engine import CpuEngine

    params = params or EngineParams()
    cpu = CpuEngine(exp, params)
    cm = cpu.run()
    cs = cpu.summary()
    eng = Engine(exp, params)
    st = eng.run()
    tm = Engine.metrics_dict(st)
    ts = eng.model_summary(st)
    return cm, cs, tm, ts


def assert_parity(cm, cs, tm, ts, keys=("rx_bytes", "flows_done", "done_time"),
                  metric_keys=PARITY_KEYS, sides=("tpu", "cpu")):
    """The canonical oracle-vs-batched parity gate.

    ``cm``/``tm`` are metric dicts, ``cs``/``ts`` model summaries; ``keys``
    are the summary vectors to compare elementwise. The overflow guards run
    first: parity is only defined for overflow-free runs (which packets
    drop on overflow is layout-defined — docs/SEMANTICS.md)."""
    hint = _HINT.format(a=sides[0], b=sides[1])
    assert (tm["ev_overflow"] == 0 and tm["ob_overflow"] == 0
            and tm.get("mq_overflow", 0) == 0), (
        f"overflow run: parity undefined (ev={tm['ev_overflow']}, "
        f"ob={tm['ob_overflow']}, mq={tm.get('mq_overflow')}) — raise the "
        f"caps" + hint
    )
    assert tm["round_cap_hits"] == 0, (
        "round cap hit: windows truncated — raise max_rounds" + hint
    )
    for k in metric_keys:
        assert tm[k] == cm[k], (
            f"counter {k!r} diverged: {sides[0]}={tm[k]} {sides[1]}={cm[k]}"
            + hint
        )
    for k in keys:
        np.testing.assert_array_equal(
            np.asarray(ts[k]), np.asarray(cs[k]),
            err_msg=f"summary {k!r} diverged" + hint,
        )


# ---- a fleet lane against its solo run -----------------------------------------
# Everything is compared but ``registry.LANE_PROGRAM_FIELDS`` (``runs_*``):
# those count the program a lane rode in, which a solo run is not.

def lane_metrics(d: dict) -> dict:
    """A metrics dict without the counters of the program the lane rode in."""
    from shadow1_tpu.telemetry.registry import LANE_PROGRAM_FIELDS

    return {k: v for k, v in d.items() if k not in LANE_PROGRAM_FIELDS}


def _unlike(got, want, skip) -> list[str]:
    """Paths of the leaves in which two states of one treedef differ, the
    fields named in ``skip`` left out."""
    import jax

    paths = [jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_leaves_with_path(want)]
    a, b = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(a) == len(b) == len(paths) > 50
    return [p for p, x, y in zip(paths, a, b)
            if p.rsplit(".", 1)[-1] not in skip
            and not np.array_equal(np.asarray(x), np.asarray(y))]


def unlike_leaves(got, want) -> list[str]:
    """Paths of the leaves in which two states of one treedef differ,
    ``metrics.runs_*`` left out."""
    from shadow1_tpu.telemetry.registry import LANE_PROGRAM_FIELDS

    return _unlike(got, want, LANE_PROGRAM_FIELDS)


# ---- a compacted run against the full-width one --------------------------------
# Everything is compared but what the round loop counts of itself
# (``registry.ROUND_PROGRAM_FIELDS``: sums over a window's trips) and the
# trips (``SimState.compact_buckets``, which a full-width state lacks).

def trip_metrics(d: dict) -> dict:
    """A metrics dict without the round loop's counts of itself."""
    from shadow1_tpu.telemetry.registry import ROUND_PROGRAM_FIELDS

    return {k: v for k, v in d.items() if k not in ROUND_PROGRAM_FIELDS}


def unlike_but_trips(compacted, full, also_not=()) -> list[str]:
    """Paths of the leaves in which a compacted run's state differs from the
    full-width run's, ``compact_buckets`` and the round loop's own counts
    (and the fields ``also_not``) left out."""
    from shadow1_tpu.telemetry.registry import ROUND_PROGRAM_FIELDS

    assert full.compact_buckets is None
    return _unlike(compacted._replace(compact_buckets=None), full,
                   {*ROUND_PROGRAM_FIELDS, *also_not})


def assert_runs_contract(lanes: list[dict], solos: list[dict]) -> None:
    """``runs_*`` is the program's count and ``fires_*`` the lane's: one
    ``runs`` number in every lane of a fleet, at least each lane's
    ``fires``, which is its solo run's, where ``runs == fires``."""
    from shadow1_tpu.consts import KIND_METRIC_FIELDS

    assert len(lanes) == len(solos) > 1
    for _, fires, runs in KIND_METRIC_FIELDS.values():
        assert len({ln[runs] for ln in lanes}) == 1, (runs, lanes)
        for ln, solo in zip(lanes, solos):
            assert ln[runs] >= ln[fires] == solo[fires] == solo[runs], (
                runs, ln, solo)
    assert sum(ln[f[2]] for ln in lanes for f in KIND_METRIC_FIELDS.values())
