"""Per-PR perf regression gate — the BENCH trajectory, enforced, per row.

    python -m shadow1_tpu.tools.benchgate            # gate vs BENCH_GATE.json
    python -m shadow1_tpu.tools.benchgate --update   # re-baseline (this backend)
    python -m shadow1_tpu.tools.benchgate --rows phold_smoke

The telemetry ring and phase profiler RECORD everything, but until PR 8
nothing ENFORCED the perf trajectory (ROADMAP item 5) — and the PR 8 gate
watched a single dense smoke PHOLD row, so the sparse TCP rounds the
ROADMAP most wants to speed up (and fleet mode's batched economics) could
regress silently. The gate now carries three rows:

* ``phold_smoke``  — dense PHOLD (bench.py's smoke shape): the per-round
  fixed cost that is the paper's whole economics;
* ``sparse_rung1`` — the rung-1 filexfer config: sparse TCP-heavy rounds,
  the regime ROADMAP item 1's bucketed-queue/megafusion work targets —
  any step of that rewrite shows up here per-PR;
* ``fleet_smoke``  — the configs/sweep_phold.yaml 4-lane sweep as one
  vmapped program: the fleet axis's per-round cost (ROADMAP items 2–3).

Each row gates **ms per inner round** (minimum over timed chunks, after a
full compile warmup — stable on a shared container where means are not)
against the committed ``BENCH_GATE.json``. Baselines are recorded PER
BACKEND per row: a TPU baseline coexists with the committed CPU one
instead of a backend mismatch auto-skipping the gate entirely — on either
backend, rows with a matching baseline gate and the others report
``no_baseline_for_backend`` (commit one from that machine with --update,
which merges: other backends' entries are preserved).

* measured > baseline × (1 + tolerance) → exit 1 (the gate fails CI);
* intentional trade-off? the one-line override:
  ``SHADOW1_BENCH_GATE_ACCEPT="why" ./ci.sh smoke`` turns the failure
  into a warning — then commit the new baseline with ``--update``;
* a big improvement prints a reminder to re-baseline (non-fatal —
  ratchets tighten deliberately, not by timing luck).

Tolerance rides per row in the baseline file so a noisy row can be
widened deliberately without loosening the others.

Always prints exactly one JSON line on stdout (the bench.py contract).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BASELINE = os.path.join(os.path.dirname(__file__), "..", "..",
                        "BENCH_GATE.json")

# bench.py's CPU-smoke shape — big enough that the round path dominates,
# small enough for seconds of CI wall.
N_HOSTS = 2048
CHUNK = 20
N_CHUNKS = 4
TOLERANCE = 0.05
ACCEPT_ENV = "SHADOW1_BENCH_GATE_ACCEPT"

SPARSE_CONFIG = "configs/rung1_filexfer.yaml"
FLEET_CONFIG = "configs/sweep_phold.yaml"
_REPO = os.path.join(os.path.dirname(__file__), "..", "..")


def host_fingerprint() -> str:
    """CPU model + logical core count — a wall-clock baseline only gates
    meaningfully on the machine class it was measured on."""
    model = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.lower().startswith("model name"):
                    model = line.split(":", 1)[1].strip()
                    break
    except OSError:
        pass
    return f"{model} x{os.cpu_count()}"


def _time_chunks(eng, st, n_chunks: int, chunk: int,
                 rounds_of) -> tuple[list, list, object]:
    """(walls, rounds-per-chunk, final state) of ``n_chunks`` timed chunks."""
    import jax

    walls, rounds = [], []
    for _ in range(n_chunks):
        r0 = rounds_of(st)
        t0 = time.perf_counter()
        st = eng.run(st, n_windows=chunk)
        jax.block_until_ready(st)
        walls.append(time.perf_counter() - t0)
        rounds.append(rounds_of(st) - r0)
    return walls, rounds, st


def _best_row(walls, rounds) -> tuple[int, float]:
    """Gate on the minimum PER-ROUND cost, not the minimum-wall chunk: a
    chunk can post the smallest wall simply by running fewer rounds."""
    best = min(range(len(walls)), key=lambda i: walls[i] / max(rounds[i], 1))
    return best, walls[best] * 1000 / max(rounds[best], 1)


def measure_phold_smoke() -> dict:
    import jax

    from shadow1_tpu.config.compiled import single_vertex_experiment
    from shadow1_tpu.consts import MS, EngineParams
    from shadow1_tpu.core.engine import Engine

    exp = single_vertex_experiment(
        n_hosts=N_HOSTS, seed=1234, end_time=(N_CHUNKS + 1) * CHUNK * MS,
        latency_ns=1 * MS, model="phold",
        model_cfg={"mean_delay_ns": float(2 * MS), "init_events": 16},
    )
    eng = Engine(exp, EngineParams(ev_cap=48, outbox_cap=24,
                                   max_rounds=128))
    t0 = time.perf_counter()
    st = eng.init_state()
    jax.block_until_ready(eng.run(st, n_windows=CHUNK))
    compile_wall = time.perf_counter() - t0

    def rounds_of(s):
        return int(s.metrics.rounds)

    walls, rounds, st = _time_chunks(eng, st, N_CHUNKS, CHUNK, rounds_of)
    best, ms = _best_row(walls, rounds)
    return {
        "metric": "phold_smoke_ms_per_round",
        "ms_per_round": round(ms, 4),
        "hosts": N_HOSTS,
        "chunk_windows": CHUNK,
        "chunks_timed": N_CHUNKS,
        "rounds_per_chunk": rounds[best],
        "events": int(st.metrics.events),
        "compile_wall_s": round(compile_wall, 3),
        "chunk_walls_s": [round(w, 4) for w in walls],
    }


def measure_sparse_rung1() -> dict:
    """The sparse TCP row: rung-1 filexfer, the op-count-bound round regime
    (docs/R6_NOTES.md). Few hosts, many rounds/window — ms/round here is
    pure round-body cost, the number ROADMAP item 1 attacks."""
    import jax

    from shadow1_tpu.config.experiment import load_experiment
    from shadow1_tpu.core.engine import Engine

    exp, params, _ = load_experiment(os.path.join(_REPO, SPARSE_CONFIG))
    eng = Engine(exp, params)
    # Short chunks, MANY of them: each timed chunk is only ~0.1 s of wall
    # on this config, so single-sample noise is large — the min over 8
    # samples is what's stable run-to-run (measured ±1.5% vs ±20% for the
    # raw samples on the shared container).
    chunk, n_chunks = 30, 8
    t0 = time.perf_counter()
    st = eng.init_state()
    jax.block_until_ready(eng.run(st, n_windows=chunk))
    compile_wall = time.perf_counter() - t0

    def rounds_of(s):
        return int(s.metrics.rounds)

    walls, rounds, st = _time_chunks(eng, st, n_chunks, chunk, rounds_of)
    best, ms = _best_row(walls, rounds)
    return {
        "metric": "sparse_rung1_ms_per_round",
        "config": SPARSE_CONFIG,
        "ms_per_round": round(ms, 4),
        "hosts": exp.n_hosts,
        "chunk_windows": chunk,
        "chunks_timed": n_chunks,
        "rounds_per_chunk": rounds[best],
        "events": int(st.metrics.events),
        "compile_wall_s": round(compile_wall, 3),
        "chunk_walls_s": [round(w, 4) for w in walls],
    }


def measure_fleet_smoke() -> dict:
    """The fleet row: the 4-lane sweep_phold sweep as ONE vmapped program.
    ms/round over the aggregate (all-lane) round count — the batched
    economics fleet mode exists for (BENCH_r06)."""
    import jax

    from shadow1_tpu.fleet.engine import FleetEngine
    from shadow1_tpu.fleet.expand import load_sweep

    plan = load_sweep(os.path.join(_REPO, FLEET_CONFIG))
    eng = FleetEngine(plan.exps, plan.params, plan.max_rounds)
    # Short chunks, many min samples — same noise discipline as the sparse
    # row (each timed chunk is ~0.1 s of wall).
    chunk, n_chunks = 8, 8
    t0 = time.perf_counter()
    st = eng.init_state()
    jax.block_until_ready(eng.run(st, n_windows=chunk))
    compile_wall = time.perf_counter() - t0

    import numpy as np

    def rounds_of(s):
        return int(np.asarray(s.metrics.rounds).sum())

    walls, rounds, st = _time_chunks(eng, st, n_chunks, chunk, rounds_of)
    best, ms = _best_row(walls, rounds)
    return {
        "metric": "fleet_smoke_ms_per_round",
        "config": FLEET_CONFIG,
        "ms_per_round": round(ms, 4),
        "experiments": len(plan.exps),
        "hosts": plan.exps[0].n_hosts,
        "chunk_windows": chunk,
        "chunks_timed": n_chunks,
        "rounds_per_chunk": rounds[best],
        "events": int(np.asarray(st.metrics.events).sum()),
        "compile_wall_s": round(compile_wall, 3),
        "chunk_walls_s": [round(w, 4) for w in walls],
    }


ROWS = {
    "phold_smoke": measure_phold_smoke,
    "sparse_rung1": measure_sparse_rung1,
    "fleet_smoke": measure_fleet_smoke,
}

# Per-row default tolerances written at --update time. The sparse rung-1
# row times ~0.4 ms/round on a 2-host config — small enough that shared-
# container scheduling state moves even the min-of-8 several percent
# run-to-run with no code change (observed spread 0.369–0.397), so it
# gates at 15% deliberately: the regressions this row exists to catch
# (ROADMAP item 1's round-body rewrites) are multiples, not percents.
# The fleet row's ~0.1 s chunks get the same treatment at 10%. The dense
# row (3+ s chunks, stable) holds the tight 5% ratchet.
ROW_TOLERANCE = {"sparse_rung1": 0.15, "fleet_smoke": 0.10}


def gate_row(name: str, row: dict, base_entry: dict | None,
             host: str, accept: str | None) -> dict:
    """One row's verdict dict (pure — unit-tested without measuring).
    ``base_entry`` is the baseline for THIS backend (already selected), or
    None when that backend has no committed baseline yet."""
    if base_entry is None:
        return {**row, "gate": "no_baseline_for_backend",
                "hint": f"commit one from this machine: python -m "
                        f"shadow1_tpu.tools.benchgate --update "
                        f"--rows {name}"}
    if base_entry.get("host") and base_entry["host"] != host:
        # A wall-clock baseline from another CPU would fail every PR on a
        # slower box (or wave real regressions through on a faster one)
        # with no code change at all. Re-baseline per machine.
        return {**row, "gate": "skipped_host_mismatch",
                "baseline_host": base_entry["host"]}
    tol = float(base_entry.get("tolerance", TOLERANCE))
    ref = float(base_entry["ms_per_round"])
    ratio = row["ms_per_round"] / ref if ref else 1.0
    verdict = {**row, "baseline_ms_per_round": ref,
               "ratio": round(ratio, 4), "tolerance": tol}
    if ratio > 1 + tol:
        if accept:
            return {**verdict, "gate": "accepted", "reason": accept}
        return {**verdict, "gate": "failed"}
    if ratio < 1 - 2 * tol:
        verdict["note"] = "improvement — consider re-baselining (--update)"
    return {**verdict, "gate": "ok"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="shadow1_tpu.tools.benchgate")
    ap.add_argument("--update", action="store_true",
                    help="write the measured rows as the committed baseline "
                         "for THIS backend (BENCH_GATE.json; other "
                         "backends' entries are preserved)")
    ap.add_argument("--rows", default=None,
                    help="comma-separated row subset (default: all of "
                         f"{','.join(ROWS)})")
    ap.add_argument("--baseline", default=BASELINE,
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    import shadow1_tpu  # noqa: F401  (x64 before jax arrays)
    import jax

    backend = jax.default_backend()
    host = host_fingerprint()
    names = list(ROWS) if not args.rows else args.rows.split(",")
    for n in names:
        if n not in ROWS:
            print(json.dumps({"error": f"unknown row {n!r}",
                              "rows": list(ROWS)}))
            return 2
    measured = {}
    for n in names:
        measured[n] = {**ROWS[n](), "backend": backend, "host": host}

    try:
        with open(args.baseline) as f:
            base = json.load(f)
    except OSError:
        base = {}
    base_rows = base.get("rows", {})

    if args.update:
        for n, row in measured.items():
            base_rows.setdefault(n, {})[backend] = {
                **row, "tolerance": ROW_TOLERANCE.get(n, TOLERANCE)}
        out = {
            "tolerance": TOLERANCE,
            "note": "benchgate baselines, per row per backend — the gate "
                    "fails CI when a row's measured ms_per_round exceeds "
                    "its baseline by > tolerance on the same backend+host; "
                    f"override once with {ACCEPT_ENV}, then re-baseline "
                    "with --update (merges: other backends kept)",
            "rows": base_rows,
        }
        with open(args.baseline, "w") as f:
            json.dump(out, f, indent=1, sort_keys=True)
            f.write("\n")
        print(json.dumps({"gate": "updated", "baseline": args.baseline,
                          "backend": backend, "rows": measured}))
        return 0

    if not base_rows:
        print(json.dumps({"gate": "no_baseline", "rows": measured,
                          "hint": "commit one with --update"}))
        return 0
    accept = os.environ.get(ACCEPT_ENV)
    verdicts = {}
    failed = False
    for n, row in measured.items():
        entry = base_rows.get(n, {}).get(backend)
        v = gate_row(n, row, entry, host, accept)
        verdicts[n] = v
        if v["gate"] == "failed":
            failed = True
            print(f"[benchgate] PERF REGRESSION ({n}): "
                  f"{v['ms_per_round']} vs baseline "
                  f"{v['baseline_ms_per_round']} ms/round "
                  f"(+{(v['ratio'] - 1) * 100:.1f}% > "
                  f"{v['tolerance'] * 100:.0f}% tolerance). If "
                  f"intentional, override once: {ACCEPT_ENV}='why' — then "
                  f"re-baseline with --update.", file=sys.stderr,
                  flush=True)
        elif v["gate"] == "accepted":
            print(f"[benchgate] REGRESSION ACCEPTED ({n}: {accept}): "
                  f"{v['ms_per_round']} vs baseline "
                  f"{v['baseline_ms_per_round']} ms/round — commit the new "
                  f"baseline: python -m shadow1_tpu.tools.benchgate "
                  f"--update", file=sys.stderr, flush=True)
        elif v.get("note"):
            print(f"[benchgate] {n}: {v['note']}", file=sys.stderr,
                  flush=True)
    print(json.dumps({"gate": "failed" if failed else "ok",
                      "backend": backend, "rows": verdicts}))
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
