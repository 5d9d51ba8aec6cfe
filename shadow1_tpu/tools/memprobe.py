"""Memory-plane verifier — estimator audit, feasibility search, sub-batch
parity proof (shadow1_tpu/mem.py, docs/SEMANTICS.md §"Memory contract").

    python -m shadow1_tpu.tools.memprobe CONFIG [CONFIG ...] --audit
    python -m shadow1_tpu.tools.memprobe CONFIG --maxfit [--budget BYTES]
    python -m shadow1_tpu.tools.memprobe SWEEP.yaml --subbatch [--sub K]

Three modes (combinable; default ``--audit``):

* ``--audit`` — estimator-vs-actual byte audit: for each config, compute
  the pre-flight estimate, then BUILD the engine + state for real and
  measure ``jax.live_arrays()``. The resident estimate must track the
  measured bytes within ``mem.AUDIT_TOLERANCE`` (10%) — this is the drift
  guard that keeps the analytic const/variant models honest against the
  abstractly-traced state. One table row per config; exit 1 when any row
  is out of tolerance.
* ``--maxfit`` — binary-search the feasible envelope on the current
  budget (backend-reported, env ``SHADOW1_MEM_BYTES``, or ``--budget``):
  the max host count H at this config's shape class, and — when the
  config carries a ``sweep:`` — the max lane count E. Estimator-only:
  nothing is allocated, so probing a 16M-host point costs milliseconds.
* ``--subbatch`` — the downshift bit-exactness proof (chaosprobe idiom):
  run the config's sweep as ONE full-E fleet with the determinism flight
  recorder on, then again as sequential sub-batches of ``--sub`` lanes
  (default: ceil(E/2)), and assert every lane's per-window digest stream
  AND parity metrics are bit-identical between the two — lanes are
  independent, so sub-batching is digest-neutral (the property
  ``--on-oom downshift`` relies on). Each sub-batch is additionally run
  THROUGH a mid-batch checkpoint cycle (snapshot at the halfway chunk,
  reload into a fresh engine, continue) — the per-sub-batch
  checkpoint/resume path that lets ``--on-oom downshift`` compose with
  ``--ckpt`` (cli._fleet_subbatched) must be digest-neutral too. Exit 3
  on divergence, paritytrace pointer in the verdict.

The last stdout line is always one JSON verdict.
"""

from __future__ import annotations

import argparse
import json
import sys

EXIT_DIVERGED = 3
EXIT_AUDIT_FAILED = 1


def _parity_counter_names():
    from shadow1_tpu.telemetry.registry import (
        LANE_PROGRAM_FIELDS,
        METRIC_SPECS,
        gauge_names,
    )

    # Per-lane parity comparands: every canonical counter that is not a
    # batch-engine-only occupancy artifact (rounds/fires are trace-shape
    # dependent and excluded from cross-run parity everywhere else too).
    skip = set(gauge_names()) | {"rounds", "round_cap_hits"}
    skip |= {n for n in METRIC_SPECS if n.startswith("fires_")}
    # ... and what counts the program a lane rode in: a sub-batch is
    # another fleet.
    skip |= set(LANE_PROGRAM_FIELDS)
    return [n for n in METRIC_SPECS if n not in skip]


def audit_config(path: str, fleet: bool = False) -> dict:
    """One estimator-vs-actual row: build the engine + state for real and
    compare measured live bytes against the resident estimate."""
    import gc

    import jax

    from shadow1_tpu import mem
    from shadow1_tpu.config.experiment import load_experiment

    if fleet:
        from shadow1_tpu.fleet.expand import load_sweep

        plan = load_sweep(path)
        exp, params, n_exp = plan.exps[0], plan.params, len(plan.exps)
    else:
        exp, params, _ = load_experiment(path)
        n_exp = 1
    est = mem.estimate(exp, params, n_exp=n_exp)
    gc.collect()
    base = mem.live_bytes()
    if fleet:
        from shadow1_tpu.fleet.engine import FleetEngine

        eng = FleetEngine(plan.exps, params, plan.max_rounds)
    else:
        from shadow1_tpu.core.engine import Engine

        eng = Engine(exp, params)
    st = eng.init_state()
    jax.block_until_ready(st)
    measured = mem.live_bytes() - base
    del st, eng
    gc.collect()
    ratio = est.resident_bytes / measured if measured else float("inf")
    return {
        "config": path,
        "n_exp": n_exp,
        "estimated_state": est.state_bytes,
        "estimated_resident": est.resident_bytes,
        "estimated_peak": est.peak_bytes,
        "measured_live": int(measured),
        "ratio": round(ratio, 4),
        "ok": bool(abs(ratio - 1.0) <= mem.AUDIT_TOLERANCE),
    }


def maxfit(path: str, budget: int) -> dict:
    """Binary-search the feasible envelope at ``budget`` — estimator-only,
    so nothing is allocated at any probed point."""
    from shadow1_tpu import mem
    from shadow1_tpu.config.experiment import load_experiment

    exp, params, _ = load_experiment(path)
    # ONE real estimate; the search itself is pure arithmetic — every
    # state plane is [.., H], so peak scales ~H (const tables too).
    base = mem.estimate(exp, params, n_exp=1)
    per_host = base.peak_bytes / max(exp.n_hosts, 1)

    def fits_h(h: int) -> bool:
        return per_host * h <= budget

    if not fits_h(1):
        # even one host exceeds the budget — an honest infeasible verdict
        # beats reporting the unverified lower bound of the bisection.
        lo = 0
    else:
        lo, hi = 1, exp.n_hosts
        # expand upward to the envelope edge first
        while fits_h(hi) and hi < (1 << 24):
            lo, hi = hi, hi * 2
        while lo + 1 < hi:
            mid = (lo + hi) // 2
            if fits_h(mid):
                lo = mid
            else:
                hi = mid
    out = {"config": path, "budget": int(budget), "hosts": exp.n_hosts,
           "max_hosts": int(lo)}
    try:
        from shadow1_tpu.fleet.expand import load_sweep

        plan = load_sweep(path)
    except Exception:  # noqa: BLE001 — no sweep: section, solo config
        plan = None
    if plan is not None:
        est = mem.estimate(plan.exps[0], plan.params,
                           n_exp=len(plan.exps))
        out["sweep_lanes"] = len(plan.exps)
        out["max_lanes"] = int(est.max_lanes(budget))
    return out


def _lane_streams(eng, st) -> dict[int, dict[int, tuple]]:
    """Per-lane {window: digest words} from a fleet state's rings."""
    from shadow1_tpu.core.digest import SUBSYSTEMS

    streams: dict[int, dict[int, tuple]] = {}
    for r in eng.drain_rings(st):
        if r["type"] != "ring":
            continue
        streams.setdefault(r["exp"], {})[r["window"]] = tuple(
            r[f"dg_{s}"] for s in SUBSYSTEMS)
    return streams


def subbatch_parity(path: str, sub: int | None, windows: int | None,
                    say) -> dict:
    """Full-E fleet vs sequential sub-batches (each cycled through a
    mid-batch checkpoint save/reload): per-lane digest streams and parity
    counters must be bit-identical (the downshift + per-batch-ckpt
    contract)."""
    import dataclasses
    import os
    import tempfile

    import jax

    from shadow1_tpu.ckpt import load_state, save_state
    from shadow1_tpu.fleet.engine import FleetEngine, fleet_metrics_per_exp
    from shadow1_tpu.fleet.expand import load_sweep

    plan = load_sweep(path)
    E = len(plan.exps)
    params = dataclasses.replace(plan.params, state_digest=1,
                                 metrics_ring=max(plan.params.metrics_ring,
                                                  64))
    sub = sub or -(-E // 2)
    n_windows = windows
    if n_windows is None:
        n_windows = min(int(-(-plan.exps[0].stop_time
                              // plan.exps[0].window)), 100)
    # Ring depth must cover the compared horizon so both sides drain the
    # identical gap-free window set.
    params = dataclasses.replace(
        params, metrics_ring=max(params.metrics_ring, n_windows))
    say(f"full fleet: {E} lanes x {n_windows} windows")
    eng_full = FleetEngine(plan.exps, params, plan.max_rounds)
    st_full = eng_full.run(n_windows=n_windows)
    jax.block_until_ready(st_full)
    full_streams = _lane_streams(eng_full, st_full)
    full_metrics = fleet_metrics_per_exp(st_full)
    counters = _parity_counter_names()
    sub_streams: dict[int, dict[int, tuple]] = {}
    sub_metrics: dict[int, dict] = {}
    half = n_windows // 2
    ck_dir = tempfile.TemporaryDirectory(prefix="memprobe_")
    ck = os.path.join(ck_dir.name, "batch.npz")
    for i in range(0, E, sub):
        say(f"sub-batch lanes [{i}, {min(i + sub, E)}) "
            f"(ckpt cycle at window {half})")
        eng_b = FleetEngine(plan.exps[i:i + sub], params,
                            plan.max_rounds[i:i + sub])
        eng_b.exp_base = i
        if half > 0:
            # Mid-batch checkpoint cycle: snapshot, reload into a FRESH
            # engine, continue — the per-sub-batch resume path of
            # --on-oom downshift + --ckpt must be digest-neutral.
            save_state(eng_b.run(n_windows=half), ck)
            eng_b = FleetEngine(plan.exps[i:i + sub], params,
                                plan.max_rounds[i:i + sub])
            eng_b.exp_base = i
            st_b = eng_b.run(load_state(eng_b.init_state(), ck),
                             n_windows=n_windows - half)
        else:
            st_b = eng_b.run(n_windows=n_windows)
        jax.block_until_ready(st_b)
        sub_streams.update(_lane_streams(eng_b, st_b))
        for j, m in enumerate(fleet_metrics_per_exp(st_b)):
            sub_metrics[i + j] = m
    ck_dir.cleanup()
    verdict = {"config": path, "experiments": E, "lanes_per_batch": sub,
               "windows": n_windows, "ckpt_cycled": half > 0,
               "streams_compared": len(full_streams)}
    for e in range(E):
        f, s = full_streams.get(e, {}), sub_streams.get(e, {})
        if f != s:
            bad = next((w for w in sorted(f) if f.get(w) != s.get(w)),
                       None)
            verdict.update(
                ok=False, diverged={"exp": e, "window": bad,
                                    "kind": "digest_stream"},
                hint=f"bisect lane {e} solo: python -m shadow1_tpu.tools."
                     f"paritytrace {path} tpu cpu")
            return verdict
        fm = {k: full_metrics[e].get(k, 0) for k in counters}
        sm = {k: sub_metrics[e].get(k, 0) for k in counters}
        if fm != sm:
            diff = {k: [fm[k], sm[k]] for k in counters if fm[k] != sm[k]}
            verdict.update(ok=False,
                           diverged={"exp": e, "kind": "metrics",
                                     "fields": diff})
            return verdict
    verdict["ok"] = True
    return verdict


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="shadow1_tpu.tools.memprobe")
    ap.add_argument("configs", nargs="+", help="YAML experiment file(s)")
    ap.add_argument("--audit", action="store_true",
                    help="estimator-vs-live-bytes audit (default mode)")
    ap.add_argument("--fleet", action="store_true",
                    help="audit the config's sweep: as a fleet state")
    ap.add_argument("--maxfit", action="store_true",
                    help="binary-search max feasible hosts/lanes")
    ap.add_argument("--subbatch", action="store_true",
                    help="sub-batched-fleet == full-fleet parity proof")
    ap.add_argument("--sub", type=int, default=None,
                    help="lanes per sub-batch (default ceil(E/2))")
    ap.add_argument("--windows", type=int, default=None,
                    help="windows for the --subbatch comparison")
    ap.add_argument("--budget", type=int, default=None,
                    help="byte budget for --maxfit (default: backend "
                         "reported / SHADOW1_MEM_BYTES)")
    ap.add_argument("--json-only", action="store_true",
                    help="suppress progress lines; print only the verdict")
    args = ap.parse_args(argv)

    import shadow1_tpu  # noqa: F401  (x64 before jax arrays)
    from shadow1_tpu import mem

    def say(msg):
        if not args.json_only:
            print(f"[memprobe] {msg}", file=sys.stderr, flush=True)

    if not (args.audit or args.maxfit or args.subbatch):
        args.audit = True
    rc = 0
    out: dict = {"ok": True}
    if args.audit:
        rows = []
        for cfg in args.configs:
            say(f"audit {cfg}")
            row = audit_config(cfg, fleet=args.fleet)
            say(f"  estimated {mem.fmt_bytes(row['estimated_resident'])} "
                f"vs measured {mem.fmt_bytes(row['measured_live'])} "
                f"(ratio {row['ratio']}) "
                f"{'ok' if row['ok'] else 'OUT OF TOLERANCE'}")
            rows.append(row)
        out["audit"] = rows
        if not all(r["ok"] for r in rows):
            out["ok"] = False
            rc = EXIT_AUDIT_FAILED
    if args.maxfit:
        budget = args.budget
        if budget is None:
            budget, src = mem.device_budget()
            if budget is None:
                print("memprobe: no budget (cpu backend reports none; "
                      "pass --budget or set SHADOW1_MEM_BYTES)",
                      file=sys.stderr)
                print(json.dumps({"ok": False, "error": "no_budget"}))
                return 2
        out["maxfit"] = [maxfit(cfg, budget) for cfg in args.configs]
    if args.subbatch:
        v = subbatch_parity(args.configs[0], args.sub, args.windows, say)
        out["subbatch"] = v
        if not v["ok"]:
            out["ok"] = False
            rc = EXIT_DIVERGED
    print(json.dumps(out))
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
