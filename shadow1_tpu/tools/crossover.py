"""The dense-scale crossover sweep: batched engine vs eager C++ by host count.

    python -m shadow1_tpu.tools.crossover [--hosts 2000,5000,...]
        [--windows N] [--cpp-windows N] [--json PATH]

The architecture thesis (docs/PERF.md "crossover"): an eager per-event DES
pays per event and collapses as its random-access working set leaves cache;
the batched engine pays per ROUND and rises with density as the fixed round
cost amortizes across SIMD lanes. This tool measures both sides of that
claim on the same workload — the dense tgen mesh of
``configs/dense_tgen50k.yaml`` scaled to each host count — and emits one
JSON row per size:

    {"n_hosts": N, "tpu_events_per_sec": ..., "cpp_events_per_sec": ...,
     "tpu_vs_cpp": ...}

Methodology: each batched run executes in a CHILD process (a device fault
can wedge a process, and the parent — which runs the C++ side — must not
hold the chip), timed over chunked 10-window device calls with the
compile excluded via a 0-window warmup; the C++ thread-per-core comparator (SURVEY §7.3.5) runs
the same config for ``--cpp-windows`` whole windows (its per-event cost is
stationary, so a shorter slice gives a stable rate). Where both sides run
the same window count the event counters must bit-match (the parity
contract); with different slices the row records both counts.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time

DEFAULT_HOSTS = (2000, 5000, 10000, 20000, 50000)
CHUNK = 10


def dense_doc(n_hosts: int) -> dict:
    """configs/dense_tgen50k.yaml scaled to ``n_hosts`` (same per-host
    parameters; only the count changes). Loaded from the yaml so the
    exhibit config has ONE source of truth."""
    import os

    import yaml

    path = os.path.join(os.path.dirname(__file__), "..", "..", "configs",
                        "dense_tgen50k.yaml")
    with open(path) as f:
        doc = yaml.safe_load(f)
    doc["hosts"][0]["count"] = n_hosts
    return doc


def child_main(n_hosts: int, windows: int) -> int:
    import shadow1_tpu  # noqa: F401
    import jax

    from shadow1_tpu.config.experiment import build_experiment
    from shadow1_tpu.core.engine import Engine
    from shadow1_tpu.platform import describe

    exp, params, _ = build_experiment(dense_doc(n_hosts))
    eng = Engine(exp, params)
    t0 = time.perf_counter()
    jax.block_until_ready(eng.run(eng.init_state(), n_windows=0))
    compile_s = time.perf_counter() - t0

    st = eng.init_state()
    done = 0
    t0 = time.perf_counter()
    while done < windows:
        step = min(CHUNK, windows - done)
        st = eng.run(st, n_windows=step)
        jax.block_until_ready(st)
        done += step
    wall = time.perf_counter() - t0
    m = Engine.metrics_dict(st)
    print(json.dumps({
        **describe(),
        "n_hosts": n_hosts,
        "windows": windows,
        "events": m["events"],
        "wall_s": round(wall, 3),
        "compile_s": round(compile_s, 2),
        "events_per_sec": round(m["events"] / wall, 1) if wall else None,
        "rounds_per_window": round(m["rounds"] / max(m["windows"], 1), 1),
        "ev_overflow": m["ev_overflow"],
        "ob_overflow": m["ob_overflow"],
    }))
    return 0


def run_cpp(n_hosts: int, windows: int) -> dict:
    from shadow1_tpu import native
    from shadow1_tpu.config.experiment import build_experiment

    exp, params, _ = build_experiment(dense_doc(n_hosts))
    try:
        native.ensure_built()
        import os

        r = native.run_net(exp, params, windows, n_threads=os.cpu_count() or 1)
    except Exception as e:  # noqa: BLE001 — no toolchain -> no baseline
        return {"cpp_error": repr(e)[:300]}
    return {
        "cpp_windows": windows,
        "cpp_events": r["events"],
        "cpp_wall_s": round(r["wall_s"], 3),
        "cpp_events_per_sec": r["events_per_sec"],
        "cpp_threads": r["n_threads"],
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--hosts", default=",".join(map(str, DEFAULT_HOSTS)))
    ap.add_argument("--windows", type=int, default=60,
                    help="batched-engine slice (windows)")
    ap.add_argument("--cpp-windows", type=int, default=None,
                    help="C++ slice (default: same as --windows; shrink at "
                         "large sizes where the eager side crawls)")
    ap.add_argument("--json", default=None)
    ap.add_argument("--skip-tpu", action="store_true",
                    help="only measure the C++ side")
    ap.add_argument("--child", type=int, default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child is not None:
        return child_main(args.child, args.windows)

    import shadow1_tpu  # noqa: F401
    from shadow1_tpu.platform import force_cpu

    # The parent only builds configs and runs the C++ side; the children
    # need the chip.
    force_cpu(1)
    rows = []
    for n in (int(x) for x in args.hosts.split(",")):
        row = {"n_hosts": n}
        if not args.skip_tpu:
            try:
                r = subprocess.run(
                    [sys.executable, "-m", "shadow1_tpu.tools.crossover",
                     "--child", str(n), "--windows", str(args.windows)],
                    capture_output=True, text=True, timeout=1800,
                )
                row.update(json.loads(r.stdout.strip().splitlines()[-1]))
            except subprocess.TimeoutExpired:
                # A wedged device can hang a child forever — bound it and
                # keep sweeping (the C++ side still produces its row).
                row["tpu_error"] = "child exceeded 1800s (wedged device?)"
            except (IndexError, ValueError):
                row["tpu_error"] = (r.stderr[-300:] or f"rc={r.returncode}")
        row.update(run_cpp(n, args.cpp_windows or args.windows))
        if row.get("events_per_sec") and row.get("cpp_events_per_sec"):
            row["tpu_vs_cpp"] = round(
                row["events_per_sec"] / row["cpp_events_per_sec"], 3
            )
            if row.get("windows") == row.get("cpp_windows"):
                row["events_match"] = row["events"] == row["cpp_events"]
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
