"""North-star benchmark: batched-engine event throughput vs the CPU oracle.

Runs the PHOLD engine-stress workload (SURVEY §4 — the reference's scheduler
benchmark, src/test/phold/) on the batched TPU engine and on the sequential
CPU reference engine, and prints ONE JSON line:

    {"metric": "phold_events_per_sec", "value": N, "unit": "events/s",
     "vs_baseline": tpu_events_per_sec / baseline_events_per_sec, ...}

``vs_baseline`` divides by the honest thread-per-core C++ DES
(detail.cpp_thread_per_core, SURVEY §7.3.5) run on the same config; a
machine that cannot build it cannot run this benchmark.

Contract:
* It measures the accelerator or nothing. jax picks the platform; when that
  platform is the CPU, or on any exception, the error is printed and the
  exit code is non-zero — there is no smaller retry and no CPU row.
* Every row names where it ran (``platform``/``device_kind``/``n_devices``,
  shadow1_tpu.platform.describe).
* The timed loop runs in CHUNKS of <=50 windows via ckpt.run_chunked;
  compile time is reported separately from timed walls.
* The answer is checked, not only timed: the C++ comparator's event count
  for its window slice must equal the engine's at the same window
  (``detail.cpp_events_match``), else the run fails.

The Python oracle is measured on a smaller host count (the eager oracle is
O(events) Python; PHOLD cost/event is scale-stable) and reported for scale
only — see ``detail.python_oracle`` for its exact config.
"""

from __future__ import annotations

import json
import time

# Benchmark workload: dense PHOLD at TPU-native scale (classic PHOLD uses
# ~10+ live events per LP; denser windows amortize the per-round fixed cost
# across more hosts — that IS the engine's design point).
N_HOSTS = 65536
INIT_EVENTS = 16
MEAN_DELAY_MS = 2
WINDOW_MS = 1
SIM_WINDOWS = 500
CHUNK = 50

CPU_HOSTS = 1024
CPU_WINDOWS = 2

# The C++ comparator runs this many windows (PHOLD is stationary, so a
# slice gives a stable events/sec); a whole number of CHUNKs, so the engine
# has a chunk boundary at the same window to compare event counts at.
CPP_WINDOWS = 2 * CHUNK


# Fleet sweep row (bench.py --fleet): E phold seed variants answered as one
# vmapped program vs E sequential solo runs — the sweep-throughput claim
# (ROADMAP Reach 1). The fleet's win is FIXED-COST amortization: one
# compile/launch bill for all E lanes instead of 16 (a solo engine
# re-traces per seed — the key is a closed-over constant). Sized to the
# regime where that fixed cost matters (small planes, many windows). The
# run-only ratio is reported alongside, unspun.
FLEET_E = 16
FLEET_HOSTS = 32
FLEET_WINDOWS = 200


def _experiment(n_hosts: int, windows: int):
    from shadow1_tpu.config.compiled import single_vertex_experiment
    from shadow1_tpu.consts import MS

    return single_vertex_experiment(
        n_hosts=n_hosts,
        seed=1234,
        end_time=windows * WINDOW_MS * MS,
        latency_ns=WINDOW_MS * MS,
        model="phold",
        model_cfg={"mean_delay_ns": float(MEAN_DELAY_MS * MS), "init_events": INIT_EVENTS},
    )


def _params():
    from shadow1_tpu.consts import EngineParams

    return EngineParams(ev_cap=48, outbox_cap=24, max_rounds=128)


def run_tpu(n_hosts: int, windows: int) -> dict:
    """Time the batched engine; also returns ``events_at_cpp_windows``, its
    event count at the CPP_WINDOWS chunk boundary."""
    import jax

    from shadow1_tpu import ckpt
    from shadow1_tpu.core.engine import Engine

    eng = Engine(_experiment(n_hosts, windows), _params())
    # Compile both chunk sizes (full chunk + any ragged tail) before timing.
    t0 = time.perf_counter()
    warm = eng.run(eng.init_state(), n_windows=CHUNK)
    tail = windows % CHUNK
    if tail:
        warm = eng.run(eng.init_state(), n_windows=tail)
    jax.block_until_ready(warm)
    compile_wall = time.perf_counter() - t0

    chunk_walls: list[float] = []
    last = time.perf_counter()
    events_at = None  # device scalar, read after the timed loop

    def on_chunk(st, done):
        nonlocal last, events_at
        jax.block_until_ready(st)
        now = time.perf_counter()
        chunk_walls.append(now - last)
        last = now
        if done == CPP_WINDOWS:
            events_at = st.metrics.events

    t0 = time.perf_counter()
    st = ckpt.run_chunked(eng, n_windows=windows, chunk=CHUNK, on_chunk=on_chunk)
    jax.block_until_ready(st)
    wall = time.perf_counter() - t0
    m = Engine.metrics_dict(st)
    return {
        "events": m["events"],
        "wall_s": wall,
        "events_per_sec": m["events"] / wall,
        "sim_sec_per_wall_sec": (windows * WINDOW_MS / 1000.0) / wall,
        "compile_wall_s": compile_wall,
        "n_chunks": len(chunk_walls),
        "chunk_wall_min_s": min(chunk_walls),
        "chunk_wall_max_s": max(chunk_walls),
        "ev_overflow": m["ev_overflow"],
        "ob_overflow": m["ob_overflow"],
        "rounds_per_window": m["rounds"] / max(m["windows"], 1),
        "events_at_cpp_windows": int(events_at),
        "backend": jax.default_backend(),
        "n_hosts": n_hosts,
        "windows": windows,
    }


def run_cpu_oracle() -> dict:
    from shadow1_tpu.cpu_engine import CpuEngine

    cpu = CpuEngine(_experiment(CPU_HOSTS, CPU_WINDOWS), _params())
    t0 = time.perf_counter()
    cm = cpu.run(n_windows=CPU_WINDOWS)
    wall = time.perf_counter() - t0
    return {
        "n_hosts": CPU_HOSTS,
        "windows": CPU_WINDOWS,
        "events": cm["events"],
        "wall_s": wall,
        "events_per_sec": cm["events"] / wall,
    }


def run_cpp_baseline(n_hosts: int) -> dict:
    """The honest thread-per-core baseline (SURVEY §7.3.5): the C++
    multi-core DES on the SAME experiment config for CPP_WINDOWS windows
    (counters bit-match the oracle and the TPU engine — tests/test_native_
    comparator.py, and main() re-checks the event count on every run).
    Reported as the best of (one thread per available core, 16 shards) —
    on a single-core box extra shards still help via smaller,
    cache-resident heaps, and the baseline should be the CPU's best foot."""
    import os

    from shadow1_tpu import native

    variants = []
    for nt in dict.fromkeys((os.cpu_count() or 1, 16)):
        r = native.run_phold(
            n_hosts=n_hosts, seed=1234, n_windows=CPP_WINDOWS,
            window_ns=WINDOW_MS * 10**6, mean_delay_ns=MEAN_DELAY_MS * 1e6,
            init_events=INIT_EVENTS, ev_cap=_params().ev_cap,
            outbox_cap=_params().outbox_cap, n_threads=nt,
        )
        variants.append(
            {"n_threads": nt, "events": r["events"], "wall_s": r["wall_s"],
             "events_per_sec": r["events_per_sec"]}
        )
    return {
        "kind": "cpp_thread_per_core",
        "n_hosts": n_hosts,
        "windows": CPP_WINDOWS,
        "cpu_cores": os.cpu_count(),
        "variants": variants,
        "best": max(variants, key=lambda v: v["events_per_sec"]),
    }


def _fleet_experiments(n_hosts: int, windows: int) -> list:
    from shadow1_tpu.config.compiled import single_vertex_experiment
    from shadow1_tpu.consts import MS

    return [
        single_vertex_experiment(
            n_hosts=n_hosts, seed=1234 + i,
            end_time=windows * WINDOW_MS * MS, latency_ns=WINDOW_MS * MS,
            model="phold",
            model_cfg={"mean_delay_ns": float(MEAN_DELAY_MS * MS),
                       "init_events": INIT_EVENTS},
        )
        for i in range(FLEET_E)
    ]


def run_fleet_bench(n_hosts: int = FLEET_HOSTS,
                    windows: int = FLEET_WINDOWS) -> dict:
    """E=16 phold seed variants: one vmapped fleet run vs 16 sequential
    solo runs, both chunked (<=CHUNK windows per program) and both paying
    their real compile bills — a solo engine re-traces per seed (the key
    is a closed-over constant), which IS the sequential cost the fleet
    amortizes away along with the per-kernel launches."""
    import jax

    from shadow1_tpu import ckpt
    from shadow1_tpu.core.engine import Engine
    from shadow1_tpu.fleet.engine import FleetEngine

    exps = _fleet_experiments(n_hosts, windows)
    params = _params()

    # -- fleet: one program for all E experiments --
    t0 = time.perf_counter()
    fleet = FleetEngine(exps, params)
    st0 = fleet.init_state()
    jax.block_until_ready(fleet.run(st0, n_windows=0))
    fleet_compile = time.perf_counter() - t0
    t0 = time.perf_counter()
    stf = ckpt.run_chunked(fleet, st0, n_windows=windows, chunk=CHUNK)
    jax.block_until_ready(stf)
    fleet_run_wall = time.perf_counter() - t0
    fleet_events = sum(m["events"] for m in fleet.metrics_per_exp(stf))
    fleet_total = fleet_compile + fleet_run_wall

    # -- sequential: E solo engines, each with its own compile + run --
    seq_compile = 0.0
    seq_run_wall = 0.0
    seq_events = 0
    for exp in exps:
        t0 = time.perf_counter()
        eng = Engine(exp, params)
        s0 = eng.init_state()
        jax.block_until_ready(eng.run(s0, n_windows=0))
        seq_compile += time.perf_counter() - t0
        t0 = time.perf_counter()
        s = ckpt.run_chunked(eng, s0, n_windows=windows, chunk=CHUNK)
        jax.block_until_ready(s)
        seq_run_wall += time.perf_counter() - t0
        seq_events += Engine.metrics_dict(s)["events"]
    seq_total = seq_compile + seq_run_wall

    return {
        "experiments": FLEET_E,
        "n_hosts": n_hosts,
        "windows": windows,
        "fleet": {
            "compile_wall_s": fleet_compile,
            "run_wall_s": fleet_run_wall,
            "total_wall_s": fleet_total,
            "events": fleet_events,
            "events_per_sec": fleet_events / fleet_run_wall,
        },
        "sequential": {
            "compile_wall_s": seq_compile,
            "run_wall_s": seq_run_wall,
            "total_wall_s": seq_total,
            "events": seq_events,
            "events_per_sec": seq_events / seq_run_wall,
        },
        "events_match": fleet_events == seq_events,
        "speedup_total": seq_total / fleet_total,
        "speedup_run_only": seq_run_wall / fleet_run_wall,
        "fleet_vs_sequential_wall_ratio": fleet_total / seq_total,
        "backend": jax.default_backend(),
    }


def _round(d: dict) -> dict:
    return {k: (round(v, 4) if isinstance(v, float) else v)
            for k, v in d.items()}


def _device() -> dict:
    """describe(), refusing the CPU: this file's metrics are the
    accelerator's, and a CPU wall under their name is not a measurement."""
    import shadow1_tpu  # noqa: F401  (x64 on, before jax arrays exist)
    from shadow1_tpu.platform import describe

    dev = describe()
    if dev["platform"] == "cpu":
        raise SystemExit(
            "bench.py: jax came up on the cpu platform "
            f"({dev['n_devices']} device(s)); this benchmark measures the "
            "accelerator and does not fall back. Run it on a machine with a "
            "chip (and without JAX_PLATFORMS=cpu).")
    return dev


def fleet_main() -> None:
    """bench.py --fleet → one fleet_e16 JSON row."""
    dev = _device()
    detail = run_fleet_bench()
    if not detail["events_match"]:
        raise SystemExit(f"bench.py --fleet: fleet and sequential event "
                         f"counts differ: {json.dumps(detail)}")
    print(json.dumps({
        "metric": "fleet_e16_events_per_sec",
        "value": round(detail["fleet"]["events_per_sec"], 1),
        "unit": "events/s (aggregate across 16 experiments)",
        **dev,
        # The sweep-throughput claim: the whole fleet's wall as a fraction
        # of 16 sequential solo runs.
        "fleet_vs_sequential_wall_ratio": round(
            detail["fleet_vs_sequential_wall_ratio"], 3),
        "detail": {k: (_round(v) if isinstance(v, dict) else v)
                   for k, v in _round(detail).items()},
    }))


def main() -> None:
    dev = _device()
    tpu = run_tpu(N_HOSTS, SIM_WINDOWS)
    cpp = run_cpp_baseline(N_HOSTS)
    cpu = run_cpu_oracle()
    base = cpp["best"]
    detail = {
        **_round(tpu),
        "baseline_kind": "cpp_thread_per_core",
        "cpp_thread_per_core": cpp,
        "cpp_events_match": all(v["events"] == tpu["events_at_cpp_windows"]
                                for v in cpp["variants"]),
        "python_oracle": _round(cpu),
    }
    # A timing of a wrong answer is not a result: no row is printed.
    if not detail["cpp_events_match"]:
        raise SystemExit(
            f"bench.py: after {CPP_WINDOWS} windows the engine and the C++ "
            f"comparator disagree on the event count: {json.dumps(detail)}")
    if tpu["ev_overflow"] or tpu["ob_overflow"]:
        raise SystemExit(
            f"bench.py: overflow counters non-zero, the caps dropped "
            f"events: {json.dumps(detail)}")
    print(json.dumps({
        "metric": "phold_events_per_sec",
        "value": round(tpu["events_per_sec"], 1),
        "unit": "events/s",
        "vs_baseline": round(tpu["events_per_sec"] / base["events_per_sec"],
                             3),
        **dev,
        "detail": detail,
    }))


if __name__ == "__main__":
    import sys

    if sys.argv[1:] == ["--fleet"]:
        fleet_main()
    elif sys.argv[1:]:
        raise SystemExit("usage: python bench.py [--fleet]")
    else:
        main()
