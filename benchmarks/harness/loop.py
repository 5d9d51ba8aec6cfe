"""One run: set-up, the measured window, the check, the result line.

The work of a run is whole *cycles*: the first ``cycle_windows`` windows of
the simulation from its initial state, in chunks of ``chunk_windows`` through
the program's chunk runner, again and again until ``--seconds`` have passed
(the cycle that crosses the line counts in full). A simulation is not
stationary (a Tor network builds circuits, then streams), so a rate over a
fixed stretch of simulated time is the only one that two commits of different
speed can be compared by. Every cycle must end on the same counters, and the
first cycle's are held against the reference's, counter for counter.

Each cycle starts from an initial state made anew between cycles, outside the
clock: the window's wall is the sum of its cycles', and the run never holds a
state that a user's run would not (the state going into a chunk and the one
coming out). What the check reads of a cycle's end is fetched to the host
between cycles, off the clock too, and nothing of it stays on the device.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import time

from benchmarks.harness import manifest as mf

# Exit codes other than 0 print no result line.
EXIT_NO_CHIP = 3
EXIT_COMPILED_IN_WINDOW = 4


def _say(**kw) -> None:
    print(json.dumps(kw), flush=True)


def _args(argv):
    ap = argparse.ArgumentParser(prog="benchmarks/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None,
                    help="a deliberately wrong run from controls/<name>.json: "
                         "tests and limits only, never a measurement")
    ap.add_argument("--keep-trace", default=None,
                    help="also write the traced window, reduced to a plain "
                         "dict, to this .json.gz (for a recorded test trace)")
    return ap.parse_args(argv)


def _device(chips: int, require_chip: bool) -> dict | None:
    """The devices as jax reports them, or None where they are not the
    chips this cell asks for."""
    import jax

    devs = jax.devices()
    if require_chip and (devs[0].platform == "cpu" or len(devs) < chips):
        print(f"benchmarks/run.py: this cell needs {chips} accelerator "
              f"chip(s); jax came up with {len(devs)} x {devs[0].platform}. "
              "No result.", file=sys.stderr)
        return None
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def _tree_bytes(tree) -> int:
    import jax

    return int(sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree)))


class _Spans:
    """Host-clock spans of set-up, by name, in seconds."""

    def __init__(self):
        self.seconds: dict[str, float] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.seconds[name] = (self.seconds.get(name, 0.0)
                                  + time.perf_counter() - t0)


def _annot(name: str):
    """A harness span on the profiler's clock (free when no trace runs)."""
    import jax

    from benchmarks.harness.trace import SPAN_PREFIX

    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def run_chunk(sim, st, windows: int):
    """One chunk through the program's chunk runner, to its end."""
    import jax

    from shadow1_tpu import ckpt

    with _annot("run-chunk"):
        st = ckpt.run_chunked(sim.engine, st, n_windows=windows, chunk=windows)
    with _annot("block"):
        jax.block_until_ready(st)
    return st


def _load_cell(root: str, args) -> dict:
    """The manifest, the cell and the data files it names."""
    man = mf.load(root)
    cell, cfg_entry = mf.cell(man, args.workload)
    cfg_path = os.path.join(root, cfg_entry["file"])
    traffic = mf.read_json(mf.find(root, man, "traffic",
                                   cell["traffic"] + ".json"))
    control = (mf.read_json(mf.find(root, man, "controls",
                                    mf.check_name(args.control, "control")
                                    + ".json"))
               if args.control else {})
    chunk, cycle = int(traffic["chunk_windows"]), int(traffic["cycle_windows"])
    t_from = int(traffic.get("trace_from_window", 0))
    t_to = t_from + int(traffic["trace_chunks"]) * chunk
    if chunk < 1 or cycle % chunk or t_from % chunk or t_to > cycle:
        raise mf.ManifestError(
            f"traffic {cell['traffic']}: a cycle of {cycle} windows must be "
            f"whole chunks of {chunk}, and the traced windows "
            f"{t_from}..{t_to} whole chunks inside it")
    return {"man": man, "cell": cell, "cfg_path": cfg_path,
            "meta": mf.read_json(cfg_path), "traffic": traffic,
            "control": control, "chunk": chunk, "cycle": cycle,
            "traced": (t_from, t_to)}


def _window(sim, fresh, c: dict, seconds: float, trace_dir: str | None):
    """Whole cycles, each from ``fresh()``, until their summed wall reaches
    ``seconds`` (one cycle when tracing, the profiler on over the traced
    chunks). Returns every cycle's final metrics, what the check reads of
    the first cycle's end (both as host copies, fetched between cycles off
    the clock), the metrics at the traced stretch's start and end, the
    summed wall of the cycles, and every chunk's wall (to find a stall by)."""
    import jax

    t_from, t_to = c["traced"]
    finals, kept, traced, wall, chunk_walls = [], None, [], 0.0, []
    while True:
        st = fresh()
        jax.block_until_ready(st)
        t0 = at = time.perf_counter()
        for done in range(0, c["cycle"], c["chunk"]):
            if trace_dir and done == t_from:
                traced.append(jax.device_get(st.metrics))
                shutil.rmtree(trace_dir, ignore_errors=True)
                jax.profiler.start_trace(trace_dir)
            st = run_chunk(sim, st, c["chunk"])
            if trace_dir and done + c["chunk"] == t_to:
                jax.profiler.stop_trace()
                traced.append(jax.device_get(st.metrics))
            at, was = time.perf_counter(), at
            chunk_walls.append(at - was)
        wall += at - t0
        finals.append(jax.device_get(st.metrics))
        if kept is None:
            kept = sim.keep(st)
        del st
        if trace_dir or wall >= seconds:
            return finals, kept, traced, wall, chunk_walls


def loop_rounds(per_window) -> int:
    """Iterations of the round loop over some windows, from the rounds each
    lane advanced in each (``[window][lane]``): a fleet's loop is one
    ``while`` over all lanes and runs every window to that window's slowest
    lane, so the sum over windows of the maximum over lanes."""
    return int(sum(max(int(r) for r in lanes) for lanes in per_window))


def _replay_rounds(sim, c: dict, traced_end):
    """The traced stretch again, after the window, one window at a call, to
    read what a chunk hides: the rounds every lane advanced in every
    window. Returns ``[window][lane]``; the stretch must end on the
    counters the traced run ended on."""
    import jax
    import numpy as np

    def rounds(st):
        return np.asarray(jax.device_get(st.metrics.rounds)).reshape(-1)

    t_from, t_to = c["traced"]
    st = sim.engine.init_state()
    if t_from:
        st = run_chunk(sim, st, t_from)
    per_window, before = [], rounds(st)
    for _ in range(t_from, t_to):
        st = run_chunk(sim, st, 1)
        now = rounds(st)
        per_window.append((now - before).tolist())
        before = now
    end = jax.device_get(st.metrics)
    if not all((a == b).all() for a, b in zip(end, traced_end)):
        raise RuntimeError("the traced stretch, run again window by window, "
                           "ends on other counters than the traced run")
    return per_window


def _check(sim, kept, finals, seeds, ref_exps, ref_params, c: dict):
    """Every cycle's end against the first's, and the first's, lane by lane,
    against the reference. Prints each number compared beside the
    reference's; the limit on every difference, and on every counter under
    ``must_be_zero``, is 0. Returns the lanes' counters, how many lanes
    failed, the reference's wall seconds, and every number compared as
    ``{name: [number, limit]}``: per counter the largest difference from the
    reference over the lanes, per ``must_be_zero`` counter its largest
    value, and the cycles that ended on other counters than the first."""
    from benchmarks.reference import comparator

    lanes = sim.lane_counters(kept)
    unlike = sum(not all((a == b).all() for a, b in zip(finals[0], f))
                 for f in finals[1:])
    same = not unlike
    _say(cycles=len(finals), every_cycle_ends_on_the_same_counters=same)
    ref_wall, failed, worst = 0.0, 0, {}
    for e, have in enumerate(lanes):
        ref_seed = seeds[e] + int(c["control"].get("reference_seed_offset", 0))
        ref = comparator.counters(ref_exps[e], ref_params, ref_seed, c["cycle"])
        ref_wall += ref["wall_s"]
        compared = {k: [have.get(k), v] for k, v in ref.items()
                    if k not in comparator.NOT_COUNTERS}
        differ = sorted(k for k, (a, b) in compared.items() if a != b)
        dropped = {k: have[k] for k in c["meta"]["must_be_zero"] if have.get(k)}
        bad = bool(differ or dropped or not same)
        failed += bad
        for k, (a, b) in compared.items():
            # A counter the engine does not have differs by all of it.
            gap = abs(a - b) if a is not None else max(1, abs(b))
            worst[k] = max(worst.get(k, 0), gap)
        for k in c["meta"]["must_be_zero"]:
            name = k + ".must_be_zero"
            worst[name] = max(worst.get(name, 0), have.get(k, 0))
        # How near the run came to a cap (never compared: a run that
        # reaches one shows in must_be_zero).
        gauges = {k: have[k] for k in ("rounds", "ev_max_fill", "ob_max_fill")
                  if k in have}
        _say(lane=e, seed=seeds[e], reference_seed=ref_seed,
             windows=c["cycle"], engine_vs_reference=compared, limit=0,
             differ=differ, must_be_zero=dropped, gauges=gauges, ok=not bad)
        if bad:
            # A check that refuses a run shows only the end of stderr.
            print(f"benchmarks/run.py: NOT CORRECT lane {e} seed {seeds[e]}: "
                  f"differ {({k: compared[k] for k in differ})}, must be zero "
                  f"{dropped}, gauges {gauges}, cycles end alike {same}",
                  file=sys.stderr, flush=True)
    worst["cycles_ending_unlike_the_first"] = unlike
    return lanes, failed, ref_wall, {k: [v, 0] for k, v in worst.items()}


def _prime_cache(c: dict, doc: dict, base_dir: str, prime_seeds) -> None:
    """Pin what set-up pays in a cell whose program is the seed's own (a
    solo engine closes over its key). The persistent cache is written once
    in a checkout, by a build under the configuration's
    ``compile_cache.written_under_seed``, and only read after: every run
    meets a cache that another seed has warmed and its own seed never has,
    whichever seeds ran before it."""
    import jax

    from benchmarks.harness import sim as simmod

    marker = os.path.join(
        jax.config.jax_compilation_cache_dir,
        f".written.{c['cell']['name']}.{prime_seeds[0]}")
    if not os.path.exists(marker):
        other = simmod.build(doc, base_dir, c["meta"]["engine"], prime_seeds)
        jax.block_until_ready(
            run_chunk(other, other.engine.init_state(), c["chunk"]))
        del other
        os.makedirs(os.path.dirname(marker), exist_ok=True)
        open(marker, "w").close()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e9)


def _layer_values(root, c, red, counters, spans) -> dict:
    values = {}
    for m in mf.metrics_of(c["man"], "per_layer", c["cell"]["name"]):
        v = mf.reader(root, c["man"], "layer_metrics", m["name"])(
            red, counters, spans)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    return values


def main(argv, root: str, started: float, require_chip: bool = True) -> int:
    args = _args(argv)
    spans = _Spans()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(root, ".jax_cache"))
    c = _load_cell(root, args)
    cell, traffic, control = c["cell"], c["traffic"], c["control"]

    with spans.span("imports"):
        import jax
        import shadow1_tpu  # noqa: F401  (x64 on, before any jax array)

        from benchmarks.harness import phases
        from benchmarks.harness import sim as simmod
        from benchmarks.harness import trace as tr
        from benchmarks.harness.meter import CompileMeter
        from benchmarks.reference import comparator

    with spans.span("backend"):
        device = _device(int(cell["chips"]), require_chip)
    if device is None:
        return EXIT_NO_CHIP
    peaks = mf.read_json(os.path.join(os.path.dirname(__file__), "peaks.json"))
    if require_chip and device["kind"] not in peaks:
        raise SystemExit(f"benchmarks/harness/peaks.json has no device "
                         f"{device['kind']!r}: add it with its source")
    _say(run=cell["name"], seed=args.seed, control=args.control, **device)

    # ---- set-up ----------------------------------------------------------
    meter = CompileMeter()
    seeds = simmod.lane_seeds(traffic, args.seed)
    engine_kind = c["meta"]["engine"]
    with spans.span("build"):
        doc, base_dir = simmod.experiment_doc(c["cfg_path"], c["meta"], traffic)
    prime_seed = (c["meta"].get("compile_cache") or {}).get("written_under_seed")
    if prime_seed is not None and not args.control:
        with spans.span("prime"):
            # Under that seed itself, whatever the mix makes of ``--seed``
            # (a pool's run would otherwise warm the cache for itself).
            _prime_cache(c, doc, base_dir,
                         [int(prime_seed) + i for i in range(len(seeds))])
    with spans.span("build"):
        sim = simmod.build(simmod.merge(doc, control.get("program") or {}),
                           base_dir, engine_kind, seeds)
        # The reference is handed the experiment as the files state it, not
        # as a control has bent it for the program.
        ref_exps, ref_params = (
            simmod.compile_only(doc, base_dir, engine_kind, seeds)
            if control.get("program") else (sim.exps, sim.loaded_params))
        comparator.prepare(ref_exps[0].model)
        first = [sim.engine.init_state()]
        jax.block_until_ready(first)
        state_bytes = _tree_bytes(first[0])
    with spans.span("warmup"):
        # The one program the window drives, compiled or loaded, then run
        # once; the warm-up's state is dropped.
        jax.block_until_ready(run_chunk(sim, first[0], c["chunk"]))
    setup = meter.snapshot()
    setup_seconds = time.perf_counter() - started

    def fresh():
        """The first cycle's initial state is set-up's; later ones are made
        anew, so that no cycle runs with an extra state held."""
        return first.pop() if first else sim.engine.init_state()

    # ---- the measured window, then the check outside it ------------------
    trace_dir = os.path.join(root, ".bench_trace") if args.trace else None
    finals, kept, traced, window_s, chunk_walls = _window(
        sim, fresh, c, args.seconds, trace_dir)
    in_window = {k: v - setup[k] for k, v in meter.snapshot().items()}
    meter.close()
    peak = _peak_bytes()
    if in_window["backend_compiles"] or in_window["persistent_misses"]:
        print(f"benchmarks/run.py: jax compiled inside the measured window "
              f"({in_window}); the warm-up does not cover what the window "
              "runs. No result.", file=sys.stderr)
        return EXIT_COMPILED_IN_WINDOW
    lanes, failed, ref_wall, compared = _check(sim, kept, finals, seeds,
                                               ref_exps, ref_params, c)
    events = len(finals) * sum(ln["events"] for ln in lanes)
    windows = len(finals) * c["cycle"]
    _say(windows_run=windows, window_wall_s=window_s, chunk_walls_s=chunk_walls,
         sim_s_per_wall_s=windows * sim.engine.window / 1e9 / window_s,
         setup_spans_s=spans.seconds, setup_compile=setup, lanes=sim.lanes,
         reference_events_per_s=events / len(finals) / ref_wall,
         engine_over_reference=len(finals) * ref_wall / window_s)

    # ---- the result line --------------------------------------------------
    dev = {**device, "memory_peak_bytes": peak}
    result = {"correct": failed == 0, "attempted": sim.lanes, "failed": failed}
    if args.trace:
        after = _Spans()    # what a traced run costs after its window
        with after.span("read_capture"):
            raw = tr.read_xplane(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        red = tr.reduce(raw)
        # The round loop's iterations in the traced chunks: per window, the
        # slowest lane's rounds (a fleet's loop is one while over all lanes).
        with after.span("replay_rounds"):
            per_window = _replay_rounds(sim, c, traced[1])
        # Device time by phase: the join of the op line with the compiled
        # program's scopes (the executable is in memory; nothing recompiles).
        with after.span("phase_table"):
            table = phases.phase_table(sim.engine.hlo_text())
        with after.span("phase_report"):
            report = phases.phase_report(raw, table)
            gaps = phases.gap_report(raw, table)
        counters = {"rounds": loop_rounds(per_window),
                    "windows": len(per_window)}
        counters.update(
            lanes=sim.lanes, chunks=int(traffic["trace_chunks"]),
            state_bytes=state_bytes,
            compile_seconds=setup["seconds"],
            persistent_cache_misses=setup["persistent_misses"],
            persistent_cache_hits=setup["persistent_hits"],
            hbm_bytes_per_s=peaks.get(device["kind"], {}).get("hbm_bytes_per_s"),
            **phases.counters_of(raw, report, table, *traced))
        values = _layer_values(root, c, red, counters, spans.seconds)
        dev.update(busy_s=red.busy_ns / 1e9, window_s=red.window_ns / 1e9)
        result["breakdown"] = {"device_ops": red.device_ops,
                               "idle_gaps": red.idle_gaps}
        _say(traced_windows=list(c["traced"]), trace_devices=red.n_devices,
             executions=red.executions, loop_rounds=counters["rounds"],
             lane_rounds_per_window=per_window,
             # The trace's own count of the loop: an op of the round body
             # that no branch guards runs once an iteration.
             op_names_seen_once_a_round=tr.names_seen(raw, counters["rounds"]),
             after_the_window_s=after.seconds)
        _say(phases=report["rows"], rollup=report["rollup"],
             phases_busy_s=report["busy_s"], unknown_ops=report["unknown_ops"],
             inherited_s=report["inherited_s"], table_instructions=len(table),
             program_spans=sorted({n for n, _, _ in tr.program_spans(raw)}),
             fires_by_lane=counters["fires_by_lane"],
             handler_kinds=counters["handler_kinds"],
             **gaps)
        if args.keep_trace:
            phases.keep(args.keep_trace, raw, table, {
                k: counters[k] for k in ("rounds", "windows", "fires_by_lane",
                                         "handler_kinds")})
    else:
        window = {"events": events, "wall_s": window_s,
                  "setup_seconds": setup_seconds, "peak_bytes": peak}
        values = {
            m["name"]: {"value": mf.reader(root, c["man"], "end_to_end",
                                           m["name"])(window),
                        "unit": m["unit"]}
            for m in mf.metrics_of(c["man"], "end_to_end", cell["name"])}
    # Every number compared beside its limit: last in the result line, and
    # the last lines of stderr (all that a check keeps of a refused run).
    result.update(metrics=values, device=dev, compared=compared)
    for name, (number, limit) in compared.items():
        print(f"compared {name} {number} limit {limit}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
