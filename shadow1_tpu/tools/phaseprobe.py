"""Per-phase wall attribution of the jitted window program.

    python -m shadow1_tpu.tools.phaseprobe smoke            # dense phold
    python -m shadow1_tpu.tools.phaseprobe cfg.yaml         # any config
    python -m shadow1_tpu.tools.phaseprobe cfg.yaml --device-trace DIR

The performance attribution plane's wall-clock half (the op/fusion half is
tools/opcensus.py). ``core/engine.window_step`` is the composition of the
``window_phases`` stage list — prepare (restart resets, work gauges, the
net model's NIC arrival batch, rebase), rounds (the pop + handler
while-loop), deliver (route + scatter + clear), telem (gauges + the
telemetry-ring row). This tool times each stage as its OWN jitted program
over window frames captured from a real run of the config, so every
ms/round of the straight run attributes to a phase:

* **capture** — run N windows stage-by-stage (same composition, same
  states bit-for-bit) recording each stage's input frame;
* **replay** — for each stage, one jitted ``lax.scan`` maps the stage over
  its N captured inputs; the min wall over reps is that phase's cost for
  those N windows (min, not mean: shared-container noise only ever adds);
* **total** — the straight ``engine.run`` over the same N windows from the
  same start state, same min-over-reps discipline;
* **coverage** — Σ phase wall / straight wall. The phases PARTITION the
  window program, so coverage ≈ 1; the jit boundaries the split adds cost
  extra rather than hiding work, so coverage < 0.9 means the attribution
  is broken (the acceptance gate: ``--min-coverage 0.9`` exits 1).

Two sub-phase rows refine the big stages without entering the coverage
sum (they are contained in their parents, estimated from isolated-primitive
timings × measured rounds/window): ``rounds.pop_est`` (the pop chain — the
rest of ``rounds`` is the handler passes) and ``deliver.route_est`` (the
latency/loss routing — the rest of ``deliver`` is the destination scatter).

Device time by phase of a real run comes from the trace itself:
``python -m shadow1_tpu cfg.yaml --profile DIR`` leaves ``DIR/phases.json``
(telemetry/phases.py). This probe times the stages one by one instead.

Prints one JSON line per phase plus a final summary line on stdout (the
bench.py contract) and an aligned human table on stderr; ``--md`` emits
the markdown attribution table docs/PERF.md commits.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

# The dense-phold smoke shape — tools/benchgate.py's phold row, so the
# attribution and the regression gate describe the same program.
SMOKE_HOSTS = 2048
SMOKE_EV_CAP = 48
SMOKE_OUTBOX_CAP = 24


def build_engine(config: str, metrics_ring: int = 0, hosts: int = SMOKE_HOSTS):
    """(engine, config_label) for a YAML path or the built-in "smoke"."""
    import dataclasses

    from shadow1_tpu.consts import MS, EngineParams
    from shadow1_tpu.core.engine import Engine

    if config == "smoke":
        from shadow1_tpu.config.compiled import single_vertex_experiment

        exp = single_vertex_experiment(
            n_hosts=hosts, seed=1234, end_time=10**15,
            latency_ns=1 * MS, model="phold",
            model_cfg={"mean_delay_ns": float(2 * MS), "init_events": 16},
        )
        params = EngineParams(ev_cap=SMOKE_EV_CAP, outbox_cap=SMOKE_OUTBOX_CAP,
                              max_rounds=128, metrics_ring=metrics_ring)
        return Engine(exp, params), "smoke_phold"
    from shadow1_tpu.config.experiment import load_experiment

    exp, params, scheduler = load_experiment(config)
    if scheduler not in (None, "tpu"):
        raise SystemExit(f"phaseprobe attributes the single-device window "
                         f"program; config asks for scheduler={scheduler!r}")
    if metrics_ring:
        params = dataclasses.replace(params, metrics_ring=metrics_ring)
    import os

    return Engine(exp, params), os.path.basename(config)


def capture_frames(eng, st0, n_windows: int):
    """Run ``n_windows`` stage-by-stage from ``st0``, recording each stage's
    input frames (stacked [N, ...] pytrees). The staged composition IS
    window_step, so the captured states match the straight run bit-for-bit."""
    import jax
    import jax.numpy as jnp

    from shadow1_tpu.core.engine import window_frame, window_phases

    phases = window_phases(eng.ctx, eng._handlers, None, eng._pre_window,
                           eng._model.make_handlers, None)
    jitted = {name: jax.jit(fn) for name, fn in phases}
    inputs = {name: [] for name, _ in phases}
    st = st0
    for _ in range(n_windows):
        fr = window_frame(st, eng.ctx)
        for name, _fn in phases:
            inputs[name].append(fr)
            fr = jitted[name](fr)
        st = fr.st
    stacked = {
        name: jax.tree.map(lambda *xs: jnp.stack(xs), *frs)
        for name, frs in inputs.items()
    }
    return phases, stacked, st


def _time_reps(f, arg, reps: int) -> float:
    """min wall of ``jax.block_until_ready(f(arg))`` over ``reps`` (after a
    compile warmup)."""
    import jax

    jax.block_until_ready(f(arg))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(f(arg))
        best = min(best, time.perf_counter() - t0)
    return best


def _scan_phase(fn):
    """One jitted program mapping ``fn`` over the stacked frames. The scan
    RETURNS the stacked outputs, so XLA cannot dead-code-eliminate any of
    the phase's work."""
    import jax
    import jax.numpy as jnp

    def mapped(frames):
        def body(carry, fr):
            return carry, fn(fr)

        _, outs = jax.lax.scan(body, jnp.zeros((), jnp.int32), frames)
        return outs

    return jax.jit(mapped)


def attribution(eng, n_windows: int = 16, warmup: int = 8, reps: int = 3,
                subphases: bool = True) -> dict:
    """The per-config attribution table: phase → ms/window, ms/round, % of
    the straight run, plus total/coverage. Importable (tests/ci assert on
    the returned dict); the CLI below is a thin printer around it."""
    import jax

    from shadow1_tpu.core.engine import Engine

    st0 = eng.run(eng.init_state(), n_windows=warmup)
    jax.block_until_ready(st0)
    m0 = Engine.metrics_dict(st0)

    # The straight reference: the engine's own jitted window loop.
    def straight(st):
        return eng.run(st, n_windows=n_windows)

    # Every share and the coverage divide by this one reading, and a stall
    # in it moves them all (a stalled phase sample moves one row), so it is
    # the minimum over four times the phases' repetitions.
    total_s = _time_reps(straight, st0, 4 * reps)
    st1 = eng.run(st0, n_windows=n_windows)
    jax.block_until_ready(st1)
    m1 = Engine.metrics_dict(st1)
    rounds = m1["rounds"] - m0["rounds"]
    rpw = rounds / n_windows

    phases, stacked, st_cap = capture_frames(eng, st0, n_windows)
    # Capture must reproduce the straight run exactly — the attribution is
    # meaningless if the staged states drifted.
    assert Engine.metrics_dict(st_cap) == m1, (
        "staged composition diverged from window_step — phase refactor bug"
    )
    total_ms_w = total_s * 1e3 / n_windows
    out = {
        "windows": n_windows,
        "reps": reps,
        "rounds_per_window": round(rpw, 2),
        "ms_per_window": round(total_ms_w, 4),
        "ms_per_round": round(total_s * 1e3 / max(rounds, 1), 4),
        "events": m1["events"] - m0["events"],
        "phases": {},
        "subphases": {},
    }
    phase_sum = 0.0
    for name, fn in phases:
        wall = _time_reps(_scan_phase(fn), stacked[name], reps)
        ms_w = wall * 1e3 / n_windows
        phase_sum += ms_w
        out["phases"][name] = {
            "ms_per_window": round(ms_w, 4),
            "ms_per_round": round(wall * 1e3 / max(rounds, 1), 4),
            "pct": round(100 * ms_w / total_ms_w, 1) if total_ms_w else None,
        }
    out["phases_ms_per_window"] = round(phase_sum, 4)
    out["coverage"] = round(phase_sum / total_ms_w, 3) if total_ms_w else None

    if subphases:
        # Contained estimates (never in the coverage sum): isolate the pop
        # chain and the routing gather on the frames they actually see.
        from shadow1_tpu.core.engine import route_outbox
        from shadow1_tpu.core.events import pop_until

        def pop_fn(fr):
            buf, ev = pop_until(fr.st.evbuf, fr.win_end)
            return fr._replace(st=fr.st._replace(evbuf=buf))

        pop_wall = _time_reps(_scan_phase(pop_fn), stacked["rounds"], reps)
        out["subphases"]["rounds.pop_est"] = {
            "ms_per_window": round(pop_wall * 1e3 * rpw / n_windows, 4),
            "ms_per_round": round(pop_wall * 1e3 / n_windows, 4),
            "note": "one pop x measured rounds/window",
        }

        def route_fn(fr):
            fp, n_sent, n_lost, n_ld = route_outbox(eng.ctx, fr.st.outbox)
            return fr._replace(dg_ob=fr.dg_ob + n_sent + n_lost + n_ld
                               + fp.arrival.sum() + fp.keep.sum())

        route_wall = _time_reps(_scan_phase(route_fn), stacked["deliver"],
                                reps)
        out["subphases"]["deliver.route_est"] = {
            "ms_per_window": round(route_wall * 1e3 / n_windows, 4),
            "ms_per_round": round(route_wall * 1e3 / max(rounds, 1), 4),
            "note": "route_outbox alone on the deliver-phase inputs",
        }
    return out


def _table(label: str, att: dict, md: bool = False) -> str:
    rows = [("phase", "ms/window", "ms/round", "% of round")]
    for name, d in att["phases"].items():
        rows.append((name, f"{d['ms_per_window']:.3f}",
                     f"{d['ms_per_round']:.3f}", f"{d['pct']:.1f}%"))
    for name, d in att["subphases"].items():
        rows.append((f"  {name}", f"{d['ms_per_window']:.3f}",
                     f"{d['ms_per_round']:.3f}", "(contained)"))
    rows.append(("TOTAL (straight run)", f"{att['ms_per_window']:.3f}",
                 f"{att['ms_per_round']:.3f}", "100%"))
    rows.append(("coverage (Σ phases / total)", "", "",
                 f"{att['coverage'] * 100:.1f}%"))
    lines = [f"== phase attribution: {label} "
             f"({att['windows']} windows, {att['rounds_per_window']} "
             f"rounds/window) =="]
    if md:
        lines = [f"| {' | '.join(rows[0])} |",
                 "|" + "---|" * len(rows[0])]
        lines += [f"| {' | '.join(r)} |" for r in rows[1:]]
        return "\n".join(lines)
    width = [max(len(r[i]) for r in rows) for i in range(4)]
    for r in rows:
        lines.append("  " + "  ".join(c.ljust(w) for c, w in zip(r, width)))
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="shadow1_tpu.tools.phaseprobe")
    ap.add_argument("config", help='YAML experiment file or "smoke" '
                                   "(the benchgate dense-phold shape)")
    ap.add_argument("--windows", type=int, default=16,
                    help="windows to capture and attribute (default 16)")
    ap.add_argument("--warmup", type=int, default=8,
                    help="windows run before capture (state realism)")
    ap.add_argument("--reps", type=int, default=3,
                    help="timing reps; min wall is reported")
    ap.add_argument("--hosts", type=int, default=SMOKE_HOSTS,
                    help="host count for the smoke config")
    ap.add_argument("--metrics-ring", type=int, default=0,
                    help="attribute with a W-deep telemetry ring (the telem "
                         "phase is ~empty without one)")
    ap.add_argument("--min-coverage", type=float, default=0.0,
                    help="exit 1 when Σ phases / total falls below this "
                         "(ci.sh passes 0.9 — the acceptance bound)")
    ap.add_argument("--md", action="store_true",
                    help="print the attribution table as markdown "
                         "(the docs/PERF.md format)")
    args = ap.parse_args(argv)

    import shadow1_tpu  # noqa: F401  (x64 before jax arrays)
    import jax

    eng, label = build_engine(args.config, metrics_ring=args.metrics_ring,
                              hosts=args.hosts)
    att = attribution(eng, n_windows=args.windows, warmup=args.warmup,
                      reps=args.reps)
    att = {"probe": "phaseprobe", "config": label,
           "backend": jax.default_backend(), **att}
    print(_table(label, att, md=args.md), file=sys.stderr, flush=True)
    print(json.dumps(att))
    if args.min_coverage and (att["coverage"] or 0) < args.min_coverage:
        print(f"[phaseprobe] attribution coverage {att['coverage']} below "
              f"{args.min_coverage} — the phase split no longer accounts "
              f"for the window program", file=sys.stderr, flush=True)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
