"""Op/fusion census of the window program — a tool AND a CI gate.

    python -m shadow1_tpu.tools.opcensus                    # gate vs OPCENSUS.json
    python -m shadow1_tpu.tools.opcensus --update           # re-baseline
    python -m shadow1_tpu.tools.opcensus configs/rung3_tor1k.yaml --sources

The performance attribution plane's static half (the wall-clock half is
tools/phaseprobe.py). The round cost of the sparse rungs is OP-COUNT bound
after fusion (docs/PERF.md round-5: 12.3k deliver-pass jaxpr eqns → ~1.3k
fusion kernels × fixed kernel cost), so the traced-eqn count per phase is
the earliest possible warning for ROADMAP item 1's kernel work: a handler
rewrite that doubles a pass's op count shows up here at trace time, before
any benchmark moves. This automates the round-5 manual census:

* **eqn census** — every window phase (core/engine.window_phases: prepare /
  rounds / deliver / telem), every handler pass (h_<kind>), the pop chain
  and the whole round body are traced to jaxprs and their equations counted
  RECURSIVELY (sub-jaxprs of cond/while/scan/pjit included). Tracing is
  deterministic: two runs produce identical counts.
* **source table** (``--sources``) — eqns grouped by the deepest user frame
  (``file.function``), reproducing the round-5 deliver-pass breakdown
  (tcp_flush / dense.extract_col / events.push_local / ...) mechanically
  instead of by hand.
* **fusion census** (``--fusion``) — the phase programs are compiled and
  the fusion-kernel instructions counted from the optimized HLO: the
  post-XLA number the per-round fixed cost actually scales with. Backend-
  dependent, so the baseline records which backend counted it (eqn counts
  are backend-independent and are what the gate enforces).
* **drift gate** — without flags, measured eqn counts compare against the
  committed ``OPCENSUS.json``: any phase drifting more than ``tolerance``
  (default 10%) fails CI (exit 1), same shape as tools/benchgate.py.
  Intentional change? override once with ``SHADOW1_OPCENSUS_ACCEPT="why"``
  and re-baseline with ``--update``.
* ``--inject N`` — self-test hook: N extra arithmetic eqns traced into the
  ``rounds`` phase, so ci.sh can assert the gate actually trips.

Always prints one JSON line on stdout (the bench.py contract).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BASELINE = os.path.join(os.path.dirname(__file__), "..", "..",
                        "OPCENSUS.json")
TOLERANCE = 0.10
ACCEPT_ENV = "SHADOW1_OPCENSUS_ACCEPT"

# The gated config set: the benchgate dense-phold shape plus the rung-1
# net/TCP config — tiny to build, but between them they trace every handler
# pass, the NIC arrival batch and the TCP flush machine.
DEFAULT_CONFIGS = ("smoke", "configs/rung1_filexfer.yaml")


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------

def _sub_jaxprs(v):
    """Yield every Jaxpr nested in an eqn param value (pjit/cond/while/scan
    bodies, custom-call jaxprs, lists thereof)."""
    from jax.extend import core as jcore

    if isinstance(v, jcore.ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, jcore.Jaxpr):
        yield v
    elif isinstance(v, (list, tuple)):
        for x in v:
            yield from _sub_jaxprs(x)


def iter_eqns(jaxpr):
    """Every equation of ``jaxpr``, sub-jaxprs included (recursive)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from iter_eqns(sub)


def _source_label(eqn) -> str:
    """``file.function`` of the deepest user frame that created the eqn —
    the round-5 census's grouping (dense.extract_col, events.push_local, ...)."""
    from jax._src import source_info_util

    frame = source_info_util.user_frame(eqn.source_info.traceback)
    if frame is None:
        return "(no source)"
    base = os.path.basename(frame.file_name)
    if base.endswith(".py"):
        base = base[:-3]
    if base == "__init__":
        base = os.path.basename(os.path.dirname(frame.file_name))
    return f"{base}.{frame.function_name}"


def count_eqns(fn, *args, sources: bool = False):
    """(total_eqns, by_source|None) of ``fn`` traced at ``args``' shapes."""
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    total = 0
    by_src: dict[str, int] = {}
    for eqn in iter_eqns(closed.jaxpr):
        total += 1
        if sources:
            lbl = _source_label(eqn)
            by_src[lbl] = by_src.get(lbl, 0) + 1
    if not sources:
        return total, None
    return total, dict(sorted(by_src.items(), key=lambda kv: -kv[1]))


def count_fusions(fn, *args) -> dict:
    """Compiled-HLO kernel census of ``fn``: fusion instructions plus total
    top-level instructions (the launch count the fixed per-kernel cost
    multiplies). Backend-dependent — report with the backend name."""
    import re

    import jax

    text = jax.jit(fn).lower(*args).compile().as_text()
    return {
        "fusions": len(re.findall(r"= \S+ fusion\(", text)),
        "instructions": sum(
            1 for line in text.splitlines()
            if re.match(r"\s+(ROOT\s+)?%?\S+ = ", line)
        ),
    }


# ---------------------------------------------------------------------------
# the census
# ---------------------------------------------------------------------------

def _inject_eqns(fn, n: int):
    """Trace ``n`` extra add eqns into ``fn`` (drift-gate self-test)."""
    if not n:
        return fn

    def wrapped(fr):
        fr = fn(fr)
        x = fr.dg_ob
        for _ in range(n - 1):
            x = x + 1
        return fr._replace(dg_ob=x - (n - 1))

    return wrapped


def census(eng, sources: bool = False, fusion: bool = False,
           inject: int = 0) -> dict:
    """The per-config census dict: ``eqns`` per phase/handler pass (the
    gated, backend-independent numbers), optional ``sources`` breakdown per
    pass and ``fusions`` per window phase."""
    import jax
    import jax.numpy as jnp

    from shadow1_tpu.consts import KIND_NAMES, NP
    from shadow1_tpu.core.engine import (
        Popped,
        run_round,
        window_frame,
        window_phases,
    )
    from shadow1_tpu.core.events import pop_until

    ctx, handlers = eng.ctx, eng._handlers
    st = eng.init_state()
    fr = window_frame(st, ctx)
    h = ctx.n_hosts
    win_end = st.win_start + ctx.window
    ev = Popped(
        mask=jnp.ones(h, bool),
        time=jnp.zeros(h, jnp.int64),
        kind=jnp.zeros(h, jnp.int32),
        p=jnp.zeros((NP, h), jnp.int32),
        tb=jnp.zeros(h, jnp.int64),
    )
    eqns: dict[str, int] = {}
    srcs: dict[str, dict] = {}
    fus: dict[str, dict] = {}
    phases = window_phases(ctx, handlers, None, eng._pre_window,
                           eng._model.make_handlers, None)
    for name, fn in phases:
        if name == "rounds":
            fn = _inject_eqns(fn, inject)
        eqns[name], by = count_eqns(fn, fr, sources=sources)
        if sources:
            srcs[name] = by
        if fusion:
            fus[name] = count_fusions(fn, fr)

    for kind, hfn in sorted(handlers.items()):
        name = f"h_{KIND_NAMES.get(kind, kind)}"
        eqns[name], by = count_eqns(hfn, st, ev, sources=sources)
        if sources:
            srcs[name] = by
    eqns["pop"], _ = count_eqns(lambda b: pop_until(b, win_end), st.evbuf)
    eqns["round"], _ = count_eqns(
        lambda s: run_round(s, ctx, handlers, win_end), st,
    )
    out: dict = {"eqns": eqns}
    if sources:
        out["sources"] = srcs
    if fusion:
        out["fusions"] = fus
        out["fusion_backend"] = jax.default_backend()
    return out


def run_census(config: str, sources=False, fusion=False, inject=0):
    """(label, census dict) for "smoke" or a YAML config path."""
    from shadow1_tpu.tools.phaseprobe import build_engine

    eng, label = build_engine(config)
    if label.endswith(".yaml"):
        label = label[:-5]
    return label, census(eng, sources=sources, fusion=fusion, inject=inject)


# ---------------------------------------------------------------------------
# the gate
# ---------------------------------------------------------------------------

def gate_config(measured: dict, base: dict, tol: float) -> list[str]:
    """Failure strings (empty = pass) comparing one config's measured
    ``eqns`` against the baseline's. Both directions are enforced: a phase
    that grew, shrank, appeared or vanished without a baseline update is
    drift — shrinkage is great news, but the baseline must say so."""
    fails = []
    b = base.get("eqns", {})
    m = measured.get("eqns", {})
    for phase, ref in b.items():
        if phase not in m:
            fails.append(f"phase {phase!r} vanished (baseline {ref} eqns)")
            continue
        if ref and abs(m[phase] - ref) / ref > tol:
            pct = 100 * (m[phase] - ref) / ref
            fails.append(f"phase {phase!r}: {m[phase]} eqns vs baseline "
                         f"{ref} ({pct:+.1f}% > ±{tol * 100:.0f}%)")
    for phase in m:
        if phase not in b:
            fails.append(f"new phase {phase!r} ({m[phase]} eqns) not in "
                         f"baseline")
    return fails


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="shadow1_tpu.tools.opcensus")
    ap.add_argument("configs", nargs="*", default=list(DEFAULT_CONFIGS),
                    help='YAML config paths and/or "smoke" (default: the '
                         "gated set)")
    ap.add_argument("--update", action="store_true",
                    help="write the measured census as the committed "
                         "baseline (OPCENSUS.json)")
    ap.add_argument("--baseline", default=BASELINE, help=argparse.SUPPRESS)
    ap.add_argument("--sources", action="store_true",
                    help="per-pass source breakdown (file.function) — the "
                         "round-5 census table, mechanically")
    ap.add_argument("--fusion", action="store_true",
                    help="also compile the window phases and count fusion "
                         "kernels (backend-dependent; slow for big configs)")
    ap.add_argument("--inject", type=int, default=0, metavar="N",
                    help="trace N extra eqns into the rounds phase "
                         "(drift-gate self-test)")
    ap.add_argument("--md", action="store_true",
                    help="print source tables as markdown (docs format)")
    args = ap.parse_args(argv)

    import shadow1_tpu  # noqa: F401  (x64 before jax arrays)

    measured: dict[str, dict] = {}
    for cfg in args.configs:
        label, c = run_census(cfg, sources=args.sources, fusion=args.fusion,
                              inject=args.inject)
        measured[label] = c
        if args.sources:
            for pname, by in c.get("sources", {}).items():
                hdr = f"== {label} {pname}: {c['eqns'][pname]} eqns =="
                print(hdr, file=sys.stderr)
                rows = [(s, n) for s, n in by.items()]
                if args.md:
                    print("| source | eqns |\n|---|---|", file=sys.stderr)
                    for s, n in rows:
                        print(f"| {s} | {n} |", file=sys.stderr)
                else:
                    for s, n in rows:
                        print(f"  {s}: {n}", file=sys.stderr)
    if args.update:
        base = {
            "tolerance": TOLERANCE,
            "configs": {k: {"eqns": v["eqns"],
                            **({"fusions": v["fusions"],
                                "fusion_backend": v["fusion_backend"]}
                               if "fusions" in v else {})}
                        for k, v in measured.items()},
            "note": "opcensus baseline — ci.sh fails when any phase's "
                    "traced eqn count drifts beyond tolerance; override "
                    f"once with {ACCEPT_ENV}, then re-baseline with "
                    "--update",
        }
        with open(args.baseline, "w") as f:
            json.dump(base, f, indent=1, sort_keys=True)
            f.write("\n")
        print(json.dumps({"census": measured, "gate": "updated",
                          "baseline": args.baseline}))
        return 0
    try:
        with open(args.baseline) as f:
            base = json.load(f)
    except OSError:
        print(json.dumps({"census": measured, "gate": "no_baseline",
                          "hint": "commit one with --update"}))
        return 0
    tol = float(base.get("tolerance", TOLERANCE))
    fails: dict[str, list] = {}
    for label, c in measured.items():
        bcfg = base.get("configs", {}).get(label)
        if bcfg is None:
            continue  # un-gated config (explicit census run)
        f = gate_config(c, bcfg, tol)
        if f:
            fails[label] = f
    verdict = {"census": measured, "tolerance": tol}
    if fails:
        accept = os.environ.get(ACCEPT_ENV)
        for label, msgs in fails.items():
            for msg in msgs:
                print(f"[opcensus] {label}: {msg}", file=sys.stderr,
                      flush=True)
        if accept:
            print(f"[opcensus] DRIFT ACCEPTED ({accept}) — commit the new "
                  f"baseline: python -m shadow1_tpu.tools.opcensus --update",
                  file=sys.stderr, flush=True)
            print(json.dumps({**verdict, "gate": "accepted",
                              "reason": accept, "fails": fails}))
            return 0
        print(f"[opcensus] OP-COUNT DRIFT: the traced window program "
              f"changed size beyond ±{tol * 100:.0f}%. If intentional, "
              f"override once: {ACCEPT_ENV}='why' — then re-baseline with "
              f"--update.", file=sys.stderr, flush=True)
        print(json.dumps({**verdict, "gate": "failed", "fails": fails}))
        return 1
    print(json.dumps({**verdict, "gate": "ok"}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
