"""Window phases on the device trace and the program's spans on the
profiler's clock (telemetry/phases.py, telemetry/profiler.py): the join from
a traced op's instruction name to its ``phase:`` scopes, attribution that
sums to busy time, and ``shadow1:`` spans in any ``jax.profiler`` capture —
from ``ckpt.run_chunked``, bare and under the fleet runner's hooks, profiler
attached or not."""

import glob
import gzip
import json
import os
import re
import subprocess
import sys

import jax
import numpy as np
import pytest

from shadow1_tpu.ckpt import run_chunked
from shadow1_tpu.config.compiled import single_vertex_experiment
from shadow1_tpu.consts import KIND_NAMES, MS, EngineParams
from shadow1_tpu.core.engine import Engine
from shadow1_tpu.fleet.engine import FleetEngine
from shadow1_tpu.fleet.run import run_fleet
from shadow1_tpu.telemetry import ANNOTATION_PREFIX, PhaseProfiler, phases

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORDED = os.path.join(ROOT, "benchmarks", "tests", "data",
                        "trace_phold32_v5e.json.gz")
PARAMS = EngineParams(ev_cap=32, outbox_cap=16)


def phold(seed=7, n_hosts=16):
    return single_vertex_experiment(
        n_hosts=n_hosts, seed=seed, end_time=60 * MS, latency_ns=10 * MS,
        model="phold", model_cfg={"mean_delay_ns": 20 * MS, "init_events": 2})


def filexfer(seed, loss=0.0):
    role = np.full(4, 1, np.int64)
    role[0] = 0
    return single_vertex_experiment(
        n_hosts=4, seed=seed, end_time=2_000 * MS, latency_ns=10 * MS,
        loss=loss, bw_bits=10**7, model="net",
        model_cfg={"app": "filexfer", "role": role,
                   "server": np.zeros(4, np.int64),
                   "flow_bytes": np.full(4, 30_000, np.int64),
                   "start_time": np.full(4, 1 * MS, np.int64),
                   "flow_count": np.where(role == 1, 1, 0)})


# ---- strings ---------------------------------------------------------------

@pytest.mark.parametrize("op_name,path", [
    ("jit(run)/phase:rounds/while/body/phase:h_timer/cond/branch_1_fun/"
     "phase:tcp_flush/add", "rounds/h_timer/tcp_flush"),
    ("jit(run)/while/body/closed_call/vmap(phase:rounds)/while/body/"
     "vmap(phase:pop)/reduce_min", "rounds/pop"),
    ("jit(run)/while/body/phase:deliver/phase:route/gather", "deliver/route"),
    ("jit(run)/while/cond/lt", ""),
    ("", ""),
])
def test_phase_path_keeps_the_phase_components_in_order(op_name, path):
    assert phases.phase_path(op_name) == path


@pytest.mark.parametrize("event,name", [
    ("%fusion.172 = s32[3670016,15]{1,0:T(8,128)} fusion(s32[3670016]{0:T(1024)} %p", "fusion.172"),
    ("%copy-start.22 = (s32[10,48,32]{1,2,0:T(8,128)S(1)}, s32[10,48,32]", "copy-start.22"),
    ("%while.12 = (s32[], s64[]) while(%tuple.8), condition=%region_4.6", "while.12"),
    ("ROOT %custom-call.160 = u32[4]{0} custom-call()", "ROOT %custom-call.160"),
    ("fusion.a", "fusion.a"),
    ("%dynamic-update-slice_fusion = s32[8]", "dynamic-update-slice_fusion"),
])
def test_instruction_name_is_the_head_of_the_event_s_hlo_text(event, name):
    assert phases.instruction_name(event) == name


def test_instruction_name_on_the_names_a_v5e_recorded():
    with gzip.open(RECORDED, "rt") as f:
        trace = json.load(f)
    ops = [e for p in trace["planes"] if p["name"].startswith("/device:")
           for ln in p["lines"] if ln["name"] == phases.OPS_LINE
           for e in ln["events"]]
    names = {phases.instruction_name(e[0]) for e in ops}
    assert len(ops) == 2454 and len(names) > 100
    assert all(re.fullmatch(r"[\w.\-]+", n) for n in names), sorted(names)[:5]
    kinds = {n.rstrip(".0123456789") for n in names}
    assert {"fusion", "while", "copy-start", "copy-done"} <= kinds


def test_rollup_key_puts_every_path_in_one_row():
    assert phases.rollup_key("rounds/h_timer/tcp_flush") == ("handlers", "h_timer")
    assert phases.rollup_key("rounds/h_app/rounds/h_app") == ("handlers", "h_app")
    assert phases.rollup_key("rounds/pop") == ("pop", None)
    assert phases.rollup_key("rounds") == ("rounds_other", None)
    assert phases.rollup_key("deliver/route") == ("deliver", None)
    assert phases.rollup_key("exchange") == ("deliver", None)
    assert phases.rollup_key("prepare") == ("prepare", None)
    assert phases.rollup_key("telem") == ("telem", None)
    assert phases.rollup_key("") == ("unattributed", None)
    assert phases.rollup_key("tcp_flush") == ("other", None)


# ---- the table of a compiled program ----------------------------------------

def _model_kinds(handlers):
    return {f"h_{KIND_NAMES.get(k, k)}" for k in handlers}


@pytest.mark.parametrize("fleet", [False, True], ids=["solo", "fleet"])
def test_phase_table_of_a_compiled_net_engine_holds_every_phase(fleet):
    eng = Engine(filexfer(11), PARAMS)
    kinds = _model_kinds(eng._handlers)     # a lane's handlers are a solo's
    if fleet:
        eng = FleetEngine([filexfer(11), filexfer(12, 0.02)], PARAMS)
    text = eng.hlo_text()
    assert phases.module_name(text) == "jit_run"
    table = phases.phase_table(text)
    paths = set(table.values())
    parts = {p for path in paths for p in path.split("/")}
    assert len(kinds) >= 4 and kinds <= parts, (kinds, parts)
    assert {"prepare", "rounds", "pop", "route", "deliver", "telem",
            "tcp_flush"} <= parts
    assert "rounds/pop" in paths and "deliver/route" in paths
    # Control flow is in the table under its scope: the round loop is a
    # `while` of phase rounds, a guarded handler pass a `conditional`.
    def scoped(op):
        return {table[phases._INSTRUCTION.match(ln).group(1)]
                for ln in text.splitlines()
                if f" {op}(" in ln and phases._INSTRUCTION.match(ln)}

    assert "rounds" in scoped("while")
    if not fleet:    # under vmap a batched cond is a select, not a branch
        assert any(c.startswith("rounds/h_") for c in scoped("conditional"))


def test_the_sharded_engine_gives_its_program_s_text_too():
    """Every engine ``cli.py`` can hand to ``device_trace`` has ``hlo_text``:
    the sharded one gives the program of its current exchange cap."""
    from shadow1_tpu.shard.engine import ShardedEngine

    eng = ShardedEngine(phold(), PARAMS, devices=jax.devices()[:2])
    text = eng.hlo_text()
    parts = {p for path in phases.phase_table(text).values()
             for p in path.split("/")}
    assert {"prepare", "rounds", "pop", "h_phold", "exchange", "deliver"} <= parts
    st = eng.run(n_windows=2)
    assert eng.hlo_text(st, 2) == text


def test_hlo_text_is_the_same_before_and_after_a_traced_run(tmp_path):
    """Tracing traces nothing into the program: the window program's text
    is one text, whatever state or window count it is asked for, and a
    capture with a PhaseProfiler attached leaves it as it was."""
    eng = Engine(phold(), PARAMS)
    before = eng.hlo_text()
    with jax.profiler.trace(str(tmp_path / "cap")):
        st = run_chunked(eng, n_windows=4, chunk=2, profiler=PhaseProfiler())
    jax.block_until_ready(st)
    assert eng.hlo_text(st, 3) == before
    assert ANNOTATION_PREFIX not in before and "TraceAnnotation" not in before


# ---- attribution --------------------------------------------------------------

def test_attribute_sums_to_busy_and_inherits_from_the_containing_while():
    table = {"while.1": "rounds", "fusion.pop": "rounds/pop",
             "conditional.2": "rounds/h_timer", "fusion.t": "rounds/h_timer/tcp_flush",
             "copy.x": "", "copy.y": "", "fusion.d": "deliver/deliver",
             "fusion.p": "prepare", "while.0": ""}
    ev = [
        ["%while.0 = () while(...)", 0, 1000],         # the window loop: no phase
        ["%fusion.p = s32[] fusion()", 0, 100],
        ["%while.1 = () while(...)", 100, 500],        # the round loop
        ["%fusion.pop = s32[] fusion()", 100, 200],
        ["%copy.x = s32[] copy()", 300, 50],           # nameless, in rounds
        ["%conditional.2 = () conditional()", 350, 200],
        ["%copy.y = s32[] copy()", 350, 20],           # nameless, in h_timer
        ["%fusion.t = s32[] fusion()", 380, 170],
        ["%fusion.d = s32[] fusion()", 600, 300],
        ["%copy.x = s32[] copy()", 950, 50],           # nameless, in no phase
        ["%fusion.new = s32[] fusion()", 1200, 10],    # not in the table
    ]
    got = phases.attribute(ev, table)
    rows = {p: r["seconds"] * 1e9 for p, r in got["rows"].items()}
    assert rows == pytest.approx({
        "prepare": 100, "rounds/pop": 200, "rounds": 50, "rounds/h_timer": 20,
        "rounds/h_timer/tcp_flush": 170, "deliver/deliver": 300, "": 60})
    assert got["busy_ns"] == sum(round(v) for v in rows.values()) == 900
    assert got["unknown_ops"] == 1 and got["inherited_s"] == pytest.approx(70e-9)
    roll = {k: round(v * 1e9) for k, v in got["rollup"].items()}
    assert roll == {"prepare": 100, "pop": 200, "handlers": 190,
                    "h_timer": 190, "rounds_other": 50, "deliver": 300,
                    "telem": 0, "other": 0, "unattributed": 60,
                    "other_programs": 0}
    assert sum(v for k, v in roll.items() if not k.startswith("h_")) == 900
    assert got["rows"]["rounds/pop"] == {
        "seconds": pytest.approx(200e-9), "ops": 1, "instances": 1}


def test_a_zero_length_op_at_a_fusion_s_start_does_not_make_it_a_container():
    """On the chip a ConcatBitcast of length 0 carries the start timestamp
    of the fusion after it and sorts behind it (dense PHOLD: custom-call.160
    and fusion.170, 26.17 ms). Only control flow contains other ops."""
    table = {"fusion.170": "deliver/deliver", "custom-call.160": "",
             "reshape.358": "deliver/deliver", "while.3": ""}
    ev = [["%while.3 = () while()", 0, 40_000_000],
          ["%fusion.170 = s32[3670016] fusion()", 1_000, 26_168_182],
          ["%custom-call.160 = s32[2097152] custom-call()", 1_000, 0],
          ["%reshape.358 = s32[56,65536,1] reshape()", 26_170_000, 5_000]]
    got = phases.attribute(ev, table)
    assert got["busy_ns"] == 26_168_182 + 5_000 and got["overlap_ns"] == 0
    assert got["rows"]["deliver/deliver"]["instances"] == 2
    assert phases.is_control_flow("while.3") and phases.is_control_flow("call")
    assert phases.is_control_flow("conditional.7.clone")
    assert not phases.is_control_flow("fusion.170")
    assert not phases.is_control_flow("custom-call.160")


def test_attribute_keeps_other_programs_apart():
    """Instruction names are unique only inside a module: an op outside the
    program's executions is never looked up in the program's table."""
    table = {"fusion.1": "prepare", "fusion.2": "telem"}
    ev = [["%fusion.1 = x", 0, 100], ["%fusion.2 = x", 100, 50],
          ["%fusion.1 = x", 500, 40]]      # another module's fusion.1
    got = phases.attribute(ev, table, executions=[(0, 200)])
    assert got["busy_ns"] == 190
    assert {p: round(r["seconds"] * 1e9) for p, r in got["rows"].items()} == {
        "prepare": 100, "telem": 50, "other_programs": 40}
    assert got["unknown_ops"] == 0
    assert phases.executions_of(
        [["jit_run(7)", 0, 200], ["jit_convert(3)", 300, 5]], "jit_run") == [(0, 200)]


def test_device_trace_writes_phases_json_for_the_engine(tmp_path, monkeypatch):
    """``device_trace(..., engine=)`` joins the capture against the engine's
    own program on exit. The CPU's capture has no device op line, so the op
    line is a TPU's in miniature: two instructions of the real table."""
    from shadow1_tpu.telemetry import device_trace

    eng = Engine(phold(), PARAMS)
    table = phases.phase_table(eng.hlo_text())
    pop = next(n for n, p in table.items() if p == "rounds/pop" and "fusion" in n)
    dlv = next(n for n, p in table.items() if p.startswith("deliver") and "fusion" in n)
    ops = [[f"%{pop} = s32[16]{{0}} fusion(...)", 10, 30],
           [f"%{dlv} = s32[16]{{0}} fusion(...)", 50, 20]]
    log_dir = str(tmp_path / "dt")
    with device_trace(log_dir, engine=eng):      # the CPU: nothing to join
        jax.block_until_ready(eng.run(n_windows=2))
    assert not os.path.exists(os.path.join(log_dir, "phases.json"))
    monkeypatch.setattr(phases, "read_device_ops",
                        lambda d: (ops, [["jit_run(1)", 0, 100]]))
    prof = PhaseProfiler()
    with device_trace(log_dir, prof, engine=eng):
        jax.block_until_ready(eng.run(n_windows=2))
    with open(os.path.join(log_dir, "phases.json")) as f:
        doc = json.load(f)
    assert doc["unknown_ops"] == 0 and doc["executions"] == 1
    assert doc["busy_ns"] == 50 and doc["rollup"]["pop"] == pytest.approx(30e-9)
    assert doc["rollup"]["deliver"] == pytest.approx(20e-9)
    assert "device-trace" in prof.span_names()


def test_a_join_that_fails_warns_and_keeps_the_run(tmp_path, monkeypatch):
    """The join runs after the body: whatever it raises, the run, its
    capture and the caller's files after the with-block survive."""
    from shadow1_tpu.telemetry import device_trace

    monkeypatch.setattr(phases, "read_device_ops",
                        lambda d: ([["%fusion.1 = s32[] fusion()", 0, 5]], []))

    class NoText:
        def hlo_text(self, st=None):
            raise RuntimeError("no program text")

    eng, log_dir = Engine(phold(), PARAMS), str(tmp_path / "dt")
    with pytest.warns(UserWarning, match="no phases.json"):
        with device_trace(log_dir, engine=NoText()):
            st = eng.run(n_windows=2)
    assert int(st.metrics.windows) == 2
    assert not os.path.exists(os.path.join(log_dir, "phases.json"))
    assert glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))


# ---- the program's spans in a jax.profiler capture ------------------------------

def _captured_spans(log_dir):
    """``(name, start, end, stats)`` of every ``shadow1:`` event in the
    newest capture under ``log_dir``."""
    f = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in jax.profiler.ProfileData.from_file(f).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(ANNOTATION_PREFIX):
                    out.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda x: x[1])


def _inside(spans, inner, outer):
    """Every ``inner`` span lies within an ``outer`` span of its chunk."""
    outers = [s for s in spans if s[0] == ANNOTATION_PREFIX + outer]
    inners = [s for s in spans if s[0] == ANNOTATION_PREFIX + inner]
    return bool(inners) and all(
        any(o[1] <= i[1] and i[2] <= o[2] and o[3]["done"] == i[3]["done"]
            for o in outers) for i in inners)


def test_run_chunked_leaves_its_spans_in_a_capture_with_no_profiler(tmp_path):
    eng = Engine(phold(), PARAMS)
    st = eng.init_state()
    jax.block_until_ready(eng.run(st, n_windows=0))     # compile outside
    seen = []
    with jax.profiler.trace(str(tmp_path / "cap")):
        st = run_chunked(eng, st, n_windows=4, chunk=2,
                         on_chunk=lambda s, done: seen.append(done),
                         retune=lambda e, s: (e, s))
        jax.block_until_ready(st)
    spans = _captured_spans(str(tmp_path / "cap"))
    names = [s[0][len(ANNOTATION_PREFIX):] for s in spans]
    assert names.count("run-chunk") == names.count("dispatch") == 2
    assert names.count("on-chunk") == 2 and names.count("retune") == 1
    assert "sync" not in names            # only under a PhaseProfiler
    assert _inside(spans, "dispatch", "run-chunk")
    # Spans of one chunk share its first window.
    assert sorted({s[3]["done"] for s in spans}) == [0, 2] and seen == [2, 4]
    assert all(s[3]["windows"] == 2 for s in spans)


def test_run_chunked_under_a_guard_spans_the_commit(tmp_path):
    from shadow1_tpu.txn import OverflowGuard

    eng = Engine(phold(), PARAMS)
    guard = OverflowGuard(eng, make_engine=lambda p: Engine(phold(), p),
                          mode="halt")
    prof = PhaseProfiler()
    run_chunked(eng, n_windows=4, chunk=2, guard=guard, profiler=prof)
    names = prof.span_names()
    assert names.count("commit") == 2 and names.count("sync") == 2
    by_name = {e["name"]: e for e in prof.events}
    assert by_name["commit"]["args"] == {"done": 2, "windows": 2}
    rc, d = by_name["run-chunk"], by_name["dispatch"]
    assert rc["ts"] <= d["ts"] and d["ts"] + d["dur"] <= rc["ts"] + rc["dur"] + 0.2


def test_the_fleet_loop_leaves_the_same_names(tmp_path):
    eng = FleetEngine([phold(7), phold(8), phold(9)], PARAMS)
    st = eng.init_state()
    jax.block_until_ready(eng.run(st, n_windows=0))
    with jax.profiler.trace(str(tmp_path / "cap")):
        st, _hb = run_fleet(eng, st, n_windows=4, every_windows=2, stream=False)
        jax.block_until_ready(st)
    spans = _captured_spans(str(tmp_path / "cap"))
    names = [s[0][len(ANNOTATION_PREFIX):] for s in spans]
    assert names.count("run-chunk") == names.count("dispatch") == 2
    assert names.count("on-chunk") == names.count("drain") == 2
    assert "compile" in names and "sync" not in names
    assert _inside(spans, "dispatch", "run-chunk")
    # Under a PhaseProfiler the same call sites also fill the Chrome trace.
    prof = PhaseProfiler()
    run_fleet(eng, n_windows=4, every_windows=2, stream=False, profiler=prof,
              ckpt_path=str(tmp_path / "ck.npz"), ckpt_every_s=0.0)
    assert {"init", "compile", "run-chunk", "dispatch", "sync", "drain",
            "on-chunk", "checkpoint"} <= set(prof.span_names())


def test_cli_fleet_trace_and_profile_run_and_write_the_spans(tmp_path):
    cfg = tmp_path / "sweep.yaml"
    cfg.write_text(
        "general: {seed: 7, stop_time: 60 ms}\n"
        "engine: {scheduler: tpu, ev_cap: 32, outbox_cap: 16}\n"
        "network: {single_vertex: {latency: 10 ms}}\n"
        "hosts: [{name: h, count: 8}]\n"
        "app: {model: phold, params: {mean_delay_ns: 2.0e7, "
        "init_events: 2}}\n"
        "sweep: {seeds: [7, 8, 9]}\n")
    trace, prof = tmp_path / "t.json", tmp_path / "prof"
    out = subprocess.run(
        [sys.executable, "-m", "shadow1_tpu", str(cfg), "--fleet",
         "--heartbeat", "2", "--trace", str(trace), "--profile", str(prof)],
        capture_output=True, text=True)
    assert out.returncode == 0, out.stderr[-800:]
    assert json.loads(out.stdout.strip().splitlines()[-1])["type"] == "fleet_summary"
    doc = json.loads(trace.read_text())
    names = [e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert names.count("run-chunk") == names.count("dispatch") == 3
    assert {"init", "compile", "sync", "drain", "on-chunk", "device-trace"} <= set(names)
    chunk = [e["args"] for e in doc["traceEvents"] if e["name"] == "dispatch"]
    assert chunk == [{"done": d, "windows": 2} for d in (0, 2, 4)]
    assert (prof / "phases.trace.json").exists()
    assert glob.glob(str(prof / "plugins" / "profile" / "*" / "*.xplane.pb"))
    # --tracker and --summary stay refused under --fleet.
    bad = subprocess.run(
        [sys.executable, "-m", "shadow1_tpu", str(cfg), "--fleet",
         "--tracker", str(tmp_path / "tr.jsonl")], capture_output=True, text=True)
    assert bad.returncode != 0 and "--tracker" in bad.stderr


def test_cli_sharded_profile_keeps_the_row_and_the_trace(tmp_path):
    """``--engine sharded --profile DIR``: the result row and
    ``DIR/phases.trace.json`` come out (the join runs against the sharded
    program; on the CPU it finds no device op and writes no table)."""
    prof = tmp_path / "prof"
    out = subprocess.run(
        [sys.executable, "-m", "shadow1_tpu",
         os.path.join(ROOT, "configs", "serve_phold.yaml"), "--engine",
         "sharded", "--windows", "4", "--profile", str(prof)],
        capture_output=True, text=True)
    assert out.returncode == 0, out.stderr[-800:]
    assert "phases.json" not in out.stderr, out.stderr[-800:]    # no warning
    row = json.loads(out.stdout.strip().splitlines()[-1])
    assert row["metrics"]["windows"] == 4
    doc = json.loads((prof / "phases.trace.json").read_text())
    names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert {"run-chunk", "dispatch", "device-trace"} <= names
    assert glob.glob(str(prof / "plugins" / "profile" / "*" / "*.xplane.pb"))


def test_a_span_costs_microseconds_with_no_session_open():
    """With no profiler session an annotation is a flag check: entering one
    must stay far below a chunk's dispatch (hundreds of microseconds)."""
    import time

    from shadow1_tpu.telemetry import maybe_span

    n = 20_000
    t0 = time.perf_counter()
    for i in range(n):
        with maybe_span(None, "dispatch", done=i, windows=5):
            pass
    assert (time.perf_counter() - t0) / n < 50e-6
