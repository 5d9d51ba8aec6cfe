"""A Bitcoin seed study on the fleet engine: per-lane model tables.

The seed's only effect in this model is which nodes originate the
transactions (``model_cfg["tx_origin"]``, drawn by the config generator; the
app itself uses no RNG). The fleet carries that table per lane
(``apps.LANE_TABLES``, ``fleet/expand.shape_class``), so every lane must equal
a solo run and the C++ reference under ITS seed, every other ``model_cfg``
difference must still be refused, and a new set of seeds must reuse the
compiled program. All at 64 nodes, 6 transactions, 3 lanes, 20 windows
(``tests/rehearsal_bitcoin64``: the benchmark cell ``bitcoin5k.flood`` in
miniature, run through the benchmark's own harness at the end; 16 of its
nodes have slow links, so that counters, not only tables, tell seeds apart).
"""

import copy
import json
import os
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest
import yaml

from shadow1_tpu.config.experiment import build_experiment
from shadow1_tpu.core.engine import Engine
from shadow1_tpu.fleet.engine import FleetEngine, fleet_metrics_per_exp
from shadow1_tpu.fleet.expand import (
    FleetConfigError,
    expand_sweep,
    shape_class,
)
from shadow1_tpu.telemetry import phases
from shadow1_tpu.telemetry.registry import MODEL_TOTALS
from tests.parity import assert_runs_contract, lane_metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REHEARSAL = os.path.join(ROOT, "tests", "rehearsal_bitcoin64")
RUNG5 = os.path.join(ROOT, "configs", "rung5_bitcoin5k.yaml")
N_WINDOWS = 20
SEEDS = [1, 2, 600000005000]            # the last is past 2**32
TABLE_KEYS = ("seen", "seen_time", "tx_rx", "reach", "msg_retries")


def doc64(seeds=None, vary=None):
    with open(os.path.join(REHEARSAL, "configs", "bitcoin64.yaml")) as f:
        doc = yaml.safe_load(f)
    if seeds is not None:
        doc["sweep"] = {"seeds": list(seeds)}
        if vary is not None:
            doc["sweep"]["vary"] = vary
    return doc


def origins(plan):
    return [np.asarray(e.model_cfg["tx_origin"]).tolist() for e in plan.exps]


@pytest.fixture(scope="module")
def plan():
    return expand_sweep(doc64(SEEDS))


@pytest.fixture(scope="module")
def fleet(plan):
    eng = FleetEngine(plan.exps, plan.params, plan.max_rounds)
    return eng, eng.run(n_windows=N_WINDOWS)


@pytest.fixture(scope="module")
def solos(plan):
    """Each lane's experiment alone on the solo engine: (summary, metrics)."""
    out = []
    for exp in plan.exps:
        eng = Engine(exp, plan.params)
        st = eng.run(n_windows=N_WINDOWS)
        out.append((eng.model_summary(st), Engine.metrics_dict(st)))
    return out


# ---- (a) the sweep is accepted and the lanes differ ---------------------------

@pytest.mark.parametrize("seeds", [[1, 2, 3],
                                   [600000005000 + i for i in range(4)]],
                         ids=["small", "past_2_32"])
def test_a_seed_sweep_is_accepted_and_the_lanes_origins_differ(seeds):
    plan = expand_sweep(doc64(seeds))
    got = origins(plan)
    assert [e.seed for e in plan.exps] == seeds
    assert len({tuple(o) for o in got}) == len(seeds), got
    assert all(len(o) == 6 and 0 <= min(o) and max(o) < 64 for o in got)
    # Nothing but the lane table differs: one shape class.
    classes = [shape_class(e) for e in plan.exps]
    assert all(c["model_cfg"]["tx_origin"] == ("lane table", (6,), "int64")
               for c in classes)
    assert all(np.array_equal(e.model_cfg["peers"],
                              plan.exps[0].model_cfg["peers"])
               for e in plan.exps)


def test_rung5_itself_builds_a_fleet_of_four_64_bit_seeds():
    """The acceptance line: the real file (5,000 nodes; config only, no
    state is made) under four 64-bit seeds gives a FleetEngine whose lanes
    differ in tx_origin and in nothing else."""
    with open(RUNG5) as f:
        doc = yaml.safe_load(f)
    seeds = [600000005000 + i for i in range(4)]
    doc["sweep"] = {"seeds": seeds}
    plan = expand_sweep(doc, base_dir=os.path.dirname(RUNG5))
    eng = FleetEngine(plan.exps, plan.params, plan.max_rounds)
    table = np.asarray(eng._variants["model_tables"]["tx_origin"])
    assert table.shape == (4, 200) and table.max() < 5000
    assert len({row.tobytes() for row in table}) == 4
    assert [np.asarray(e.model_cfg["tx_origin"]).tolist()
            for e in plan.exps] == table.tolist()


# ---- (b) every lane is the solo engine's run under that lane's seed ----------

@pytest.mark.parametrize("lane", range(len(SEEDS)))
def test_a_lane_equals_the_solo_engine_bit_for_bit(fleet, solos, lane):
    eng, st = fleet
    summary, metrics = solos[lane]
    got = eng.model_summary(st, lane)
    for k in TABLE_KEYS:
        assert np.array_equal(got[k], summary[k]), k
    assert {k: int(v) for k, v in got.items() if np.ndim(v) == 0} == \
        {k: int(v) for k, v in summary.items() if np.ndim(v) == 0}
    assert lane_metrics(fleet_metrics_per_exp(st)[lane]) \
        == lane_metrics(metrics)
    assert int(summary["total_tx_rx"]) > 0
    assert eng.model_totals(st)[lane] == {
        k: int(summary[k])
        for k in ("total_seen", "total_tx_rx", "total_msg_retries")}
    assert set(eng.model_totals(st)[lane]) <= set(MODEL_TOTALS)


def test_runs_count_the_program_and_fires_the_lane(fleet, solos):
    """``runs_*`` (the guard as the program took it: some lane has the
    kind) is one number in every lane and at least the lane's own
    ``fires_*``; on the solo engine they are equal."""
    _, st = fleet
    assert_runs_contract(fleet_metrics_per_exp(st), [m for _, m in solos])


def test_runs_window_end_counts_the_windows_in_which_some_lane_sent(fleet):
    """The window end runs only in a window some lane sent in (``core/engine
    .deliver_window``): the same run again one window at a call (the same
    compiled program; the window count is an argument), reading every lane's
    ``pkts_sent`` after each. The miniature is quiet between the dial and
    the first transaction at 300 ms, as the cell ``bitcoin5k.flood`` is to
    2 s, so the guard engages."""
    eng, st_end = fleet
    st, sent, before = eng.init_state(), [], np.zeros(len(SEEDS), np.int64)
    for _ in range(N_WINDOWS):
        st = eng.run(st, n_windows=1)
        now = np.asarray(st.metrics.pkts_sent)
        sent.append(bool((now != before).any()))
        before = now
    lanes = fleet_metrics_per_exp(st)
    assert lanes == fleet_metrics_per_exp(st_end)
    assert {ln["runs_window_end"] for ln in lanes} == {sum(sent)}
    assert 0 < sum(sent) < N_WINDOWS == lanes[0]["windows"], sent


# ---- (c) every lane is the C++ reference's run under that lane's seed --------

@pytest.mark.parametrize("lane", range(len(SEEDS)))
def test_a_lane_equals_the_reference_counter_for_counter(fleet, plan, lane):
    from benchmarks.reference import comparator

    eng, st = fleet
    ref = comparator.counters(plan.exps[lane], plan.params, SEEDS[lane],
                              N_WINDOWS)
    have = {**eng.model_totals(st)[lane], **fleet_metrics_per_exp(st)[lane]}
    compared = {k: (have.get(k), v) for k, v in ref.items()
                if k not in comparator.NOT_COUNTERS}
    assert len(compared) >= 14 and {"total_seen", "total_tx_rx"} <= set(compared)
    assert all(a == b for a, b in compared.values()), compared
    assert have["total_tx_rx"] > 0
    assert have["ev_overflow"] == have["ob_overflow"] == 0


# ---- (d) a lane run under another lane's table is caught ----------------------

def test_two_lanes_tables_swapped_is_caught(fleet, solos, plan):
    eng, st = fleet
    swapped = FleetEngine(plan.exps, plan.params, plan.max_rounds)
    table = swapped._variants["model_tables"]["tx_origin"]
    swapped._variants["model_tables"]["tx_origin"] = table[jnp.asarray([1, 0, 2])]
    st2 = swapped.run(n_windows=N_WINDOWS)
    for lane, other in ((0, 1), (1, 0)):
        got = swapped.model_summary(st2, lane)
        assert not np.array_equal(got["seen_time"], solos[lane][0]["seen_time"])
        assert np.array_equal(got["seen_time"], solos[other][0]["seen_time"])
    assert np.array_equal(swapped.model_summary(st2, 2)["seen_time"],
                          solos[2][0]["seen_time"])
    # The sound fleet's own lanes are not each other's either.
    assert not np.array_equal(eng.model_summary(st, 0)["seen_time"],
                              eng.model_summary(st, 1)["seen_time"])


# ---- (e) every other model_cfg difference is still refused --------------------

@pytest.mark.parametrize("over", [
    {"graph": {"kind": "ring_chord", "k": 2}},          # peers
    {"tx": {"count": 7}},                               # tx_origin's shape, tx_time
    {"tx": {"start": "350 ms"}},                        # tx_time alone
    {"tx_size": 500},
], ids=["peers", "tx_count", "tx_time", "tx_size"])
def test_lanes_that_differ_in_anything_but_the_table_are_refused(over):
    vary = [{}, {"app": {"params": over}}, {}]
    with pytest.raises(FleetConfigError) as ei:
        expand_sweep(doc64([1, 2, 3], vary))
    assert ei.value.knob == "model_cfg" and ei.value.kind == "uniform"
    assert "tx_origin" in str(ei.value)


def test_an_engine_refuses_a_rebind_to_another_shape_class(plan):
    eng = FleetEngine(plan.exps, plan.params, plan.max_rounds)
    other = expand_sweep(doc64([1, 2, 3], [{"app": {"params": {"tx_size": 500}}}] * 3))
    with pytest.raises(FleetConfigError) as ei:
        eng.rebind(other.exps, other.max_rounds)
    assert ei.value.knob == "model_cfg" and ei.value.kind == "shape"


# ---- (f) new seeds ride the compiled program ----------------------------------

def test_a_rebind_to_new_seeds_keeps_the_program_and_compiles_nothing(fleet, solos):
    from shadow1_tpu.serve.cache import EngineCache, shape_class_key

    eng, _ = fleet
    new = expand_sweep(doc64([2, 77, 1 << 40]))
    sig, traces = eng.variant_signature(), eng._run_jit._cache_size()
    old = (eng.exps, eng.max_rounds)
    try:
        eng.rebind(new.exps, new.max_rounds)
        st = eng.run(n_windows=N_WINDOWS)
        assert eng.variant_signature() == sig
        assert eng._run_jit._cache_size() == traces
        # Lane 0 now runs seed 2: what lane 1 of the fixture ran.
        assert np.array_equal(eng.model_summary(st, 0)["seen_time"],
                              solos[1][0]["seen_time"])
        assert not np.array_equal(eng.model_summary(st, 1)["seen_time"],
                                  solos[1][0]["seen_time"])
    finally:
        eng.rebind(*old)
    # The serve plane's cache keys on the same rule: one entry, a hit.
    keys = {shape_class_key(e, new.params, 3)[0] for e in new.exps + old[0]}
    assert len(keys) == 1
    cache = EngineCache()
    first, how1 = cache.get(old[0], new.params, old[1])
    again, how2 = cache.get(new.exps, new.params, new.max_rounds)
    assert (how1, how2) == ("miss", "hit") and again is first


# ---- (g) the generator's draw --------------------------------------------------

def test_seed_55_draws_rung5_s_origins_as_it_always_has():
    with open(RUNG5) as f:
        exp, _, _ = build_experiment(yaml.safe_load(f),
                                     base_dir=os.path.dirname(RUNG5))
    origin = np.asarray(exp.model_cfg["tx_origin"])
    assert origin.dtype == np.int64 and origin.shape == (200,)
    assert origin[:10].tolist() == [1521, 767, 2222, 4083, 666, 1485, 3899,
                                    3381, 3255, 5]
    assert int(origin.sum()) == 497366
    want = np.random.RandomState(55 ^ 0xB17C01).randint(0, 5000, 200)
    assert np.array_equal(origin, want)


def test_seeds_that_differ_anywhere_in_64_bits_draw_different_origins():
    def draw(seed):
        d = doc64()
        d["general"]["seed"] = seed
        return tuple(build_experiment(d)[0].model_cfg["tx_origin"].tolist())

    base = 600000005000
    seeds = [7, 7 + (1 << 32), 7 + (1 << 40), 7 + (1 << 62), base,
             base + (1 << 32), base + (1 << 50), (1 << 32) - 1, 1 << 32]
    draws = [draw(s) for s in seeds]
    assert len(set(draws)) == len(seeds)
    assert draw(7) == draws[0]              # and a seed draws what it drew


# ---- serialization without a division (what rung 5's compile time forced) ------

def test_serialization_is_a_multiply_where_every_rate_divides_8e9():
    """``ser_delay`` equals the division it replaces on every input, traces
    no ``div`` / ``rem`` when handed ``Ctx.ser_up``, and the tables are None
    as soon as one link's rate does not divide 8e9 (that configuration keeps
    the division: PERF.md section 6, PR 28)."""
    import jax

    from shadow1_tpu.consts import SEC
    from shadow1_tpu.core.engine import build_base_ctx, ser_tables_np
    from shadow1_tpu.net import nic
    from shadow1_tpu.tools.opcensus import iter_eqns

    rates = np.array([64_000, 10**6, 10**7, 5 * 10**7, 10**8, 10**9,
                      4 * 10**9, 8 * 10**9], np.int64)
    wire = np.random.RandomState(5).randint(0, 70_000, (64, len(rates)))
    wire[0], wire[1] = 0, 65_575
    nspb = (8 * SEC) // rates
    want = (wire * (8 * SEC) + rates - 1) // rates
    assert np.array_equal(nic.ser_delay(wire, jnp.asarray(rates),
                                        jnp.asarray(nspb)), want)
    assert np.array_equal(nic.ser_delay(wire, jnp.asarray(rates)), want)

    def prims(*ser):
        jaxpr = jax.make_jaxpr(
            lambda w: nic.ser_delay(w, jnp.asarray(rates), *ser))(wire)
        return {e.primitive.name for e in iter_eqns(jaxpr.jaxpr)}

    assert not {"div", "rem"} & prims(jnp.asarray(nspb))
    assert "div" in prims()

    exp = expand_sweep(doc64([1])).exps[0]          # 50 Mbit and 64 kbit
    up, dn = ser_tables_np(exp)
    assert sorted(set(up.tolist())) == [160, 125_000] and np.array_equal(up, dn)
    ctx = build_base_ctx(exp, expand_sweep(doc64([1])).params)
    assert np.array_equal(ctx.ser_up, up) and np.array_equal(ctx.ser_dn, dn)
    exp.bw_dn = exp.bw_dn.copy()
    exp.bw_dn[3] = 10**10                           # 0.8 ns a byte: no integer
    assert ser_tables_np(exp) == (None, None)
    assert build_base_ctx(exp, expand_sweep(doc64([1])).params).ser_up is None


def test_a_rate_that_does_not_divide_8e9_keeps_the_division_and_the_parity():
    """The general path stays what it was: a 3 Mbit network against the CPU
    oracle, every table and counter."""
    from tests.parity import assert_parity, run_both
    from tests.test_bitcoin_parity import BTC_KEYS, btc_exp

    from shadow1_tpu.consts import EngineParams
    from shadow1_tpu.core.engine import ser_tables_np

    exp = btc_exp(bw=3 * 10**6)
    assert ser_tables_np(exp) == (None, None)
    cm, cs, tm, ts = run_both(exp, EngineParams(ev_cap=256))
    assert np.asarray(ts["reach"]).tolist() == [16] * 6
    assert_parity(cm, cs, tm, ts, keys=BTC_KEYS)


# ---- the phase scopes -----------------------------------------------------------

BTC_SCOPES = {"btc_dial", "btc_create", "btc_msg", "btc_notify"}


@pytest.mark.parametrize("which", ["solo", "fleet"])
def test_the_gossip_scopes_are_in_the_program_s_phase_table(plan, fleet, which):
    """Every ``phase:btc_*`` scope reaches the compiled program, under the
    handler pass that runs it; an op line made of the program's own
    instructions is attributed with nothing unknown and sums to busy."""
    eng = fleet[0] if which == "fleet" else Engine(plan.exps[0], plan.params)
    table = phases.phase_table(eng.hlo_text())
    paths = set(table.values())
    parts = {p for path in paths for p in path.split("/")}
    assert BTC_SCOPES <= parts, sorted(parts)
    assert any(p.startswith("rounds/h_app/btc_msg") for p in paths)
    assert any(p.startswith("rounds/h_app/btc_dial") for p in paths)
    assert any(p.startswith("rounds/h_deliver") and "btc_notify" in p
               for p in paths)
    # TCP's flush inside a gossip send keeps its own row.
    assert any("btc_msg" in p and p.endswith("tcp_flush") for p in paths)
    ops = [[f"%{name} = s32[] fusion()", 10 * i, 7]
           for i, name in enumerate(n for n in table
                                    if not phases.is_control_flow(n))]
    got = phases.attribute(ops, table)
    assert got["unknown_ops"] == 0 and got["busy_ns"] == 7 * len(ops)
    rows = got["rows"]
    assert round(sum(r["seconds"] for r in rows.values()) * 1e9) == got["busy_ns"]
    gossip = sum(r["seconds"] for p, r in rows.items()
                 if BTC_SCOPES & set(p.split("/")))
    assert 0 < gossip < got["busy_ns"] / 1e9
    assert phases.rollup_key("rounds/h_app/btc_msg/tcp_flush") == \
        ("handlers", "h_app")


# ---- (h) the cell in miniature through the benchmark's harness ----------------

def _bench(capsys, seed, *more):
    from benchmarks.harness import loop

    rc = loop.main(["--workload", "bitcoin64.flood3", "--seed", str(seed),
                    "--seconds", "0.2", "--trace", "0", *more], REHEARSAL,
                   time.perf_counter(), require_chip=False)
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    return rc, lines[-1], [ln for ln in lines if "engine_vs_reference" in ln]


def test_the_cell_in_miniature_is_correct_in_every_lane(capsys):
    rc, res, lanes = _bench(capsys, 3_000_000_019)
    assert rc == 0 and res["correct"] is True
    assert (res["attempted"], res["failed"]) == (3, 0)
    assert sorted(ln["seed"] for ln in lanes) == \
        [600000005000 + i for i in range(3)]
    for ln in lanes:
        cmp_ = ln["engine_vs_reference"]
        assert ln["ok"] and ln["limit"] == 0 and not ln["must_be_zero"]
        assert {"events", "total_seen", "total_tx_rx"} <= set(cmp_)
        assert all(a == b for a, b in cmp_.values())
        assert cmp_["total_tx_rx"][0] > 0
    assert res["metrics"]["events_per_s"]["value"] > 0


def test_the_cell_in_miniature_flooding_from_other_origins_is_not_correct(capsys):
    """The control that fits this model: the program draws its origins under
    other seeds than the reference (``controls/other_origins*.json``)."""
    rc, res, lanes = _bench(capsys, 11, "--control", "other_origins3")
    assert rc == 0 and res["correct"] is False and res["failed"] == 3
    assert all({"events", "total_seen"} <= set(ln["differ"]) for ln in lanes)


def test_wrong_seed_cannot_see_a_model_that_draws_nothing_at_run_time(capsys):
    """``wrong_seed`` hands the reference the lane's own experiment, tables
    included, and another RNG seed: with no loss, jitter or random size
    nothing draws from it, so the run still equals the reference. Written
    down as a test so that nobody reads a passing ``wrong_seed`` as a check
    of this cell's seeds (PERF.md section 6, PR 28)."""
    rc, res, lanes = _bench(capsys, 11, "--control", "wrong_seed")
    assert rc == 0 and res["correct"] is True
    assert all(ln["reference_seed"] == ln["seed"] + 1 for ln in lanes)


# ---- the command line, as a user starts a study --------------------------------

def test_cli_runs_rung5_as_a_seed_study_under_fleet(tmp_path):
    with open(RUNG5) as f:
        doc = yaml.safe_load(f)
    small = doc64()
    doc["hosts"] = small["hosts"]
    doc["general"]["stop_time"] = small["general"]["stop_time"]
    doc["engine"] = small["engine"]
    doc["app"]["params"] = copy.deepcopy(small["app"]["params"])
    doc["sweep"] = {"seeds": SEEDS}
    cfg = tmp_path / "study.yaml"
    cfg.write_text(yaml.safe_dump(doc))
    out = subprocess.run(
        [sys.executable, "-m", "shadow1_tpu", str(cfg), "--fleet",
         "--heartbeat", "10"], capture_output=True, text=True)
    assert out.returncode == 0, out.stderr[-1500:]
    recs = [json.loads(ln) for ln in out.stdout.strip().splitlines()]
    assert [r["type"] for r in recs] == ["fleet_exp"] * 3 + ["fleet_summary"]
    assert [r["seed"] for r in recs[:3]] == SEEDS
    totals = [r["model"] for r in recs[:3]]
    assert all(t["total_tx_rx"] > 0 and t["total_seen"] >= t["total_tx_rx"]
               and t["total_msg_retries"] == 0 for t in totals)
    assert all(r["drops"]["total"] == 0 for r in recs[:3])
    beats = [json.loads(ln) for ln in out.stderr.splitlines()
             if ln.startswith('{"type": "heartbeat"')]
    assert len(beats) == 2
    assert beats[-1]["fleet"]["model_per_exp"] == totals
