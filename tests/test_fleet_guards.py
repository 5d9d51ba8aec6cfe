"""The "any host has this" guards on a fleet: real conditionals, and exact.

``core/engine.any_host`` reduces a guard's predicate over the fleet's lane
axis, so ``vmap`` keeps each ``lax.cond`` a conditional instead of running
both branches and selecting every leaf (``run_round``'s docstring has the
contract). Held here, on miniatures of the fleets the other test files build
(tgen: ``test_tgen_parity.tgen_exp``; bitcoin: ``rehearsal_bitcoin64``; Tor:
``rehearsal_tor20``; filexfer: ``test_fleet``'s slow parity case):

* the helper alone: the literal ``mask.any()`` where no lane axis is named,
  one unbatched value under the named axis;
* the lowered programs: a fleet holds one ``case`` for every handler pass,
  one for the window end and one for every bootstrap-phase guard of its app
  (the per-stream guards of the TCP stack, tgen and filexfer keep the lane's
  own predicate, which ``vmap`` turns into selects: PERF.md §6, PR 38), and
  the solo program is what the literal ``mask.any()`` lowers to;
* a tgen fleet whose lanes disagree on which kinds a round holds still
  equals its solo runs leaf for leaf, and ``runs_*`` counts what it should
  (``test_tor_fleet`` and ``test_bitcoin_fleet`` hold the same for their
  fleets, on the runs they already make).
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from shadow1_tpu.apps import bitcoin, tor
from shadow1_tpu.config.compiled import single_vertex_experiment
from shadow1_tpu.consts import KIND_METRIC_FIELDS, MS, EngineParams
from shadow1_tpu.core import engine as core_engine
from shadow1_tpu.core.engine import Engine, any_host, lane_branch
from shadow1_tpu.fleet.engine import (
    LANE_AXIS,
    FleetEngine,
    fleet_metrics_per_exp,
    slice_experiment,
)
from shadow1_tpu.fleet.expand import expand_sweep
from shadow1_tpu.telemetry.registry import LANE_PROGRAM_FIELDS
from tests.parity import assert_runs_contract, unlike_leaves
from tests.test_bitcoin_fleet import doc64
from tests.test_fleet import filexfer_exp
from tests.test_tgen_parity import tgen_exp
from tests.test_tor_fleet import doc20

GUARDED = (core_engine, bitcoin, tor)
# The TCP stack's passes (deliver, timer, tx-resume, app), the window end
# (``deliver_window``, PR 40) and the round's push commit (``_commit_pushes``,
# PR 49): reduced over the lanes. Its two guards (passive
# open, FIN) and tgen's / filexfer's two (teardown) recur with every
# connection: a ``case`` on the solo engine, the lane's own predicate
# (selects) on a fleet. Then the app's bootstrap guards.
PASSES, PER_STREAM = 4 + 1 + 1, 2


def _filexfer_exps():
    return ([filexfer_exp(11, 0.0), filexfer_exp(12, 0.05)],
            EngineParams(ev_cap=32, outbox_cap=16))


def _tgen_exps():
    return ([tgen_exp(n_hosts=6, seed=s, streams=2) for s in (21, 22)],
            EngineParams(ev_cap=256))


def _plan(doc):
    plan = expand_sweep(doc)
    return plan.exps, plan.params


# model: (experiments, guards reduced over a fleet's lanes, guards in all)
MINIATURES = {
    "tgen": (_tgen_exps, PASSES, PASSES + PER_STREAM + 2),
    "bitcoin": (lambda: _plan(doc64([1, 2])), PASSES + 3,
                PASSES + PER_STREAM + 3),
    "tor": (lambda: _plan(doc20([600000003000, 600000003001])), PASSES + 9,
            PASSES + PER_STREAM + 9),
    "filexfer": (_filexfer_exps, PASSES, PASSES + PER_STREAM + 2),
}


def _fleet(exps, params):
    # tor20 states a compact_cap (8 of 20 columns a trip): the guards below
    # are then counted in the bucket-wide round, the program's only one.
    return FleetEngine(exps, params)


def _ops(eng) -> dict:
    """``case`` and ``select`` ops of the window program as jax lowers it
    (StableHLO, before any compiler pass: nothing compiles here)."""
    st = jax.eval_shape(eng.init_state)
    args = (st, jnp.asarray(0, jnp.int32))
    if isinstance(eng, FleetEngine):
        args += (eng._variants,)
    text = eng._run_jit.lower(*args).as_text()
    return {op: text.count(f"stablehlo.{op}") for op in ("case", "select")}


# ---- the helper alone ---------------------------------------------------------

def _ctx(**kw):
    exp = single_vertex_experiment(
        n_hosts=4, seed=1, end_time=10 * MS, latency_ns=10 * MS,
        model="phold", model_cfg={"mean_delay_ns": 2.0e7, "init_events": 1})
    return dataclasses.replace(Engine(exp, EngineParams()).ctx, **kw)


@pytest.mark.parametrize("shape", [(5,), (3, 5), ()])
def test_without_a_lane_axis_the_predicate_is_mask_any_to_the_letter(shape):
    ctx = _ctx()
    assert ctx.lane_axis is None
    mask = jnp.zeros(shape, bool)
    assert str(jax.make_jaxpr(lambda m: any_host(ctx, m))(mask)) \
        == str(jax.make_jaxpr(lambda m: m.any())(mask))


def test_under_the_lane_axis_the_predicate_is_one_value_for_all_lanes():
    ctx = _ctx(lane_axis=LANE_AXIS)
    masks = jnp.asarray([[False, False], [False, True], [False, False]])
    for m, want in ((masks, True), (jnp.zeros_like(masks), False)):
        got = jax.vmap(lambda x: any_host(ctx, x), axis_name=LANE_AXIS,
                       out_axes=None)(m)    # out_axes=None: not batched
        assert got.shape == () and bool(got) is want
    # The cond it guards stays a cond under vmap, and skips a dead branch.
    text = jax.jit(jax.vmap(
        lambda x: jax.lax.cond(any_host(ctx, x), lambda: x.all(),
                               lambda: jnp.zeros((), bool)),
        axis_name=LANE_AXIS)).lower(masks).as_text()
    assert text.count("stablehlo.case") == 1
    with pytest.raises(NameError, match=LANE_AXIS):
        any_host(ctx, masks[0])     # a lane's Ctx outside the fleet's vmap


def test_a_guarded_branch_is_itself_without_a_lane_axis_and_one_call_under_one():
    def branch(x):
        return x + x.sum()

    assert lane_branch(_ctx(), branch) is branch
    ctx = _ctx(lane_axis=LANE_AXIS)

    def guarded(x):
        return jax.lax.cond(any_host(ctx, x > 0), lane_branch(ctx, branch),
                            lambda y: y, x)

    xs = jnp.arange(6).reshape(3, 2)
    fleet = jax.vmap(guarded, axis_name=LANE_AXIS)
    assert (fleet(xs) == jax.vmap(branch)(xs)).all()
    assert (fleet(-xs) == -xs).all()
    # What makes vmap batch the branch once however often its rule visits
    # it: the taken branch is one call of a jitted callee, and nothing else.
    cond, = [e for e in jax.make_jaxpr(fleet)(xs).eqns
             if e.primitive.name == "cond"]
    taken = cond.params["branches"][1].jaxpr.eqns
    assert [e.primitive.name for e in taken] == ["jit"], taken
    # A leaf the branch leaves alone is handed back, not returned: the guard
    # yields what its block writes, as a cond that sees into its branch does.
    pair = jax.vmap(
        lambda a, b: jax.lax.cond(
            any_host(ctx, a > 0), lane_branch(ctx, lambda a, b: (a + 1, b)),
            lambda a, b: (a, b), a, b),
        axis_name=LANE_AXIS)
    got = pair(xs, -xs)
    assert (got[0] == xs + 1).all() and (got[1] == -xs).all()
    cond, = [e for e in jax.make_jaxpr(pair)(xs, -xs).eqns
             if e.primitive.name == "cond"]
    assert len(cond.outvars) == 1, cond


# ---- (i) the lowered programs --------------------------------------------------

@pytest.mark.parametrize("model", sorted(MINIATURES))
def test_a_fleet_holds_a_case_for_every_reduced_guard_and_the_solo_program_is_as_it_was(
        model, monkeypatch):
    build, reduced, guards = MINIATURES[model]
    exps, params = build()
    eng = _fleet(exps, params)
    fleet, params = _ops(eng), eng.params      # the width the fleet runs at
    solo = _ops(Engine(exps[0], params))
    assert (fleet["case"], solo["case"]) == (reduced, guards), (fleet, solo)
    # What vmap made of a guard before: both branches, every leaf selected.
    for mod in GUARDED:
        monkeypatch.setattr(mod, "any_host", lambda ctx, mask: mask.any())
    monkeypatch.setattr(core_engine, "any_lane", lambda ctx, hit: hit)
    literal = _ops(Engine(exps[0], params))
    assert literal == solo, (literal, solo)
    batched = _ops(_fleet(exps, params))
    assert batched["case"] == 0 and batched["select"] > fleet["select"], (
        batched, fleet)


# ---- (ii), (iii) a fleet whose lanes disagree, against its solo runs -----------

N_WINDOWS = 60


@pytest.fixture(scope="module")
def tgen_fleet():
    exps, params = _tgen_exps()
    eng = FleetEngine(exps, params)
    return eng, exps, eng.run(n_windows=N_WINDOWS)


@pytest.fixture(scope="module")
def tgen_solos(tgen_fleet):
    eng, exps, _ = tgen_fleet
    return [Engine(exp, eng.params).run(n_windows=N_WINDOWS) for exp in exps]


@pytest.mark.parametrize("lane", range(2))
def test_a_tgen_lane_run_beside_a_lane_of_other_kinds_equals_its_solo_run(
        tgen_fleet, tgen_solos, lane):
    eng, _, st = tgen_fleet
    want = tgen_solos[lane]
    assert not unlike_leaves(slice_experiment(st, lane), want)
    m = fleet_metrics_per_exp(st)[lane]
    assert m["events"] > 300 and m["ev_overflow"] == m["round_cap_hits"] == 0
    # Rounds in which the program ran a pass that this lane had no event
    # for: the case the contract is about occurred.
    assert any(m[runs] > m[fires]
               for _, fires, runs in KIND_METRIC_FIELDS.values()), m


def test_tgen_runs_count_the_program_and_fires_the_lane(tgen_fleet,
                                                       tgen_solos):
    _, _, st = tgen_fleet
    assert_runs_contract(fleet_metrics_per_exp(st),
                         [Engine.metrics_dict(s) for s in tgen_solos])


def test_the_fleet_aggregate_reports_runs_once_not_once_a_lane(tgen_fleet):
    _, _, st = tgen_fleet
    lanes = fleet_metrics_per_exp(st)
    agg = FleetEngine.metrics_dict(st)
    for k in LANE_PROGRAM_FIELDS:
        assert agg[k] == lanes[0][k] == lanes[1][k]
    assert agg["events"] == lanes[0]["events"] + lanes[1]["events"]


def test_a_lane_that_hits_its_own_round_cap_rides_on_as_the_identity(
        tgen_solos):
    """The fleet's round loop runs while ANY lane's would. A lane stopped by
    its own ``max_rounds`` with events still eligible (they go past-due into
    the next window) must pop nothing in the rounds the other lane still
    runs: both lanes equal their solo runs, the capped one under its cap."""
    exps, params = _tgen_exps()
    caps = [3, params.max_rounds]
    st = FleetEngine(exps, params, max_rounds=caps).run(n_windows=N_WINDOWS)
    capped = Engine(exps[0], dataclasses.replace(params, max_rounds=caps[0])
                    ).run(n_windows=N_WINDOWS)
    lanes = fleet_metrics_per_exp(st)
    assert lanes[0]["round_cap_hits"] > 5 and lanes[1]["round_cap_hits"] == 0
    assert lanes[0]["rounds"] < lanes[1]["rounds"]
    for lane, want in enumerate((capped, tgen_solos[1])):
        assert not unlike_leaves(slice_experiment(st, lane), want), lane
