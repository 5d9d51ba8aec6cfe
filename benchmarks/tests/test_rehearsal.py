"""A whole run on the CPU at 20 to 32 hosts, both engines: the look for a
chip skipped, everything after it driven as on the chip."""

import json
import os
import time

import pytest

from benchmarks.harness import loop

REHEARSAL = os.path.join(os.path.dirname(os.path.abspath(__file__)), "rehearsal")
SOLO, FLEET, TOR = "phold32.dense4", "phold32f.lanes3", "tor20.seeds2"


def run(capsys, cell, seed, *more, require_chip=False):
    rc = loop.main(["--workload", cell, "--seed", str(seed), "--seconds", "0.3",
                    "--trace", "0", *more], REHEARSAL, time.perf_counter(),
                   require_chip=require_chip)
    cap = capsys.readouterr()
    lines = [json.loads(ln) for ln in cap.out.strip().splitlines()]
    if lines and "compared" in lines[-1]:
        # Every number compared, beside its limit: the result line's last
        # key, and the last lines of stderr, name for name.
        said = [ln.split() for ln in cap.err.strip().splitlines()
                if ln.startswith("compared ")]
        assert list(lines[-1])[-1] == "compared"
        assert cap.err.strip().splitlines()[-len(said):] == [" ".join(w) for w in said]
        assert {w[1]: [int(w[2]), int(w[4])] for w in said} == lines[-1]["compared"]
        over = [k for k, (n, lim) in lines[-1]["compared"].items() if n > lim]
        assert bool(over) == (not lines[-1]["correct"]), over
    return rc, lines


def lanes(lines):
    return [ln for ln in lines if "engine_vs_reference" in ln]


@pytest.mark.parametrize("cell,n_lanes", [(SOLO, 1), (FLEET, 3), (TOR, 2)])
def test_a_sound_run_is_correct_and_two_seeds_differ(capsys, cell, n_lanes):
    seen = []
    for seed in (7, 3_000_000_019):   # the driver's seeds pass 2**31
        rc, lines = run(capsys, cell, seed)
        res = lines[-1]
        assert rc == 0 and res["correct"] is True
        assert (res["attempted"], res["failed"]) == (n_lanes, 0)
        assert set(res) >= {"correct", "attempted", "failed", "metrics", "device"}
        assert all(m["value"] > 0 for k, m in res["metrics"].items()
                   if not k.startswith("peak"))   # the CPU reports no memory peak
        per_lane = lanes(lines)
        assert len(per_lane) == n_lanes
        for ln in per_lane:
            assert ln["ok"] and ln["limit"] == 0 and len(ln["engine_vs_reference"]) >= 5
            assert all(a == b for a, b in ln["engine_vs_reference"].values())
        seen.append([ln["engine_vs_reference"] for ln in per_lane])
    assert seen[0] != seen[1], "two seeds must not give the same counters"
    assert len({json.dumps(x) for x in seen[1]}) == n_lanes, "lanes must differ"


@pytest.mark.parametrize("cell,control", [
    (SOLO, "wrong_seed"), (FLEET, "wrong_seed"), (SOLO, "small_caps")])
def test_a_control_comes_out_not_correct(capsys, cell, control):
    """The reference under the next seed; the program with event buffers too
    small for the traffic against the reference at the stated caps (the
    solo mix alone fills 20 slots a host)."""
    rc, lines = run(capsys, cell, 11, "--control", control)
    res = lines[-1]
    assert rc == 0 and res["correct"] is False and res["failed"] == res["attempted"]
    assert all(ln["differ"] for ln in lanes(lines))
    if control == "small_caps":
        assert all(ln["must_be_zero"] for ln in lanes(lines))
        assert res["compared"]["ev_overflow.must_be_zero"][0] > 0


@pytest.mark.parametrize("cell", [SOLO, FLEET])
def test_a_chunk_that_returns_its_state_unchanged_is_caught(capsys, monkeypatch, cell):
    """The timed path broken underneath: the third chunk of every cycle does
    nothing, so each cycle ends two windows short."""
    real, calls = loop.run_chunk, [0]

    def broken(sim, st, windows):
        calls[0] += 1
        return st if calls[0] % 4 == 3 else real(sim, st, windows)

    monkeypatch.setattr(loop, "run_chunk", broken)
    rc, lines = run(capsys, cell, 13)
    assert rc == 0 and lines[-1]["correct"] is False
    assert all("events" in ln["differ"] for ln in lanes(lines))


@pytest.mark.parametrize("cell", [SOLO, FLEET])
def test_later_cycles_run_with_nothing_of_an_earlier_one_on_the_device(
        capsys, monkeypatch, cell):
    """A cell whose cycle is under ``--seconds`` runs several cycles a run.
    What the check reads of the first cycle's end, and every cycle's final
    counters, are host copies: while a later cycle runs, the device holds
    what it held during the first (the running state and the engine's own
    constants), so the memory peak is the program's however many cycles fit."""
    from benchmarks.harness import sim as simmod

    real_chunk, real_keep = loop.run_chunk, simmod.Sim.keep
    live, kept, calls = [], [], [0]

    def watched(sim, st, windows):
        import jax

        calls[0] += 1
        if calls[0] > 1 and (calls[0] - 2) % 4 == 0:    # a cycle's first chunk
            live.append(sum(a.nbytes for a in jax.live_arrays()))
        return real_chunk(sim, st, windows)

    def keeping(self, st):
        kept.append(real_keep(self, st))
        return kept[-1]

    monkeypatch.setattr(loop, "run_chunk", watched)
    monkeypatch.setattr(simmod.Sim, "keep", keeping)
    rc, lines = run(capsys, cell, 37)
    import jax      # not before the run: it says where jax's cache lives
    import numpy as np

    assert rc == 0 and lines[-1]["correct"] is True
    cycles = [ln for ln in lines if "cycles" in ln][0]["cycles"]
    assert cycles >= 3 and len(live) == cycles and len(kept) == 1
    assert len(set(live)) == 1, live
    leaves = jax.tree.leaves(kept[0])
    assert leaves and all(isinstance(x, (np.ndarray, np.generic)) for x in leaves)
    assert not any(isinstance(x, jax.Array) for x in leaves)


def test_one_altered_counter_in_one_lane_is_caught(capsys, monkeypatch):
    from benchmarks.harness import sim as simmod

    real = simmod.Sim.lane_counters

    def altered(self, kept):
        out = real(self, kept)
        out[1]["pkts_delivered"] += 1
        return out

    monkeypatch.setattr(simmod.Sim, "lane_counters", altered)
    rc, lines = run(capsys, FLEET, 17)
    assert lines[-1]["correct"] is False and lines[-1]["failed"] == 1
    assert [ln["differ"] for ln in lanes(lines)] == [[], ["pkts_delivered"], []]
    assert lines[-1]["compared"]["pkts_delivered"] == [1, 0]
    assert lines[-1]["compared"]["events"] == [0, 0]


@pytest.mark.parametrize("cell,useful", [(SOLO, False), (TOR, True)])
def test_a_traced_run_reports_the_phases(capsys, monkeypatch, cell, useful):
    """``--trace 1`` from end to end on the CPU, with a capture recorded on
    the chip and its program's phase table put where the profiler's capture
    and the compiled program's text are read (a CPU capture has no device op
    line). The last line keeps its keys and only ``metrics`` grows; the phase
    table and the named gaps are on an earlier line."""
    import gzip

    from benchmarks.harness import manifest as mf
    from benchmarks.harness import phases
    from benchmarks.harness import trace as tr

    data = os.path.join(os.path.dirname(REHEARSAL), "data")
    with gzip.open(os.path.join(data, "trace_phold32_spans_v5e.json.gz"), "rt") as f:
        capture = json.load(f)
    table = mf.read_json(os.path.join(
        data, "trace_phold32_spans_v5e.phase_table.json"))["table"]
    if useful:      # a program with two handler kinds
        table = {**table, "no.such.op.1": "rounds/h_timer", "no.such.op.2": "rounds/h_app"}
    monkeypatch.setattr(tr, "read_xplane", lambda log_dir: capture)
    monkeypatch.setattr(phases, "phase_table", lambda text: (
        table if "HloModule" in text else {}))
    rc, lines = run(capsys, cell, 7, "--trace", "1")
    res = lines[-1]
    assert rc == 0 and res["correct"] is True
    assert list(res) == ["correct", "attempted", "failed", "breakdown", "metrics", "device",
                         "compared"]
    assert sorted(res["breakdown"]) == ["device_ops", "idle_gaps"]
    assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
    man = mf.load(REHEARSAL)
    want = {e["name"] for e in mf.metrics_of(man, "per_layer", cell)}
    phase = {"prepare_ms_per_window", "pop_ms_per_round", "handlers_ms_per_round",
             "deliver_ms_per_window", "phase_unattributed_share", "exec_idle_share",
             "dispatch_ms_per_chunk"}
    # The share of useful handler passes is the fleets' with several kinds.
    if useful:
        phase |= {"handler_pass_useful_share"}
    assert phase <= set(res["metrics"]) <= want
    assert ("handler_pass_useful_share" in want) == useful
    assert all(isinstance(m["value"], (int, float)) for m in res["metrics"].values())
    table_line = [ln for ln in lines if "phases" in ln][0]
    assert table_line["unknown_ops"] == 0 and table_line["gaps"]
    assert table_line["program_spans"] == ["dispatch", "run-chunk"]
    assert table_line["handler_kinds"] == (3 if useful else 1)
    assert sum(r["seconds"] for r in table_line["phases"].values()) == pytest.approx(
        res["device"]["busy_s"], rel=1e-9)
    assert len(table_line["fires_by_lane"]) == res["attempted"]


def test_loop_rounds_is_each_window_s_slowest_lane():
    # Lane 0 is slowest in the first window, lane 2 in the second, lane 1 in
    # the third: the loop ran 9 + 8 + 7, not the 19 of the busiest lane.
    assert loop.loop_rounds([[9, 1, 2], [3, 4, 8], [5, 7, 1]]) == 24
    assert loop.loop_rounds([[6], [7]]) == 13


def test_a_fleet_s_rounds_are_the_loop_s_iterations(monkeypatch):
    """Three lanes whose slowest lane changes from window to window: what the
    per-layer metrics divide by is how often the round body ran, counted
    here by a callback in the body itself, and more than any one lane's
    rounds."""
    import jax
    import numpy as np

    from benchmarks.harness import sim as simmod
    from shadow1_tpu.core import engine as program

    real, ran = program.run_round, [0]

    def counted(st, ctx, handlers, win_end):
        jax.debug.callback(lambda: ran.__setitem__(0, ran[0] + 1))
        return real(st, ctx, handlers, win_end)

    monkeypatch.setattr(program, "run_round", counted)

    class Args:
        workload, control = FLEET, None

    c = loop._load_cell(REHEARSAL, Args)
    doc, base = simmod.experiment_doc(c["cfg_path"], c["meta"], c["traffic"])
    sim = simmod.build(doc, base, "fleet", simmod.lane_seeds(c["traffic"], 11))
    t_from, t_to = c["traced"]
    st = loop.run_chunk(sim, sim.engine.init_state(), t_from)
    jax.effects_barrier()
    before, ran_before = np.asarray(st.metrics.rounds), ran[0]
    st = loop.run_chunk(sim, st, t_to - t_from)
    jax.effects_barrier()
    in_stretch = ran[0] - ran_before
    busiest_lane = int((np.asarray(st.metrics.rounds) - before).max())

    per_window = loop._replay_rounds(sim, c, jax.device_get(st.metrics))
    assert np.shape(per_window) == (t_to - t_from, 3)
    assert len({int(np.argmax(w)) for w in per_window}) > 1, per_window
    assert loop.loop_rounds(per_window) == in_stretch > busiest_lane

    # The stretch run again must end where the traced run ended.
    wrong = jax.device_get(st.metrics)._replace(events=np.asarray([0, 0, 0]))
    with pytest.raises(RuntimeError):
        loop._replay_rounds(sim, c, wrong)


def test_a_pool_of_seeds_is_the_same_set_in_another_order():
    from benchmarks.harness import sim as simmod

    mix = {"lanes": 32, "seed_pool_first": 5000}
    a, b = simmod.lane_seeds(mix, 7), simmod.lane_seeds(mix, 3_000_000_019)
    assert a != b and a == simmod.lane_seeds(mix, 7)
    assert sorted(a) == sorted(b) == list(range(5000, 5032))
    assert simmod.lane_seeds({"lanes": 2, "seed_mul": 1000}, 9) == [9000, 9001]


def test_a_cell_that_pins_its_cache_writes_it_once_and_reads_it_after(capsys):
    """The solo rehearsal configuration names the seed its programs are
    written to the cache under: the first run leaves the marker, later runs
    of any seed write nothing and stay correct."""
    import glob

    for seed in (29, 31):
        rc, lines = run(capsys, SOLO, seed)
        import jax      # not before the run: it says where jax's cache lives

        assert rc == 0 and lines[-1]["correct"] is True
        assert "prime" in [ln for ln in lines if "setup_spans_s" in ln][0]["setup_spans_s"]
        assert jax.config.jax_persistent_cache_min_compile_time_secs > 1e6
        marks = glob.glob(os.path.join(jax.config.jax_compilation_cache_dir,
                                       ".written." + SOLO + ".*"))
        assert len(marks) == 1


def test_a_solo_pool_of_one_runs_one_simulation_and_primes_under_the_config_s_seed(capsys):
    """A solo cell whose rate the seed's draws would move by more than the
    bound runs a pool of one seed: every ``--seed`` is the same simulation,
    still a seed new to the pinned cache (which is written under the
    configuration's seed, not the pool's)."""
    import glob

    seen = []
    for seed in (43, 3_000_000_047):
        rc, lines = run(capsys, "phold32.pool1", seed)
        assert rc == 0 and lines[-1]["correct"] is True
        (lane,) = lanes(lines)
        assert lane["seed"] == lane["reference_seed"] == 600000000032
        seen.append(lane["engine_vs_reference"])
    assert seen[0] == seen[1]
    import jax

    marks = glob.glob(os.path.join(jax.config.jax_compilation_cache_dir,
                                   ".written.phold32.pool1.*"))
    assert [m.rsplit(".", 1)[1] for m in marks] == ["2147480023"]


def test_a_cpu_backend_gives_no_result(capsys):
    rc, lines = run(capsys, SOLO, 19, require_chip=True)
    assert rc == loop.EXIT_NO_CHIP and not any("correct" in ln for ln in lines)


def test_a_compile_inside_the_window_fails_the_run(capsys, monkeypatch):
    import jax
    import jax.numpy as jnp

    real, calls = loop.run_chunk, [0]

    def compiling(sim, st, windows):
        calls[0] += 1
        if calls[0] == 3:   # after the warm-up: inside the window
            jax.jit(lambda x: x * 3 + calls[0])(jnp.arange(7)).block_until_ready()
        return real(sim, st, windows)

    monkeypatch.setattr(loop, "run_chunk", compiling)
    rc, lines = run(capsys, SOLO, 23)
    assert rc == loop.EXIT_COMPILED_IN_WINDOW
    assert not any("correct" in ln for ln in lines)
