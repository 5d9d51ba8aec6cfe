"""Several cycles inside ``run_seconds``: the benchmark's harness holds
nothing of an earlier cycle on the device.

A cell whose cycle is shorter than ``--seconds`` runs it again and again
from the initial state (``benchmarks/harness/loop._window``). Since PR 33
that is the normal case of the TCP fleets (``tgen100.seeds32`` fits three
cycles into its 10 s), and ``peak_hbm_mb`` may not step up with it: what the
check reads of the first cycle's end (``Sim.keep``) and every cycle's final
counters are host copies, so while a later cycle runs the device holds what
it held during the first. The twin of
``benchmarks/tests/test_rehearsal.py::test_later_cycles_run_with_nothing_of_an_earlier_one_on_the_device``
on the tier-1 side, with the TCP fleet in miniature beside the two PHOLD
cells; the harness's clock is replaced by a counter, so that every run is
exactly three cycles whatever the machine.
"""

import itertools
import json
import os
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks", "tests", "rehearsal")
BITCOIN = os.path.join(ROOT, "tests", "rehearsal_bitcoin64")
CYCLES = 3


@pytest.mark.parametrize("root,cell", [
    (BENCH, "phold32.dense4"), (BENCH, "phold32f.lanes3"),
    (BITCOIN, "bitcoin64.flood3")], ids=["solo", "fleet", "tcp_fleet"])
def test_three_cycles_a_run_leave_nothing_of_the_first_on_the_device(
        capsys, monkeypatch, root, cell):
    from benchmarks.harness import loop
    from benchmarks.harness import sim as simmod

    with open(os.path.join(root, "traffic", cell.split(".")[1] + ".json")) as f:
        traffic = json.load(f)
    chunks = traffic["cycle_windows"] // traffic["chunk_windows"]
    # One tick a reading: a cycle's wall is its chunks, and CYCLES of them
    # are the first sum to reach --seconds.
    ticks = itertools.count(1.0)
    monkeypatch.setattr(loop, "time", types.SimpleNamespace(
        perf_counter=lambda: next(ticks)))

    real_chunk, real_keep = loop.run_chunk, simmod.Sim.keep
    live, kept = [], []

    def watched(sim, st, windows):
        import jax

        # From the initial state: set-up's runs (a solo cell's cache priming
        # in a new checkout, the warm-up), then every cycle's first chunk.
        if not np.asarray(st.metrics.windows).any():
            # The chunk log's waiter holds a chunk's one scalar until the
            # result is ready AND its thread has run: let it.
            from shadow1_tpu.telemetry import chunk_log

            assert chunk_log().settle(5.0)
            live.append(sum(a.nbytes for a in jax.live_arrays()))
        return real_chunk(sim, st, windows)

    def keeping(self, st):
        kept.append(real_keep(self, st))
        return kept[-1]

    monkeypatch.setattr(loop, "run_chunk", watched)
    monkeypatch.setattr(simmod.Sim, "keep", keeping)
    rc = loop.main(["--workload", cell, "--seed", "37", "--trace", "0",
                    "--seconds", str(chunks * (CYCLES - 1) + 1)],
                   root, 0.0, require_chip=False)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.strip().splitlines()]
    import jax      # not before the run: it says where jax's cache lives

    assert rc == 0 and lines[-1]["correct"] is True
    assert [ln for ln in lines if "cycles" in ln][0]["cycles"] == CYCLES
    assert len(live) > CYCLES and len(kept) == 1
    assert len(set(live[-CYCLES:])) == 1, live
    leaves = jax.tree.leaves(kept[0])
    assert leaves and all(isinstance(x, (np.ndarray, np.generic)) for x in leaves)
    assert not any(isinstance(x, jax.Array) for x in leaves)
