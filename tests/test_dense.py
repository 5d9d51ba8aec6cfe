"""core/dense's one-hot read (``read_sel`` + ``extract_col``) against the
gather it replaces.

Values: the dense read — spelled out, and as ``get_col`` — must equal numpy's
``take_along_axis`` at the clipped column bit for bit, in every dtype
the state planes use (bool, i32, u32 with the top bit set, i64), at every
rank callers pass ([C,H], [L,C,H], [L1,L2,C,H]), for columns below 0 and at
or above C, eagerly, under ``jit`` and under ``vmap`` (what the fleet engine
compiles). ``table_rows`` + ``pick_row`` (PR 51), the read of a 64-bit
``[R, W]`` table at a row per host and a column per slot, must equal numpy's
``table[row, idx]`` in both half words the same three ways.

Shape of the program: in the ``rounds`` phase of a TCP model (filexfer, Tor,
Bitcoin), solo and under ``vmap`` over two lanes, no ``gather`` equation has
a frame in ``core/dense.py`` or ``tcp/tcp.py``, and in PHOLD's and tgen's
none comes from ``rng._neg_log1m_q32``. At the window's ends of a one-vertex
TCP model (tgen, Tor, Bitcoin) the ``prepare`` phase holds no ``gather`` at
all and ``route_outbox`` none in the ``deliver`` phase; nor on rung 1, whose
two vertices each hold one run of hosts (its four lookups are compares and
selects since PR 42), while a ``vertex: spread`` map of the same network
keeps exactly one, ``host_vertex[dst]`` (on 200 vertices too, PR 51:
``tests/test_bitcoin_cities.py``). On the v5e such a gather is an
element-serial kCustom fusion, 7–13.5 ns an element (PERF.md §6, PR 26,
PR 31, PR 33, PR 34 and PR 42).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shadow1_tpu.core.dense import (
    extract_col,
    get_col,
    pick_row,
    read_sel,
    table_rows,
)
from tests.test_tor_fleet import _named_eqns

C, H = 5, 7
RANKS = {"CH": (), "LCH": (3,), "LLCH": (2, 3)}
DTYPES = ("bool", "int32", "uint32", "int64")
# Every host's column in range / some below 0 and some at or above C.
COLS = {
    "inrange": np.array([0, 4, 2, 1, 3, 0, 4], np.int32),
    "clipped": np.array([-1, 5, 2, -7, 99, 0, 4], np.int32),
}
MODES = ("eager", "jit", "vmap")


def _plane(dtype: str, lead: tuple) -> np.ndarray:
    rng = np.random.default_rng(len(lead) * 10 + len(dtype))
    shape = lead + (C, H)
    if dtype == "bool":
        return rng.integers(0, 2, shape).astype(bool)
    if dtype == "uint32":  # values >= 2**31: the wrapping-sum contract
        return rng.integers(2**31, 2**32, shape, dtype=np.uint64).astype(
            np.uint32)
    if dtype == "int64":   # beyond 32 bits, both signs
        return rng.integers(-2**62, 2**62, shape, dtype=np.int64)
    return rng.integers(-2**31, 2**31, shape, dtype=np.int64).astype(np.int32)


def dense_read(arr, col):
    return extract_col(read_sel(col, arr.shape[-2]), arr)


READERS = {"sel_extract": dense_read, "get_col": get_col}


def _gather_ref(arr: np.ndarray, col: np.ndarray) -> np.ndarray:
    """The read as a gather: take_along_axis at the clipped column."""
    c = np.clip(col, 0, arr.shape[-2] - 1)
    idx = np.broadcast_to(c, arr.shape[:-2] + (1, c.shape[0]))
    return np.take_along_axis(arr, idx, axis=-2).squeeze(-2)


@pytest.mark.parametrize("reader", READERS)
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("cols", COLS)
@pytest.mark.parametrize("rank", RANKS)
@pytest.mark.parametrize("dtype", DTYPES)
def test_dense_read_equals_gather(dtype, rank, cols, mode, reader):
    read = READERS[reader]
    arr = _plane(dtype, RANKS[rank])
    col = COLS[cols]
    if mode == "vmap":
        # Two lanes with different planes and columns, as the fleet stacks
        # its experiments.
        arrs = np.stack([arr, arr[..., ::-1, :]])
        colv = np.stack([col, col[::-1]])
        got = jax.vmap(read)(jnp.asarray(arrs), jnp.asarray(colv))
        want = np.stack([_gather_ref(a, c) for a, c in zip(arrs, colv)])
    else:
        f = jax.jit(read) if mode == "jit" else read
        got = f(jnp.asarray(arr), jnp.asarray(col))
        want = _gather_ref(arr, col)
    assert got.dtype == arr.dtype
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("dtype", DTYPES)
def test_one_sel_serves_many_planes(dtype):
    """_tcp_flush and tcp.Sock build read_sel once and extract every plane
    through it: the same values as a gather per plane, [S,H] and [MQ,S,H]."""
    col = COLS["clipped"]
    sel = read_sel(jnp.asarray(col), C)
    for lead in RANKS.values():
        arr = _plane(dtype, lead)
        np.testing.assert_array_equal(
            np.asarray(extract_col(sel, jnp.asarray(arr))), _gather_ref(arr, col))


# ---------------------------------------------------------------------------
# a table's rows per host, and a pick per slot (PR 51)
# ---------------------------------------------------------------------------

def _table(dtype: str, rows: int, width: int) -> np.ndarray:
    """Every byte of the 64 bits in use, the top bit too; no two entries
    alike."""
    t = np.random.default_rng(rows * width).integers(
        0, 2**64, (rows, width), dtype=np.uint64)
    t[0, 0], t[-1, -1] = 0, 2**64 - 1
    return t.astype(dtype)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("shape", [(3, 3), (17, 17), (200, 200), (40, 9)],
                         ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("dtype", ["int64", "uint64"])
def test_table_rows_then_pick_row_equals_the_lookup(dtype, shape, mode):
    """``pick_row(table_rows(table, row), idx)`` is ``table[row[h], idx[c,
    h]]``, both half words, eagerly, under ``jit`` and under ``vmap`` with a
    table and an ``idx`` a lane (as the fleet's thresholds are)."""
    rows, width = shape
    n_hosts, cap = 37, 5
    r = np.random.default_rng(rows)
    table = _table(dtype, rows, width)
    row = r.integers(0, rows, n_hosts).astype(np.int32)
    idx = r.integers(0, width, (cap, n_hosts)).astype(np.int32)

    def read(t, i):
        lo, hi = table_rows(t, jnp.asarray(row))
        assert lo.shape == hi.shape == (width, n_hosts)
        assert lo.dtype == hi.dtype == jnp.uint32
        return pick_row((lo, hi), i)

    if mode == "vmap":
        tables = np.stack([table, table[::-1, ::-1]])
        idxs = np.stack([idx, (idx + 1) % width])
        lo, hi = jax.vmap(read)(jnp.asarray(tables), jnp.asarray(idxs))
        want = np.stack([t[row[None, :], i] for t, i in zip(tables, idxs)])
    else:
        f = jax.jit(read) if mode == "jit" else read
        lo, hi = f(jnp.asarray(table), jnp.asarray(idx))
        want = table[row[None, :], idx]
    got = (np.asarray(hi).astype(np.uint64) << np.uint64(32)
           | np.asarray(lo).astype(np.uint64)).astype(dtype)
    np.testing.assert_array_equal(got, want)
    assert (np.asarray(hi) != 0).any() and (np.asarray(lo) >> 31).any()


def test_pick_row_reads_zero_outside_the_plane():
    plane = jnp.asarray(np.arange(1, 13, dtype=np.uint32).reshape(4, 3))
    idx = jnp.asarray([[0, 3, 2], [-1, 4, 1]], jnp.int32)
    got, = pick_row((plane,), idx)
    np.testing.assert_array_equal(np.asarray(got), [[1, 11, 9], [0, 0, 6]])


# ---------------------------------------------------------------------------
# static guard: no gather from core/dense.py or tcp/tcp.py in the TCP round
# ---------------------------------------------------------------------------

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READ_FILES = ("shadow1_tpu/core/dense.py", "shadow1_tpu/tcp/tcp.py")


def _frames(eqn) -> set[tuple[str, str]]:
    """(function name, file name) of every frame of an equation's traceback."""
    tb = eqn.source_info.traceback
    return set() if tb is None else {(f.function_name, f.file_name)
                                     for f in tb.frames}


@functools.lru_cache(maxsize=None)
def _engine(config: str):
    """The solo engine of an experiment file, its path from the repo's
    root."""
    from shadow1_tpu.tools.phaseprobe import build_engine

    return build_engine(os.path.join(ROOT, config))[0]


@functools.lru_cache(maxsize=None)
def _phases(config: str):
    """(the window's phase fns by name, their entry frame) for an experiment
    file — the jaxprs tools/opcensus.py traces."""
    from shadow1_tpu.core.engine import window_frame, window_phases

    eng = _engine(config)
    phases = dict(window_phases(eng.ctx, eng._handlers, None, eng._pre_window,
                                eng._model.make_handlers, None))
    return phases, window_frame(eng.init_state(), eng.ctx)


def _rounds(config: str):
    """(rounds-phase fn, its frame)."""
    phases, frame = _phases(config)
    return phases["rounds"], frame


def _eqns(phase, lanes: int):
    """(primitive, frames) of every equation of a (phase fn, frame), solo or
    under ``vmap`` over ``lanes``."""
    from shadow1_tpu.tools.opcensus import iter_eqns

    fn, fr = phase
    if lanes:
        fn = jax.vmap(fn)
        fr = jax.tree_util.tree_map(lambda x: jnp.stack([x] * lanes), fr)
    return [(e.primitive.name, _frames(e))
            for e in iter_eqns(jax.make_jaxpr(fn)(fr).jaxpr)]


def _eqns_from(rounds, lanes: int, function: str):
    """(primitive, function names) of the equations ``function`` is a frame
    of."""
    return [(prim, fns) for prim, frames in _eqns(rounds, lanes)
            if function in (fns := {fn for fn, _ in frames})]


@pytest.fixture(scope="module", params=[
    "configs/rung1_filexfer.yaml",
    "benchmarks/tests/rehearsal/configs/tor20.yaml",
    "tests/rehearsal_bitcoin64/configs/bitcoin64.yaml",
], ids=["filexfer", "tor20", "bitcoin64"])
def tcp_rounds(request):
    return _rounds(request.param)


@pytest.mark.parametrize("lanes", [0, 2], ids=["solo", "vmap2"])
def test_tcp_round_has_no_gather(tcp_rounds, lanes):
    """Every socket-field and app-table read of the round — ``_tcp_flush``,
    ``tcp.Sock.g``, the apps' ``get_col`` sites — is a one-hot pass."""
    reads = [(prim, {fn for fn, file in frames if "shadow1_tpu" in file})
             for prim, frames in _eqns(tcp_rounds, lanes)
             if any(file.endswith(READ_FILES) for _, file in frames)]
    # The guard can see the reads: they are there, as one-hot reduces.
    assert len(reads) > 1000
    assert any("extract_col" in fns for _, fns in reads)
    assert any("_tcp_flush" in fns for _, fns in reads)
    gathers = [sorted(fns) for prim, fns in reads if prim == "gather"]
    assert not gathers, (
        f"{len(gathers)} gather eqns traced through core/dense.py or "
        f"tcp/tcp.py, the first from {gathers[0]}")


# The ``gather`` equations of the whole ``rounds`` phase, by model: none but
# ``apps/tor.py``'s own (``_pick_weighted``, ``dir_ids[d_idx]``: PERF.md §7a8).
ROUND_GATHERS = {"configs/rung1_filexfer.yaml": 0,
                 "benchmarks/tests/rehearsal/configs/tor20.yaml": 11,
                 "tests/rehearsal_bitcoin64/configs/bitcoin64.yaml": 0}


@pytest.mark.parametrize("lanes", [0, 2], ids=["solo", "vmap2"])
@pytest.mark.parametrize("config", sorted(ROUND_GATHERS),
                         ids=["tor20", "filexfer", "bitcoin64"])
def test_tcp_round_sweeps_no_plane_a_queue_a_socket_tall(config, lanes):
    """The message boundaries are one pool a host, ``[P, H]`` (PR 48): no
    equation of the round reads or writes an array ``[msgq_cap, sockets, H]``
    (under ``vmap`` with the lanes in front), the pool's planes are there, and
    the pool brought no ``gather``."""
    from shadow1_tpu.tools.opcensus import iter_eqns

    pr, h = _engine(config).params, _engine(config).exp.n_hosts
    fn, fr = _rounds(config)
    lead = (lanes,) if lanes else ()
    if lanes:
        fn = jax.vmap(fn)
        fr = jax.tree_util.tree_map(lambda x: jnp.stack([x] * lanes), fr)
    eqns = list(iter_eqns(jax.make_jaxpr(fn)(fr).jaxpr))
    shapes = {tuple(v.aval.shape) for e in eqns
              for v in (*e.invars, *e.outvars) if hasattr(v.aval, "shape")}
    tall = lead + (pr.msgq_cap, pr.sockets_per_host, h)
    assert tall not in shapes
    assert not [s for s in shapes if s[-3:] == tall[-3:]]
    assert lead + (pr.mq_pool, h) in shapes
    assert sum(e.primitive.name == "gather" for e in eqns) \
        == ROUND_GATHERS[config]


def _round_eqns(config: str, lanes: int):
    """The ``rounds`` phase's equations with their name stacks: the solo
    engine's, or for ``lanes`` the program a fleet traces — the lanes' axis
    named, so that every guard's predicate is reduced over it and stays a
    conditional (a plain ``vmap`` of the solo program turns each into both
    branches and a select of every leaf)."""
    import dataclasses

    from shadow1_tpu.core.engine import window_frame, window_phases

    eng = _engine(config)
    ctx = dataclasses.replace(eng.ctx, lane_axis="lane") if lanes else eng.ctx
    make = eng._model.make_handlers
    fn = dict(window_phases(ctx, make(ctx), None, eng._pre_window, make,
                            None))["rounds"]
    fr = window_frame(eng.init_state(), ctx)
    if lanes:
        fn = jax.vmap(fn, axis_name="lane")
        fr = jax.tree_util.tree_map(lambda x: jnp.stack([x] * lanes), fr)
    return list(_named_eqns(jax.make_jaxpr(fn)(fr).jaxpr))


# An equation that only hands its operands to a body of its own.
CONTAINERS = {"while", "cond", "pjit", "jit", "closed_call", "core_call"}
PLANE_SWEEPS = {"tgen100": "configs/rung2_tgen100.yaml",
                "filexfer": "configs/rung1_filexfer.yaml",
                "bitcoin64": "tests/rehearsal_bitcoin64/configs/bitcoin64.yaml",
                "tor20": "benchmarks/tests/rehearsal/configs/tor20.yaml"}


@pytest.mark.parametrize("lanes", [0, 2], ids=["solo", "fleet2"])
@pytest.mark.parametrize("config", PLANE_SWEEPS)
def test_tcp_round_sweeps_the_event_payload_in_pop_and_commit_only(config,
                                                                   lanes):
    """A round's pushes are staged as [H]-vectors and written in one commit
    (PR 49): in the ``rounds`` phase no equation outside ``phase:pop`` and
    ``phase:push_commit`` reads or writes an array ``[NP, ev_cap, W]`` (the
    loops and conditionals that only carry it apart), both scopes do, and
    the passes are there to be looked at."""
    from shadow1_tpu.consts import NP

    cap = _engine(PLANE_SWEEPS[config]).params.ev_cap
    sweeps, scopes, same = [], set(), {}
    for e, stack in _round_eqns(PLANE_SWEEPS[config], lanes):
        scopes.update(stack.split("/"))
        name = e.primitive.name
        if name in CONTAINERS:
            continue
        # ``vmap`` hands a conditional whose predicate differs by lane (the
        # per-stream guards of tcp/ and the apps) each operand as
        # ``select(the lane takes this branch, x, stop_gradient(x))``: the
        # same words either way, which XLA folds. Not a sweep.
        if name == "stop_gradient":
            same[e.outvars[0]] = same.get(e.invars[0], e.invars[0])
            continue
        if name == "select_n" and len(
                {same.get(v, v) for v in e.invars[1:]}) == 1:
            same[e.outvars[0]] = same.get(e.invars[1], e.invars[1])
            continue
        if name == "broadcast_in_dim" and e.outvars[0].aval.dtype == bool:
            continue
        shapes = [tuple(v.aval.shape) for v in (*e.invars, *e.outvars)
                  if hasattr(v.aval, "shape")]
        if any(len(s) >= 3 and s[-3:-1] == (NP, cap) for s in shapes):
            sweeps.append((name, stack))
    inside = [s for _, s in sweeps
              if "phase:pop" in s or "phase:push_commit" in s]
    outside = [(p, s) for p, s in sweeps if s not in inside]
    assert not outside, (f"{len(outside)} equations over the payload plane "
                         f"outside pop and push_commit, the first {outside[0]}")
    assert any("phase:pop" in s for s in inside)
    assert any("phase:push_commit" in s for s in inside)
    assert {"phase:h_deliver", "phase:h_timer", "phase:tcp_flush"} <= scopes


@pytest.mark.parametrize("lanes", [0, 2], ids=["solo", "fleet2"])
def test_phold_round_pushes_directly(lanes):
    """One handler kind, no conditional: the pass's pushes fuse as they are,
    the round opens no stage and traces no ``push_commit`` scope."""
    eqns = _round_eqns("configs/serve_phold.yaml", lanes)
    assert any("phase:h_phold" in stack for _, stack in eqns)
    assert not [stack for _, stack in eqns if "push_commit" in stack]


@pytest.fixture(scope="module", params=["configs/serve_phold.yaml",
                                        "configs/rung2_tgen100.yaml"],
                ids=["phold", "tgen100"])
def draw_rounds(request):
    return _rounds(request.param)


@pytest.mark.parametrize("lanes", [0, 2], ids=["solo", "vmap2"])
def test_exponential_draw_has_no_gather(draw_rounds, lanes):
    draw = _eqns_from(draw_rounds, lanes, "_neg_log1m_q32")
    # The guard can see the draw: its table read is there, as a matmul.
    assert any("_log_tbl_read" in fns and prim == "dot_general" for prim, fns in draw)
    n_gather = sum(prim == "gather" for prim, _ in draw)
    assert n_gather == 0, f"{n_gather} gather eqns traced from _neg_log1m_q32"


# ---------------------------------------------------------------------------
# static guard: no gather at the window's ends of a one-vertex TCP model
# ---------------------------------------------------------------------------

RUNG1 = "configs/rung1_filexfer.yaml"   # two vertices, two runs of hosts
ONE_VERTEX = {
    "tgen100": "configs/rung2_tgen100.yaml",
    "tor20": "benchmarks/tests/rehearsal/configs/tor20.yaml",
    "bitcoin64": "tests/rehearsal_bitcoin64/configs/bitcoin64.yaml",
}


def _window_end_eqns(config: str, lanes: int):
    """(primitive, function names) of the ``prepare`` phase's equations, and
    of those of the ``deliver`` phase that ``route_outbox`` is a frame of."""
    phases, frame = _phases(config)
    names = lambda eqns: [(prim, {fn for fn, _ in frames})
                          for prim, frames in eqns]
    prepare = names(_eqns((phases["prepare"], frame), lanes))
    route = [(prim, fns)
             for prim, fns in names(_eqns((phases["deliver"], frame), lanes))
             if "route_outbox" in fns]
    # The guard can see both sites: the arrival batch's sort and un-sort,
    # and the loss draw.
    assert sum(prim == "sort" for prim, _ in prepare) == 2
    assert len(route) > 50 and any("uniform_lt" in fns for _, fns in route)
    return prepare, route


@pytest.mark.parametrize("lanes", [0, 2], ids=["solo", "vmap2"])
@pytest.mark.parametrize("config", ONE_VERTEX)
def test_one_vertex_window_ends_have_no_gather(config, lanes):
    """``pre_window``'s packet lengths ride its sort and ``route_outbox``
    reads a [1, 1] table as a broadcast: neither end of the window gathers."""
    prepare, route = _window_end_eqns(ONE_VERTEX[config], lanes)
    for phase, eqns in (("prepare", prepare), ("deliver/route_outbox", route)):
        gathers = [sorted(fns) for prim, fns in eqns if prim == "gather"]
        assert not gathers, (
            f"{len(gathers)} gather eqns in {phase}, the first from "
            f"{gathers[0]}")


# Rung 1 with forty more clients dealt round-robin over its two vertices
# (``vertex: spread``): a run of ``host_vertex`` per host, more than
# core/engine.MAX_VERTEX_RUNS.
SPREAD = """
general: {{seed: 11, stop_time: 2 s}}
engine: {{scheduler: tpu, ev_cap: 128}}
network: {{graphml: {root}/configs/topology_2pop.graphml}}
hosts:
  - {{name: server, count: 1, vertex: pop_west, bandwidth_up: 10 Mbit, bandwidth_down: 10 Mbit}}
  - {{name: client, count: 40, vertex: spread, bandwidth_up: 10 Mbit, bandwidth_down: 10 Mbit}}
app:
  model: filexfer
  groups:
    server: {{role: 0}}
    client: {{role: 1, server: "@server", flow_bytes: 10000, flow_count: 1, start_time: 1 ms}}
"""


@pytest.fixture(scope="module")
def route_nets(tmp_path_factory):
    """name -> (experiment file, gathers ``route_outbox`` may trace)."""
    spread = tmp_path_factory.mktemp("spread") / "spread_filexfer.yaml"
    spread.write_text(SPREAD.format(root=ROOT))
    return {"rung1": (RUNG1, 0), "spread": (str(spread), 1)}


@pytest.mark.parametrize("lanes", [0, 2], ids=["solo", "vmap2"])
@pytest.mark.parametrize("net", ["rung1", "spread"])
def test_route_gathers_by_network(route_nets, net, lanes):
    """With two vertices ``route_outbox`` reads the vertices and both path
    tables: where ``host_vertex`` is a few runs of hosts (rung 1) none of
    the reads is a lookup; where it is a run per host, ``host_vertex[dst]``
    stays one and is the only one — which also shows that the guard above
    sees such reads. The path tables are no lookup at any number of vertices
    up to ``MAX_ROW_VERTICES`` (selects up to ``MAX_DENSE_VERTICES``, then a
    read per host row and a masked sum per slot, PR 51:
    ``tests/test_window_ends.py`` holds each form to the gathered one and
    counts ``table[vs, vd]`` past the bound, ``tests/test_bitcoin_cities.py``
    counts on 200 vertices)."""
    from shadow1_tpu.core.engine import MAX_VERTEX_RUNS

    config, n_gathers = route_nets[net]
    ctx = _engine(config).ctx
    assert ctx.lat_vv.shape == (2, 2)
    assert (ctx.vertex_runs is None) == (net == "spread")
    runs = 1 + int((np.diff(np.asarray(ctx.host_vertex)) != 0).sum())
    assert (runs > MAX_VERTEX_RUNS) == (net == "spread")
    prepare, route = _window_end_eqns(config, lanes)
    assert sum(prim == "gather" for prim, _ in route) == n_gathers
    assert all("vertex_of" in fns for prim, fns in route if prim == "gather")
    assert not [fns for prim, fns in prepare if prim == "gather"]
