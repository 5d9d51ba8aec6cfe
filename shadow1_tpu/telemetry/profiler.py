"""Host-side phase profiler — one set of spans, three carriers.

The reference's heartbeat rows carry wall time next to sim time so the
sim/wall ratio and its phases are derivable from the log (SURVEY §5); the
batched rebuild's phases are coarser — compile, init, run-chunk (⊃ dispatch,
sync), commit, on-chunk (⊃ drain, checkpoint), retune — and the question a
perf PR actually asks is "where did the wall clock go between heartbeats?".

Every ``maybe_span(profiler, name)`` call site of the program is carried
three ways:

* as a ``jax.profiler.TraceAnnotation("shadow1:" + name)``, always — so any
  ``jax.profiler`` capture (``--profile DIR``, a benchmark's traced run)
  holds the program's spans on the *device trace's clock*, beside the device
  ops, whether or not a PhaseProfiler is attached. With no profiler session
  open an annotation is a flag check;
* as one complete (``"ph": "X"``) Chrome trace event of the attached
  ``PhaseProfiler`` (``--trace PATH``), on a ``time.perf_counter`` clock of
  its own: ``write(path)`` emits JSON that chrome://tracing and Perfetto
  (https://ui.perfetto.dev) load directly;
* per chunk, as one row of the process-wide ``chunk_log()``, always — on
  ``time.perf_counter_ns``, with the instant the chunk's result was ready
  (taken by a waiter thread, never by a sync on the caller) and the host's
  health over the chunk. It is what an UNTRACED run keeps of its chunk
  boundaries: a chunk much slower than its twins earns one ``stall`` line
  on stderr that says where the time sat (``ChunkLog``, below).

Spans of one chunk share the arguments ``done`` (the chunk's first window)
and ``windows``; a span's parent is the span that contains it on its thread.
"""

from __future__ import annotations

import atexit
import collections
import contextlib
import itertools
import json
import os
import queue
import resource
import statistics
import sys
import threading
import time

from shadow1_tpu.telemetry.registry import (
    CHUNK_CAP_TOTALS,
    CHUNK_LOSS_TOTALS,
    CHUNK_PUSH_TOTALS,
    CHUNK_ROUTE_TOTALS,
    CHUNK_TOTALS,
    REC_STALL,
)

# Canonical phase names (docs/OBSERVABILITY.md) — free-form names are
# allowed, but the wired-in call sites use these.
PH_COMPILE = "compile"
PH_INIT = "init"
PH_RUN_CHUNK = "run-chunk"
PH_DISPATCH = "dispatch"    # engine.run / guard.run_guarded returning
PH_ARGS = "args"            # in dispatch: the run call's arguments made
PH_CALL = "call"            # in dispatch: the jitted call returning
PH_WAIT = "wait"            # dispatch's end to the result ready (waiter thread)
PH_SYNC = "sync"            # block_until_ready, only under a PhaseProfiler
PH_COMMIT = "commit"        # txn.OverflowGuard.commit
PH_ON_CHUNK = "on-chunk"    # the chunk-boundary hook (heartbeat, snapshot)
PH_RETUNE = "retune"        # between-chunk cap adaptation
PH_DRAIN = "drain"
PH_CHECKPOINT = "checkpoint"
# Device-trace span (the jax.profiler capture window — see device_trace).
PH_DEVICE_TRACE = "device-trace"
# Every span of the program is a TraceAnnotation under this prefix.
ANNOTATION_PREFIX = "shadow1:"


def annotation(name: str, **args):
    """The span ``name`` on the profiler's clock alone."""
    import jax

    return jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name, **args)


class PhaseProfiler:
    """Collects complete-span trace events; thread-safe, append-only."""

    def __init__(self, process_name: str = "shadow1_tpu"):
        self.t0 = time.perf_counter()
        self.process_name = process_name
        self.events: list[dict] = []
        self._lock = threading.Lock()

    def _now_us(self) -> float:
        return (time.perf_counter() - self.t0) * 1e6

    @contextlib.contextmanager
    def span(self, name: str, **args):
        """Time a phase: ``with prof.span("run-chunk", windows=128): ...``"""
        t_start = self._now_us()
        try:
            with annotation(name, **args):
                yield self
        finally:
            t_end = self._now_us()
            ev = {
                "name": name,
                "ph": "X",
                "ts": round(t_start, 1),
                "dur": round(t_end - t_start, 1),
                "pid": os.getpid(),
                "tid": threading.get_ident() & 0xFFFFFFFF,
            }
            if args:
                ev["args"] = args
            with self._lock:
                self.events.append(ev)

    def chrome_trace(self) -> dict:
        """The Chrome trace-event JSON object (dict form)."""
        meta = [{
            "name": "process_name",
            "ph": "M",
            "pid": os.getpid(),
            "tid": 0,
            "args": {"name": self.process_name},
        }]
        with self._lock:
            events = meta + list(self.events)
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def write(self, path: str) -> None:
        """Write the trace JSON (atomic: tmp + rename, like ckpt saves)."""
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.chrome_trace(), f)
        os.replace(tmp, path)

    def span_names(self) -> list[str]:
        with self._lock:
            return [e["name"] for e in self.events if e.get("ph") == "X"]


# What the chunk log keeps of a chunk's spans, each a duration on its row:
# the chunk's own, and those of the boundary BEFORE it — what the loop ran
# between the result of the chunk before and this chunk's run call.
_ROW_SPANS = {PH_DISPATCH: "dispatch_ns", PH_ARGS: "args_ns",
              PH_CALL: "call_ns"}
_BOUNDARY_SPANS = {PH_COMMIT: "commit_ns", PH_ON_CHUNK: "on_chunk_ns",
                   PH_DRAIN: "drain_ns", PH_CHECKPOINT: "checkpoint_ns",
                   PH_RETUNE: "retune_ns"}
# Of those, the four that never contain one another ON A ROW (``on-chunk``
# holds ``drain`` and ``checkpoint``; a fleet's ``commit`` holds its ``drain``
# fetch in the trace, and ``_TimedBoundary`` keeps that fetch out of the row's
# ``commit_ns``): with what is left of the turnaround they split it, for the
# stall line's ``where``.
_BOUNDARY_LEAVES = (PH_COMMIT, PH_DRAIN, PH_CHECKPOINT, PH_RETUNE)


def _carried(profiler: PhaseProfiler | None, name: str, args: dict):
    """The span on the first two carriers."""
    if profiler is None:
        return annotation(name, **args)
    return profiler.span(name, **args)


class _Timed:
    """A span that is also a duration in ``into`` (a chunk's row, or the
    thread's boundary spans); ``dispatch`` closing stamps ``dispatched_ns``
    as well."""

    __slots__ = ("cm", "into", "key", "t0")

    def __init__(self, cm, into: dict, key: str):
        self.cm, self.into, self.key = cm, into, key

    def __enter__(self):
        self.t0 = time.perf_counter_ns()
        return self.cm.__enter__()

    def __exit__(self, *exc):
        out = self.cm.__exit__(*exc)
        now = time.perf_counter_ns()
        self.into[self.key] = self.into.get(self.key, 0) + now - self.t0
        if self.key == "dispatch_ns":
            self.into["dispatched_ns"] = now
        return out


class _TimedBoundary(_Timed):
    """A boundary span. One that lies inside an open ``commit`` (the fleet's
    ``drain`` fetch: fleet/run.py) comes out of ``commit_ns``: on a row
    ``commit`` is the commit's own time, so ``_BOUNDARY_LEAVES`` overlap
    nowhere while the trace keeps the nesting."""

    __slots__ = ("outer",)

    def __enter__(self):
        self.outer, _THREAD.timed = getattr(_THREAD, "timed", None), self
        return super().__enter__()

    def __exit__(self, *exc):
        before = self.into.get(self.key, 0)
        out = super().__exit__(*exc)
        _THREAD.timed = self.outer
        if self.outer is not None and self.outer.key == "commit_ns":
            self.into["commit_ns"] = (self.into.get("commit_ns", 0)
                                      - (self.into[self.key] - before))
        return out


def maybe_span(profiler: PhaseProfiler | None, name: str, **args):
    """``profiler.span(...)``, or the bare annotation where no PhaseProfiler
    is attached — call sites stay branchless, and every span is in any
    ``jax.profiler`` capture either way. The chunk log keeps a chunk's own
    spans (``_ROW_SPANS``) on the row of the chunk whose ``run-chunk`` is
    open on this thread, and the spans between two chunks
    (``_BOUNDARY_SPANS``) for the row of the chunk that follows them."""
    cm = _carried(profiler, name, args)
    if name in _ROW_SPANS:
        ch = getattr(_THREAD, "chunk", None)
        if ch is not None and ch.row is not None:
            return _Timed(cm, ch.row, _ROW_SPANS[name])
    elif name in _BOUNDARY_SPANS and _LOG.enabled:
        return _TimedBoundary(cm, _boundary(), _BOUNDARY_SPANS[name])
    return cm


def run_span(name: str):
    """For the engines' ``run`` methods: the span ``name`` of the chunk
    whose ``run-chunk`` is open on this thread (its ``done`` / ``windows``,
    its PhaseProfiler), or the bare annotation outside a chunk loop."""
    ch = getattr(_THREAD, "chunk", None)
    if ch is None:
        return annotation(name)
    return maybe_span(ch.profiler, name, **ch.ids)


# ---- the third carrier: the chunk log --------------------------------------

# Of the caller's thread: ``.chunk`` the chunk whose ``run-chunk`` is open,
# ``.boundary`` the durations of the spans since the last one closed,
# ``.last`` the row of the last one closed, until a heartbeat takes it.
_THREAD = threading.local()
# To the waiter: close the row you hold open now.
_FLUSH = object()
# An engine is known by the number of its first chunk: ``id()`` of a
# collected engine comes back on another.
_ENGINES = itertools.count(1)


def _boundary() -> dict:
    spans = getattr(_THREAD, "boundary", None)
    if spans is None:
        spans = _THREAD.boundary = {}
    return spans


def _engine_no(engine) -> int:
    no = getattr(engine, "_chunk_log_no", None)
    if no is None:
        no = next(_ENGINES)
        try:
            engine._chunk_log_no = no
        except AttributeError:  # it takes no attribute: no twin, no stall
            pass
    return no


def _windows_leaf(st):
    """The one scalar of a RESULT that the log ever touches."""
    return getattr(getattr(st, "metrics", None), "windows", None)


# What a row keeps of its chunk's INPUT state besides ``first_window``: the
# running totals a reader subtracts from the next row's (``work_between``).
_TOTALS = CHUNK_TOTALS[:-1]
# Totals that only some programs keep, and the leaf of the state each is
# (where a ``compact_cap`` is in force); ``_TOTALS`` are ``Metrics`` fields,
# on every row that has any.
_CAP_TOTALS = {"buckets": "compact_buckets"}
assert tuple(_CAP_TOTALS) == CHUNK_CAP_TOTALS
# The ``Metrics`` fields among a row's totals: ``_TOTALS``, the loss plane's
# (what a chunk sent, lost, resent and dropped out of order), the push
# commits' trips and the window ends' route lookups.
_METRIC_TOTALS = (*_TOTALS, *CHUNK_LOSS_TOTALS, *CHUNK_PUSH_TOTALS,
                  *CHUNK_ROUTE_TOTALS)
# Every total a row may carry, in the order ``_input_leaves`` reads them.
_ROW_TOTALS = (*_METRIC_TOTALS, *_CAP_TOTALS)
# ... and in the order a heartbeat's block lists them, ``hosts`` among them.
_BLOCK_TOTALS = (*CHUNK_TOTALS, *_ROW_TOTALS[len(_TOTALS):])


def _input_leaves(st) -> tuple:
    """The scalars of an input state that the log reads: ``metrics.windows``,
    the ``_METRIC_TOTALS`` and the ``_CAP_TOTALS`` (None where the state has
    none)."""
    m = getattr(st, "metrics", None)
    return (*(getattr(m, k, None) for k in ("windows", *_METRIC_TOTALS)),
            *(getattr(st, leaf, None) for leaf in _CAP_TOTALS.values()))


def _host_count(engine) -> int | None:
    """``n_hosts`` x lanes of an engine, or None where it does not say."""
    n = getattr(getattr(engine, "exp", None), "n_hosts", None)
    return None if n is None else int(n) * int(getattr(engine, "n_exp", 1))


def work_between(row: dict, after: dict | None) -> dict | None:
    """What the chunk of ``row`` did — events, rounds (a lane's own, summed
    over lanes), ``active_hosts`` and ``elig_events`` (sums over its
    windows), the loss plane's ``pkts_sent``, ``pkts_lost``,
    ``tcp_fast_rtx``, ``tcp_rto``, ``tcp_ooo_drops``, ``push_commit_trips``,
    ``route_rows`` (the outbox rows its window ends looked up) where both
    rows carry them, ``buckets`` (the compacted round loop's trips) where the
    program counts them — where ``after`` is the row of the chunk that
    continued it: the same engine's, adjacent in ``seq``, starting on the
    window ``row`` ended on. Else None: a row's totals are of its chunk's
    START."""
    if (after is None or after["engine"] != row["engine"]
            or after["seq"] != row["seq"] + 1
            or row.get("first_window") is None
            or after.get("first_window") != row["first_window"] + row["windows"]
            or any(k not in r for r in (row, after) for k in _TOTALS)):
        return None
    return {k: after[k] - row[k] for k in _ROW_TOTALS
            if k in row and k in after}


def _pressure_us(what: str) -> int | None:
    """The ``some`` total of ``/proc/pressure/<what>`` (microseconds some
    task was stalled on it), or None where the file cannot be read."""
    try:
        with open(f"/proc/pressure/{what}", "rb") as f:
            first = f.readline()
        return int(first.rsplit(b"total=", 1)[1])
    except (OSError, IndexError, ValueError):
        return None


def _health_now() -> dict:
    """The counters whose change over a chunk says how the host fared:
    process CPU seconds, context switches (involuntary: the process was
    taken off a core), major faults (paging), block I/O, and the kernel's
    pressure totals where it exposes them."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    now = {"cpu_s": time.process_time(), "nivcsw": ru.ru_nivcsw,
           "nvcsw": ru.ru_nvcsw, "majflt": ru.ru_majflt,
           "inblock": ru.ru_inblock, "oublock": ru.ru_oublock}
    for what, key in (("cpu", "psi_cpu_us"), ("io", "psi_io_us"),
                      ("memory", "psi_mem_us")):
        total = _pressure_us(what)
        if total is not None:
            now[key] = total
    return now


def _ms(ns) -> float:
    return round(ns / 1e6, 4)


def _ms_key(key: str) -> str:
    return key[:-len("ns")] + "ms"


class _Chunk:
    """One chunk of a chunk loop: its ``run-chunk`` span on all three
    carriers. ``watch(st)``, after ``dispatch``, hands the result's one
    scalar to the waiter; from then on the row is the waiter's alone."""

    __slots__ = ("log", "profiler", "ids", "cm", "row", "leaf")

    def __init__(self, log, profiler, engine, st, done: int, windows: int):
        self.profiler = profiler
        self.ids = {"done": done, "windows": windows}
        self.cm = _carried(profiler, PH_RUN_CHUNK, self.ids)
        self.log = log
        self.row = self.leaf = None
        if log.enabled:
            self.leaf = _input_leaves(st)
            self.row = {"seq": next(log._seq), "engine": _engine_no(engine),
                        "done": done, "windows": windows}
            hosts = _host_count(engine)
            if hosts is not None:
                self.row["hosts"] = hosts

    def __enter__(self):
        _THREAD.chunk = self
        if self.row is not None:
            # What the loop ran since its last chunk; a loop's first chunk
            # (``done`` 0) follows none, whatever ran before the loop.
            spans = _boundary()
            if self.ids["done"]:
                self.row.update(spans)
            spans.clear()
            self.row["enter_ns"] = time.perf_counter_ns()
        self.cm.__enter__()
        return self

    def __exit__(self, *exc):
        _THREAD.chunk = self.leaf = None
        _THREAD.last = self.row
        return self.cm.__exit__(*exc)

    def watch(self, st) -> None:
        if self.row is None:
            return
        self.row.setdefault("dispatched_ns", time.perf_counter_ns())
        leaf, self.leaf = self.leaf, None
        self.log._submit([self.row, leaf, _windows_leaf(st), self.profiler,
                          self.ids])


class ChunkLog:
    """The last ``KEEP`` chunks this process ran through a chunk loop, one
    row a chunk, on ``time.perf_counter_ns`` — kept always, traced or not
    (docs/OBSERVABILITY.md "Chunk log").

    A row: ``seq`` (chunks in the order they were opened), ``engine`` (a
    number of the engine object), ``done`` (the loop's count),
    ``first_window`` (the input state's ``metrics.windows``), ``windows``,
    ``events``, ``rounds``, ``active_hosts``, ``elig_events``, the loss
    plane's ``pkts_sent``, ``pkts_lost``, ``tcp_fast_rtx``, ``tcp_rto``,
    ``tcp_ooo_drops``, the push commits' ``push_commit_trips``, the window
    ends' ``route_rows``, and where a
    ``compact_cap`` is in force ``buckets`` (the input
    state's running totals, summed over a fleet's lanes: what the chunk did
    is the NEXT row's less these, ``work_between``) and ``hosts`` (the
    engine's ``n_hosts`` x lanes), where the state and the engine have them,
    ``enter_ns`` (the ``run-chunk`` span opening), ``dispatched_ns``
    (``dispatch`` closing), ``ready_ns`` (the result ready), the durations
    ``dispatch_ns`` ⊃ ``args_ns``, ``call_ns``; of the boundary before the
    chunk, where the loop ran them since its last chunk, ``commit_ns``,
    ``on_chunk_ns``, ``drain_ns``, ``checkpoint_ns``, ``retune_ns``, and
    ``turnaround_ns`` (``enter_ns`` less the ``ready_ns`` of the chunk
    before it, where that is the same engine's and ends on this chunk's
    first window: negative where the loop ran ahead of the device);
    ``health`` (how the host fared from the last row's closing to this
    one's, ``_health_now``'s deltas and ``load1``); ``wall_ns`` (the
    chunk's own time: ``ready_ns`` less the later of ``enter_ns`` and the
    ready of its engine's chunk before it); ``stall`` (wall over median) on
    a chunk judged a stall; ``error`` where the result's readiness raised.

    Readiness is taken by ONE daemon thread, started with the first chunk,
    asleep on its queue between chunks: handed a chunk, it reads the ten
    scalars of the input state's metrics and gives them up, blocks
    on one scalar leaf of the result (never the state) under a ``wait``
    span, stamps ``ready_ns`` and gives the leaf up at once; it closes the
    row (health, verdict, into the log) when the next chunk is handed over
    and its input read, not while the caller runs (``_wait``). The caller
    pays two clock reads and one queue put a chunk; a row in the log is
    complete, and holds plain numbers only. Loops on
    two threads share the waiter: one's chunk is stamped after the
    other's that was handed over before it."""

    KEEP = 512
    # A chunk is a stall beyond this many times the median of its baseline:
    # the last BASELINE of its twins (same engine, size and first window: a
    # harness runs the same windows again and again), or where it has no
    # twin of its neighbours (same engine and size: a simulation is not
    # stationary, so a neighbour proves less), MIN_ROWS of them at least.
    # And by at least MIN_EXCESS_NS: a chunk of a millisecond doubles on any
    # host (every tier-1 test would earn lines), and no rate is read off it.
    TWINS, NEIGHBOURS, MIN_ROWS, BASELINE = 1.5, 3.0, 3, 16
    MIN_EXCESS_NS = 20_000_000
    # A row nothing follows is closed after this long.
    LINGER_S = 0.05

    def __init__(self, keep: int = KEEP):
        # Tests and measurements of the log's own price switch it off here;
        # the program never does.
        self.enabled = True
        self.count = 0          # chunks completed since clear()
        self.stalls = 0
        self.t0 = time.perf_counter()
        self._rows: collections.deque = collections.deque(maxlen=keep)
        self._lock = threading.Lock()
        self._seq = itertools.count()
        self._queue: queue.SimpleQueue = queue.SimpleQueue()
        self._waiter: threading.Thread | None = None
        self._sent = self._finished = 0
        self._health: dict | None = None

    # -- the caller's side ---------------------------------------------------
    def chunk(self, profiler, engine, st, done: int, windows: int) -> _Chunk:
        """The ``run-chunk`` span of the chunk that takes ``st`` through
        ``windows`` windows of ``engine``: a context manager."""
        return _Chunk(self, profiler, engine, st, done, windows)

    def _submit(self, item) -> None:
        with self._lock:
            self._sent += 1
            if self._waiter is None or not self._waiter.is_alive():
                if self._waiter is None:
                    atexit.register(self._stop)
                self._waiter = threading.Thread(
                    target=self._wait, name="shadow1-chunk-wait", daemon=True)
                self._waiter.start()
        self._queue.put(item)

    def _stop(self) -> None:
        """At exit: let the waiter finish what is out (it holds a device
        array while it waits), then end it."""
        self.settle(2.0)
        self._queue.put(None)

    def settle(self, wait_s: float) -> bool:
        """Wait up to ``wait_s`` for every chunk handed over so far to be
        in the log; whether they are."""
        if self._finished < self._sent:
            self._queue.put(_FLUSH)
        deadline = time.perf_counter() + wait_s
        while self._finished < self._sent:
            if time.perf_counter() >= deadline:
                return False
            time.sleep(0.0002)
        return True

    # -- the waiter's side -----------------------------------------------------
    def _wait(self) -> None:
        # A chunk's row is closed (health, verdict, into the log) not when
        # its result is ready but when the NEXT chunk is handed over: the
        # caller woke from its own wait at that same instant and runs, and
        # whatever this thread did then would take the interpreter from it
        # (0.55 ms a chunk, measured); at the next hand-over the caller is
        # about to wait again. Until then this thread sleeps on the queue:
        # a reader that cannot wait sends _FLUSH, and after LINGER_S the
        # row is closed anyway.
        self._health = _health_now()
        row = None
        while True:
            try:
                item = self._queue.get(
                    timeout=None if row is None else self.LINGER_S)
            except queue.Empty:
                item = _FLUSH
            handed = isinstance(item, list)
            if handed:
                # Before the held row is closed: it is this chunk's totals
                # that say what the held one did (work_between).
                try:
                    self._input(item)
                except Exception as e:
                    item[0]["error"] = repr(e)
            if row is not None:
                try:
                    self._close(row, item[0] if handed else None)
                except Exception as e:  # the log must never end a run
                    row["error"] = repr(e)
                row = None
                self._finished += 1
            if item is None:
                return
            if item is _FLUSH:
                continue
            try:
                row = self._ready(item)
            except Exception as e:
                item[0]["error"] = repr(e)
                self._finished += 1
            finally:
                item.clear()    # nothing of a state stays, whatever came

    @staticmethod
    def _input(item: list) -> None:
        """Read what the row of ``item`` keeps of its chunk's INPUT state
        (``_input_leaves``: ``first_window``, the maximum over a fleet's
        lanes, and the running totals, summed over them) and give the
        leaves up. The input is ready no later than the result, and the
        caller has just handed the chunk over: it is about to wait. One
        plain read after another: ``copy_to_host_async`` (``device_get``
        starts every copy with it) leaves the array it was last called on
        alive until its next call, a leaf of a state its chunk is done with
        (tests/test_bench_cycles.py's live-array sum finds the 8 bytes)."""
        import numpy as np

        row, leaves = item[0], item[1]
        item[1] = None
        if leaves is None:
            return
        values = [None if x is None else np.asarray(x) for x in leaves]
        del leaves
        row["first_window"] = (None if values[0] is None
                               else int(np.max(values[0])))
        for k, v in zip(_ROW_TOTALS, values[1:]):
            if v is not None:
                row[k] = int(np.sum(v))

    def _ready(self, item: list) -> dict:
        """Wait for the chunk of ``item`` (its row, nothing where the input
        state's scalars were, the result's one scalar, the PhaseProfiler,
        the spans' arguments) and stamp its row. The scalar is given up the
        moment the result is ready and nothing else is done then: the
        caller may drop its state right away, and the log must keep no
        leaf of it alive."""
        row, _, leaf_out, profiler, ids = item
        row.setdefault("first_window", None)
        with _carried(profiler, PH_WAIT, ids):
            try:
                block = getattr(leaf_out, "block_until_ready", None)
                if block is not None:
                    block()
            except Exception as e:  # the caller meets it at its own sync
                row["error"] = repr(e)
            row["ready_ns"] = time.perf_counter_ns()
            item[2] = leaf_out = block = None
        return row

    def _close(self, row: dict, after: dict | None = None) -> None:
        """The host's health up to now, the turnaround, the verdict; then
        the row is in the log. Every chunk, while the caller waits for the
        next one (``after`` is that one's row, its input's totals read):
        three walks of at most ``KEEP`` rows."""
        now = _health_now()
        health = {k: now[k] - self._health[k] for k in now if k in self._health}
        health["cpu_s"] = round(health["cpu_s"], 6)
        try:
            health["load1"] = round(os.getloadavg()[0], 2)
        except OSError:
            pass
        row["health"], self._health = health, now
        with self._lock:
            mine = [r for r in self._rows if r["engine"] == row["engine"]]
        first = row["first_window"]
        # The chunk's own wall: a loop that runs ahead of the device opens
        # a chunk while the one before it still runs.
        start = row["enter_ns"]
        if mine:
            prev = mine[-1]
            start = max(start, prev["ready_ns"])
            if (prev["seq"] + 1 == row["seq"] and first is not None
                    and prev["first_window"] is not None
                    and prev["first_window"] + prev["windows"] == first):
                row["turnaround_ns"] = row["enter_ns"] - prev["ready_ns"]
        row["wall_ns"] = wall = row["ready_ns"] - start
        # Twins, or with none the neighbours — but a loop's first chunk has
        # no chunk before it: a harness that calls the chunk runner once a
        # chunk is judged by twins alone (bitcoin's first front of
        # transactions is 3x its quiet windows, every cycle).
        same = [r for r in mine if r["windows"] == row["windows"]]
        base = [] if first is None else [
            r for r in same if r["first_window"] == first]
        against, factor = "twins", self.TWINS
        if not base:
            base = same if row["done"] else []
            against, factor = "neighbours", self.NEIGHBOURS
        base = base[-self.BASELINE:]
        line = None
        if len(base) >= self.MIN_ROWS:
            median = statistics.median(r["wall_ns"] for r in base)
            if wall > factor * median and wall - median >= self.MIN_EXCESS_NS:
                row["stall"] = round(wall / median, 2)
                self.stalls += 1
                line = self._stall_line(row, base, against, median)
                line.update(self._stall_work(row, after, base, mine))
        with self._lock:
            self._rows.append(row)
            self.count += 1
        if line is not None:
            # The harness attaches no logger, and its result line is the
            # last of stdout: stderr, always.
            print(json.dumps(line), file=sys.stderr, flush=True)

    @staticmethod
    def _parts(row: dict) -> dict:
        """Where a chunk's time sat, in ns, no part inside another: in
        ``dispatch`` the two sub-spans, then the wait; before them the
        boundary's ``commit``, ``drain``, ``checkpoint``, ``retune`` where
        the loop ran them, and ``turnaround``: what is left of it."""
        parts = {"args": row.get("args_ns", 0), "call": row.get("call_ns", 0),
                 "wait": row["ready_ns"] - max(
                     row["dispatched_ns"], row["ready_ns"] - row["wall_ns"])}
        for name in _BOUNDARY_LEAVES:
            if _BOUNDARY_SPANS[name] in row:
                parts[name] = row[_BOUNDARY_SPANS[name]]
        if "turnaround_ns" in row:
            parts["turnaround"] = row["turnaround_ns"] - sum(
                parts.get(name, 0) for name in _BOUNDARY_LEAVES)
        return parts

    def _stall_line(self, row: dict, base: list, against: str,
                    median: float) -> dict:
        """The ``stall`` record of ``row``: its parts and its health beside
        the medians of ``base``, the rows it was judged by."""
        parts, medians = self._parts(row), {}
        for k in parts:
            have = [p[k] for p in map(self._parts, base) if k in p]
            medians[k] = statistics.median(have) if have else 0
        health = {k: statistics.median(r["health"][k] for r in base
                                       if k in r["health"])
                  for k in row["health"]
                  if any(k in r["health"] for r in base)}
        return {
            "type": REC_STALL, "level": "warning",
            "wall_s": round(time.perf_counter() - self.t0, 3),
            "msg": "a chunk took far longer than the chunks it repeats"
                   if against == "twins" else
                   "a chunk took far longer than the chunks before it",
            "chunk": row["seq"], "engine": row["engine"],
            "first_window": row["first_window"], "windows": row["windows"],
            "against": against, "rows": len(base),
            "wall_ms": _ms(row["wall_ns"]), "median_ms": _ms(median),
            "ratio": row["stall"],
            # The part that grew most over its own median.
            "where": max(parts, key=lambda k: parts[k] - medians[k]),
            "ms": {k: _ms(v) for k, v in parts.items()},
            "median_of_ms": {k: _ms(v) for k, v in medians.items()},
            "health": row["health"], "median_of_health": health,
        }

    @staticmethod
    def _stall_work(row: dict, after: dict | None, base: list,
                    mine: list) -> dict:
        """Beside a stall line's wall: the rounds and events of the chunk,
        where the chunk that continued it is known, and the medians over
        the rows it was judged by that have one in the log — a chunk that
        did more than they did was slow for that."""
        did = work_between(row, after)
        if did is None:
            return {}
        by_seq = {r["seq"]: r for r in (*mine, row)}
        theirs = [w for w in (work_between(r, by_seq.get(r["seq"] + 1))
                              for r in base) if w is not None]
        out = {"rounds": did["rounds"], "events": did["events"]}
        if theirs:
            out.update(
                median_of_rounds=statistics.median(w["rounds"] for w in theirs),
                median_of_events=statistics.median(w["events"] for w in theirs))
        # What it resent (fast retransmits + RTOs) and lost, where its rows
        # carry the loss plane: a chunk that recovered more than its twins
        # ran more rounds for that.
        resent = ChunkLog._retransmits(did)
        if resent is not None:
            out.update(retransmits=resent, pkts_lost=did["pkts_lost"])
            have = [r for r in map(ChunkLog._retransmits, theirs)
                    if r is not None]
            if have:
                out["median_of_retransmits"] = statistics.median(have)
        return out

    @staticmethod
    def _retransmits(did: dict) -> int | None:
        """Fast retransmits + RTOs of a chunk's work, or None where its rows
        carry no loss totals."""
        if any(k not in did for k in CHUNK_LOSS_TOTALS):
            return None
        return did["tcp_fast_rtx"] + did["tcp_rto"]

    # -- readers -----------------------------------------------------------------
    def rows(self, wait_s: float = 1.0) -> list[dict]:
        """Copies of the kept rows, oldest first (after ``settle(wait_s)``:
        a chunk still on the device is not in the log yet)."""
        self.settle(wait_s)
        with self._lock:
            return [dict(r) for r in self._rows]

    def block(self, wait_s: float = 0.05) -> dict | None:
        """A heartbeat's ``chunk`` block: the chunk this thread ran last
        from the host's side, in ms — its ``dispatch``, its wait, the
        turnaround before it and the spans the loop ran in that — and the
        host's health over it. Once a chunk: None outside a chunk loop,
        for a second heartbeat of one chunk, and where the chunk's result
        is still out after ``wait_s``."""
        row, _THREAD.last = getattr(_THREAD, "last", None), None
        if row is None:
            return None
        self.settle(wait_s)
        if "wall_ns" not in row:
            return None
        out = {"dispatch_ms": _ms(row.get("dispatch_ns", 0)),
               "wait_ms": _ms(row["ready_ns"] - row["dispatched_ns"])}
        for key in ("turnaround_ns", *_BOUNDARY_SPANS.values()):
            if key in row:
                out[_ms_key(key)] = _ms(row[key])
        out.update({k: row[k] for k in _BLOCK_TOTALS if k in row})
        return {**out, **row["health"]}

    def summary(self, wait_s: float = 1.0) -> dict:
        """The kept chunks in one block (the CLI's ``chunks``): of the
        engine and chunk size with most rows, the medians of the boundary's
        parts in ms (a span of the boundary over the rows that have it);
        ``boundary_ms`` a chunk (``dispatch`` + turnaround: from a chunk's
        result to the next one's launch) and its share of a chunk's wall
        plus turnaround (medians too). The host's side: a loop that runs
        ahead of the device hides it (a negative turnaround counts as
        none)."""
        rows = self.rows(wait_s)
        out = {"count": self.count, "stalls": self.stalls}
        groups = collections.Counter((r["engine"], r["windows"]) for r in rows)
        if not groups:
            return out
        (engine, windows), _ = groups.most_common(1)[0]
        rows = [r for r in rows
                if (r["engine"], r["windows"]) == (engine, windows)]
        turns = [max(r["turnaround_ns"], 0) for r in rows
                 if "turnaround_ns" in r]

        def med(values):
            values = list(values)
            return _ms(statistics.median(values)) if values else None

        out.update(
            rows=len(rows), windows=windows,
            dispatch_ms=med(r.get("dispatch_ns", 0) for r in rows),
            args_ms=med(r.get("args_ns", 0) for r in rows),
            call_ms=med(r.get("call_ns", 0) for r in rows),
            wait_ms=med(r["ready_ns"] - r["dispatched_ns"] for r in rows),
            turnaround_ms=med(turns))
        for key in _BOUNDARY_SPANS.values():
            if any(key in r for r in rows):
                out[_ms_key(key)] = med(r[key] for r in rows if key in r)
        # What those chunks did, of each that the next row continues.
        did = [w for w in map(work_between, rows, rows[1:]) if w is not None]
        if did:
            out.update({k: sum(w[k] for w in did) for k in _ROW_TOTALS
                        if all(k in w for w in did)})
        if "hosts" in rows[-1]:
            out["hosts"] = rows[-1]["hosts"]
        out["boundary_ms"] = round(out["dispatch_ms"]
                                   + (out["turnaround_ms"] or 0.0), 4)
        # Medians, so that the warm-up's dispatch (it compiles) is one row.
        wall = med(r["wall_ns"] for r in rows) + (out["turnaround_ms"] or 0.0)
        out["boundary_share"] = (round(out["boundary_ms"] / wall, 6)
                                 if wall else None)
        return out

    def clear(self) -> None:
        self.settle(1.0)
        with self._lock:
            self._rows.clear()
            self.count = self.stalls = 0


_LOG = ChunkLog()


def chunk_log() -> ChunkLog:
    """The process's chunk log."""
    return _LOG


class CompileMeter:
    """What jax spent building programs in this process, from jax's own
    monitoring events: trace + lowering + backend compile seconds (a
    persistent-cache hit counts its retrieval time there) and the
    persistent cache's hit/miss tally. Lets a result row report compile
    apart from run whether the run was one device execution or many
    chunks, and say whether the compile was warm."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def finish(self) -> dict:
        """Stop listening (jax's listener lists are process-global) and
        return the tally."""
        import jax

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)
        return {"seconds": round(self.seconds, 3),
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


@contextlib.contextmanager
def device_trace(log_dir: str, profiler: PhaseProfiler | None = None,
                 engine=None, st=None):
    """A ``jax.profiler`` capture scoped over the with-body, written to
    ``log_dir`` (the TensorBoard profile plugin reads that directory), with
    the program's own spans in it (``shadow1:<name>``, module docstring) and
    a ``device-trace`` span marking the capture window.

    The TPU trace does **not** carry the window program's
    ``jax.named_scope("phase:...")`` scopes: it names a device op by its HLO
    text. With ``engine`` (an ``Engine`` or ``FleetEngine``; ``st`` gives
    the state's shapes, default the initial state's) the phase of every
    captured op is joined on exit from the compiled program's text
    (telemetry/phases.py) and written to ``log_dir/phases.json``: device
    seconds by phase path, the fixed roll-up, busy time, ``unknown_ops``
    (0 when the captured program is ``engine``'s). That costs one more
    lowering and compile (a persistent-cache load where the cache holds the
    program), after the body, off its clock, and only where the capture
    holds device ops.

    Degrades gracefully: if the installed jax cannot start a profiler
    session (no profiler support, or a session already active), the body
    still runs and a warning names the reason — attribution tools must
    never fail a run over a missing trace backend, nor over a join that
    fails after it (a warning, no ``phases.json``). A capture with no
    device op (the CPU backend) writes no ``phases.json``."""
    import jax

    started = False
    try:
        try:
            jax.profiler.start_trace(log_dir)
            started = True
        except Exception as e:  # profiler backend unavailable — not fatal
            import warnings

            warnings.warn(f"jax device trace unavailable ({e}); the body "
                          "runs untraced")
        with maybe_span(profiler, PH_DEVICE_TRACE, log_dir=log_dir):
            yield
    finally:
        if started:
            jax.profiler.stop_trace()
    if started and engine is not None:
        from shadow1_tpu.telemetry import phases

        try:
            table = phases.attribute_capture(
                log_dir, lambda: engine.hlo_text(st))
        except Exception as e:  # the run is done: keep it and its capture
            import warnings

            warnings.warn(f"no phases.json: the join of the capture with "
                          f"the compiled program failed ({e!r})")
            table = None
        if table is not None:
            path = os.path.join(log_dir, "phases.json")
            with open(path + ".tmp", "w") as f:
                json.dump(table, f)
            os.replace(path + ".tmp", path)
