"""Randomized differential test of the batched event buffer.

A few hundred random push/pop/rebase/deliver operations run against a plain
Python heap model; every pop's (mask, time, kind, tb, payload) and the
final buffer census must match exactly — on one plain buffer, as the solo
engine calls the primitives, and on two stacked buffers under ``jax.vmap``,
as FleetEngine does, the two lanes running different operation sequences
against two heap models; with every push written into the planes directly
(PHOLD's round) and with every push staged and committed (a TCP round,
PR 49).

This is the unstructured counterpart of tests/test_events.py: the
structured tests pin the documented contracts; the fuzz sweep hunts the
interactions nobody thought to write down (epoch advances between pushes,
same-time tb ties across push/deliver sources, overflow under load,
past-due leftovers, eligibility-counter drift). The heap model is ~40
lines of obviously-correct Python — the judge's "real OS as oracle" trick
(SURVEY §4) scaled down to the data structure.
"""

import heapq

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shadow1_tpu.consts import NP, TB_PACKET_BASE
from shadow1_tpu.core import events as ev


class HeapModel:
    """Per-host (time, tb) heaps with the engine's exact semantics."""

    def __init__(self, n_hosts, cap):
        self.h = [[] for _ in range(n_hosts)]
        self.cap = cap
        self.self_ctr = [0] * n_hosts

    def push_local(self, mask, time, kind, p):
        over = []
        for i, m in enumerate(mask):
            if not m:
                over.append(False)
                continue
            if len(self.h[i]) >= self.cap:
                over.append(True)
                continue
            heapq.heappush(
                self.h[i], (int(time[i]), self.self_ctr[i], int(kind[i]),
                            tuple(int(x) for x in p[:, i]))
            )
            self.self_ctr[i] += 1
            over.append(False)
        return over

    def push_back(self, mask, time, tb, kind, p):
        over = []
        for i, m in enumerate(mask):
            if not m:
                over.append(False)
                continue
            if len(self.h[i]) >= self.cap:
                over.append(True)
                continue
            heapq.heappush(
                self.h[i], (int(time[i]), int(tb[i]), int(kind[i]),
                            tuple(int(x) for x in p[:, i]))
            )
            over.append(False)
        return over

    def deliver(self, dst, time, tb, kind, p, mask):
        n_over = 0
        for j, m in enumerate(mask):
            if not m:
                continue
            d = int(dst[j])
            if len(self.h[d]) >= self.cap:
                n_over += 1
                continue
            heapq.heappush(
                self.h[d], (int(time[j]), int(tb[j]), int(kind[j]),
                            tuple(int(x) for x in p[:, j]))
            )
        return n_over

    def pop_until(self, until):
        out = []
        for i, hp in enumerate(self.h):
            if hp and hp[0][0] < until:
                out.append(heapq.heappop(hp))
            else:
                out.append(None)
        return out

    def census(self):
        return [sorted(hp) for hp in self.h]


def buf_census(buf):
    """Live events per host as sorted (time, tb, kind, payload) lists."""
    kind = np.asarray(buf.kind)
    t = np.asarray(buf.abs_time())
    tb = np.asarray(ev.tb_join(buf.tb_hi, buf.tb_lo))
    p = np.asarray(buf.p)
    cap, n = kind.shape
    out = []
    for i in range(n):
        rows = [
            (int(t[c, i]), int(tb[c, i]), int(kind[c, i]),
             tuple(int(x) for x in p[:, c, i]))
            for c in range(cap) if kind[c, i] != 0
        ]
        out.append(sorted(rows))
    return out


class Lanes:
    """``n`` event buffers stepped together: one plain buffer (n == 1, the
    primitives called directly) or a stacked pytree with the primitives
    under ``jax.vmap``; jitted either way, as the engines run them. A lane
    may sit an operation out: it keeps its buffer, so the lanes' operation
    sequences differ."""

    def __init__(self, n, n_hosts, cap):
        self.n = n
        self._jitted = {}
        one = ev.evbuf_init(n_hosts, cap)
        self.buf = one if n == 1 else jax.tree.map(
            lambda x: jnp.stack([x] * n), one)

    def lane(self, i):
        return self.buf if self.n == 1 else jax.tree.map(
            lambda x: x[i], self.buf)

    def apply(self, fn, args):
        """``fn(buf, *args[i]) -> (buf, extra)`` in every lane whose
        ``args[i]`` is not None; returns each lane's ``extra`` (None where
        it sat out). Idle lanes are fed a busy lane's arguments and their
        result is dropped."""
        if fn not in self._jitted:
            self._jitted[fn] = jax.jit(fn if self.n == 1 else jax.vmap(fn))
        run = self._jitted[fn]
        busy = [a for a in args if a is not None]
        if self.n == 1:
            self.buf, extra = run(self.buf, *busy[0])
            return [extra]
        cols = zip(*[a if a is not None else busy[0] for a in args])
        new, extra = run(self.buf, *[jnp.stack(c) for c in cols])
        sel = jnp.asarray([a is not None for a in args])
        self.buf = jax.tree.map(
            lambda x, old: jnp.where(
                sel.reshape((-1,) + (1,) * (x.ndim - 1)), x, old),
            new, self.buf)
        return [None if a is None else jax.tree.map(lambda x: x[i], extra)
                for i, a in enumerate(args)]


def _rebase(buf, epoch, until):
    return ev.rebase(buf, epoch, until), ()


def _deliver(buf, *a):
    buf, n_over, _ = ev.deliver_batch(buf, *a)
    return buf, n_over


N_DELIVER = 7   # deliver batches are padded to this (masked) width
N_BURST = 6     # push sites of a "burst": one handler pass's worth


def _burst(buf, mask, t, tb, kind, p):
    """N_BURST pushes, one a row of the arguments, the third a push_back."""
    overs = []
    for j in range(N_BURST):
        if j == 2:
            buf, over = ev.push_back(buf, mask[j], t[j], tb[j], kind[j], p[j])
        else:
            buf, over = ev.push_local(buf, mask[j], t[j], kind[j], p[j])
        overs.append(over)
    return buf, jnp.stack(overs)


def _staged(fn, rows):
    """``fn``'s pushes staged and committed once (a round of the TCP
    engines, core/engine.run_round) instead of written one by one."""
    def run(buf, *a):
        buf, extra = fn(ev.stage_open(buf, rows, ev.free_slots(buf)), *a)
        return ev.push_commit(buf)[0], extra

    return run


PUSHERS = {
    "direct": (ev.push_local, ev.push_back, _burst),
    "staged": (_staged(ev.push_local, 1), _staged(ev.push_back, 1),
               _staged(_burst, N_BURST)),
}


@pytest.mark.parametrize("pushes", PUSHERS)
@pytest.mark.parametrize("lanes", [1, 2], ids=["solo", "vmap2"])
def test_event_core_fuzz_vs_heap_model(lanes, pushes):
    push_local, push_back, burst = PUSHERS[pushes]
    H, C = 6, 10
    until_bound = 10_000
    rngs = [np.random.default_rng(20260731 + i) for i in range(lanes)]
    models = [HeapModel(H, C) for _ in range(lanes)]
    epoch = [0] * lanes
    pkt_ctr = [0] * lanes
    bufs = Lanes(lanes, H, C)

    def do_rebase(new_epoch):
        # Engine convention: the eligibility bound is epoch-relative
        # (win_end = win_start + W); pops below use until ≤ e + u.
        args = []
        for i, e in enumerate(new_epoch):
            if e is not None:
                epoch[i] = e
            args.append(None if e is None else
                        (jnp.int64(e), jnp.int64(e + until_bound)))
        bufs.apply(_rebase, args)

    do_rebase([0] * lanes)
    for step in range(300):
        ops = [rng.choice(["push", "pop", "pop", "rebase", "deliver",
                           "pushback", "burst"]) for rng in rngs]
        for op in dict.fromkeys(ops):
            on = [i for i in range(lanes) if ops[i] == op]
            args = [None] * lanes
            want = [None] * lanes
            if op == "push":
                for i in on:
                    rng = rngs[i]
                    mask = rng.random(H) < 0.7
                    # Narrow time range forces (time, tb) ties; occasional
                    # far future and past-due (pre-epoch) values exercise
                    # the clamps.
                    t = np.maximum(epoch[i] + rng.integers(-50, 200, H), 0)
                    if rng.random() < 0.1:
                        t = t + 5 * 10**9          # beyond the i32 horizon
                    kind = rng.integers(1, 5, H)
                    p = rng.integers(0, 100, (NP, H))
                    want[i] = models[i].push_local(mask, t, kind, p)
                    args[i] = (jnp.asarray(mask), jnp.asarray(t, jnp.int64),
                               jnp.asarray(kind, jnp.int32),
                               jnp.asarray(p, jnp.int32))
                over = bufs.apply(push_local, args)
                for i in on:
                    assert np.asarray(over[i]).tolist() == want[i], (step, i)
            elif op == "burst":
                # What one handler pass does to a host's buffer: several
                # pushes in a row, some masked, one with a given tie-break.
                for i in on:
                    rng = rngs[i]
                    mask = rng.random((N_BURST, H)) < 0.5
                    t = epoch[i] + rng.integers(0, 200, (N_BURST, H))
                    tb = TB_PACKET_BASE + pkt_ctr[i] + np.arange(H)
                    pkt_ctr[i] += H
                    tb = np.broadcast_to(tb, (N_BURST, H))
                    kind = rng.integers(1, 5, (N_BURST, H))
                    p = rng.integers(0, 100, (N_BURST, NP, H))
                    want[i] = [
                        models[i].push_back(mask[j], t[j], tb[j], kind[j],
                                            p[j]) if j == 2 else
                        models[i].push_local(mask[j], t[j], kind[j], p[j])
                        for j in range(N_BURST)]
                    args[i] = (jnp.asarray(mask), jnp.asarray(t, jnp.int64),
                               jnp.asarray(tb, jnp.int64),
                               jnp.asarray(kind, jnp.int32),
                               jnp.asarray(p, jnp.int32))
                over = bufs.apply(burst, args)
                for i in on:
                    assert np.asarray(over[i]).tolist() == want[i], (step, i)
            elif op == "pop":
                for i in on:
                    until = epoch[i] + int(rngs[i].integers(0, until_bound))
                    want[i] = models[i].pop_until(until)
                    args[i] = (jnp.int64(until),)
                popped = bufs.apply(ev.pop_until, args)
                for i in on:
                    pe = popped[i]
                    for h, exp in enumerate(want[i]):
                        if exp is None:
                            assert not bool(pe.mask[h]), (step, i, h)
                        else:
                            assert bool(pe.mask[h]), (step, i, h)
                            assert int(pe.time[h]) == exp[0], (step, i, h)
                            assert int(pe.tb[h]) == exp[1], (step, i, h)
                            assert int(pe.kind[h]) == exp[2], (step, i, h)
                            assert tuple(int(x) for x in pe.p[:, h]) == exp[3]
            elif op == "pushback":
                # Re-insert events with EXPLICIT (caller-owned) tie-breaks —
                # the cpu-model defer/requeue path (events.push_back).
                for i in on:
                    rng = rngs[i]
                    mask = rng.random(H) < 0.5
                    t = epoch[i] + rng.integers(0, 300, H)
                    tb = TB_PACKET_BASE + pkt_ctr[i] + np.arange(H)
                    pkt_ctr[i] += H
                    kind = rng.integers(1, 5, H)
                    p = rng.integers(0, 100, (NP, H))
                    want[i] = models[i].push_back(mask, t, tb, kind, p)
                    args[i] = (jnp.asarray(mask), jnp.asarray(t, jnp.int64),
                               jnp.asarray(tb, jnp.int64),
                               jnp.asarray(kind, jnp.int32),
                               jnp.asarray(p, jnp.int32))
                over = bufs.apply(push_back, args)
                for i in on:
                    assert np.asarray(over[i]).tolist() == want[i], (step, i)
            elif op == "rebase":
                # Epoch only advances (window starts are monotone).
                do_rebase([epoch[i] + int(rngs[i].integers(0, 300))
                           if i in on else None for i in range(lanes)])
            else:  # deliver (window-granularity: rebase precedes next pops)
                for i in on:
                    rng = rngs[i]
                    n = N_DELIVER
                    mask = (rng.random(n) < 0.9) & (
                        np.arange(n) < rng.integers(1, n + 1))
                    dst = rng.integers(0, H, n)
                    t = epoch[i] + rng.integers(0, 500, n)
                    tb = TB_PACKET_BASE + np.arange(pkt_ctr[i], pkt_ctr[i] + n)
                    pkt_ctr[i] += n
                    kind = rng.integers(1, 5, n)
                    p = rng.integers(0, 100, (NP, n))
                    want[i] = models[i].deliver(dst, t, tb, kind, p, mask)
                    args[i] = (jnp.asarray(dst, jnp.int32),
                               jnp.asarray(t, jnp.int64),
                               jnp.asarray(tb, jnp.int64),
                               jnp.asarray(kind, jnp.int32),
                               jnp.asarray(p, jnp.int32), jnp.asarray(mask))
                n_over = bufs.apply(_deliver, args)
                for i in on:
                    assert int(n_over[i]) == want[i], (step, i)
                do_rebase([epoch[i] if i in on else None
                           for i in range(lanes)])

    for i in range(lanes):
        assert buf_census(bufs.lane(i)) == models[i].census(), i


@pytest.mark.parametrize("lanes", [1, 2], ids=["solo", "vmap2"])
def test_n_elig_counter_matches_plane_scan(lanes):
    """After an arbitrary op sequence the maintained eligibility counters
    equal a fresh plane scan (the invariant any_eligible/compaction rely
    on) — in every lane, the lanes pushing back and popping at different
    steps."""
    rngs = [np.random.default_rng(7 + i) for i in range(lanes)]
    H, C = 5, 8
    bufs = Lanes(lanes, H, C)
    bufs.apply(_rebase, [(jnp.int64(0), jnp.int64(1000))] * lanes)
    k = jnp.full(H, 1, jnp.int32)
    for step in range(40):
        bufs.apply(ev.push_local, [
            (jnp.asarray(rng.random(H) < 0.6),
             jnp.asarray(rng.integers(0, 2000, H), jnp.int64),  # some inelig
             k, jnp.zeros((NP, H), jnp.int32)) for rng in rngs])
        # push_back keeps the counters too (the cpu model's requeue): every
        # few steps, in the lanes that draw it.
        backs = [(jnp.asarray(rng.random(H) < 0.5),
                  jnp.asarray(rng.integers(0, 2000, H), jnp.int64),
                  jnp.asarray(TB_PACKET_BASE + step * H + np.arange(H),
                              jnp.int64),
                  k, jnp.zeros((NP, H), jnp.int32))
                 if rng.random() < 0.3 else None for rng in rngs]
        if any(a is not None for a in backs):
            bufs.apply(ev.push_back, backs)
        pops = [(jnp.int64(1000),) if rng.random() < 0.5 else None
                for rng in rngs]
        if any(a is not None for a in pops):
            bufs.apply(ev.pop_until, pops)
        for i in range(lanes):
            buf = bufs.lane(i)
            scan = ((np.asarray(buf.kind) != 0)
                    & (np.asarray(buf.t32) < int(buf.u32))).sum(axis=0)
            assert np.asarray(buf.n_elig).tolist() == scan.tolist(), i
