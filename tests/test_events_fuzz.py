"""Randomized differential test of the batched event buffer.

A few hundred random push/pop/rebase/deliver operations run against a plain
Python heap model; every pop's (mask, time, kind, tb, payload) and the
final buffer census must match exactly, for BOTH pop/push implementations
(XLA reductions and the fused Pallas kernels, interpret mode on CPU).

This is the unstructured counterpart of tests/test_events.py: the
structured tests pin the documented contracts; the fuzz sweep hunts the
interactions nobody thought to write down (epoch advances between pushes,
same-time tb ties across push/deliver sources, overflow under load,
past-due leftovers, eligibility-counter drift). The heap model is ~40
lines of obviously-correct Python — the judge's "real OS as oracle" trick
(SURVEY §4) scaled down to the data structure.
"""

import heapq

import jax.numpy as jnp
import numpy as np
import pytest

from shadow1_tpu.consts import NP, TB_PACKET_BASE
from shadow1_tpu.core import events as ev
from shadow1_tpu.core.popk import (
    pop_until_fused,
    push_back_fused,
    push_local_fused,
)


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


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_event_core_fuzz_vs_heap_model(impl):
    rng = np.random.default_rng(20260731)
    H, C = 6, 10
    buf = ev.evbuf_init(H, C)
    model = HeapModel(H, C)
    epoch = 0
    until_bound = 10_000
    pkt_ctr = 0

    def do_rebase(e, u):
        nonlocal buf, epoch
        epoch = e
        # Engine convention: the eligibility bound is epoch-relative
        # (win_end = win_start + W); pops below use until ≤ e + u.
        buf = ev.rebase(buf, e, e + u)

    do_rebase(0, until_bound)
    for step in range(300):
        op = rng.choice(["push", "pop", "pop", "rebase", "deliver",
                         "pushback"])
        if op == "push":
            mask = rng.random(H) < 0.7
            # Narrow time range forces (time, tb) ties; occasional far
            # future and past-due (pre-epoch) values exercise the clamps.
            t = epoch + rng.integers(-50, 200, H)
            t = np.maximum(t, 0)
            if rng.random() < 0.1:
                t = t + 5 * 10**9          # beyond the i32 horizon
            kind = rng.integers(1, 5, H)
            p = rng.integers(0, 100, (NP, H))
            over_m = model.push_local(mask, t, kind, p)
            buf, over = ev.push_local(
                buf, jnp.asarray(mask), jnp.asarray(t, jnp.int64),
                jnp.asarray(kind, jnp.int32), jnp.asarray(p, jnp.int32),
            ) if impl == "xla" else push_local_fused(
                buf, jnp.asarray(mask), jnp.asarray(t, jnp.int64),
                jnp.asarray(kind, jnp.int32), jnp.asarray(p, jnp.int32),
            )
            assert np.asarray(over).tolist() == over_m, step
        elif op == "pop":
            until = epoch + int(rng.integers(0, until_bound))
            got = model.pop_until(until)
            if impl == "xla":
                buf, pe = ev.pop_until(buf, jnp.int64(until))
            else:
                buf, pe = pop_until_fused(buf, jnp.int64(until))
            for i, exp in enumerate(got):
                if exp is None:
                    assert not bool(pe.mask[i]), (step, i)
                else:
                    assert bool(pe.mask[i]), (step, i)
                    assert int(pe.time[i]) == exp[0], (step, i)
                    assert int(pe.tb[i]) == exp[1], (step, i)
                    assert int(pe.kind[i]) == exp[2], (step, i)
                    assert tuple(int(x) for x in pe.p[:, i]) == exp[3]
        elif op == "pushback":
            # Re-insert events with EXPLICIT (caller-owned) tie-breaks —
            # the cpu-model defer/requeue path (events.push_back).
            mask = rng.random(H) < 0.5
            t = epoch + rng.integers(0, 300, H)
            tb = TB_PACKET_BASE + pkt_ctr + np.arange(H)
            pkt_ctr += H
            kind = rng.integers(1, 5, H)
            p = rng.integers(0, 100, (NP, H))
            over_m = model.push_back(mask, t, tb, kind, p)
            fn = ev.push_back if impl == "xla" else push_back_fused
            buf, over = fn(
                buf, jnp.asarray(mask), jnp.asarray(t, jnp.int64),
                jnp.asarray(tb, jnp.int64), jnp.asarray(kind, jnp.int32),
                jnp.asarray(p, jnp.int32),
            )
            assert np.asarray(over).tolist() == over_m, step
        elif op == "rebase":
            # Epoch only advances (window starts are monotone).
            do_rebase(epoch + int(rng.integers(0, 300)), until_bound)
        else:  # deliver (window-granularity: rebase precedes next pops)
            n = int(rng.integers(1, 8))
            dst = rng.integers(0, H, n)
            t = epoch + rng.integers(0, 500, n)
            tb = TB_PACKET_BASE + np.arange(pkt_ctr, pkt_ctr + n)
            pkt_ctr += n
            kind = rng.integers(1, 5, n)
            p = rng.integers(0, 100, (NP, n))
            mask = rng.random(n) < 0.9
            n_over_m = model.deliver(dst, t, tb, kind, p, mask)
            buf, n_over, _ = ev.deliver_batch(
                buf, jnp.asarray(dst, jnp.int32), jnp.asarray(t, jnp.int64),
                jnp.asarray(tb, jnp.int64), jnp.asarray(kind, jnp.int32),
                jnp.asarray(p, jnp.int32), jnp.asarray(mask),
            )
            assert int(n_over) == n_over_m, step
            do_rebase(epoch, until_bound)

    assert buf_census(buf) == model.census()


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_n_elig_counter_matches_plane_scan(impl):
    """After an arbitrary op sequence the maintained eligibility counters
    equal a fresh plane scan (the invariant any_eligible/compaction rely
    on) — for both implementations."""
    rng = np.random.default_rng(7)
    H, C = 5, 8
    buf = ev.evbuf_init(H, C)
    buf = ev.rebase(buf, 0, 1000)
    k = jnp.full(H, 1, jnp.int32)
    push = ev.push_local if impl == "xla" else push_local_fused
    pop = ev.pop_until if impl == "xla" else pop_until_fused
    for _ in range(40):
        m = jnp.asarray(rng.random(H) < 0.6)
        t = jnp.asarray(rng.integers(0, 2000, H), jnp.int64)  # some inelig
        buf, _ = push(buf, m, t, k, jnp.zeros((NP, H), jnp.int32))
        if rng.random() < 0.5:
            buf, _ = pop(buf, jnp.int64(1000))
        scan = ((np.asarray(buf.kind) != 0)
                & (np.asarray(buf.t32) < int(buf.u32))).sum(axis=0)
        assert np.asarray(buf.n_elig).tolist() == scan.tolist()
