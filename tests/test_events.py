"""Unit tests for the batched event-buffer primitives."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shadow1_tpu.consts import NP, K_PHOLD
from shadow1_tpu.core.events import (
    PUSH_RB,
    RB,
    PushRowsError,
    deliver_batch,
    evbuf_init,
    free_slots,
    pop_until,
    push_back,
    push_commit,
    push_local,
    rebase,
    stage_open,
    tb_join,
    tb_split,
)

ZP = lambda h: jnp.zeros((NP, h), jnp.int32)


def test_push_pop_order():
    buf = evbuf_init(2, 8)
    k = jnp.full(2, K_PHOLD, jnp.int32)
    both = jnp.ones(2, bool)
    # Push times out of order; same-time pushes must pop FIFO (by tb).
    for t in [50, 10, 30, 10]:
        buf, over = push_local(buf, both, jnp.full(2, t, jnp.int64), k, ZP(2))
        assert not bool(over.any())
    seen = []
    for _ in range(4):
        buf, ev = pop_until(buf, jnp.int64(10**9))
        assert bool(ev.mask.all())
        seen.append(int(ev.time[0]))
    assert seen == [10, 10, 30, 50]
    buf, ev = pop_until(buf, jnp.int64(10**9))
    assert not bool(ev.mask.any())


def test_pop_respects_until():
    buf = evbuf_init(1, 4)
    one = jnp.ones(1, bool)
    k = jnp.full(1, K_PHOLD, jnp.int32)
    buf, _ = push_local(buf, one, jnp.full(1, 100, jnp.int64), k, ZP(1))
    buf, ev = pop_until(buf, jnp.int64(100))  # window end exclusive
    assert not bool(ev.mask[0])
    buf, ev = pop_until(buf, jnp.int64(101))
    assert bool(ev.mask[0]) and int(ev.time[0]) == 100


def test_push_overflow_counts():
    buf = evbuf_init(1, 2)
    one = jnp.ones(1, bool)
    k = jnp.full(1, K_PHOLD, jnp.int32)
    for i in range(3):
        buf, over = push_local(buf, one, jnp.full(1, i + 1, jnp.int64), k, ZP(1))
        assert bool(over[0]) == (i == 2)


def test_deliver_batch_ranks_and_overflow():
    buf = evbuf_init(3, 2)
    n = 5
    dst = jnp.array([1, 1, 1, 2, 0], jnp.int32)  # 3 packets to host 1 (cap 2)
    time = jnp.array([10, 20, 30, 40, 50], jnp.int64)
    tb = jnp.arange(n, dtype=jnp.int64) + (1 << 62)
    kind = jnp.full(n, K_PHOLD, jnp.int32)
    p = jnp.zeros((NP, n), jnp.int32)
    mask = jnp.ones(n, bool)
    buf, n_over, _ = deliver_batch(buf, dst, time, tb, kind, p, mask)
    assert int(n_over) == 1
    counts = np.asarray((buf.kind != 0).sum(axis=0))
    assert counts.tolist() == [1, 2, 1]
    # deliver_batch writes absolute times only; the window-start rebase
    # refreshes the i32 pop keys before the next round loop reads them
    # (core/engine.py window_step order).
    buf = rebase(buf, 0)
    # Host 1 keeps its two earliest-listed packets (rank order), pops in time order.
    buf, ev = pop_until(buf, jnp.int64(10**9))
    assert ev.time.tolist()[1] == 10 and ev.time.tolist()[2] == 40


def test_far_future_event_beyond_i32_horizon():
    """An event scheduled past the 2**31-ns rebase horizon saturates the i32
    pop key (ineligible) until the epoch catches up, then pops at its exact
    time — the Tor bootstrap / long-RTO shape (core/events.py t32)."""
    buf = evbuf_init(1, 4)
    one = jnp.ones(1, bool)
    k = jnp.full(1, K_PHOLD, jnp.int32)
    t_far = 5 * 10**9  # +5 s, ~2.3x past the horizon at epoch 0
    buf, over = push_local(buf, one, jnp.full(1, t_far, jnp.int64), k, ZP(1))
    assert not bool(over[0])
    # Windows advance in 1-second steps; the event must stay invisible even
    # to a generous until bound while clamped.
    for epoch in range(0, 5 * 10**9, 10**9):
        buf = rebase(buf, epoch)
        buf, ev = pop_until(buf, jnp.int64(epoch + 10**9))
        assert not bool(ev.mask[0]), epoch
    buf = rebase(buf, 5 * 10**9 - 1)
    buf, ev = pop_until(buf, jnp.int64(5 * 10**9 + 1))
    assert bool(ev.mask[0]) and int(ev.time[0]) == t_far


def test_past_due_events_keep_exact_time_and_order():
    """Events left eligible by a max_rounds cap-hit window rebase to a LATER
    epoch: their reconstructed pop times must stay exact and their (time,
    tb) order must survive — t32 goes negative rather than clamping to 0
    (core/events.py I32_PASTDUE; round-5 review finding)."""
    buf = evbuf_init(1, 4)
    one = jnp.ones(1, bool)
    k = jnp.full(1, K_PHOLD, jnp.int32)
    # Three events, all before the NEXT window's start (past-due there).
    for t in (300, 100, 200):
        buf, _ = push_local(buf, one, jnp.full(1, t, jnp.int64), k, ZP(1))
    b = rebase(buf, 1000, 2000)  # epoch has moved past all three
    seen = []
    for _ in range(3):
        b, ev2 = pop_until(b, jnp.int64(2000))
        assert bool(ev2.mask[0])
        seen.append(int(ev2.time[0]))
    assert seen == [100, 200, 300], seen


def test_tb_split_join_order():
    """tb_split is an order-preserving bijection into lexicographic
    (hi, lo) i32 — including low words with the top bit set (the sign-flip
    encoding) and the packet-tb range."""
    vals = np.array(
        [0, 1, 2**31 - 1, 2**31, 2**32 - 1, 2**32, (1 << 62) + 7,
         (1 << 62) + (5 << 32) + 0xFFFFFFFF, (1 << 62) + (6 << 32)],
        dtype=np.int64,
    )
    hi, lo = tb_split(jnp.asarray(vals))
    back = np.asarray(tb_join(hi, lo))
    np.testing.assert_array_equal(back, vals)
    # Lexicographic (hi, signed lo) order == numeric order.
    pairs = list(zip(np.asarray(hi).tolist(), np.asarray(lo).tolist()))
    order = sorted(range(len(vals)), key=lambda i: pairs[i])
    assert order == sorted(range(len(vals)), key=lambda i: int(vals[i]))


def test_payload_matches_at_chain():
    """dense.payload (stacked rows) is bit-identical to the .at[i].set chain
    it replaced in the packet builders, including None planes, scalar
    broadcast, and the over-NP guard."""
    import pytest

    from shadow1_tpu.core.dense import payload

    rng = np.random.default_rng(7)
    h = 6
    rows = [jnp.asarray(rng.integers(0, 99, h), jnp.int32), None,
            jnp.int32(41), None, jnp.asarray(rng.integers(0, 9, h), jnp.int32)]
    p = payload(h, *rows)
    ref = jnp.zeros((NP, h), jnp.int32)
    for i, r in enumerate(rows):
        if r is not None:
            ref = ref.at[i].set(r)
    np.testing.assert_array_equal(np.asarray(p), np.asarray(ref))
    assert p.dtype == jnp.int32 and p.shape == (NP, h)
    with pytest.raises(ValueError, match="rows > NP"):
        payload(h, *([jnp.int32(0)] * (NP + 1)))


# ---------------------------------------------------------------------------
# deliver_batch against a numpy oracle of the layout rule
# ---------------------------------------------------------------------------

def _deliver_oracle(kind0, dst, mask):
    """The layout rule alone: packet r of a host, in flat source order,
    goes to that host's r-th free slot in ascending slot index; when free
    slots run out the highest ranks drop. Returns (slot -> flat packet
    index or -1 as [C, H], n_overflow, the busiest host's placed count)."""
    cap, n_hosts = kind0.shape
    placed = np.full((cap, n_hosts), -1)
    n_over = busiest = 0
    by_host: dict[int, list[int]] = {}
    for i in np.flatnonzero(mask):
        by_host.setdefault(int(dst[i]), []).append(int(i))
    for h, pkts in by_host.items():
        slots = np.flatnonzero(kind0[:, h] == 0)
        for c, i in zip(slots, pkts):
            placed[c, h] = i
        n_over += max(len(pkts) - len(slots), 0)
        busiest = max(busiest, min(len(pkts), len(slots)))
    return placed, n_over, busiest


def _deliver_case(name):
    """The lanes of one named case, each (occupied [C, H] bool, dst [N],
    mask [N]): one, but for ``lanes``, which has three of different
    in-degree."""
    rng = np.random.default_rng(sum(map(ord, name)))
    cap, n_hosts = 12, 7
    occ = rng.random((cap, n_hosts)) < 0.4
    if name == "empty":           # nothing sent: zero trips
        return [(occ, np.zeros(16, np.int64), np.zeros(16, bool))]
    if name == "all_masked":      # destinations present, none valid
        return [(occ, rng.integers(0, n_hosts, 16), np.zeros(16, bool))]
    if name == "full_host":       # host 3 has no free slot, host 4 one
        occ[:, 3] = True
        occ[:, 4] = True
        occ[5, 4] = False
        dst = np.r_[np.full(4, 3), np.full(3, 4), rng.integers(0, n_hosts, 9)]
        return [(occ, dst, np.ones(16, bool))]
    if name == "over_rb":         # in-degree > RB and > the free slots
        occ[:, 2] = np.arange(cap) % 2 == 0          # 6 free slots
        dst = np.r_[np.full(2 * RB + 3, 2), rng.integers(0, n_hosts, 20)]
        return [(occ, dst, rng.random(len(dst)) < 0.9)]
    if name == "wide":            # (H + 1) << nb > 2**31: the int64 keys
        n = 2**14 + 5
        hosts = 2**17
        occ = rng.random((3, hosts)) < 0.5
        dst = rng.integers(0, hosts, n)
        dst[:9] = hosts - 1       # the last host over RB and over its slots
        dst[9:20] = 0
        return [(occ, dst, rng.random(n) < 0.8)]
    assert name == "lanes"    # in-degree 0, 3 and 2 * RB + 1 at host 1
    lanes = []
    for deg in (0, 3, 2 * RB + 1):
        dst = np.r_[np.full(deg, 1), 2 + np.arange(2 * RB + 9 - deg) % 5]
        mask = np.arange(len(dst)) < (deg + 8 if deg else 0)
        occ = rng.random((cap, n_hosts)) < 0.3
        occ[:, 1] = False
        lanes.append((occ, dst, mask))
    return lanes


def _deliver_inputs(occ, dst, mask, seed=0):
    rng = np.random.default_rng(seed)
    cap, n_hosts = occ.shape
    n = len(dst)
    buf = evbuf_init(n_hosts, cap)
    buf = buf._replace(
        kind=jnp.asarray(np.where(occ, K_PHOLD, 0), jnp.int32),
        time_hi=jnp.asarray(rng.integers(0, 99, occ.shape), jnp.int32),
        time_lo=jnp.asarray(rng.integers(0, 99, occ.shape), jnp.int32),
        tb_hi=jnp.asarray(rng.integers(0, 99, occ.shape), jnp.int32),
        tb_lo=jnp.asarray(rng.integers(0, 99, occ.shape), jnp.int32),
        p=jnp.asarray(rng.integers(0, 99, (NP,) + occ.shape), jnp.int32),
    )
    args = (jnp.asarray(dst, jnp.int32),
            jnp.asarray(rng.integers(0, 2**40, n), jnp.int64),
            jnp.asarray(rng.integers(0, 2**61, n), jnp.int64),
            jnp.asarray(rng.integers(1, 5, n), jnp.int32),
            jnp.asarray(rng.integers(0, 1000, (NP, n)), jnp.int32),
            jnp.asarray(mask))
    return buf, args


def _check_deliver(buf0, args, out, occ, dst, mask):
    """``out`` = deliver_batch(buf0, *args) holds the oracle's layout in
    every plane, its overflow count, and trips * RB ranks."""
    buf, n_over, n_ranks = out
    placed, want_over, busiest = _deliver_oracle(np.where(occ, 1, 0), dst, mask)
    _, time, tb, kind, p, _ = (np.asarray(a) for a in args)
    got = placed >= 0
    src = np.where(got, placed, 0)
    thi, tlo = (np.asarray(x) for x in tb_split(jnp.asarray(time)))
    bhi, blo = (np.asarray(x) for x in tb_split(jnp.asarray(tb)))
    for plane, rows in (("time_hi", thi), ("time_lo", tlo), ("tb_hi", bhi),
                        ("tb_lo", blo), ("kind", kind)):
        np.testing.assert_array_equal(
            np.asarray(getattr(buf, plane)),
            np.where(got, rows[src], np.asarray(getattr(buf0, plane))), plane)
    np.testing.assert_array_equal(
        np.asarray(buf.p), np.where(got[None], p[:, src], np.asarray(buf0.p)))
    for leaf in ("t32", "self_ctr", "epoch", "n_elig", "u32"):
        np.testing.assert_array_equal(np.asarray(getattr(buf, leaf)),
                                      np.asarray(getattr(buf0, leaf)), leaf)
    assert int(n_over) == want_over
    assert int(n_ranks) == -(-busiest // RB) * RB


@pytest.mark.parametrize(
    "case", ["empty", "all_masked", "full_host", "over_rb", "wide", "lanes"])
def test_deliver_batch_layout_rule(case):
    lanes = _deliver_case(case)
    if case == "wide":
        occ, dst, _ = lanes[0]
        nb = max((len(dst) - 1).bit_length(), 1)
        assert (occ.shape[1] + 1) << nb > 2**31
    ins = [_deliver_inputs(*lane, seed=i) for i, lane in enumerate(lanes)]
    if len(lanes) == 1:
        outs = [jax.jit(deliver_batch)(ins[0][0], *ins[0][1])]
    else:
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ins)
        batched = jax.jit(jax.vmap(deliver_batch))(stacked[0], *stacked[1])
        outs = [jax.tree_util.tree_map(lambda x: x[i], batched)
                for i in range(len(lanes))]
    for lane, (buf0, args), out in zip(lanes, ins, outs):
        _check_deliver(buf0, args, out, *lane)
    ranks = [int(out[2]) for out in outs]
    if case in ("empty", "all_masked"):
        assert ranks == [0]                  # zero trips
    if case == "lanes":
        assert ranks == [0, RB, 3 * RB]      # each lane its own trip count


# ---------------------------------------------------------------------------
# static guard: deliver_batch gathers by arriving rank, never by slot
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lanes", [0, 2], ids=["solo", "vmap2"])
def test_deliver_batch_gathers_no_slot_wide_index(lanes):
    """Every ``gather`` deliver_batch traces fetches at most RB * H indices
    (a block of ranks) or searchsorted's H + 1 — never one per event slot
    (C * H), which the chip walks one index at a time (PERF.md §6, PR 29)."""
    from shadow1_tpu.tools.opcensus import iter_eqns

    cap, n_hosts, n = 56, 4096, 32 * 4096
    buf = jax.eval_shape(lambda: evbuf_init(n_hosts, cap))
    sd = jax.ShapeDtypeStruct
    args = (sd((n,), jnp.int32), sd((n,), jnp.int64), sd((n,), jnp.int64),
            sd((n,), jnp.int32), sd((NP, n), jnp.int32), sd((n,), jnp.bool_))
    fn, batch = deliver_batch, 1
    if lanes:
        fn, batch = jax.vmap(deliver_batch), lanes
        buf, args = jax.tree_util.tree_map(
            lambda x: sd((lanes,) + x.shape, x.dtype), (buf, args))
    gathers = [e for e in iter_eqns(jax.make_jaxpr(fn)(buf, *args).jaxpr)
               if e.primitive.name == "gather"]
    # The guard can see the fill: its index and row gathers are there.
    assert len(gathers) >= 2
    for e in gathers:
        n_idx = int(np.prod(e.invars[1].aval.shape[:-1])) // batch
        assert n_idx <= max(RB * n_hosts, n_hosts + 1), (n_idx, e)
    assert RB * n_hosts < cap * n_hosts


# ---------------------------------------------------------------------------
# the outbox against per-host Python lists
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lanes", [0, 2], ids=["solo", "vmap2"])
def test_outbox_append_matches_list_model(lanes):
    """outbox_append under random masks that run past ``outbox_cap``, with
    outbox_space before and outbox_clear between, against one Python list a
    host: the ok mask, ``cnt``, the lifetime ``pkt_ctr`` (a clear does not
    reset it), and every stored row — the departure time through its
    (hi, lo) split, ``ctr`` as the counter's low word. Under ``vmap2`` two
    lanes fill at different rates, so one is full where the other is not."""
    from shadow1_tpu.core.outbox import (
        outbox_append,
        outbox_clear,
        outbox_init,
        outbox_space,
    )

    h, cap, n = 5, 4, max(lanes, 1)
    # Lifetime counters that start near the i32 edges: ctr keeps the low word.
    ctr0 = np.array([2**32 - 3, 0, 7, 2**31 - 2, 1], np.int64)
    ob = outbox_init(h, cap)._replace(pkt_ctr=jnp.asarray(ctr0))
    append, space, clear = outbox_append, outbox_space, outbox_clear
    if lanes:
        ob = jax.tree_util.tree_map(lambda x: jnp.stack([x] * n), ob)
        append, space, clear = map(jax.vmap, (append, space, clear))
    append, space, clear = map(jax.jit, (append, space, clear))

    def lane(x, i):
        return jax.tree_util.tree_map(lambda a: np.asarray(a[i]), x) \
            if lanes else jax.tree_util.tree_map(np.asarray, x)

    rngs = [np.random.default_rng(30 + i) for i in range(n)]
    rows = [[[] for _ in range(h)] for _ in range(n)]
    sent = [ctr0.copy() for _ in range(n)]
    full_seen = 0
    for step in range(40):
        free = space(ob)
        args, want_ok = [], []
        for i, rng in enumerate(rngs):
            mask = rng.random(h) < (0.9, 0.5)[i]
            dst = rng.integers(0, h, h).astype(np.int32)
            kind = rng.integers(1, 5, h).astype(np.int32)
            depart = rng.integers(0, 2**40, h)      # past one i32 word
            p = rng.integers(0, 100, (NP, h)).astype(np.int32)
            args.append((mask, dst, kind, depart, p))
            np.testing.assert_array_equal(
                lane(free, i), [cap - len(r) for r in rows[i]])
            ok = []
            for j in range(h):
                ok.append(bool(mask[j]) and len(rows[i][j]) < cap)
                full_seen += bool(mask[j]) and not ok[-1]
                if ok[-1]:
                    rows[i][j].append((
                        dst[j], kind[j], depart[j],
                        np.int64(sent[i][j]).astype(np.int32),
                        p[:, j].tolist()))
                    sent[i][j] += 1
            want_ok.append(ok)
        cols = [jnp.asarray(np.stack(c) if lanes else c[0])
                for c in zip(*args)]
        ob, ok = append(ob, *cols)
        for i in range(n):
            o = lane(ob, i)
            assert lane(ok, i).tolist() == want_ok[i], (step, i)
            assert o.cnt.tolist() == [len(r) for r in rows[i]], (step, i)
            assert o.pkt_ctr.tolist() == sent[i].tolist(), (step, i)
            depart = np.asarray(tb_join(o.depart_hi, o.depart_lo))
            for j in range(h):
                got = [(o.dst[r, j], o.kind[r, j], depart[r, j], o.ctr[r, j],
                        o.p[:, r, j].tolist()) for r in range(len(rows[i][j]))]
                assert got == rows[i][j], (step, i, j)
        if step % 7 == 6:
            ob = clear(ob)
            rows = [[[] for _ in range(h)] for _ in range(n)]
    assert full_seen > 10       # the masks did run past the cap


# ---------------------------------------------------------------------------
# A round's pushes staged and committed once (PR 49) against the same pushes
# written one after another.
# ---------------------------------------------------------------------------

def _holed_buffer(rng, hosts, cap):
    """A buffer whose hosts have 0, 1, 2 and many free slots, the holes at
    random slots, rebased at epoch 1,000 with a bound 500 past it."""
    buf = evbuf_init(hosts, cap)
    free = np.array([0, 1, 2] + list(rng.integers(3, cap + 1, hosts - 3)))
    occ = np.stack([rng.permutation(cap) >= f for f in free], axis=1)
    t = rng.integers(900, 2000, (cap, hosts))
    thi, tlo = tb_split(jnp.asarray(t, jnp.int64))
    bhi, blo = tb_split(jnp.asarray(rng.integers(0, 1 << 40, (cap, hosts)),
                                    jnp.int64))
    buf = buf._replace(
        time_hi=jnp.where(occ, thi, buf.time_hi),
        time_lo=jnp.where(occ, tlo, buf.time_lo),
        tb_hi=jnp.where(occ, bhi, 0), tb_lo=jnp.where(occ, blo, 0),
        kind=jnp.where(occ, 3, 0).astype(jnp.int32),
        p=jnp.asarray(rng.integers(0, 99, (NP, cap, hosts)) * occ, jnp.int32),
        self_ctr=jnp.asarray(rng.integers(0, 50, hosts), jnp.int64))
    return rebase(buf, 1000, 1500), free


def _sites(rng, hosts, n, share):
    """``n`` push sites: (mask, time, kind, payload) each, every fourth a
    ``push_back`` (tb given)."""
    out = []
    for i in range(n):
        site = dict(
            mask=jnp.asarray(rng.random(hosts) < share),
            time=jnp.asarray(rng.integers(950, 1800, hosts), jnp.int64),
            kind=jnp.asarray(rng.integers(1, 6, hosts), jnp.int32),
            p=jnp.asarray(rng.integers(-5, 99, (NP, hosts)), jnp.int32))
        if i % 4 == 3:
            site["tb"] = jnp.asarray(
                (1 << 41) + rng.integers(0, 1 << 20, hosts), jnp.int64)
        out.append(site)
    return out


def _push_all(buf, sites):
    overs = []
    for s in sites:
        if "tb" in s:
            buf, over = push_back(buf, s["mask"], s["time"], s["tb"],
                                  s["kind"], s["p"])
        else:
            buf, over = push_local(buf, s["mask"], s["time"], s["kind"],
                                   s["p"])
        overs.append(over)
    return buf, jnp.stack(overs)


@pytest.mark.parametrize("n_sites", [3, 11])
@pytest.mark.parametrize("cap", [8, 96])
@pytest.mark.parametrize("lanes", [1, 4], ids=["solo", "vmap4"])
def test_a_staged_round_equals_its_pushes_one_after_another(lanes, cap,
                                                            n_sites):
    """N sites staged and committed once leave the buffer N sequential
    pushes leave, LEAF FOR LEAF — planes (so slot for slot), ``self_ctr``,
    ``n_elig`` — and the same overflow mask a site, ``push_back`` among
    them; hosts with 0, 1, 2 and many free slots, masks drawn per site;
    four lanes under ``vmap`` that need different trip counts (one stages
    nothing), the loop's predicate reduced over them."""
    hosts = 9
    rng = np.random.default_rng(1000 * lanes + 10 * cap + n_sites)
    bufs, sites = [], []
    for lane in range(lanes):
        buf, free = _holed_buffer(rng, hosts, cap)
        assert sorted(free)[:3] == [0, 1, 2] and free.max() > 2
        bufs.append(buf)
        sites.append(_sites(rng, hosts, n_sites,
                            share=0.8 if lanes == 1 else lane / 3))

    def direct(buf, ss):
        return _push_all(buf, ss)

    def staged(buf, ss, any_lane=lambda hit: hit):
        buf, overs = _push_all(stage_open(buf, n_sites, free_slots(buf)), ss)
        buf, trips, n_max = push_commit(buf, any_lane)
        assert buf.stage is None
        return buf, overs, trips, n_max

    if lanes == 1:
        want = jax.jit(direct)(bufs[0], sites[0])
        got = jax.jit(staged)(bufs[0], sites[0])
        trips, n_max = [int(got[2])], [int(got[3])]
    else:
        stack = lambda xs: jax.tree.map(lambda *x: jnp.stack(x), *xs)
        want = jax.jit(jax.vmap(direct))(stack(bufs), stack(sites))
        reduce = lambda hit: jax.lax.pmax(hit.astype(jnp.int32), "lane") > 0
        got = jax.jit(jax.vmap(lambda b, s: staged(b, s, reduce),
                               axis_name="lane"))(stack(bufs), stack(sites))
        trips, n_max = got[2].tolist(), got[3].tolist()
        assert n_max[0] == 0 and trips[0] == 0      # a lane that staged nothing
        assert len(set(trips)) > 1
    for a, b in zip(jax.tree.leaves(want[:2]), jax.tree.leaves(got[:2]),
                    strict=True):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert bool(np.asarray(want[1]).any())          # some site overflowed
    assert trips == [-(-n // PUSH_RB) for n in n_max]
    if n_sites > PUSH_RB and cap > 8:
        assert max(n_max) > PUSH_RB                 # more than one trip ran


def test_a_site_past_the_rows_or_inside_a_loop_fails_the_trace():
    """The stage holds a row a site: one push more than the rows it was
    opened with is refused when traced, and so is a push from inside a
    loop's body (a site there would run more than once a round)."""
    buf, _ = _holed_buffer(np.random.default_rng(5), 9, 8)
    (site,) = _sites(np.random.default_rng(6), 9, 1, 0.5)
    two = stage_open(buf, 2, free_slots(buf))
    two, _ = _push_all(two, [site, site])
    with pytest.raises(PushRowsError, match="more than the 2 push sites"):
        _push_all(two, [site])

    def body(_, b):
        return _push_all(b, [site])[0]

    with pytest.raises(PushRowsError, match="inside a loop"):
        jax.lax.fori_loop(0, 2, body, stage_open(buf, 2, free_slots(buf)))
    # The same loop around a direct push is nobody's business.
    jax.lax.fori_loop(0, 2, body, buf)
