"""Round-path primitive ablation — where do the ms/round actually go?

    python -m shadow1_tpu.tools.roundprobe [probe ...] [--iters N]
        [--hosts H] [--cap C]

Round-5 context: the round-4 host-minor rewrite was justified by per-OP
microbenchmarks (min-reduce 7× faster, payload HBM 12.8× smaller —
docs/PERF.md) but the COMPOSITE engine round measured several times slower
on-chip (phold ms/round 1.4 → 8.4; rung3/rung5 throughput down 2.6-4×).
This tool times the actual engine primitives in isolation, warm, as jitted
``fori_loop`` bodies carrying the buffer through the loop — the same data
dependence the real round loop has — so the per-iteration cost attributes
ms/round to a specific primitive instead of a shape microbenchmark.

Probes (each prints us/iter):

* ``pop``      — ``pop_until`` alone (the two min-reductions + one-hot
                 extraction of kind, tb and the [NP,C,H] payload)
* ``pop_nop``  — ``pop_until`` variant WITHOUT payload extraction (splits
                 the extract_col cost out of ``pop``)
* ``pop_gat``  — ``pop_until`` variant extracting kind/tb/payload by
                 index-gather (first_true_idx + get_col) instead of the
                 masked-sum ``extract_col`` — the round-3 extraction style
                 on the round-4 layout (A/B for the regression hunt)
* ``push``     — ``push_local`` alone (first-free search + 4 wheres)
* ``cycle``    — push then pop (the minimal self-sustaining round kernel)
* ``wcycle``   — the same cycle under ``lax.while_loop`` with the engine's
                 ``any_eligible`` cond (isolates loop-structure cost:
                 wcycle − cycle ≈ what the while/cond machinery adds)
* ``rng``      — the phold handler's two hash draws + exponential + randint
                 (the non-event-buffer half of a phold round)
* ``obox``     — ``outbox_append`` alone (5 ``set_col`` one-hot writes)
* ``phold_win``— the full phold ``window_step`` (fori over windows), the
                 composite these primitives should sum to
* ``deliver``  — ``deliver_batch`` of H packets (the per-window merge)

One JSON line per probe. Compare ``pop + push`` against ``phold_win``'s
per-round cost: a large residual means the cost is in the round loop
structure (cond gating, metrics plumbing, while_loop carry), not the event
primitives.
"""

from __future__ import annotations

import argparse
import json
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("probes", nargs="*",
                    default=["pop", "pop_nop", "pop_gat", "push", "cycle",
                             "wcycle", "rng", "obox", "phold_win", "deliver"])
    # 5000, not 50: each probe times ONE XLA execution, whose fixed
    # dispatch latency is spread over the iterations — too few and the
    # measurement is mostly that latency (docs/PERF.md round-5 correction).
    # Subtract runs at two counts to net it out.
    # At iters > cap the pop-family probes drain the seeded buffer and push
    # probes saturate it — harmless for TIMING on this engine (every
    # primitive is a fixed set of data-independent tensor passes; an empty
    # pop or overflowed push runs the same ops as a live one), but the
    # nominal workload mix no longer matches the probe name; pass
    # --cap >= --iters when that distinction matters.
    ap.add_argument("--iters", type=int, default=5000)
    ap.add_argument("--hosts", type=int, default=1000)
    ap.add_argument("--cap", type=int, default=256)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="run even on the CPU backend (smoke/compile check "
                         "only — CPU timings do not attribute TPU cost)")
    ap.add_argument("--pallas", action="store_true",
                    help="phold_win probe: run the engine with "
                         "pop_impl=push_impl='pallas' (core/popk.py); the "
                         "primitive-level fused probes are pop_f/push_f/"
                         "cycle_f/obox_f")
    ap.add_argument("--metrics-ring", type=int, default=0,
                    help="phold_win probe: run with a W-deep telemetry "
                         "ring (the ring-write cost per window)")
    ap.add_argument("--state-digest", action="store_true",
                    help="phold_win probe: run with the determinism flight "
                         "recorder on (implies a ring; the acceptance "
                         "budget is ≤5%% ms/round vs the plain ring — "
                         "docs/PERF.md)")
    args = ap.parse_args()

    import shadow1_tpu  # noqa: F401
    import jax
    import jax.numpy as jnp
    import numpy as np

    from shadow1_tpu.consts import MS, NP
    from shadow1_tpu.core import events as ev

    H, C, iters = args.hosts, args.cap, args.iters
    print(json.dumps({"backend": jax.default_backend(), "hosts": H,
                      "cap": C, "iters": iters}), flush=True)
    if jax.default_backend() == "cpu" and not args.allow_cpu:
        print(json.dumps({"error": "cpu backend — not the platform under "
                                   "test"}))
        return 1

    rng = np.random.default_rng(7)

    def seeded_buf(fill: int) -> ev.EventBuf:
        """A buffer with ``fill`` live events per host at random times.

        Times stay under the i32 rebase horizon (epoch 0) so the seeded
        keys are exact under the round-5 i32 round path (core/events.py)."""
        buf = ev.evbuf_init(H, C)
        t = jnp.asarray(rng.integers(0, 1 << 30, (C, H)), jnp.int64)
        tb = jnp.asarray(rng.integers(0, 1 << 40, (C, H)), jnp.int64)
        thi, tlo = ev.tb_split(t)
        hi, lo = ev.tb_split(tb)
        live = jnp.asarray(np.arange(C)[:, None] < fill, bool)
        buf = buf._replace(
            time_hi=jnp.where(live, thi, buf.time_hi),
            time_lo=jnp.where(live, tlo, buf.time_lo),
            tb_hi=jnp.where(live, hi, buf.tb_hi),
            tb_lo=jnp.where(live, lo, buf.tb_lo),
            kind=jnp.where(live, 1, buf.kind),
        )
        return ev.rebase(buf, 0)

    def timeit(name, make_step, carry0):
        """us/iter of ``carry = step(carry)`` over ``iters`` fori rounds."""
        def loop(carry, n):
            return jax.lax.fori_loop(0, n, lambda _, c: make_step(c), carry)

        f = jax.jit(loop, static_argnums=1)
        # Warm with the SAME static iter count: jit caches per static arg,
        # so warming with n=1 would leave the timed call paying a fresh
        # compile of the n=iters program (seconds — it would swamp the
        # microseconds under measurement).
        jax.block_until_ready(f(carry0, iters))
        t0 = time.perf_counter()
        jax.block_until_ready(f(carry0, iters))
        wall = time.perf_counter() - t0
        print(json.dumps({"probe": name,
                          "us_per_iter": round(1e6 * wall / iters, 1)}),
              flush=True)

    until = jnp.int64(1 << 30)                      # everything eligible
    until_i32 = jnp.int32(1 << 30)

    for probe in args.probes:
        if probe == "pop":
            def step(buf):
                buf, p = ev.pop_until(buf, until)
                # keep the pop results live without re-inserting (the buffer
                # drains over iters; seeded C slots >> iters keeps it warm)
                return buf._replace(self_ctr=buf.self_ctr + p.time)

            timeit("pop", step, seeded_buf(C))
        elif probe == "pop_nop":
            def step(buf):
                # pop_until minus the payload/kind extraction: the i32
                # lexicographic min chain and the buffer clear only.
                elig = (buf.kind != 0) & (buf.t32 < until_i32)
                t_masked = jnp.where(elig, buf.t32, ev.I32_FREE)
                min_t = t_masked.min(axis=0)
                tie = elig & (t_masked == min_t[None, :])
                hi_masked = jnp.where(tie, buf.tb_hi, ev.I32_MAX)
                min_hi = hi_masked.min(axis=0)
                tie2 = tie & (hi_masked == min_hi[None, :])
                lo_masked = jnp.where(tie2, buf.tb_lo, ev.I32_MAX)
                min_lo = lo_masked.min(axis=0)
                sel = tie2 & (lo_masked == min_lo[None, :])
                return buf._replace(
                    kind=jnp.where(sel, 0, buf.kind),
                    t32=jnp.where(sel, ev.I32_FREE, buf.t32),
                    self_ctr=buf.self_ctr + min_t.astype(jnp.int64),
                )

            timeit("pop_nop", step, seeded_buf(C))
        elif probe == "pop_gat":
            def step(buf):
                buf, p = ev.pop_until(buf, until, extract="gather")
                return buf._replace(self_ctr=buf.self_ctr + p.time)

            timeit("pop_gat", step, seeded_buf(C))
        elif probe == "pop_f":
            from shadow1_tpu.core.popk import pop_until_fused

            def step(buf):
                buf, p = pop_until_fused(buf, until)
                return buf._replace(self_ctr=buf.self_ctr + p.time)

            timeit("pop_f", step, seeded_buf(C))
        elif probe == "push_f":
            from shadow1_tpu.core.popk import push_local_fused

            k = jnp.ones(H, jnp.int32)
            pay = jnp.zeros((NP, H), jnp.int32)
            m = jnp.ones(H, bool)

            def step(buf):
                buf2, _over = push_local_fused(
                    buf, m, buf.self_ctr + 1, k, pay
                )
                return buf2._replace(kind=buf.kind)

            timeit("push_f", step, seeded_buf(C // 2))
        elif probe == "cycle_f":
            from shadow1_tpu.core.popk import pop_until_fused, push_local_fused

            k = jnp.ones(H, jnp.int32)
            pay = jnp.zeros((NP, H), jnp.int32)
            m = jnp.ones(H, bool)

            def step(buf):
                buf, p = pop_until_fused(buf, until)
                buf, _over = push_local_fused(buf, p.mask & m, p.time + 7, k,
                                              pay)
                return buf

            timeit("cycle_f", step, seeded_buf(C // 2))
        elif probe == "obox_f":
            from shadow1_tpu.core import outbox as ob
            from shadow1_tpu.core.popk import outbox_append_fused

            dst = jnp.ones(H, jnp.int32)
            k = jnp.ones(H, jnp.int32)
            pay = jnp.zeros((NP, H), jnp.int32)
            m = jnp.ones(H, bool)

            def step(box):
                box2, _ok = outbox_append_fused(
                    box, m, dst, k, box.pkt_ctr + 7, pay
                )
                return box2._replace(cnt=box.cnt)

            timeit("obox_f", step, ob.outbox_init(H, 64))
        elif probe == "wcycle":
            k = jnp.ones(H, jnp.int32)
            pay = jnp.zeros((NP, H), jnp.int32)
            m = jnp.ones(H, bool)

            def wloop(buf, n):
                def cond(carry):
                    b, r = carry
                    return (r < n) & ev.any_eligible(b, until)

                def body(carry):
                    b, r = carry
                    b, p = ev.pop_until(b, until)
                    b, _over = ev.push_local(b, p.mask & m, p.time + 7, k,
                                             pay)
                    return b, r + 1

                buf, _ = jax.lax.while_loop(
                    cond, body, (buf, jnp.zeros((), jnp.int32))
                )
                return buf

            f = jax.jit(wloop, static_argnums=1)
            carry0 = seeded_buf(C // 2)
            jax.block_until_ready(f(carry0, iters))
            t0 = time.perf_counter()
            jax.block_until_ready(f(carry0, iters))
            wall = time.perf_counter() - t0
            print(json.dumps({"probe": "wcycle",
                              "us_per_iter": round(1e6 * wall / iters, 1)}),
                  flush=True)
        elif probe == "rng":
            from shadow1_tpu import rng as prng
            from shadow1_tpu.consts import R_PHOLD_DELAY, R_PHOLD_DST

            key = prng.base_key(7)
            hosts = jnp.arange(H, dtype=jnp.int32)

            def step(ctr):
                delay = prng.exponential_ns(
                    prng.bits_v(key, R_PHOLD_DELAY, hosts, ctr), 1e6
                )
                dst = prng.randint(
                    prng.bits_v(key, R_PHOLD_DST, hosts, ctr), H
                )
                return ctr + 1 + (delay % 2) + dst.astype(jnp.int64)

            timeit("rng", step, jnp.zeros(H, jnp.int64))
        elif probe == "obox":
            from shadow1_tpu.core import outbox as ob

            dst = jnp.ones(H, jnp.int32)
            k = jnp.ones(H, jnp.int32)
            pay = jnp.zeros((NP, H), jnp.int32)
            m = jnp.ones(H, bool)

            def step(box):
                box2, _ok = ob.outbox_append(
                    box, m, dst, k, box.pkt_ctr + 7, pay
                )
                # hold occupancy so the append never saturates over iters
                return box2._replace(cnt=box.cnt)

            timeit("obox", step, ob.outbox_init(H, 64))
        elif probe == "push":
            k = jnp.ones(H, jnp.int32)
            pay = jnp.zeros((NP, H), jnp.int32)
            m = jnp.ones(H, bool)

            def step(buf):
                buf2, _over = ev.push_local(
                    buf, m, buf.self_ctr + 1, k, pay
                )
                # keep occupancy constant: restore kind so the buffer never
                # fills (cost of the where is part of the probe's point)
                return buf2._replace(kind=buf.kind)

            timeit("push", step, seeded_buf(C // 2))
        elif probe == "cycle":
            k = jnp.ones(H, jnp.int32)
            pay = jnp.zeros((NP, H), jnp.int32)
            m = jnp.ones(H, bool)

            def step(buf):
                buf, p = ev.pop_until(buf, until)
                buf, _over = ev.push_local(buf, p.mask & m, p.time + 7, k,
                                           pay)
                return buf

            timeit("cycle", step, seeded_buf(C // 2))
        elif probe == "phold_win":
            from shadow1_tpu.config.compiled import single_vertex_experiment
            from shadow1_tpu.consts import EngineParams
            from shadow1_tpu.core.engine import Engine

            exp = single_vertex_experiment(
                n_hosts=H, seed=77, end_time=10**15, latency_ns=30 * MS,
                model="phold",
                model_cfg={"mean_delay_ns": float(60 * MS),
                           "init_events": 4},
            )
            impl = "pallas" if args.pallas else "xla"
            ring = args.metrics_ring or (256 if args.state_digest else 0)
            eng = Engine(exp, EngineParams(ev_cap=C, pop_impl=impl,
                                           push_impl=impl,
                                           metrics_ring=ring,
                                           state_digest=int(args.state_digest)))
            st0 = eng.run(eng.init_state(), n_windows=10)  # warm state
            jax.block_until_ready(st0)
            m0 = Engine.metrics_dict(st0)
            t0 = time.perf_counter()
            st1 = eng.run(st0, n_windows=iters)
            jax.block_until_ready(st1)
            wall = time.perf_counter() - t0
            m1 = Engine.metrics_dict(st1)
            rounds = m1["rounds"] - m0["rounds"]
            print(json.dumps({
                "probe": "phold_win",
                "us_per_window": round(1e6 * wall / iters, 1),
                "rounds_per_window": round(rounds / iters, 2),
                "us_per_round": round(1e6 * wall / max(rounds, 1), 1),
            }), flush=True)
        elif probe == "deliver":
            dst = jnp.asarray(rng.integers(0, H, H), jnp.int32)
            t = jnp.asarray(rng.integers(0, 1 << 40, H), jnp.int64)
            tb = jnp.asarray(rng.integers(0, 1 << 40, H), jnp.int64)
            k = jnp.ones(H, jnp.int32)
            pay = jnp.zeros((NP, H), jnp.int32)
            m = jnp.ones(H, bool)

            def step(buf):
                buf2, _over, _ranks = ev.deliver_batch(buf, dst, t, tb, k, pay, m)
                # hold occupancy: keep the timing honest across iters
                return buf2._replace(kind=buf.kind, time_hi=buf.time_hi,
                                     time_lo=buf.time_lo, t32=buf.t32)

            timeit("deliver", step, seeded_buf(C // 2))
        else:
            print(json.dumps({"error": f"unknown probe {probe!r}"}))
            return 2
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
