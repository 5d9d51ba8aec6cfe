"""Device-fault bisection probes for the TPU.

    python -m shadow1_tpu.tools.faultprobe [probe ...]

For a run that faults or hangs the device ("TPU worker process crashed"):
every probe here prints the backend it actually ran on, and the tool exits
nonzero if the default backend is not TPU (a CPU "ok" tells you nothing
about the fault).

Probes isolate the round-4 layout's structurally-new device code paths:

* ``sort0``   — the arrival-batching 2-key lax.sort along axis 0
* ``scan0``   — the max-plus associative_scan along axis 0
* ``pop``     — pop_until/push_local cycle on a [C, H] event buffer
* ``phold``   — 60 engine windows at [1000, 256] (times ms/round)
* ``tor N``   — N windows of the rung-3 Tor config (the known fault
                reproducer; default 50)

Run probes in order; the first to kill the worker identifies the
culprit. Each run is one process — after a fault, re-run from a fresh
process (the wedged runtime poisons subsequent calls).
"""

from __future__ import annotations

import json
import sys
import time


def main() -> int:
    import shadow1_tpu  # noqa: F401
    import jax
    import jax.numpy as jnp
    import numpy as np

    backend = jax.default_backend()
    print(json.dumps({"backend": backend}), flush=True)
    if backend != "tpu":
        print(json.dumps({"error": "default backend is not tpu; probes "
                                   "would not exercise the device"}))
        return 1

    args = sys.argv[1:] or ["sort0", "scan0", "pop", "phold"]
    # Parse positionally: "tor" may be followed by a numeric window count
    # anywhere in the list; mixed probe lists are fine.
    todo: list[tuple[str, int]] = []
    i = 0
    while i < len(args):
        name, n = args[i], 0
        if name == "tor" and i + 1 < len(args) and args[i + 1].isdigit():
            n = int(args[i + 1])
            i += 1
        todo.append((name, n))
        i += 1
    H, C = 1000, 256

    for probe, probe_n in todo:
        t0 = time.perf_counter()
        if probe == "sort0":
            t = jnp.asarray(np.random.randint(0, 1 << 40, (C, H)), jnp.int64)
            tb = jnp.asarray(np.random.randint(0, 1 << 40, (C, H)), jnp.int64)
            idx = jnp.broadcast_to(
                jnp.arange(C, dtype=jnp.int32)[:, None], (C, H)
            )
            f = jax.jit(lambda a, b, c: jax.lax.sort(
                (a, b, c), dimension=0, num_keys=2))
            jax.block_until_ready(f(t, tb, idx))
        elif probe == "scan0":
            a = jnp.asarray(np.random.randint(0, 1 << 30, (C, H)), jnp.int64)
            f = jax.jit(lambda x: jax.lax.associative_scan(
                lambda p, q: (p[0] + q[0], jnp.maximum(p[1] + q[0], q[1])),
                (x, x), axis=0))
            jax.block_until_ready(f(a))
        elif probe == "pop":
            from shadow1_tpu.core.events import evbuf_init, pop_until, push_local

            buf = evbuf_init(H, C)
            k = jnp.full(H, 1, jnp.int32)
            p = jnp.zeros((10, H), jnp.int32)
            m = jnp.ones(H, bool)

            def cyc(b):
                b, _ = push_local(b, m, jnp.zeros(H, jnp.int64), k, p)
                b, _ev = pop_until(b, jnp.int64(10))
                return b

            jax.block_until_ready(jax.jit(cyc)(buf))
        elif probe == "phold":
            from shadow1_tpu.config.compiled import single_vertex_experiment
            from shadow1_tpu.consts import MS, EngineParams
            from shadow1_tpu.core.engine import Engine

            exp = single_vertex_experiment(
                n_hosts=H, seed=77, end_time=10**12, latency_ns=30 * MS,
                model="phold",
                model_cfg={"mean_delay_ns": float(60 * MS), "init_events": 4},
            )
            eng = Engine(exp, EngineParams(ev_cap=C))
            st = eng.run(eng.init_state(), n_windows=20)
            jax.block_until_ready(st)
            m0 = Engine.metrics_dict(st)
            t1 = time.perf_counter()
            st = eng.run(st, n_windows=40)
            jax.block_until_ready(st)
            m1 = Engine.metrics_dict(st)
            r = m1["rounds"] - m0["rounds"]
            print(json.dumps({
                "probe": "phold",
                "ms_per_round": round(
                    1000 * (time.perf_counter() - t1) / max(r, 1), 3),
            }), flush=True)
            continue
        elif probe == "tor":
            from shadow1_tpu.config.experiment import load_experiment
            from shadow1_tpu.core.engine import Engine

            n = probe_n or 50
            exp, params, _ = load_experiment("configs/rung3_tor1k.yaml")
            eng = Engine(exp, params)
            st = eng.run(eng.init_state(), n_windows=n)
            jax.block_until_ready(st)
            print(json.dumps({
                "probe": "tor", "windows": n,
                "events": Engine.metrics_dict(st)["events"],
                "wall_s": round(time.perf_counter() - t0, 2),
            }), flush=True)
            continue
        else:
            print(json.dumps({"error": f"unknown probe {probe!r}"}))
            return 2
        print(json.dumps({
            "probe": probe, "ok": True,
            "wall_s": round(time.perf_counter() - t0, 2),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
