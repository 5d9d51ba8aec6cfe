"""shadow1_tpu — a TPU-native discrete-event network simulation framework.

A ground-up rebuild of the capabilities of Shadow v1.x (reference:
``joskid/shadow-1``, surveyed in /root/repo/SURVEY.md): deterministic
simulation of large host networks (Tor, Bitcoin, tgen-style traffic) over
weighted latency/loss/bandwidth topologies with a full virtual TCP stack —
re-expressed as batched tensor computation on TPU.

Architecture (see SURVEY.md §7):

* Per-host event priority queues (reference: ``src/main/core/scheduler/``)
  collapse into fixed-capacity per-host event tensors advanced in
  conservative time windows (lookahead = minimum topology latency),
  mirroring the reference's barrier-round scheduler
  (``src/main/core/master.c`` runahead + ``scheduler.c`` rounds).
* Packet routing/propagation (reference: ``src/main/routing/topology.c``)
  becomes gather over a dense vertex-level latency matrix in HBM plus a
  sorted scatter into destination event buffers once per window.
* The virtual TCP stack (reference: ``src/main/host/descriptor/tcp.c``)
  is vectorized across every socket of every host.
* Multi-chip scaling shards the host axis over an ICI mesh; the one
  cross-shard exchange per window is the batched packet all_to_all.

Two engines implement identical semantics behind one experiment format:
``shadow1_tpu.cpu_engine`` (readable heapq reference — the oracle) and
``shadow1_tpu.core.engine`` (the batched TPU engine). Determinism is a hard
invariant: same seed ⇒ identical event streams on both engines and across
shardings.
"""

import os

import jax

# Simulation time is int64 nanoseconds (the reference's SimulationTime is
# ns-resolution). Enable 64-bit support; every float array in the package is
# explicitly dtyped (f32) so this does not silently promote compute to f64.
jax.config.update("jax_enable_x64", True)

# Persistent compilation cache: engine round bodies are large programs and
# compile is the dominant cost of most runs. Where JAX_COMPILATION_CACHE_DIR
# is set, jax reads it and nothing is set here. Otherwise the cache lives at
# one fixed path inside the checkout — the path is part of the cache key, so
# a directory that moves never hits.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)

__version__ = "0.1.0"
