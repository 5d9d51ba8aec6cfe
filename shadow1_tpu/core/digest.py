"""Determinism flight recorder — per-window order-independent state digests.

The determinism contract (docs/SEMANTICS.md) says oracle, single-chip,
sharded, and resumed runs are bit-identical — but the parity tests only
observe it at end of run, as whole-run counter equality. This module makes
the contract *continuously* observable: one integer digest word per engine
subsystem per conservative window, computed INSIDE the jitted window loop
(window granularity, never the round path) and recorded as telemetry-ring
columns. Any two runs of the same config — tpu↔cpu, sharded↔single,
resume↔straight-through — must carry identical digest streams;
the first differing (window, subsystem) pinpoints a violation that an
end-of-run assert could only report as "some key mismatched after millions
of windows" (``tools/paritytrace.py`` automates the bisection).

Digest construction (the properties everything below hangs on):

* each semantic element (an occupied event slot, a buffered packet, a live
  socket, a host's NIC/counter row) hashes to one u32 word via a
  splitmix64-style polynomial fold of its *semantic* fields — keyed by
  global host id and value keys like ``(time, tb)``, NEVER by slot index
  or memory layout, so cap migrations (tune/resize.py) and slot
  permutation cannot change it;
* a subsystem's window digest is the plain i64 SUM of its element words —
  order-independent and associative, so the sharded engine psums per-shard
  partial sums into the exact single-device value, and the eager CPU
  oracle can maintain the same sum incrementally (add on push, subtract
  on pop) instead of rescanning its heap;
* i32-semantics fields are masked to their low 32 bits before folding, so
  the TPU's i32 planes (natural wraparound) and the oracle's u32 Python
  ints hash identically.

Three bit-identical implementations live here, mirroring rng.py's twins:
jnp (traced, for the batched engines), numpy-vector (the oracle's [H]
planes), and plain-Python-int (the oracle's per-event / per-socket paths).

What is mixed per subsystem — and what is deliberately excluded — is
documented in docs/SEMANTICS.md §"State digest"; keep the two in sync.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from shadow1_tpu.consts import NP, TCP_FREE
from shadow1_tpu.rng import _mix, _mix_np

# The five digested subsystems, in canonical (ring-column) order.
SUBSYSTEMS = ("evbuf", "outbox", "tcp", "nic", "rng")
DIGEST_FIELDS = tuple(f"dg_{s}" for s in SUBSYSTEMS)

_M64 = (1 << 64) - 1
_M32 = 0xFFFFFFFF
# Odd fold multiplier (xorshift128+/splitmix family constant). The fold is
# a polynomial hash z = z*K + v; the double splitmix finalizer on top makes
# the output word avalanche.
_K = 0x2545F4914F6CDD1D
_K_NP = np.uint64(_K)

# Distinct per-subsystem seed constants so an element can never alias an
# element of another subsystem (or the mq sub-stream of the tcp plane).
SEED_EVBUF = 0xA0761D6478BD642F
SEED_OUTBOX = 0xE7037ED1A0B428DB
SEED_TCP = 0x8EBC6AF09C88C6E3
SEED_MQ = 0x589965CC75374CC3
SEED_NIC = 0x1D8E4E27C47D124F
SEED_RNG = 0xEB44ACCAB455D165

# TCP plane field order is THE canonical order both engines fold in — it is
# imported from the tcp module so the schema cannot drift from the state.
from shadow1_tpu.tcp.tcp import _FIELDS_BOOL as TCP_FIELDS_BOOL  # noqa: E402
from shadow1_tpu.tcp.tcp import _FIELDS_I32 as TCP_FIELDS_I32  # noqa: E402
from shadow1_tpu.tcp.tcp import _FIELDS_I64 as TCP_FIELDS_I64  # noqa: E402


# ---------------------------------------------------------------------------
# jnp implementation (traced; used by core/engine.window_step)
# ---------------------------------------------------------------------------

def _u(v):
    """Field → u64 fold input. i32/bool widen via u32 (masking to the low 32
    bits — the i32-semantics rule); i64 reinterprets mod 2^64."""
    v = jnp.asarray(v)
    if v.dtype == jnp.int32:
        return v.astype(jnp.uint32).astype(jnp.uint64)
    if v.dtype == jnp.bool_:
        return v.astype(jnp.uint64)
    return v.astype(jnp.uint64)


def _fold(z, v):
    return z * _K_NP + _u(v)


def _words(seed: int, fields) -> jnp.ndarray:
    """Element hash words: fold ``fields`` (broadcastable arrays) in order
    onto the subsystem seed, finalize, return u32 words."""
    z = jnp.asarray(np.uint64(seed))
    for v in fields:
        z = _fold(z, v)
    return (_mix(_mix(z)) >> np.uint64(32)).astype(jnp.uint32)


def _masked_sum(words, mask) -> jnp.ndarray:
    """i64 sum of the selected u32 words (exact: < 2^32 per element)."""
    return jnp.where(mask, words.astype(jnp.int64), 0).sum()


def digest_evbuf(buf, hosts) -> jnp.ndarray:
    """Occupied event slots keyed by (host, time, tb, kind, payload)."""
    mask = buf.kind != 0  # K_NONE
    from shadow1_tpu.core.events import tb_join

    fields = [
        jnp.broadcast_to(hosts[None, :], buf.kind.shape),
        buf.abs_time(),
        tb_join(buf.tb_hi, buf.tb_lo),
        buf.kind,
    ] + [buf.p[i] for i in range(NP)]
    return _masked_sum(_words(SEED_EVBUF, fields), mask)


def digest_outbox(ob, hosts) -> jnp.ndarray:
    """This window's buffered sends keyed by (src, dst, depart, ctr, kind,
    payload) — computed BEFORE outbox_clear (window_step does this)."""
    cap, h = ob.dst.shape
    mask = jnp.arange(cap)[:, None] < ob.cnt[None, :]
    fields = [
        jnp.broadcast_to(hosts[None, :], (cap, h)),
        ob.dst,
        ob.abs_depart(),
        ob.ctr,
        ob.kind,
    ] + [ob.p[i] for i in range(NP)]
    return _masked_sum(_words(SEED_OUTBOX, fields), mask)


def digest_tcp(tcp: dict, hosts) -> jnp.ndarray:
    """Live sockets (st != TCP_FREE): every semantic field in canonical
    order, plus the socket's pending message boundaries (summed
    positionlessly — retirement order is ack-driven on both engines)."""
    from shadow1_tpu.core.events import tb_join

    s, h = tcp["st"].shape
    live = tcp["st"] != TCP_FREE
    socks = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[:, None], (s, h))
    fields = [jnp.broadcast_to(hosts[None, :], (s, h)), socks]
    fields += [tcp[f] for f in TCP_FIELDS_I32]
    fields += [tb_join(tcp[f + "_hi"], tcp[f + "_lo"]) for f in TCP_FIELDS_I64]
    fields += [tcp[f] for f in TCP_FIELDS_BOOL]
    total = _masked_sum(_words(SEED_TCP, fields), live)
    # The host pool's slots [P, H], each keyed by the socket it names; a
    # slot counts where that socket is live (a one-hot read of ``live`` at
    # mq_sock, no gather: a free slot's −1 matches no socket).
    mq_sock = tcp["mq_sock"]
    mq_mask = ((mq_sock[:, None, :] == socks[None, :, :])
               & live[None, :, :]).any(axis=1)
    mq_fields = [
        jnp.broadcast_to(hosts[None, :], mq_sock.shape),
        mq_sock,
        tcp["mq_end"],
        tcp["mq_meta"],
    ]
    return total + _masked_sum(_words(SEED_MQ, mq_fields), mq_mask)


def digest_nic(nic, hosts) -> jnp.ndarray:
    """Per-host NIC clocks/counters (tx/rx free-at, byte counters, AQM coin
    counter)."""
    fields = [hosts, nic.tx_free, nic.rx_free, nic.tx_bytes, nic.rx_bytes,
              nic.aqm_ctr]
    return _masked_sum(_words(SEED_NIC, fields),
                       jnp.ones(hosts.shape, bool))


def digest_rng(hosts, vectors) -> jnp.ndarray:
    """Per-host deterministic counters: evbuf self_ctr, outbox pkt_ctr, the
    virtual-CPU busy clocks, plus model-level draw counters (``vectors`` is
    the canonical per-model list — see model_host_vectors)."""
    fields = [hosts] + list(vectors)
    return _masked_sum(_words(SEED_RNG, fields),
                       jnp.ones(hosts.shape, bool))


def model_host_vectors(model) -> list:
    """The model-level [H] counter vectors folded into the rng digest, in a
    canonical per-model order. PHOLD contributes (hops, ctr); the net model
    contributes nothing here (its NIC/TCP planes carry their own words; app
    state is deliberately outside the digest contract — docs/SEMANTICS.md).
    Keep ``model_vector_names`` below in lockstep: it labels these vectors
    in paritytrace's plane-diff dumps."""
    f = getattr(model, "_fields", ())
    if "hops" in f and "ctr" in f:
        return [model.hops, model.ctr]
    return []


def model_vector_names(model) -> list[str]:
    """Labels for model_host_vectors' vectors, same order, same dispatch."""
    f = getattr(model, "_fields", ())
    if "hops" in f and "ctr" in f:
        return ["hops", "ctr"]
    return []


def state_digests(st, ctx, dg_outbox) -> jnp.ndarray:
    """The per-window digest vector (i64 [len(SUBSYSTEMS)], SUBSYSTEMS
    order). ``dg_outbox`` is computed by the caller BEFORE the window-end
    delivery clears the outbox; everything else digests the post-delivery
    window-boundary state."""
    hosts = ctx.hosts
    dg_ev = digest_evbuf(st.evbuf, hosts)
    model = st.model
    mf = getattr(model, "_fields", ())
    if "nic" in mf and "tcp" in mf:
        dg_tcp = digest_tcp(model.tcp, hosts)
        dg_nic = digest_nic(model.nic, hosts)
    else:
        dg_tcp = jnp.zeros((), jnp.int64)
        dg_nic = jnp.zeros((), jnp.int64)
    vectors = [st.evbuf.self_ctr, st.outbox.pkt_ctr, st.cpu_busy]
    vectors += model_host_vectors(model)
    dg_rng = digest_rng(hosts, vectors)
    return jnp.stack([dg_ev, dg_outbox, dg_tcp, dg_nic, dg_rng])


# ---------------------------------------------------------------------------
# Plain-Python-int twins (the oracle's per-event / per-socket paths)
# ---------------------------------------------------------------------------

def _mix_int(z: int) -> int:
    z ^= z >> 30
    z = (z * 0xBF58476D1CE4E5B9) & _M64
    z ^= z >> 27
    z = (z * 0x94D049BB133111EB) & _M64
    z ^= z >> 31
    return z


def word_int(seed: int, fields) -> int:
    """Python-int twin of _words for one element. i32-semantics fields must
    be pre-masked with & 0xFFFFFFFF by the caller; i64 fields may be any
    Python int (folded mod 2^64, matching the u64 reinterpret)."""
    z = seed
    for v in fields:
        z = (z * _K + (int(v) & _M64)) & _M64
    return _mix_int(_mix_int(z)) >> 32


def event_word(host: int, time: int, tb: int, kind: int, p: tuple) -> int:
    """Oracle event hash — identical to digest_evbuf's element word. ``p``
    is the (possibly short) payload tuple; missing columns are zero."""
    fields = [host, time, tb, kind]
    fields += [int(p[i]) & _M32 if i < len(p) else 0 for i in range(NP)]
    return word_int(SEED_EVBUF, fields)


def packet_word(src: int, dst: int, depart: int, ctr: int, kind: int,
                p: tuple) -> int:
    """Oracle outbox-send hash — identical to digest_outbox's element word
    (``ctr`` is the per-src lifetime packet counter; only its low 32 bits
    ride the outbox plane)."""
    fields = [src, dst, depart, ctr & _M32, kind]
    fields += [int(p[i]) & _M32 if i < len(p) else 0 for i in range(NP)]
    return word_int(SEED_OUTBOX, fields)


def sock_word(host: int, sock: int, k) -> int:
    """Oracle live-socket hash — identical to digest_tcp's element word.
    ``k`` is a CpuSock; field order is the canonical tcp-plane order."""
    fields = [host, sock]
    fields += [getattr(k, f) & _M32 for f in TCP_FIELDS_I32]
    fields += [getattr(k, f) for f in TCP_FIELDS_I64]
    fields += [1 if getattr(k, f) else 0 for f in TCP_FIELDS_BOOL]
    total = word_int(SEED_TCP, fields)
    for end, meta in k.mq:
        total += word_int(SEED_MQ, [host, sock, end & _M32, meta & _M32])
    return total


# ---------------------------------------------------------------------------
# numpy-vector twins (the oracle's [H] planes — one call per boundary)
# ---------------------------------------------------------------------------

def _words_np(seed: int, fields) -> np.ndarray:
    with np.errstate(over="ignore"):
        z = np.uint64(seed)
        for v in fields:
            v = np.asarray(v)
            if v.dtype == np.int32 or v.dtype == np.bool_:
                v = v.astype(np.uint32)
            z = z * _K_NP + v.astype(np.uint64)
        return (_mix_np(_mix_np(z)) >> np.uint64(32)).astype(np.uint32)


def digest_nic_np(tx_free, rx_free, tx_bytes, rx_bytes, aqm_ctr) -> int:
    h = np.arange(len(tx_free), dtype=np.int64)
    w = _words_np(SEED_NIC, [h, tx_free, rx_free, tx_bytes, rx_bytes,
                             aqm_ctr])
    return int(w.astype(np.int64).sum())


def digest_rng_np(vectors) -> int:
    h = np.arange(len(vectors[0]), dtype=np.int64)
    w = _words_np(SEED_RNG, [h] + list(vectors))
    return int(w.astype(np.int64).sum())
