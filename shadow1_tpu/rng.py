"""Counter-based deterministic randomness shared by both engines.

The reference gives every host its own seeded RNG (src/main/host/host.c) so
results are independent of worker scheduling. We go one step further: every
draw is a pure function of ``(seed, purpose, host, counter)`` — order
independent, so the eager CPU oracle and the batched TPU engine produce
bit-identical streams no matter when each computes its draws.

Backend-exactness (round-2 postmortem): the original implementation used
Threefry ``fold_in`` chains plus a float ``log1p`` transform; the float
transcendental evaluates differently on the TPU than on CPU, silently
breaking the determinism invariant on the target hardware (142,577 vs
142,576 events over the same 50-window program). Every transform here is
now **pure integer arithmetic** (or a single IEEE-exact f64 round for the
mean scaling), identical on every XLA backend by construction:

* ``bits`` — a splitmix64-style avalanche hash of the packed
  (seed, purpose, host, ctr) tuple: ~10 u64 ops instead of 3 chained
  Threefry blocks (~8x cheaper on the hot path, and elementwise — no vmap).
* ``exponential_ns`` — fixed-point −ln(1−u) via count-leading-zeros + a
  4096-entry Q32 log2 table with linear interpolation (relative error
  ~1e-7), times an integer-rounded mean.
* ``uniform_lt`` — probability compares as an integer threshold on the raw
  bits, never a float comparison.

The DieHarder-grade quality of the splitmix64 finalizer is far beyond what
a DES needs (the reference uses GLib's Mersenne/rand per host).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from shadow1_tpu.core.dense import extract_col, onehot_col

_U64 = jnp.uint64

# splitmix64 finalizer constants (public domain, Stafford mix13).
_C1 = np.uint64(0xBF58476D1CE4E5B9)
_C2 = np.uint64(0x94D049BB133111EB)
# Odd multipliers decorrelating the (purpose, host, ctr) lanes.
_P1 = np.uint64(0x9E3779B97F4A7C15)  # golden-ratio increment
_P2 = np.uint64(0xC2B2AE3D27D4EB4F)
_P3 = np.uint64(0x165667B19E3779F9)


def base_key(seed: int) -> jax.Array:
    """The per-experiment key: a u64 scalar derived from the seed."""
    return jnp.asarray(base_key_np(seed), _U64)


def _mix(z):
    z = z ^ (z >> np.uint64(30))
    z = z * _C1
    z = z ^ (z >> np.uint64(27))
    z = z * _C2
    z = z ^ (z >> np.uint64(31))
    return z


def bits(seed_key, purpose, host, ctr) -> jax.Array:
    """One u32 of raw randomness for (purpose, host, ctr).

    Elementwise over any broadcastable host/ctr shapes (u64 wraparound
    arithmetic; exact on every backend)."""
    z = (
        jnp.asarray(seed_key, _U64)
        + jnp.asarray(purpose, _U64) * _P1
        + jnp.asarray(host, jnp.int64).astype(_U64) * _P2
        + jnp.asarray(ctr, jnp.int64).astype(_U64) * _P3
    )
    z = _mix(_mix(z))
    return (z >> np.uint64(32)).astype(jnp.uint32)


# Historical alias: the Threefry version needed an explicit vmap; the hash is
# natively vectorized. Signature: (key, purpose, host[H], ctr[H]) -> u32 [H].
bits_v = bits


def uniform01(b: jax.Array) -> jax.Array:
    """u32 bits → float32 in [0, 1). Single exact multiply (display/summary
    use only — probability *decisions* must use uniform_lt)."""
    return b.astype(jnp.float32) * jnp.float32(2.0 ** -32)


def prob_threshold(p) -> np.ndarray:
    """Probability (numpy array/scalar, host-side) → u64 threshold such that
    ``bits < threshold`` occurs with probability p (exact at 2^-32)."""
    return (np.round(np.asarray(p, np.float64) * 2.0 ** 32)).astype(np.uint64)


def uniform_lt(b: jax.Array, threshold) -> jax.Array:
    """Integer Bernoulli: True with probability threshold / 2^32."""
    return b.astype(_U64) < jnp.asarray(threshold, _U64)


# --- fixed-point −ln(1−u) ---------------------------------------------------
# x = 2^32 − b ∈ [1, 2^32] is uniform; −ln(x/2^32) = (32 − log2 x)·ln2.
# log2 x = k + log2(1+f) with k = floor(log2 x): table the fraction in Q32.
_LOG_BITS = 12
# Kept as numpy so importing this module never initializes a JAX backend
# (platform probing must run first; see shadow1_tpu.platform). jnp.asarray
# inside the traced function embeds it as a compile-time constant.
_LOG_TBL_NP = np.round(
    np.log2(1.0 + np.arange(2 ** _LOG_BITS + 1) / 2 ** _LOG_BITS) * 2.0 ** 32
).astype(np.uint64)
_LN2_Q32 = np.uint64(round(np.log(2.0) * 2 ** 32))

# The table as it is read: idx ≤ 2^_LOG_BITS − 1, so only tbl[:-1] is ever
# ``lo`` (all < 2^32; the 33-bit tbl[-1] = 2^32 is only ever ``hi``) and
# hi − lo = diff(tbl)[idx] < 2^21. Two u32 words at ONE index, cut into
# seven byte planes (4 of lo, 3 of hi − lo) and laid out for the two-level
# read of ``_log_tbl_read``: idx = a·_LOG_B + b, rows (plane, b), columns a.
_LOG_B_BITS = 4
_LOG_B = 2 ** _LOG_B_BITS
_LOG_A = 2 ** (_LOG_BITS - _LOG_B_BITS)
_LOG_BYTES_NP = np.stack(
    [(t >> np.uint64(8 * i)) & np.uint64(0xFF)
     for t, n in ((_LOG_TBL_NP[:-1], 4), (np.diff(_LOG_TBL_NP), 3))
     for i in range(n)]
).reshape(7, _LOG_A, _LOG_B).swapaxes(1, 2).reshape(7 * _LOG_B, _LOG_A)


def _log_tbl_read(idx: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``(tbl[idx], tbl[idx + 1] − tbl[idx])`` as u32, without a gather.

    On the v5e XLA walks a gather one element at a time, 7 ns each here:
    1.87 ms of dense PHOLD's 5.99 ms round (PERF.md §6, PR 31). So the read
    is core/dense's one-hot read (a one-hot over the sublane axis, elements
    on lanes), in two levels so that the one-hots are _LOG_A + _LOG_B tall,
    not 4096: level 1 picks row-block ``a`` for every ``b`` at once, a
    matmul of the byte planes with ``onehot(a)``; level 2 is ``extract_col``
    at ``b``. Exact on any backend: each sum has one non-zero term, a byte.
    XLA:TPU makes one fusion of ``onehot(a)``, the matmul and the reduce
    (nothing [rows, N] wide is stored); 256 × 16 is the fastest split at
    N = 65,536 and no slower than the gather at N = 100 (PERF.md §6, PR 31)."""
    shape = idx.shape
    idx = idx.reshape(-1)
    rows = jnp.dot(
        jnp.asarray(_LOG_BYTES_NP, jnp.bfloat16),
        onehot_col(idx >> _LOG_B_BITS, _LOG_A).astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    ).reshape(7, _LOG_B, -1)
    b0, b1, b2, b3, d0, d1, d2 = extract_col(
        onehot_col(idx & (_LOG_B - 1), _LOG_B), rows).astype(jnp.uint32)  # [7, N]
    lo = b0 | (b1 << 8) | (b2 << 16) | (b3 << 24)
    d = d0 | (d1 << 8) | (d2 << 16)
    return lo.reshape(shape), d.reshape(shape)


def _neg_log1m_q32(b: jax.Array) -> jax.Array:
    """u32 bits → Q32 fixed-point −ln(1 − b/2^32), exact integer pipeline."""
    x = (np.uint64(1) << np.uint64(32)) - b.astype(_U64)   # [1, 2^32]
    k = np.uint64(63) - jax.lax.clz(x.astype(jnp.int64)).astype(_U64)
    m = x << (np.uint64(63) - k)                            # top bit at 63
    frac = (m << np.uint64(1)) >> np.uint64(1)              # low 63 = fraction
    idx = (frac >> np.uint64(63 - _LOG_BITS)).astype(jnp.int32)
    rem = (frac >> np.uint64(63 - _LOG_BITS - 24)) & np.uint64((1 << 24) - 1)
    lo, d = _log_tbl_read(idx)                              # d = hi − lo
    log2_frac_q32 = lo.astype(_U64) + ((d.astype(_U64) * rem) >> np.uint64(24))
    log2_x_q32 = (k << np.uint64(32)) + log2_frac_q32
    e2_q32 = (np.uint64(32) << np.uint64(32)) - log2_x_q32  # (32 − log2 x)
    # × ln2 at Q27 (e2 ≤ 2^37, so the product stays under 2^64; ln2's Q27
    # floor costs ~6e-9 relative — no e2 truncation at all).
    return (e2_q32 * (_LN2_Q32 >> np.uint64(5))) >> np.uint64(27)


def exponential_ns(b: jax.Array, mean_ns) -> jax.Array:
    """u32 bits → int64 ns exponential with the given mean.

    Integer pipeline: Q32 −ln(1−u) times the rounded mean; clamped to ≥1 ns
    so events always advance time. The mean scaling is one f64 multiply +
    round (IEEE-exact, backend-identical); everything else is integer."""
    e_q32 = _neg_log1m_q32(b)
    mean = jnp.round(jnp.asarray(mean_ns, jnp.float64)).astype(_U64)
    # Means are clamped to 2^38 ns (~4.6 simulated minutes, outside any
    # ladder config) to keep the integer pipeline overflow-free rather than
    # silently wrapping.
    mean = jnp.minimum(mean, np.uint64(1) << np.uint64(38))
    # d = mean · e_q32 / 2^32 via a hi/lo split so nothing overflows u64 and
    # the only truncation is 7 low bits of the Q32 fraction (~3e-8 of e):
    # mean·e_hi ≤ 2^38·22.2 and mean·(e_lo>>7) ≤ 2^38·2^25 = 2^63.
    e_hi = e_q32 >> np.uint64(32)
    e_lo = e_q32 & np.uint64(0xFFFFFFFF)
    d = mean * e_hi + ((mean * (e_lo >> np.uint64(7))) >> np.uint64(25))
    return jnp.maximum(d.astype(jnp.int64), 1)


def randint(b: jax.Array, n) -> jax.Array:
    """u32 bits → integer in [0, n) via 64-bit multiply-shift (exact, no bias
    for n ≪ 2^32 beyond the standard multiply-shift approximation; identical
    in both engines)."""
    n = jnp.asarray(n).astype(jnp.uint64)  # scalar or per-element array
    return ((b.astype(jnp.uint64) * n) >> jnp.uint64(32)).astype(jnp.int32)


# --------------------------------------------------------------------------
# NumPy twins — bit-exact reimplementations for the eager CPU oracle.
#
# Because every transform above is pure integer arithmetic, it has an exact
# host-side twin (no device dispatch per draw — the oracle used to issue
# eager jnp calls, each a device roundtrip). tests/test_rng guards
# jnp-vs-numpy equality draw-for-draw. All constants are np.uint64 to dodge
# NumPy's uint64+int -> float64 promotion trap.
# --------------------------------------------------------------------------
_U64_1 = np.uint64(1)


def base_key_np(seed: int) -> np.uint64:
    z = (int(seed) * 0x9E3779B97F4A7C15 + 0x94D049BB133111EB) & ((1 << 64) - 1)
    return np.uint64(z)


def _mix_np(z):
    z = z ^ (z >> np.uint64(30))
    z = z * _C1
    z = z ^ (z >> np.uint64(27))
    z = z * _C2
    z = z ^ (z >> np.uint64(31))
    return z


def bits_np(seed_key: np.uint64, purpose, host, ctr) -> np.ndarray:
    with np.errstate(over="ignore"):  # u64 wraparound is the point
        z = (
            np.uint64(seed_key)
            + np.uint64(purpose) * _P1
            + np.asarray(host, np.uint64) * _P2
            + np.asarray(ctr, np.uint64) * _P3
        )
        z = _mix_np(_mix_np(z))
    return (z >> np.uint64(32)).astype(np.uint32)


def _neg_log1m_q32_np(b: np.ndarray) -> np.ndarray:
    x = (_U64_1 << np.uint64(32)) - b.astype(np.uint64)
    # floor(log2 x) via frexp (exact: x <= 2^32 is exactly representable).
    _, e = np.frexp(x.astype(np.float64))
    k = (e - 1).astype(np.uint64)
    m = x << (np.uint64(63) - k)
    frac = (m << _U64_1) >> _U64_1
    idx = (frac >> np.uint64(63 - _LOG_BITS)).astype(np.int64)
    rem = (frac >> np.uint64(63 - _LOG_BITS - 24)) & np.uint64((1 << 24) - 1)
    lo = _LOG_TBL_NP[idx]
    hi = _LOG_TBL_NP[idx + 1]
    log2_frac_q32 = lo + (((hi - lo) * rem) >> np.uint64(24))
    log2_x_q32 = (k << np.uint64(32)) + log2_frac_q32
    e2_q32 = (np.uint64(32) << np.uint64(32)) - log2_x_q32
    return (e2_q32 * (_LN2_Q32 >> np.uint64(5))) >> np.uint64(27)


def exponential_ns_np(b: np.ndarray, mean_ns) -> np.ndarray:
    e_q32 = _neg_log1m_q32_np(np.asarray(b))
    mean = np.round(np.asarray(mean_ns, np.float64)).astype(np.uint64)
    mean = np.minimum(mean, _U64_1 << np.uint64(38))
    e_hi = e_q32 >> np.uint64(32)
    e_lo = e_q32 & np.uint64(0xFFFFFFFF)
    d = mean * e_hi + ((mean * (e_lo >> np.uint64(7))) >> np.uint64(25))
    return np.maximum(d.astype(np.int64), 1)


def randint_np(b, n) -> np.ndarray:
    return (
        (np.asarray(b, np.uint64) * np.uint64(n)) >> np.uint64(32)
    ).astype(np.int32)
