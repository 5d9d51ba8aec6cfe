"""RNG invariants: jnp/NumPy twin equality, backend-exactness, statistics.

The determinism contract (docs/SEMANTICS.md) requires every draw to be a
pure function of (seed, purpose, host, counter) with identical values on
every backend and in the eager oracle. The integer pipeline makes that hold
by construction; these tests guard the construction.

Run as a command this file is the same check on the backend jax picks (the
chip, through the chip tool), at any size:

    PYTHONPATH=. python tests/test_rng.py 33554432

prints one JSON line (where it ran, inputs, mismatches by function) and
exits 1 if any draw differs from its numpy twin.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shadow1_tpu import rng


def _sample_bits(n=50000, seed=99):
    key = rng.base_key(seed)
    key_np = rng.base_key_np(seed)
    host = np.arange(n, dtype=np.int64) % 1000
    ctr = np.arange(n, dtype=np.int64) * 7
    bj = np.asarray(rng.bits(key, 3, jnp.asarray(host), jnp.asarray(ctr)))
    bn = rng.bits_np(key_np, 3, host, ctr)
    return bj, bn


def test_bits_numpy_twin_exact():
    bj, bn = _sample_bits()
    np.testing.assert_array_equal(bj, bn)


def test_exponential_numpy_twin_exact():
    bj, bn = _sample_bits()
    for mean in (1.0, 1e3, 2e6, 1e9, 2.0**40):  # incl. the clamp region
        ej = np.asarray(rng.exponential_ns(jnp.asarray(bj), mean))
        en = rng.exponential_ns_np(bn, mean)
        np.testing.assert_array_equal(ej, en)


def test_randint_numpy_twin_exact():
    bj, bn = _sample_bits()
    for n in (2, 7, 4096, 10_000_019):
        np.testing.assert_array_equal(
            np.asarray(rng.randint(jnp.asarray(bj), n)), rng.randint_np(bn, n)
        )


def test_exponential_matches_float_reference():
    """The fixed-point pipeline tracks -mean*log1p(-u) to ~1e-4 relative
    (away from the 1 ns clamp)."""
    bj, _ = _sample_bits()
    mean = 2e6
    e = np.asarray(rng.exponential_ns(jnp.asarray(bj), mean)).astype(float)
    u = bj.astype(np.float64) / 2.0**32
    ref = np.maximum(-mean * np.log1p(-u), 1)
    big = ref > 1000  # ignore the clamp region
    rel = np.abs(e[big] - ref[big]) / ref[big]
    assert rel.max() < 1e-3, rel.max()
    assert abs(e.mean() / mean - 1) < 0.02


def test_bits_statistics():
    bj, _ = _sample_bits(200000)
    assert abs(bj.mean() / 2.0**32 - 0.5) < 0.005
    # byte-level chi2 well within 4 sigma of the 255-dof expectation
    h = np.bincount(bj & 255, minlength=256)
    chi2 = (((h - h.mean()) ** 2) / h.mean()).sum()
    assert chi2 < 255 + 4 * np.sqrt(2 * 255), chi2
    # no collisions across distinct (host, ctr) in the sample
    assert len(np.unique(bj)) > 0.99 * len(bj)


def test_prob_threshold_bernoulli():
    bj, bn = _sample_bits(200000)
    thr = rng.prob_threshold(0.25)
    got = np.asarray(rng.uniform_lt(jnp.asarray(bj), thr)).mean()
    assert abs(got - 0.25) < 0.005
    assert rng.prob_threshold(0.0) == 0
    assert rng.prob_threshold(1.0) == 1 << 32


# ---------------------------------------------------------------------------
# The log2 table is read densely (rng._log_tbl_read), not with tbl[idx]: the
# untouched numpy twin, which still reads tbl[idx] and tbl[idx + 1], is the
# reference at every index and at the edges of the input.
# ---------------------------------------------------------------------------

def _split_np(b):
    """(idx, rem) of the twin's pipeline for u32 bits b."""
    x = (np.uint64(1) << np.uint64(32)) - b.astype(np.uint64)
    k = (np.frexp(x.astype(np.float64))[1] - 1).astype(np.uint64)
    frac = ((x << (np.uint64(63) - k)) << np.uint64(1)) >> np.uint64(1)
    return (frac >> np.uint64(63 - rng._LOG_BITS),
            (frac >> np.uint64(63 - rng._LOG_BITS - 24)) & np.uint64((1 << 24) - 1))


def _bits_idx_x_rem():
    """b for every idx crossed with the extremes of rem, for several k.

    x = 2^32 − b = 2^k + idx·2^(k−12) + r holds only k − 12 bits of rem, so
    rem is a multiple of 2^(36−k): 0 and 2^23 have a b, 1 and 2^24 − 1 have
    none (x ≤ 2^32) and the smallest and largest rem each k allows stand in
    for them."""
    idx = np.arange(2 ** rng._LOG_BITS, dtype=np.uint64)
    out = []
    for k in (31, 30, 24, 17, 13, 12):
        nb = k - rng._LOG_BITS
        for r in sorted({0, 1, (1 << nb) >> 1, (1 << nb) - 1} & set(range(1 << nb))):
            x = (np.uint64(1) << np.uint64(k)) + (idx << np.uint64(nb)) + np.uint64(r)
            out.append((np.uint64(1) << np.uint64(32)) - x)
    return np.concatenate(out).astype(np.uint32)


_EDGE_BITS = np.array([0, 1, 2, 2**31 - 1, 2**31, 2**31 + 1, 2**32 - 2, 2**32 - 1],
                      np.uint64).astype(np.uint32)


def _bits_vmap2_h100():
    return np.random.default_rng(7).integers(0, 2**32, (2, 100), dtype=np.uint64).astype(np.uint32)


@pytest.mark.parametrize("bits,batched", [
    pytest.param(_bits_idx_x_rem, False, id="idx_x_rem"),
    pytest.param(lambda: _EDGE_BITS, False, id="edges"),
    pytest.param(_bits_vmap2_h100, True, id="vmap2_h100"),
])
def test_dense_table_read_equals_numpy_twin(bits, batched):
    b = bits()
    wrap = jax.vmap if batched else (lambda f: f)
    got = np.asarray(jax.jit(wrap(rng._neg_log1m_q32))(jnp.asarray(b)))
    np.testing.assert_array_equal(got, rng._neg_log1m_q32_np(b))
    for mean in (1.0, 2e6, 2.0**40):
        f = jax.jit(wrap(lambda x, mean=mean: rng.exponential_ns(x, mean)))
        np.testing.assert_array_equal(np.asarray(f(jnp.asarray(b))),
                                      rng.exponential_ns_np(b, mean))
    if not batched and b.size > 4096:   # the case covers what its name says
        idx, rem = _split_np(b)
        for r in (0, 2**23, rem.max()):
            assert len(np.unique(idx[rem == r])) == 2 ** rng._LOG_BITS
        assert rem.max() == 2**24 - 32 and rem[rem > 0].min() == 32


def test_log_table_layout_facts():
    """What the two-plane, one-index layout stands on; a change of _LOG_BITS
    or of the table that breaks one of these must fail here, loudly."""
    tbl = rng._LOG_TBL_NP
    n = 2 ** rng._LOG_BITS
    assert tbl.shape == (n + 1,) and tbl[-1] == 2**32
    assert tbl[:-1].max() < 2**32                 # lo fits 4 byte planes
    d = np.diff(tbl)
    assert d.min() > 0 and d.max() < 2**21        # hi − lo fits 3
    assert rng._LOG_A * rng._LOG_B == n
    assert rng._LOG_BYTES_NP.shape == (7 * rng._LOG_B, rng._LOG_A)
    assert rng._LOG_BYTES_NP.max() <= 255         # exact in bf16
    # idx never reaches n, so idx + 1 <= n and tbl[-1] is only ever ``hi``.
    idx, _ = _split_np(np.concatenate([_EDGE_BITS, _bits_idx_x_rem()]))
    assert idx.min() == 0 and idx.max() == n - 1
    # The read itself, at every index.
    lo, dd = jax.jit(rng._log_tbl_read)(jnp.arange(n, dtype=jnp.int32))
    assert lo.dtype == jnp.uint32 and dd.dtype == jnp.uint32
    np.testing.assert_array_equal(np.asarray(lo), tbl[:-1])
    np.testing.assert_array_equal(np.asarray(dd), d)


# ---------------------------------------------------------------------------
# The twin check at size: what `PYTHONPATH=. python tests/test_rng.py N` runs on the chip.
# ---------------------------------------------------------------------------

def twin_check(n: int, chunk: int = 1 << 22) -> dict:
    """``bits``, ``exponential_ns`` and ``randint`` against their numpy twins
    on ``n`` inputs each, in chunks; the first chunk's bits start with every
    edge case (``_EDGE_BITS``, every idx crossed with the extremes of rem).
    Returns where it ran and the mismatches by function."""
    chunk = min(chunk, n)
    edge = np.concatenate([_EDGE_BITS, _bits_idx_x_rem()])[:chunk]
    key, key_np = rng.base_key(31), rng.base_key_np(31)
    f_bits = jax.jit(lambda host, ctr: rng.bits(key, 5, host, ctr))
    f_exp, f_int = jax.jit(rng.exponential_ns), jax.jit(rng.randint)
    means = (1.0, 1e3, 2e6, 1e9, 2.0**38, 2.0**40)
    mods = (2, 7, 4096, 65536, 10_000_019, 2**31 - 1)
    bad = {"bits": 0, "exponential_ns": 0, "randint": 0}
    for i, lo in enumerate(range(0, n, chunk)):
        # hosts and counters past 2^31 and 2^32 too: the hash takes int64.
        host = (np.arange(lo, lo + chunk, dtype=np.int64) * 2_654_435_761) % (1 << 33)
        ctr = np.arange(lo, lo + chunk, dtype=np.int64) * 7 + (i << 31)
        b = np.asarray(f_bits(jnp.asarray(host), jnp.asarray(ctr)))
        bad["bits"] += int((b != rng.bits_np(key_np, 5, host, ctr)).sum())
        if i == 0:
            b = b.copy()
            b[:len(edge)] = edge
        mean = means[i % len(means)]
        if i % 4 == 3:      # a mean per element, as tgen's ``mean_bytes``
            mean = np.asarray(means)[np.arange(chunk) % len(means)]
        e = np.asarray(f_exp(jnp.asarray(b), jnp.asarray(mean, jnp.float64)))
        bad["exponential_ns"] += int((e != rng.exponential_ns_np(b, mean)).sum())
        m = mods[i % len(mods)]
        r = np.asarray(f_int(jnp.asarray(b), jnp.asarray(m, jnp.uint64)))
        bad["randint"] += int((r != rng.randint_np(b, m)).sum())
    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "inputs_per_function": (n + chunk - 1) // chunk * chunk,
            "edge_inputs": len(edge), "mismatches": bad}


def test_twin_check_small():
    out = twin_check(1 << 18, chunk=1 << 17)
    assert out["inputs_per_function"] == 1 << 18 and out["edge_inputs"] > 2 ** rng._LOG_BITS
    assert out["mismatches"] == {"bits": 0, "exponential_ns": 0, "randint": 0}


if __name__ == "__main__":
    import json
    import sys

    _out = twin_check(int(sys.argv[1]))
    print(json.dumps(_out))
    sys.exit(1 if any(_out["mismatches"].values()) else 0)
