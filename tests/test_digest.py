"""Determinism flight recorder: state digests + paritytrace bisection.

The digest contract (ISSUE 3 acceptance, docs/SEMANTICS.md §"State
digest"): per-window subsystem digest words are bit-identical across the
CPU oracle, the single-chip engine, the sharded engine and a
checkpoint-resumed run; they are invariant under
slot-layout permutation (identity lives in (time, tb) keys, never slot
indices); and a single flipped bit in any digested subsystem changes that
subsystem's word in that window. ``tools/paritytrace.py`` turns the stream
into a first-divergence bisector — tested here end to end via corruption
injection.
"""

import dataclasses
import json

import numpy as np
import pytest

from shadow1_tpu.config.compiled import single_vertex_experiment
from shadow1_tpu.consts import MS, SEC, EngineParams
from shadow1_tpu.core import digest as D
from shadow1_tpu.core.engine import Engine
from shadow1_tpu.cpu_engine import CpuEngine
from shadow1_tpu.telemetry.registry import RING_DIGESTS
from shadow1_tpu.telemetry.ring import drain_ring


def phold_exp(n_hosts=16, seed=7, end=200 * MS, loss=0.0):
    return single_vertex_experiment(
        n_hosts=n_hosts,
        seed=seed,
        end_time=end,
        latency_ns=10 * MS,
        loss=loss,
        model="phold",
        model_cfg={"mean_delay_ns": float(20 * MS), "init_events": 2},
    )


def filexfer_exp(n_hosts=2, seed=11, loss=0.02, end=4 * SEC):
    role = np.full(n_hosts, 1, np.int64)
    role[0] = 0
    return single_vertex_experiment(
        n_hosts=n_hosts,
        seed=seed,
        end_time=end,
        latency_ns=10 * MS,
        loss=loss,
        bw_bits=10**7,
        model="net",
        model_cfg={
            "app": "filexfer",
            "role": role,
            "server": np.zeros(n_hosts, np.int64),
            "flow_bytes": np.full(n_hosts, 40_000, np.int64),
            "start_time": np.full(n_hosts, 1 * MS, np.int64),
            "flow_count": np.where(role == 1, 1, 0),
        },
    )


DIGEST_PARAMS = EngineParams(metrics_ring=1024, state_digest=1)


def ring_digests(st, window_ns):
    return {
        r["window"]: tuple(r[f] for f in RING_DIGESTS)
        for r in drain_ring(st, window_ns)
        if r["type"] == "ring"
    }


def oracle_digests(eng):
    return {
        r["window"]: tuple(r[f"dg_{s}"] for s in D.SUBSYSTEMS)
        for r in eng.digest_rows
    }


def assert_streams_equal(a, b, label):
    assert sorted(a) == sorted(b), (label, "window sets differ")
    for w in sorted(a):
        assert a[w] == b[w], (label, "window", w, a[w], b[w])


# ---------------------------------------------------------------------------
# implementation twins
# ---------------------------------------------------------------------------

def test_word_impl_twins_agree():
    """The jnp, numpy-vector, and python-int hash pipelines are the same
    function — the precondition for oracle↔engine digest equality."""
    rng = np.random.RandomState(3)
    hosts = np.arange(8, dtype=np.int64)
    cols = [hosts] + [rng.randint(0, 1 << 62, 8).astype(np.int64)
                      for _ in range(3)]
    w_np = D._words_np(D.SEED_RNG, cols)
    w_jnp = np.asarray(D._words(D.SEED_RNG, cols))
    np.testing.assert_array_equal(w_np, w_jnp)
    for i in range(8):
        assert D.word_int(D.SEED_RNG, [c[i] for c in cols]) == int(w_np[i])
    # i32 masking rule: an i32 field hashes as its low 32 bits.
    v32 = rng.randint(-(1 << 31), 1 << 31, 8).astype(np.int32)
    w_np = D._words_np(D.SEED_EVBUF, [hosts, v32])
    w_jnp = np.asarray(D._words(D.SEED_EVBUF, [hosts,
                                               np.asarray(v32)]))
    np.testing.assert_array_equal(w_np, w_jnp)
    for i in range(8):
        assert D.word_int(
            D.SEED_EVBUF, [hosts[i], int(v32[i]) & 0xFFFFFFFF]
        ) == int(w_np[i])


# ---------------------------------------------------------------------------
# invariance + sensitivity fuzz
# ---------------------------------------------------------------------------

def _mid_state(exp=None, params=None):
    eng = Engine(exp or phold_exp(), params or DIGEST_PARAMS)
    st = eng.run(n_windows=5)
    return eng, st


def test_evbuf_digest_slot_permutation_invariant():
    """Permuting event slots (what a cap migration or a different push
    layout does) must not change the digest: identity is (host, time, tb),
    never the slot index."""
    import jax.numpy as jnp

    eng, st = _mid_state()
    buf = st.evbuf
    assert int(np.asarray((buf.kind != 0).sum())) > 0
    rng = np.random.RandomState(0)
    perm = rng.permutation(buf.kind.shape[0])
    permuted = buf._replace(
        time_hi=buf.time_hi[perm], time_lo=buf.time_lo[perm],
        t32=buf.t32[perm], tb_hi=buf.tb_hi[perm], tb_lo=buf.tb_lo[perm],
        kind=buf.kind[perm], p=jnp.asarray(np.asarray(buf.p)[:, perm]),
    )
    hosts = eng.ctx.hosts
    assert int(D.digest_evbuf(buf, hosts)) == int(
        D.digest_evbuf(permuted, hosts))


def test_digest_bit_flip_changes_exactly_its_subsystem():
    """A single corrupted value in any digested plane changes that
    subsystem's word (and, for independent planes, only that word)."""
    eng, st = _mid_state()
    hosts = eng.ctx.hosts
    dg0 = np.asarray(D.state_digests(st, eng.ctx, jnp_zero()))

    def vec(st2):
        return np.asarray(D.state_digests(st2, eng.ctx, jnp_zero()))

    # evbuf: flip one payload bit of an occupied slot
    kind = np.asarray(st.evbuf.kind)
    c, h = [int(x[0]) for x in np.nonzero(kind != 0)]
    p = np.asarray(st.evbuf.p).copy()
    p[0, c, h] ^= 1
    st_ev = st._replace(evbuf=st.evbuf._replace(p=p))
    delta = vec(st_ev) != dg0
    assert delta[0] and not delta[2] and not delta[3] and not delta[4]

    # rng: bump a tie-break counter
    sc = np.asarray(st.evbuf.self_ctr).copy()
    sc[3] += 1
    delta = vec(st._replace(evbuf=st.evbuf._replace(self_ctr=sc))) != dg0
    assert delta[4] and not delta[0]

    # outbox digest: sends of a window hash through digest_outbox
    ob0 = int(D.digest_outbox(st.outbox, hosts))
    cnt = np.asarray(st.outbox.cnt).copy()
    if cnt.max() == 0:  # make a slot visible if the boundary outbox is empty
        cnt[0] = 1
    else:
        cnt[int(cnt.argmax())] -= 1
    ob1 = int(D.digest_outbox(
        st.outbox._replace(cnt=cnt), hosts))
    assert ob0 != ob1


def jnp_zero():
    import jax.numpy as jnp

    return jnp.zeros((), jnp.int64)


def test_tcp_nic_digest_bit_flip_sensitivity():
    eng, st = _mid_state(filexfer_exp(end=2 * SEC),
                         dataclasses.replace(DIGEST_PARAMS))
    dg0 = np.asarray(D.state_digests(st, eng.ctx, jnp_zero()))
    # tcp: bump a live socket's snd_nxt
    tcp = dict(st.model.tcp)
    live = np.nonzero(np.asarray(tcp["st"]) != 0)
    assert len(live[0]), "no live socket mid-transfer?"
    v = np.asarray(tcp["snd_nxt"]).copy()
    v[live[0][0], live[1][0]] ^= 1
    tcp["snd_nxt"] = v
    d = np.asarray(D.state_digests(
        st._replace(model=st.model._replace(tcp=tcp)), eng.ctx,
        jnp_zero())) != dg0
    assert d[2] and not d[3] and not d[4] and not d[0]
    # nic: bump a byte counter
    nb = np.asarray(st.model.nic.rx_bytes).copy()
    nb[1] += 1
    d = np.asarray(D.state_digests(
        st._replace(model=st.model._replace(
            nic=st.model.nic._replace(rx_bytes=nb))), eng.ctx,
        jnp_zero())) != dg0
    assert d[3] and not d[2]


# ---------------------------------------------------------------------------
# cross-engine / cross-impl stream equality (the acceptance matrix)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("loss", [0.0, 0.3])
def test_phold_digest_stream_cpu_vs_tpu(loss):
    exp = phold_exp(loss=loss)
    cpu = CpuEngine(exp, DIGEST_PARAMS)
    cpu.run()
    eng = Engine(exp, DIGEST_PARAMS)
    st = eng.run()
    assert_streams_equal(ring_digests(st, eng.window), oracle_digests(cpu),
                         f"phold loss={loss}")


def test_net_digest_stream_cpu_vs_tpu():
    """TCP/NIC plane digests under loss (retransmits, dup-ACKs, FIN
    teardown all exercised by the lossy transfer)."""
    exp = filexfer_exp()
    cpu = CpuEngine(exp, DIGEST_PARAMS)
    cpu.run()
    eng = Engine(exp, DIGEST_PARAMS)
    st = eng.run()
    tm = Engine.metrics_dict(st)
    assert tm["tcp_rto"] + tm["tcp_fast_rtx"] > 0  # loss actually recovered
    assert_streams_equal(ring_digests(st, eng.window), oracle_digests(cpu),
                         "filexfer")


def test_digest_stream_sharded_vs_single():
    from shadow1_tpu.shard.engine import ShardedEngine

    exp = phold_exp(n_hosts=64, end=100 * MS)
    eng = Engine(exp, DIGEST_PARAMS)
    st1 = eng.run()
    sh = ShardedEngine(exp, DIGEST_PARAMS)
    assert sh.n_dev == 8
    st8 = sh.run()
    assert_streams_equal(ring_digests(st1, eng.window),
                         ring_digests(st8, sh.window), "sharded")


def test_digest_stream_resume_vs_straight(tmp_path):
    """No digest state rides snapshots — the words are pure functions of
    engine state, so a save/load roundtrip continues the stream exactly."""
    from shadow1_tpu import ckpt

    exp = phold_exp()
    eng = Engine(exp, DIGEST_PARAMS)
    ref = eng.run(n_windows=20)
    path = str(tmp_path / "dg.npz")
    st = eng.run(n_windows=10)
    ckpt.save_state(st, path)
    st = ckpt.load_state(eng.init_state(), path)
    st = eng.run(st, n_windows=10)
    assert_streams_equal(ring_digests(ref, eng.window),
                         ring_digests(st, eng.window), "resume")


def test_digest_off_means_zero_columns_and_requires_ring():
    eng = Engine(phold_exp(), EngineParams(metrics_ring=64))
    st = eng.run(n_windows=5)
    for r in drain_ring(st, eng.window):
        assert all(r[f] == 0 for f in RING_DIGESTS)
    with pytest.raises(ValueError, match="metrics_ring"):
        Engine(phold_exp(), EngineParams(state_digest=1))


# ---------------------------------------------------------------------------
# paritytrace end to end
# ---------------------------------------------------------------------------

PHOLD_YAML = """\
general: {{seed: 7, stop_time: {stop} ms}}
engine: {{scheduler: tpu}}
network: {{single_vertex: {{latency: 10 ms}}}}
hosts:
  - {{name: host, count: 12}}
app:
  model: phold
  params: {{mean_delay_ns: 2.0e7, init_events: 2}}
"""


def _write_cfg(tmp_path, stop_ms=400):
    p = tmp_path / "phold.yaml"
    p.write_text(PHOLD_YAML.format(stop=stop_ms))
    return str(p)


def test_paritytrace_clean_run_exit_zero(tmp_path, capsys):
    from shadow1_tpu.tools import paritytrace

    rc = paritytrace.main([_write_cfg(tmp_path), "tpu", "cpu",
                           "--windows", "20", "--chunk", "8"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["first_divergence"] is None


@pytest.mark.parametrize("subsys,side", [("rng", "b"), ("evbuf", "a")])
def test_paritytrace_localizes_injected_corruption(tmp_path, capsys,
                                                   subsys, side):
    """The acceptance bisect: a corruption injected at window K is reported
    as first divergence exactly (K, subsys), whichever side is corrupted."""
    from shadow1_tpu.tools import paritytrace

    dump = tmp_path / "diff.jsonl"
    rc = paritytrace.main([
        _write_cfg(tmp_path), "tpu", "cpu", "--windows", "30",
        "--chunk", "10", "--inject", f"17:{subsys}:{side}",
        "--dump", str(dump),
    ])
    assert rc == 3
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["first_divergence"]["window"] == 17
    assert out["first_divergence"]["subsystems"] == [subsys]
    recs = [json.loads(x) for x in dump.read_text().splitlines()]
    assert any(r.get("type") == "plane_diff" for r in recs)


def test_paritytrace_side_grammar_names_what_it_rejects():
    """A side spec is ``cpu`` or ``tpu | sharded[:D]`` with ``+resume``:
    anything else (the ``+pallas`` modifier went with the kernels in PR 30)
    is refused by name before an engine is built."""
    from shadow1_tpu.tools.paritytrace import make_side

    exp = phold_exp(n_hosts=8, end=20 * MS)
    for spec, msg in (("tpu+pallas", r"unknown side modifiers \['pallas'\]"),
                      ("tpu+resume+fast", r"unknown side modifiers \['fast'\]"),
                      ("gpu", "unknown side kind 'gpu'"),
                      ("cpu+resume", "cpu oracle takes no modifiers")):
        with pytest.raises(ValueError, match=msg):
            make_side(spec, exp, DIGEST_PARAMS, 4)


def test_paritytrace_resume_side_identical(tmp_path, capsys):
    from shadow1_tpu.tools import paritytrace

    rc = paritytrace.main([_write_cfg(tmp_path, stop_ms=200), "tpu",
                           "tpu+resume", "--windows", "12", "--chunk", "4"])
    assert rc == 0
