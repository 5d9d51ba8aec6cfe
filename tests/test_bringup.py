"""Bring-up invariants: the things that must hold before a number measured
on the chip can be believed.

* nothing switches platform on its own — the chip smoke and the benchmark
  FAIL on a CPU, naming it — and every row says where it ran;
* the compile cache can be placed from outside;
* an engine refuses, by name and before it builds anything, parameters it
  cannot run;
* a comparator binary is trusted for its source's content, not its mtime;
* a parent that only verifies a checkpoint never initialises a backend.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, cwd=REPO, **env_over):
    """Run python with conftest's cache placement undone, then ``env_over``."""
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_over)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_fails_on_cpu_naming_it():
    """chip_smoke.py's own logic under JAX_PLATFORMS=cpu: the first child
    reports its platform through platform.describe(), the platform check
    every leg shares rejects it, and no result line is printed."""
    r = _run(["chip_smoke.py"], JAX_PLATFORMS="cpu")
    assert r.returncode != 0
    assert "'cpu'" in r.stderr and "not 'tpu'" in r.stderr, r.stderr
    assert '"ok"' not in r.stdout, r.stdout

    import chip_smoke  # the repo root is on pytest's pythonpath

    # The same check on a leg's row: a CLI row from a CPU run.
    with pytest.raises(chip_smoke.SmokeFailure, match="'cpu'"):
        chip_smoke.require_tpu({"platform": "cpu", "device_kind": "cpu",
                                "n_devices": 1}, "leg1")
    # ...and a row that does not say where it ran is a failure too.
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.require_tpu({"engine": "tpu"}, "leg1")
    chip_smoke.require_tpu({"platform": "tpu", "device_kind": "TPU v5 lite",
                            "n_devices": 1}, "leg1")


def test_bench_refuses_cpu():
    r = _run(["bench.py"], JAX_PLATFORMS="cpu")
    assert r.returncode != 0
    assert "cpu platform" in r.stderr and "does not fall back" in r.stderr
    assert r.stdout.strip() == ""  # no row: a CPU wall is not a TPU datum


def test_cli_row_says_where_it_ran_and_what_compile_cost(capsys):
    """"engine" names the code path and cannot tell a chip run from a CPU
    run; platform/device_kind/n_devices can. compile is kept apart from
    run. (The oracle path: no compile to wait for.)"""
    from shadow1_tpu.cli import main

    rc = main([os.path.join(REPO, "configs", "rung1_filexfer.yaml"),
               "--engine", "cpu", "--windows", "20"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["platform"] == "cpu" and out["device_kind"]
    assert out["n_devices"] >= 1
    assert set(out["compile"]) == {"seconds", "cache_hits", "cache_misses"}


_PRINT_CACHE = ("import shadow1_tpu, jax; "
                "print(jax.config.jax_compilation_cache_dir)")


def test_compile_cache_dir_placeable_from_outside():
    r = _run(["-c", _PRINT_CACHE], JAX_COMPILATION_CACHE_DIR="/x")
    assert r.stdout.strip() == "/x", (r.stdout, r.stderr[-500:])
    # Unset: one fixed path inside the checkout, wherever the cwd is.
    r = _run(["-c", "import sys; sys.path.insert(0, %r); %s"
              % (REPO, _PRINT_CACHE)], cwd="/")
    assert r.stdout.strip() == os.path.join(REPO, ".jax_cache"), (
        r.stdout, r.stderr[-500:])


def test_engine_constructors_refuse_params_they_cannot_run():
    """Digest words and probe samples are columns of the telemetry ring, so
    either without a ring is a ValueError from every batched engine's
    constructor; the sharded engine also refuses a host count its devices
    do not divide."""
    from shadow1_tpu.consts import MS, EngineParams
    from shadow1_tpu.core.engine import Engine
    from shadow1_tpu.fleet.engine import FleetEngine
    from shadow1_tpu.shard.engine import ShardedEngine
    from tests.test_phold_parity import make_exp

    exp = make_exp(n_hosts=16, end=10 * MS)
    builders = (lambda p: Engine(exp, p), lambda p: ShardedEngine(exp, p),
                lambda p: FleetEngine([exp, exp], p))
    for params, msg in (
            (EngineParams(state_digest=1), "state_digest=1 requires metrics_ring"),
            (EngineParams(probes=((0, -1),)), "probes require metrics_ring")):
        for build in builders:
            with pytest.raises(ValueError, match=msg):
                build(params)
    with pytest.raises(ValueError, match="n_hosts=12 not divisible by 8"):
        ShardedEngine(make_exp(n_hosts=12, end=10 * MS), EngineParams())


def test_dryrun_multichip_says_how_to_ask(monkeypatch):
    """Too few devices and no JAX_PLATFORMS=cpu request: fail, saying how to
    ask for the virtual-device dry run — never switch platform unasked."""
    import jax

    import __graft_entry__ as ge

    monkeypatch.setattr(ge, "_cpu_asked_for", lambda: False)
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
        ge.dryrun_multichip(len(jax.devices()) + 1)


def test_stale_binary_is_rebuilt_on_source_content(tmp_path, monkeypatch):
    """``build/`` is git-ignored and travels with copies of the tree: a
    binary found there is trusted only when the hash stamped beside it is
    the hash of the committed source — whatever the mtimes say."""
    from shadow1_tpu import native

    monkeypatch.setattr(native, "_BUILD", tmp_path)
    monkeypatch.setattr(native, "_TABLE", tmp_path / "log2_q32.tbl")
    binary = tmp_path / "phold_comparator"
    monkeypatch.setattr(native, "_BIN", binary)
    try:
        native.ensure_built()
    except native.NativeUnavailable as e:
        pytest.skip(str(e))
    good = binary.read_bytes()
    stamp = tmp_path / "phold_comparator.src.sha256"
    tbl_stamp = tmp_path / "log2_q32.tbl.src.sha256"
    assert len(stamp.read_text()) == 64

    def plant_stale(stamp_text):
        # Newer than the source by construction (written now): the old
        # mtime rule would have kept both.
        binary.write_bytes(b"#!/bin/sh\nexit 1\n")
        native._TABLE.write_bytes(b"stale")
        for s in (stamp, tbl_stamp):
            if stamp_text is None:
                s.unlink()
            else:
                s.write_text(stamp_text)

    # Built from some other source (stamp disagrees), then no stamp at all
    # (a build/ left by a tree that predates the stamps).
    for stamp_text in ("0" * 64, None):
        plant_stale(stamp_text)
        native.ensure_built()
        assert binary.read_bytes() == good
        assert native._TABLE.stat().st_size > 4096
        assert stamp.read_text() == native._digest(
            native._DIR / "phold_comparator.cpp")
    # Unchanged source: no rebuild.
    before = binary.stat().st_mtime_ns
    native.ensure_built()
    assert binary.stat().st_mtime_ns == before


def test_verifying_a_checkpoint_leaves_the_backend_alone(tmp_path):
    """The --ckpt supervisor verifies a leftover checkpoint before it
    spawns the child that needs the chip: verify_file in a fresh process
    must not initialise a jax backend (it once did, through core/)."""
    from shadow1_tpu import ckpt
    from shadow1_tpu.consts import MS, EngineParams
    from shadow1_tpu.core.engine import Engine
    from tests.test_phold_parity import make_exp

    eng = Engine(make_exp(n_hosts=4, end=10 * MS),
                 EngineParams(ev_cap=16, outbox_cap=16))
    path = str(tmp_path / "snap.npz")
    ckpt.save_state(eng.init_state(), path)
    r = _run(["-c", "import sys\n"
              "from shadow1_tpu import ckpt\n"
              "from shadow1_tpu.platform import assert_backend_untouched\n"
              "ok, why = ckpt.verify_file(sys.argv[1])\n"
              "assert ok, why\n"
              "assert_backend_untouched('verify_file')\n", path])
    assert r.returncode == 0, r.stderr[-1500:]
