"""Cross-BACKEND determinism: the engine on the real accelerator vs the
CPU oracle (docs/SEMANTICS.md `Randomness`).

The rest of the suite forces the CPU platform (conftest), so the round-2
regression — identical programs producing different event counts on the
TPU than on CPU, via backend-dependent float transcendentals — was
invisible to it. These tests run the comparison in a SUBPROCESS on the
default (accelerator) platform. The pytest parent keeps the CPU conftest
gave it, so it never holds the chip its children need. Where the default
platform is the CPU there is nothing to compare and the tests skip; on a
machine with a chip they must pass:

    python -m pytest tests/test_backend_parity.py -q -m slow

VERDICT r2 #5: ≥1k hosts, ≥50 windows, identical counters (PHOLD).
VERDICT r4 #6: the NET model (TCP + filexfer + Tor) asserted on the chip
too — the full semantic counter set plus per-host summaries.
"""

import json
import os
import re
import subprocess
import sys

import pytest

# Slow tier: each child pays a full accelerator compile of its programs
# (the net child two TCP round bodies) plus an eager oracle run, and on a
# machine without a chip the file only skips. ./ci.sh all and any
# accelerator-attached run exercise it.
pytestmark = pytest.mark.slow

_PHOLD_CHILD = r"""
import json
import shadow1_tpu
import jax
print("BACKEND_UP", jax.default_backend(), flush=True)  # init sentinel
if jax.default_backend() == "cpu":
    raise SystemExit(0)  # nothing to compare; the parent skips
from shadow1_tpu.config.compiled import single_vertex_experiment
from shadow1_tpu.consts import MS, EngineParams
from shadow1_tpu.core.engine import Engine
from shadow1_tpu.cpu_engine import CpuEngine

exp = single_vertex_experiment(
    n_hosts=1024, seed=2024, end_time=60 * MS, latency_ns=1 * MS,
    model="phold", model_cfg={"mean_delay_ns": float(2 * MS), "init_events": 4},
)
params = EngineParams(ev_cap=32, outbox_cap=16, max_rounds=64)
eng = Engine(exp, params)
st = eng.run()  # 60 windows on the DEFAULT backend (accelerator when alive)
m = Engine.metrics_dict(st)
cm = CpuEngine(exp, params).run()
print(json.dumps({"backend": jax.default_backend(), "tpu": m, "cpu": cm}))
"""

# The net-model child: lossy TCP file transfers AND a miniature Tor net
# (weighted paths, telescoped circuits, cell streams) on the accelerator,
# vs the CPU oracle, in 100-window chunks.
_NET_CHILD = r"""
import json
import numpy as np
import shadow1_tpu
import jax
print("BACKEND_UP", jax.default_backend(), flush=True)  # init sentinel
if jax.default_backend() == "cpu":
    raise SystemExit(0)  # nothing to compare; the parent skips
from shadow1_tpu import ckpt
from shadow1_tpu.consts import SEC, EngineParams
from shadow1_tpu.core.engine import Engine
from shadow1_tpu.cpu_engine import CpuEngine
import __graft_entry__ as ge
from tests.test_tor_parity import TOR_KEYS

CASES = {
    "filexfer": (
        ge._flagship_exp(64, 2 * SEC), EngineParams(ev_cap=256),
        ("rx_bytes", "flows_done", "done_time"),
    ),
    "tor": (
        ge._tor_exp(24, 10 * SEC),
        EngineParams(ev_cap=128, outbox_cap=32, sockets_per_host=16),
        TOR_KEYS,
    ),
}
out = {"backend": jax.default_backend(), "cases": {}}
for name, (exp, params, sum_keys) in CASES.items():
    eng = Engine(exp, params)
    st = ckpt.run_chunked(eng, chunk=100)
    ts = eng.model_summary(st)
    cpu = CpuEngine(exp, params)
    cm = cpu.run()
    cs = cpu.summary()
    out["cases"][name] = {
        "tpu": Engine.metrics_dict(st),
        "cpu": cm,
        "tpu_sum": {k: np.asarray(ts[k]).tolist() for k in sum_keys},
        "cpu_sum": {k: np.asarray(cs[k]).tolist() for k in sum_keys},
    }
print(json.dumps(out))
"""

# The full cross-engine semantic counter set (tests/test_net_parity.py
# PARITY_KEYS + the NIC/AQM fidelity counters).
SEMANTIC_KEYS = [
    "events", "pkts_sent", "pkts_delivered", "pkts_lost",
    "ev_overflow", "ob_overflow", "tcp_fast_rtx", "tcp_rto", "tcp_ooo_drops",
    "nic_tx_drops", "nic_rx_drops", "nic_aqm_drops",
    "pops_pkt", "pops_deliver", "pops_timer", "pops_txr", "pops_app",
]


def _run_on_accelerator(child_src: str, timeout_s: int) -> dict:
    """Run ``child_src`` on the default (accelerator) platform; skip when
    that platform is the CPU, FAIL when the engine breaks on a live
    accelerator (the regression these tests exist to catch)."""
    # Undo conftest's CPU-forcing env mutations for the child so it boots
    # the default platform.
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    if "XLA_FLAGS" in env:
        flags = re.sub(
            r"--xla_force_host_platform_device_count=\d+", "", env["XLA_FLAGS"]
        ).strip()
        if flags:
            env["XLA_FLAGS"] = flags
        else:
            del env["XLA_FLAGS"]  # whitespace-only XLA_FLAGS is a hard error
    cwd = str(__import__("pathlib").Path(__file__).resolve().parent.parent)
    out = subprocess.run([sys.executable, "-c", child_src], env=env, cwd=cwd,
                         capture_output=True, text=True, timeout=timeout_s)
    up = re.search(r"^BACKEND_UP (\S+)", out.stdout, re.M)
    if up and up.group(1) == "cpu":
        pytest.skip("default backend is cpu — no accelerator to compare")
    assert out.returncode == 0, (
        f"child failed on backend {up.group(1) if up else '(never came up)'}"
        f":\n{out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_accelerator_vs_oracle_counters():
    r = _run_on_accelerator(_PHOLD_CHILD, timeout_s=600)
    for k in ("events", "pkts_sent", "pkts_delivered", "pkts_lost",
              "ev_overflow", "ob_overflow"):
        assert r["tpu"][k] == r["cpu"][k], (k, r["tpu"][k], r["cpu"][k])


def test_accelerator_net_model_vs_oracle():
    """The TCP/Tor path on the real chip under a parity assertion (VERDICT
    r4 #6): full semantic counters + per-host summaries, bit-identical."""
    r = _run_on_accelerator(_NET_CHILD, timeout_s=1500)
    for name, case in r["cases"].items():
        for k in SEMANTIC_KEYS:
            assert case["tpu"][k] == case["cpu"][k], (name, k, case["tpu"][k],
                                                      case["cpu"][k])
        assert case["tpu"]["events"] > 0, name
        for k, tv in case["tpu_sum"].items():
            assert tv == case["cpu_sum"][k], (name, k)
