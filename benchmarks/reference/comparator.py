"""The plain reference: two sequential-semantics C++ discrete-event
simulators, built and driven from here.

``phold_comparator.cpp`` and ``net_comparator.cpp`` are the benchmark's own
copies (a later PR may not move the yardstick). This module builds them with
the system g++ into ``benchmarks/.build/`` (keyed on the content of the
source), writes the experiment they read, runs them as child processes and
returns their counters. It is numpy only: it imports nothing of the program
and never touches jax, so the process that holds the chip can call it while
the chip is its own.

What it is handed is the *experiment*: host counts, roles, topology arrays,
capacities and the seed, as the experiment file states them. What it makes
itself: the Q32 log2 table of the fixed-point exponential, the loss
thresholds and Tor's path-selection tables.
"""

from __future__ import annotations

import hashlib
import json
import os
import pathlib
import subprocess
import tempfile

import numpy as np

_DIR = pathlib.Path(__file__).resolve().parent
_BUILD = _DIR.parent / ".build"
_TABLE = _BUILD / "log2_q32.tbl"

# Printed by a comparator beside its counters; never compared.
NOT_COUNTERS = ("wall_s", "events_per_sec", "n_threads")

_LOG_BITS = 12
_NET_MAGIC = 0x53484457434D5032
_NO_STOP = 1 << 62


class ReferenceFailure(RuntimeError):
    """The reference could not be built or run, or refuses the experiment."""


def _log2_table() -> bytes:
    """log2(1 + i/2^12) in Q32 for i in 0..2^12, then round(ln 2 * 2^32):
    the table the simulators' fixed-point exponential interpolates in."""
    tbl = np.round(np.log2(1.0 + np.arange(2 ** _LOG_BITS + 1) / 2 ** _LOG_BITS)
                   * 2.0 ** 32).astype(np.uint64)
    ln2 = np.uint64(round(float(np.log(2.0)) * 2 ** 32))
    return tbl.tobytes() + ln2.tobytes()


def _build(src_name: str) -> pathlib.Path:
    """The binary for ``src_name``, rebuilt unless it was built from exactly
    these source bytes (the digest sits beside it)."""
    _BUILD.mkdir(parents=True, exist_ok=True)
    table = _log2_table()
    if not _TABLE.exists() or _TABLE.read_bytes() != table:
        tmp = _TABLE.with_name(_TABLE.name + f".{os.getpid()}.tmp")
        tmp.write_bytes(table)
        os.replace(tmp, _TABLE)
    src = _DIR / src_name
    want = hashlib.sha256(src.read_bytes()).hexdigest()
    binary = _BUILD / src.stem
    stamp = _BUILD / (src.stem + ".src.sha256")
    if binary.exists() and stamp.exists() and stamp.read_text().strip() == want:
        return binary
    tmp = binary.with_name(binary.name + f".{os.getpid()}.tmp")
    cmd = ["g++", "-O2", "-std=c++17", "-pthread", "-o", str(tmp), str(src)]
    try:
        out = subprocess.run(cmd, capture_output=True, text=True, timeout=300)
    except (FileNotFoundError, subprocess.TimeoutExpired) as e:
        raise ReferenceFailure(f"g++ unavailable: {e!r}") from e
    if out.returncode != 0:
        raise ReferenceFailure(f"g++ failed: {out.stderr[-800:]}")
    os.replace(tmp, binary)
    stamp.write_text(want)
    return binary


_SOURCES = {"phold": "phold_comparator.cpp", "net": "net_comparator.cpp"}


def prepare(model: str) -> None:
    """Build the simulator for ``model`` now (set-up), so that the check
    after the window only runs it."""
    if model not in _SOURCES:
        raise ReferenceFailure(f"no reference for model {model!r}")
    _build(_SOURCES[model])


def _run(cmd: list[str], timeout_s: float) -> dict:
    try:
        out = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=timeout_s)
    except subprocess.TimeoutExpired as e:
        raise ReferenceFailure(f"reference exceeded {timeout_s:.0f}s") from e
    if out.returncode != 0:
        raise ReferenceFailure(
            f"reference rc={out.returncode}: {out.stderr[-500:]}")
    try:
        return json.loads(out.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError) as e:
        raise ReferenceFailure(f"reference printed no result: {e!r}") from e


def _threads() -> int:
    return os.cpu_count() or 1


def _run_phold(exp, params, seed: int, n_windows: int, timeout_s: float) -> dict:
    cfg = exp.model_cfg
    return _run([
        str(_build(_SOURCES["phold"])), str(_TABLE), str(exp.n_hosts),
        str(seed), str(n_windows), str(exp.window),
        str(int(round(float(cfg["mean_delay_ns"])))),
        str(int(cfg.get("init_events", 1))), str(params.ev_cap),
        str(params.outbox_cap), str(_threads())], timeout_s)


def _tor_tables(cfg: dict) -> tuple[list, list]:
    """Member ids and cumulative bandwidth weights for guard / exit / any
    relay sampling, and the directory authorities' ids: the consensus, from
    the per-host roles and weights of the experiment file."""
    role = np.asarray(cfg["role"], np.int32)
    weight = np.asarray(cfg["relay_weight"], np.int64)
    relay = role == 0

    def cum_ids(member):
        ids = np.nonzero(member)[0].astype(np.int64)
        return ids, np.cumsum(weight[ids])

    g = cum_ids(relay & np.asarray(cfg["is_guard"], bool))
    e = cum_ids(relay & np.asarray(cfg["is_exit"], bool))
    r = cum_ids(relay)
    dirs = np.nonzero(role == 2)[0].astype(np.int64)
    return [g[0], e[0], r[0], dirs], [g[1], e[1], r[1]]


def _dump_net(exp, params, seed: int, n_windows: int, path: str) -> None:
    """Write the net experiment in the layout ``read_config`` of
    net_comparator.cpp reads. Refuses what that simulator does not model."""
    for knob, name in (
        (np.asarray(exp.stop_time).min() < _NO_STOP, "host stop times"),
        (getattr(exp, "faults", None) is not None, "fault schedule"),
        (np.asarray(exp.cpu_ns_per_event).max() > 0, "virtual CPU"),
        (np.asarray(exp.tx_qlen_bytes).max() > 0, "tx queue bound"),
        (np.asarray(exp.rx_qlen_bytes).max() > 0, "rx queue bound"),
        (np.asarray(exp.aqm_max_bytes).max() > 0, "RED AQM"),
    ):
        if knob:
            raise ReferenceFailure(f"the reference does not model {name}")
    cfg = exp.model_cfg
    app = cfg["app"]
    pr = params
    lat = np.asarray(exp.lat_vv, np.int64)
    jit = np.asarray(exp.jitter_vv, np.int64)
    loss_thr = np.round(np.asarray(exp.loss_vv, np.float64)
                        * 2.0 ** 32).astype(np.uint64)
    z = np.zeros(0, np.int64)
    u0 = np.zeros(0, np.uint64)

    def rounded(x):
        return np.round(np.asarray(x, np.float64)).astype(np.uint64)

    a = [z] * 5
    m0 = m1 = u0
    s = [0] * 5
    tids, tcum, peers = [z] * 4, [z] * 3, z
    if app == "filexfer":
        app_id = 1
        a = [cfg["role"], cfg["server"], cfg["flow_bytes"],
             cfg["start_time"], cfg["flow_count"]]
    elif app == "tgen":
        app_id = 2
        mb = np.asarray(cfg["mean_bytes"], np.float64)
        a = [cfg["active"], cfg["streams"], z, cfg["start_time"],
             np.maximum(mb.astype(np.int64), 1)]
        m0, m1 = rounded(mb), rounded(cfg["mean_think_ns"])
        s[0] = 1 if cfg.get("fixed_size") else 0
    elif app == "tor":
        app_id = 3
        a = [cfg["role"], cfg["n_circuits"], cfg["n_streams"],
             cfg["start_time"], z]
        m0 = rounded(cfg["mean_stream_cells"])
        m1 = rounded(cfg["mean_think_ns"])
        s[0] = int(cfg.get("consensus_bytes", 2048))
        s[1] = int(cfg.get("cells_max", 120))
        s[2] = int(cfg.get("ct_cap", 64))
        tids, tcum = _tor_tables(cfg)
    elif app == "bitcoin":
        app_id = 4
        p2 = np.asarray(cfg["peers"], np.int64)
        a = [cfg["tx_origin"], cfg["tx_time"], z, z, z]
        s = [int(cfg.get("tx_size", 400)), int(cfg.get("inv_size", 36)),
             int(cfg.get("connect_time", 0)), p2.shape[1],
             len(np.asarray(cfg["tx_origin"]))]
        peers = p2.reshape(-1)
    else:
        raise ReferenceFailure(f"the reference has no app {app!r}")

    with open(path, "wb") as f:
        def w_i64(x):
            f.write(np.asarray(x, np.int64).tobytes())

        def w_vec(x, dt=np.int64):
            arr = np.asarray(x, dt)
            w_i64(arr.size)
            f.write(arr.tobytes())

        f.write(np.uint64(_NET_MAGIC).tobytes())
        for v in (exp.n_hosts, seed, exp.window, n_windows, pr.ev_cap,
                  pr.outbox_cap, pr.sockets_per_host, pr.msgq_cap,
                  pr.send_burst, pr.mss, pr.init_cwnd_mss, pr.sndbuf,
                  pr.rcvbuf, pr.rto_min, pr.rto_max, pr.rto_init,
                  pr.dupack_thresh, lat.shape[0], int(jit.max() > 0), app_id):
            w_i64(v)
        w_vec(lat.reshape(-1))
        w_vec(jit.reshape(-1))
        w_vec(loss_thr.reshape(-1), np.uint64)
        w_vec(exp.host_vertex)
        w_vec(exp.bw_up)
        w_vec(exp.bw_dn)
        for x in a:
            w_vec(x)
        w_vec(m0, np.uint64)
        w_vec(m1, np.uint64)
        for v in s:
            w_i64(v)
        for i in range(3):
            w_vec(tids[i])
            w_vec(tcum[i])
        w_vec(tids[3])
        w_vec(peers)


def _run_net(exp, params, seed: int, n_windows: int, timeout_s: float) -> dict:
    binary = _build(_SOURCES["net"])
    fd, blob = tempfile.mkstemp(suffix=".blob", dir=_BUILD)
    os.close(fd)
    try:
        _dump_net(exp, params, seed, n_windows, blob)
        return _run([str(binary), str(_TABLE), blob, str(_threads())],
                    timeout_s)
    finally:
        os.unlink(blob)


def counters(exp, params, seed: int, n_windows: int,
             timeout_s: float = 600.0) -> dict:
    """The reference's counters after ``n_windows`` windows of ``exp`` run
    under ``seed``, with its wall seconds and thread count beside them."""
    if exp.model == "phold":
        return _run_phold(exp, params, seed, n_windows, timeout_s)
    if exp.model == "net":
        return _run_net(exp, params, seed, n_windows, timeout_s)
    raise ReferenceFailure(f"no reference for model {exp.model!r}")
