"""Does the system still start on the chip? The quickest proof.

    python chip_smoke.py            # from the repo root, on a machine with a TPU

Drives the main paths once, through the entry points a user calls, as
sequential child processes — this script itself never imports jax, because
a parent that has touched jax holds the chip its children need:

  leg 0  which device: platform.describe() in one short child, so a machine
         without a chip fails in seconds.
  leg 1  the TCP/NIC/app round path, a whole configured run in ONE device
         execution: python -m shadow1_tpu configs/rung1_filexfer.yaml --summary
         (BASELINE.json config 1; 500 windows), every counter checked
         against the C++ comparator, the device's memory budget and peak
         read back.
  leg 2  the same run supervised and chunked (--ckpt, --heartbeat 100): the
         supervisor parent must leave the chip to its child; metrics must
         equal leg 1's key for key; the compile must be a persistent-cache
         hit.
  leg 3  a state size users would call real — PHOLD at the driver's shape:
         python bench.py (65,536 hosts x 500 windows, 201 MB of event
         planes), event count checked against the C++ PHOLD comparator.
  leg 4  the serve daemon answers two same-shape requests; the second is an
         engine-cache hit; SIGTERM drains it (exit 8, its clean-drain code).

Why leg 1 is rung 1 and not the 1,000-host Tor rung: compile. XLA:TPU needs
minutes for the TCP round body at ANY width, and far longer for rung 3 (Tor
+ compaction) than this script's whole limit of 1,200 s, cold (PERF.md §5
has the seconds). Legs 1 and 2 take the config and window count as
arguments, so the run at real width is the same code with a larger budget:

    python -c "import chip_smoke as s; s.BUDGET_S = 2000; \
        s.leg1_tcp('configs/rung3_tor1k.yaml', 200)"

Every leg's row must say platform == "tpu"; a leg that lands anywhere else
is a failure, not a skip. Compile wall and run wall are printed per leg.
Exit 0 and a last stdout line {"ok": true, "device": {...}} only when every
leg passed; otherwise a message on stderr, no result line, exit 1. Logs of
every child land under chiprun_out/chip_smoke/.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(REPO, "chiprun_out", "chip_smoke")
PY = sys.executable

TCP_CONFIG = "configs/rung1_filexfer.yaml"
TCP_WINDOWS = 500   # the config's whole run: 20 s of sim at a 40 ms window
TCP_CHUNK = 100
SERVE_CONFIG = "configs/serve_phold.yaml"

# The contract allows 1200 s, compilation included; every child's timeout is
# what is left of this, so a hang fails the smoke inside the limit.
BUDGET_S = 1140.0
_T0 = time.monotonic()

# What the comparator prints beside its counters.
_CPP_NOT_COUNTERS = ("wall_s", "events_per_sec", "n_threads")

# shadow1_tpu.consts.EXIT_SERVE_SHUTDOWN — the daemon's documented exit
# after a clean SIGTERM drain (importing the package would import jax).
EXIT_SERVE_SHUTDOWN = 8


class SmokeFailure(Exception):
    pass


def _left() -> float:
    left = BUDGET_S - (time.monotonic() - _T0)
    if left <= 0:
        raise SmokeFailure(f"out of time: {BUDGET_S:.0f}s budget spent")
    return left


def _kill_group(proc: subprocess.Popen) -> None:
    """SIGKILL the child's whole process group (a supervisor's own child
    included); every child is started as a group leader."""
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass
    proc.wait()


def run(name: str, cmd: list[str], env: dict | None = None,
        timeout_s: float | None = None) -> tuple[int, str, str, float]:
    """Run one child to its end; (rc, stdout, stderr, wall). stdout/stderr
    are kept under OUT for the post-mortem."""
    os.makedirs(OUT, exist_ok=True)
    out_p, err_p = (os.path.join(OUT, f"{name}.{s}") for s in ("out", "err"))
    t0 = time.monotonic()
    left = _left()
    with open(out_p, "w") as fo, open(err_p, "w") as fe:
        proc = subprocess.Popen(cmd, cwd=REPO, env=env, stdout=fo, stderr=fe,
                                start_new_session=True)
        try:
            rc = proc.wait(timeout=min(timeout_s, left) if timeout_s else left)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"{name}: no end after "
                               f"{time.monotonic() - t0:.0f}s; killed "
                               f"(see {err_p})") from None
        finally:
            if proc.poll() is None:
                _kill_group(proc)
    wall = time.monotonic() - t0
    with open(out_p) as f:
        out = f.read()
    with open(err_p) as f:
        err = f.read()
    return rc, out, err, wall


def check(cond: bool, leg: str, msg: str) -> None:
    if not cond:
        raise SmokeFailure(f"{leg}: {msg}")


def json_records(text: str) -> list[dict]:
    recs = []
    for line in text.splitlines():
        if line.startswith("{"):
            try:
                recs.append(json.loads(line))
            except ValueError:
                pass
    return recs


def last_json(text: str, leg: str) -> dict:
    recs = json_records(text)
    check(bool(recs), leg, "printed no JSON row")
    return recs[-1]


def require_tpu(row: dict, leg: str) -> None:
    """The platform check every leg makes on its own row."""
    plat = row.get("platform")
    check(plat == "tpu", leg,
          f"ran on platform {plat!r}, not 'tpu' (device_kind="
          f"{row.get('device_kind')!r}, n_devices={row.get('n_devices')!r}) "
          f"— a chip smoke that lands anywhere else has failed")
    check(bool(row.get("device_kind")) and row.get("n_devices", 0) >= 1, leg,
          f"row does not name its device: {row.get('device_kind')!r} x "
          f"{row.get('n_devices')!r}")


def report(leg: str, where: dict, wall: float, **fields) -> None:
    """One stdout line per leg: where it ran, compile apart from run."""
    print(json.dumps({
        "leg": leg, "platform": where["platform"],
        "device_kind": where["device_kind"],
        "n_devices": where["n_devices"],
        "process_wall_s": round(wall, 1), **fields}), flush=True)


def cli_walls(row: dict) -> dict:
    """Compile apart from run, from a CLI row's ``compile`` block."""
    comp = row["compile"]
    return {"compile_s": comp["seconds"],
            "compile_cache": "hit" if comp["cache_hits"] else "cold",
            "run_s": round(row["wall_seconds"] - comp["seconds"], 3)}


# ---------------------------------------------------------------------------
# legs
# ---------------------------------------------------------------------------

def leg0_device() -> dict:
    """Which device is this? One short child answers through
    platform.describe(), so a machine without a chip fails in seconds
    instead of after a CPU run of leg 1."""
    rc, out, err, _ = run("leg0_device", [
        PY, "-c", "import json, shadow1_tpu; "
                  "from shadow1_tpu.platform import describe; "
                  "print(json.dumps(describe()))"], timeout_s=180)
    check(rc == 0, "leg0", f"backend init failed rc={rc}: {err[-800:]}")
    dev = last_json(out, "leg0")
    require_tpu(dev, "leg0")
    return dev


_CPP_CHILD = """
import json, sys
import shadow1_tpu
from shadow1_tpu.platform import force_cpu
force_cpu(1)
from shadow1_tpu import native
from shadow1_tpu.config.experiment import load_experiment
exp, params, _ = load_experiment(sys.argv[1])
native.ensure_net_built()
print(json.dumps(native.run_net(exp, params, int(sys.argv[2]))))
"""


def cpp_counters(config: str, windows: int, leg: str) -> dict:
    """The C++ comparator's counters for the same windows — host only, its
    binary rebuilt unless it was built from exactly the committed source."""
    rc, out, err, _ = run(f"{leg}_cpp", [PY, "-c", _CPP_CHILD, config,
                                         str(windows)], timeout_s=600)
    check(rc == 0, leg, f"C++ comparator failed rc={rc}: {err[-800:]}")
    return last_json(out, leg)


def leg1_tcp(config: str = TCP_CONFIG, windows: int = TCP_WINDOWS) -> dict:
    leg = "leg1"
    rc, out, err, wall = run(leg, [
        PY, "-m", "shadow1_tpu", config, "--windows", str(windows),
        "--summary"])
    check(rc == 0, leg, f"exit {rc}: {err[-1500:]}")
    row = last_json(out, leg)
    require_tpu(row, leg)
    m, s = row["metrics"], row["summary"]
    check(m["windows"] == windows, leg, f"ran {m['windows']} windows")
    check(m["ev_overflow"] == 0 and m["ob_overflow"] == 0, leg,
          f"overflow: ev={m['ev_overflow']} ob={m['ob_overflow']}")
    for k in ("events", "pkts_delivered"):
        check(m[k] > 0, leg, f"{k} == {m[k]}: the network did nothing")
    check(any(v > 0 for k, v in s.items() if k.startswith("total_")), leg,
          f"the application did nothing: summary {s}")
    cpp = cpp_counters(config, windows, leg)
    have = {**s, **m}
    diff = {k: (have.get(k), v) for k, v in cpp.items()
            if k not in _CPP_NOT_COUNTERS and have.get(k) != v}
    check(not diff, leg, f"engine != C++ comparator (engine, cpp): {diff}")
    mems = [r for r in json_records(err) if r.get("type") == "mem"]
    pre = [r for r in mems if "budget_source" in r]
    check(bool(pre) and pre[0]["budget_source"] == "backend", leg,
          f"the device reported no memory budget: {pre[:1]}")
    fin = [r for r in mems if r.get("event") == "final"]
    check(bool(fin) and fin[-1].get("peak_in_use"), leg,
          "no final mem record with peak_in_use")
    report(leg, row, wall, **cli_walls(row), windows=windows,
           events=m["events"], executions=1, cpp_events_match=True,
           cpp_counters_checked=len(cpp) - len(_CPP_NOT_COUNTERS),
           peak_in_use=fin[-1]["peak_in_use"],
           estimated_peak=fin[-1].get("estimated_peak"))
    return row


def cache_dir() -> str:
    """shadow1_tpu/__init__.py's rule, restated: importing it imports jax."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        REPO, ".jax_cache")


def leg2_supervised(ref: dict, config: str = TCP_CONFIG,
                    windows: int = TCP_WINDOWS,
                    chunk: int = TCP_CHUNK) -> dict:
    leg = "leg2"
    cdir = cache_dir()
    check(os.path.isdir(cdir) and bool(os.listdir(cdir)), leg,
          f"compile cache {cdir} is missing or empty after leg 1")
    rc, out, err, wall = run(leg, [
        PY, "-m", "shadow1_tpu", config, "--windows", str(windows),
        "--summary", "--ckpt", os.path.join(OUT, "ck"),
        "--heartbeat", str(chunk)])
    check(rc == 0, leg, f"exit {rc}: {err[-1500:]}")
    row = last_json(out, leg)
    require_tpu(row, leg)
    want, got = ref["metrics"], row["metrics"]
    diff = {k: (want.get(k), got.get(k)) for k in want.keys() | got.keys()
            if want.get(k) != got.get(k)}
    check(not diff, leg, f"metrics differ from leg 1 (leg1, leg2): {diff}")
    check(row["compile"]["cache_hits"] >= 1, leg,
          f"compile was not a persistent-cache hit: {row['compile']} "
          f"(cache dir {cdir})")
    report(leg, row, wall, **cli_walls(row), windows=windows,
           events=got["events"], executions=-(-windows // chunk),
           metrics_equal_leg1=True, cache_dir=cdir,
           leg1_compile_s=ref["compile"]["seconds"])
    return row


def leg3_phold() -> None:
    leg = "leg3"
    rc, out, err, wall = run(leg, [PY, "bench.py"])
    check(rc == 0, leg, f"exit {rc}: {err[-1500:]}")
    row = last_json(out, leg)
    require_tpu(row, leg)
    d = row["detail"]
    check(d["ev_overflow"] == 0 and d["ob_overflow"] == 0, leg,
          f"overflow: ev={d['ev_overflow']} ob={d['ob_overflow']}")
    check(d["cpp_events_match"] is True, leg,
          "engine and C++ PHOLD comparator disagree on the event count")
    report(leg, row, wall, compile_s=d["compile_wall_s"], run_s=d["wall_s"],
           n_hosts=d["n_hosts"], windows=d["windows"], events=d["events"],
           events_per_sec=row["value"], vs_cpp=row["vs_baseline"],
           cpp_events_match=True)


def leg4_serve(config: str = SERVE_CONFIG) -> None:
    leg = "leg4"
    # Relative to REPO (every child's cwd): the spool holds a Unix socket,
    # whose path may be ~100 bytes at most wherever the checkout lives.
    spool = os.path.relpath(os.path.join(OUT, "spool"), REPO)
    err_p = os.path.join(OUT, "leg4_daemon.err")
    t0 = time.monotonic()
    with open(err_p, "w") as fe:
        daemon = subprocess.Popen(
            [PY, "-m", "shadow1_tpu", "serve", "--spool", spool,
             "--poll-s", "0.05"], cwd=REPO, stdout=subprocess.DEVNULL,
            stderr=fe, start_new_session=True)
    try:
        # The daemon announces itself in <spool>/daemon.json once its
        # backend is up (the start event carries describe()).
        while not os.path.exists(os.path.join(REPO, spool, "daemon.json")):
            check(daemon.poll() is None, leg,
                  f"daemon died at start rc={daemon.poll()} (see {err_p})")
            _left()
            time.sleep(0.2)
        finals = []
        for i in range(2):
            rc, out, err, _ = run(f"leg4_submit{i}", [
                PY, "-m", "shadow1_tpu", "submit", config, "--spool", spool,
                "--timeout-s", "300", "--json-only"], timeout_s=330)
            # submit exits through cli's assert_backend_untouched: rc 0
            # also says the client never initialised a backend.
            check(rc == 0, leg, f"submit {i} exit {rc}: {err[-800:]}")
            finals.append(last_json(out, leg))
        for i, f in enumerate(finals):
            check(f.get("state") == "done", leg, f"job {i} ended {f}")
        check(finals[1].get("cache") == "hit", leg,
              f"second job was an engine-cache {finals[1].get('cache')!r}")
        daemon.send_signal(signal.SIGTERM)
        try:
            rc = daemon.wait(timeout=min(120, _left()))
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"{leg}: daemon did not drain on SIGTERM "
                               f"(see {err_p})") from None
        check(rc == EXIT_SERVE_SHUTDOWN, leg,
              f"daemon exit {rc} after SIGTERM, not the clean-drain code "
              f"{EXIT_SERVE_SHUTDOWN} (see {err_p})")
    finally:
        if daemon.poll() is None:
            _kill_group(daemon)
    with open(err_p) as f:
        events = [r for r in json_records(f.read())
                  if r.get("type") == "serve"]
    start = [r for r in events if r.get("event") == "start"]
    check(bool(start), leg, "daemon logged no start event")
    require_tpu(start[0], leg)
    down = [r for r in events if r.get("event") == "shutdown"]
    check(bool(down) and down[-1]["queued"] == 0
          and down[-1]["ledger"]["jobs_done"] == 2, leg,
          f"daemon did not drain to an empty queue: {down[-1:]}")
    report(leg, start[0], time.monotonic() - t0, jobs_done=2,
           cache=[f.get("cache") for f in finals])


def main() -> int:
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    try:
        dev = leg0_device()
        ref = leg1_tcp()
        leg2_supervised(ref)
        leg3_phold()
        leg4_serve()
        # One process for each chip: this parent must never touch jax.
        check("jax" not in sys.modules, "parent", "chip_smoke.py imported jax")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED — {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["device_kind"],
        "count": dev["n_devices"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
